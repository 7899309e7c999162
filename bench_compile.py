"""Compile / cold-start benchmark leg: persistent executable cache.

Measures what mxnet_tpu.compile_cache exists to kill — the XLA compile
stall a restarted process pays before its first request/batch — on the
two grids that hurt most:

* **serve grid**: ``ServeEngine`` construction with a power-of-two
  bucket grid (every bucket compiles + warms at construction);
* **bucketing grid**: a 4-bucket unrolled-LSTM ``BucketingModule``
  driven through ``precompile`` (the fused default bucket's donated
  train step + each extra bucket's classic fwd+bwd program).

Both run in a FRESH subprocess (the only honest cold measurement — an
in-process repeat would hit jit's own caches; same pattern as
test_checkpoint's crash subprocess), twice against one cache dir:

  compile_cold_s           cold process, empty cache: full XLA compiles
  compile_warm_s           cold process, warm cache: deserialize instead
  compile_cache_speedup    compile_cold_s / compile_warm_s
  compile_cache_hit_rate   hits / (hits + misses) in the warm child
                           (acceptance: 1.0 — every program loads)
  compile_cache_bytes      bytes on disk after both legs
  compile_cache_mode       'serialize' or 'builtin' (backend fallback)

  compile_backend          always 'cpu': the children are pinned to the
                           host (the parent may hold the chip)

JAX's builtin persistent cache is switched off for both children so the
comparison isolates THIS cache.
"""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

SERVE_BUCKETS = (1, 2, 4, 8)
LSTM_BUCKETS = (4, 8, 12, 16)
IMG_SHAPE = (3, 32, 32)
CONV_FILTERS = 64
CLASSES = 10
LSTM_BATCH = 8
LSTM_HIDDEN = 256
LSTM_EMBED = 32
LSTM_VOCAB = 128


def _save_serve_model(tmp):
    """A small CNN: the shape of real vision serving, and the shape of
    the cache's best case — conv programs spend their compile budget in
    XLA optimization but deserialize to cheap library-call code."""
    import mxnet_tpu as mx
    net = mx.sym.Variable("data")
    for i in range(3):
        net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1),
                                 num_filter=CONV_FILTERS,
                                 name="conv%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc_out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(np.zeros((8,) + IMG_SHAPE, np.float32),
                           np.zeros(8, np.float32), batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    prefix = os.path.join(tmp, "model")
    mx.model.save_checkpoint(prefix, 0, net, arg, aux)
    return prefix


def child_main(prefix):
    """One cold-process measurement: serve grid + LSTM bucketing grid.
    Prints ONE json line; the parent diffs cold vs warm runs."""
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache as cc
    from mxnet_tpu.models.lstm import lstm_unroll

    t0 = time.perf_counter()
    eng = mx.serve.ServeEngine.from_checkpoint(
        prefix, 0,
        input_shapes={"data": (1,) + IMG_SHAPE, "softmax_label": (1,)},
        batch_buckets=SERVE_BUCKETS)
    serve_s = time.perf_counter() - t0
    eng.close()

    def sym_gen(seq_len):
        net = lstm_unroll(1, seq_len, LSTM_VOCAB, num_hidden=LSTM_HIDDEN,
                          num_embed=LSTM_EMBED, num_label=LSTM_VOCAB)
        return net, ("data", "l0_init_c", "l0_init_h"), ("softmax_label",)

    def shapes(seq_len):
        return ([("data", (LSTM_BATCH, seq_len)),
                 ("l0_init_c", (LSTM_BATCH, LSTM_HIDDEN)),
                 ("l0_init_h", (LSTM_BATCH, LSTM_HIDDEN))],
                [("softmax_label", (LSTM_BATCH, seq_len))])

    mod = mx.mod.BucketingModule(sym_gen,
                                 default_bucket_key=LSTM_BUCKETS[-1],
                                 context=mx.cpu())
    d, l = shapes(LSTM_BUCKETS[-1])
    mod.bind(data_shapes=d, label_shapes=l)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    t1 = time.perf_counter()
    mod.precompile({k: shapes(k) for k in LSTM_BUCKETS})
    bucket_s = time.perf_counter() - t1

    totals = cc.get_stats().totals()
    cache = cc.get_cache()
    line = {"serve_s": serve_s, "bucket_s": bucket_s,
            "hits": totals["hits"], "misses": totals["misses"],
            "bypasses": totals["bypasses"],
            "trace_lower_s": round(totals["trace_lower_s"], 3),
            "compile_s": round(totals["compile_s"], 3),
            "deserialize_s": round(totals["deserialize_s"], 3),
            "mode": cache.mode if cache else "off",
            "disk_bytes": cache.store.disk_bytes() if cache else 0}
    print("BENCH_COMPILE_CHILD " + json.dumps(line), flush=True)


def _run_child(prefix, cache_dir, timeout_s=900):
    env = dict(os.environ)
    env["MXNET_COMPILE_CACHE"] = cache_dir
    env.setdefault("MXNET_COMPILE_CACHE_SIZE_MB", "512")
    # the parent may hold the chip, and a chip belongs to one process
    env["JAX_PLATFORMS"] = "cpu"
    # isolate the measurement from jax's own persistent cache (every
    # entry point places one): switched off, its directory left alone
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", prefix],
        env=env, capture_output=True, text=True, timeout=timeout_s)
    if res.returncode != 0:
        raise RuntimeError("bench_compile child failed: %s"
                           % res.stderr[-1200:])
    for ln in res.stdout.splitlines():
        if ln.startswith("BENCH_COMPILE_CHILD "):
            return json.loads(ln.split(" ", 1)[1])
    raise RuntimeError("bench_compile child printed no result line: %s"
                       % res.stdout[-800:])


def run(feed=lambda *_: None):
    """Returns dict of compile_* metrics.  `feed` is the watchdog
    heartbeat."""
    tmp = tempfile.mkdtemp(prefix="bench_compile_")
    try:
        cache_dir = os.path.join(tmp, "cache")
        os.makedirs(cache_dir)
        prefix = _save_serve_model(tmp)
        feed("compile-cold")
        cold = _run_child(prefix, cache_dir)
        feed("compile-warm")
        warm = _run_child(prefix, cache_dir)
        cold_s = cold["serve_s"] + cold["bucket_s"]
        warm_s = warm["serve_s"] + warm["bucket_s"]
        lookups = warm["hits"] + warm["misses"]
        hit_rate = warm["hits"] / lookups if lookups else 0.0
        return {
            "compile_cold_s": round(cold_s, 3),
            "compile_cold_serve_s": round(cold["serve_s"], 3),
            "compile_cold_bucket_s": round(cold["bucket_s"], 3),
            "compile_warm_s": round(warm_s, 3),
            "compile_warm_serve_s": round(warm["serve_s"], 3),
            "compile_warm_bucket_s": round(warm["bucket_s"], 3),
            "compile_cache_speedup": round(cold_s / warm_s, 2)
            if warm_s else None,
            "compile_cache_hit_rate": round(hit_rate, 4),
            "compile_cache_bytes": warm["disk_bytes"],
            "compile_cache_mode": warm["mode"],
            "compile_backend": "cpu",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child_main(sys.argv[2])
        return
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()          # children inherit the exported choice
    print(json.dumps(run()), flush=True)


if __name__ == "__main__":
    main()
