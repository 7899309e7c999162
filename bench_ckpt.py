"""Checkpoint benchmark leg: the cost of fault tolerance.

Measures what the checkpoint subsystem promises — an async save costs
~one step of stall, not seconds — on the SAME fused-train-step path
bench.py times:

  ckpt_save_s            end-to-end wall time of one committed async
                         save (snapshot -> shard files -> fsync ->
                         rename -> COMMIT), writer-thread side
  ckpt_restore_s         restore of that step back into a module
  ckpt_bytes_s           serialized bytes / ckpt_save_s
  ckpt_step_overhead_s   extra TRAIN-THREAD time per save: steady-state
                         steps/s with a save every K steps vs without,
                         expressed as seconds added per save
  ckpt_overhead_frac     fractional steps/s loss at save_every=K
                         (acceptance: < 0.10 at K=100)

The model is a deliberately checkpoint-heavy MLP (~8M params + Adam
slots => ~100MB serialized with m+v) so the leg exercises real byte
volume without bench.py's ResNet compile cost.
"""
import os
import shutil
import tempfile
import time

import numpy as np

SAVE_EVERY = 100


def _build_module(batch=256, hidden=1024, layers=4, classes=100):
    import mxnet_tpu as mx
    net = mx.sym.Variable("data")
    for i in range(layers):
        net = mx.sym.FullyConnected(net, num_hidden=hidden,
                                    name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc_out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.rand(batch, hidden).astype(np.float32)
    y = rng.randint(0, classes, batch).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(net, context=mx.tpu(0))   # falls back to cpu off-TPU
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    batch0 = next(iter(it))
    return mod, batch0


def _steps_per_s(mod, batch, iters, mgr=None, save_every=SAVE_EVERY,
                 feed=lambda *_: None):
    from mxnet_tpu.checkpoint import save_module
    import jax
    t0 = time.perf_counter()
    for i in range(1, iters + 1):
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
        if mgr is not None and i % save_every == 0:
            save_module(mgr, mod, i)
        if i % 50 == 0:
            feed("ckpt-train")
    if mod._fused_state is not None:
        jax.block_until_ready(
            next(iter(mod._fused_state["params"].values())))
    else:
        mod.get_outputs()[0].asnumpy()
    return iters / (time.perf_counter() - t0)


def run(iters=2 * SAVE_EVERY, warmup=10, feed=lambda *_: None):
    """Returns dict of ckpt_* metrics.  `feed` is the watchdog heartbeat."""
    from mxnet_tpu.checkpoint import CheckpointManager, restore_module
    out = {}
    mod, batch = _build_module()
    feed("ckpt-warmup")
    for _ in range(warmup):
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        feed("ckpt-baseline")
        base_rate = _steps_per_s(mod, batch, iters, feed=feed)
        feed("ckpt-saving")
        mgr = CheckpointManager(os.path.join(tmp, "store"), keep_last_n=2,
                                name="bench")
        with_rate = _steps_per_s(mod, batch, iters, mgr=mgr, feed=feed)
        mgr.wait()
        saves = iters // SAVE_EVERY
        rep = mgr.stats.report()
        out["ckpt_save_s"] = rep["last_save_s"]
        out["ckpt_bytes"] = int(rep["last_bytes"])
        out["ckpt_bytes_s"] = round(rep["last_bytes_per_s"], 1)
        # per-save train-thread cost from the throughput delta (the
        # number a user pays), not the internal overhead counter
        dt = iters / with_rate - iters / base_rate
        out["ckpt_step_overhead_s"] = round(max(dt, 0.0) / saves, 4)
        out["ckpt_overhead_frac"] = round(
            max(0.0, 1.0 - with_rate / base_rate), 4)
        out["ckpt_save_every"] = SAVE_EVERY
        out["ckpt_steps_s_base"] = round(base_rate, 2)
        out["ckpt_steps_s_saving"] = round(with_rate, 2)
        feed("ckpt-restore")
        t0 = time.perf_counter()
        restore_module(mgr, mod)
        out["ckpt_restore_s"] = round(time.perf_counter() - t0, 4)
        mgr.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    import json
    print(json.dumps(run()))
