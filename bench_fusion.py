"""Fusion + autotune bench legs (ISSUE 11).

Three questions, measured:

1. **Does epilogue fusion speed up the serve step on THIS host?**
   The wide-FC model (the quantized leg's GEMM-heavy shape) served
   batch-8 through the fused vs unfused serving pipeline, interleaved
   windows (host drift must not fake a speedup in either direction):

     fused_step_ms        steady-state per-batch forward latency, fused
                          (lower is better — registered so in bench_gate)
     fused_step_speedup   unfused / fused latency ratio (median window)

   Honest expectation: on hosts where XLA's OWN fusion already covers
   the bias+activation tail (XLA:CPU does), this hovers near 1.0 — the
   symbol-level fusion's measured win there is graph size (trace/lower
   wall, compile-cache keys, calibration surface), and the >= 1.15
   epilogue win is a TPU/MXU expectation.  docs/perf.md records which
   regime the bench host is in; bench_gate holds the measured number
   either way.

2. **What does the fused serving path sustain end to end?**

     serve_qps_fused      closed-loop multithreaded QPS against a
                          ServeEngine(fuse=True), outputs parity-checked
                          against the unfused engine per request

3. **Does the autotuner recover the hand-tuned superstep win?**
   fit-side tuning on a small dispatch-bound MLP (the regime superstep
   exists for):

     autotune_superstep_k the K the measurement picked
     autotune_speedup     per-step cost at K=1 / at the picked K, read
                          from the tuner's own measurement log (>= 1 by
                          construction iff the tuner picked the argmin)
"""
import threading
import time

import numpy as np

IN_F = 512
HIDDEN_F = 1024
CLASSES = 10
BATCH = 8
FWD_ITERS = 30
WINDOWS = 4
SERVE_THREADS = 8
SERVE_REQS = 25


def _wide_model():
    import mxnet_tpu as mx
    rng = np.random.RandomState(11)

    def xavier(n_out, n_in):
        return (rng.randn(n_out, n_in) *
                np.sqrt(2.0 / n_in)).astype(np.float32)

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN_F, name="ffc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN_F, name="ffc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="ffc_out")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    args = {"ffc0_weight": xavier(HIDDEN_F, IN_F),
            "ffc0_bias": np.zeros(HIDDEN_F, np.float32),
            "ffc1_weight": xavier(HIDDEN_F, HIDDEN_F),
            "ffc1_bias": np.zeros(HIDDEN_F, np.float32),
            "ffc_out_weight": xavier(CLASSES, HIDDEN_F),
            "ffc_out_bias": np.zeros(CLASSES, np.float32)}
    return net, args


def _peak(rates, tolerance=1.3):
    med = sorted(rates)[len(rates) // 2]
    return max(r for r in rates if r <= tolerance * med)


def step_leg(feed=lambda *_: None):
    """fused_step_ms / fused_step_speedup: batch-8 predictor forward,
    fused vs unfused pipeline, interleaved windows."""
    from mxnet_tpu.passes import build_serving_pipeline
    from mxnet_tpu.predictor import Predictor

    net, args = _wide_model()
    shapes = {"data": (BATCH, IN_F), "softmax_label": (BATCH,)}
    preds = {}
    for fuse in (False, True):
        pipe = build_serving_pipeline(fuse=fuse, name="bench-fuse%s" % fuse)
        preds[fuse] = Predictor(net.tojson(), dict(args), dict(shapes),
                                pipeline=pipe)
    X = np.random.RandomState(3).rand(BATCH, IN_F).astype(np.float32)
    outs = {}
    for fuse, p in preds.items():
        p.set_input("data", X)
        p.forward()
        outs[fuse] = p.get_output(0)          # warm + parity material
    np.testing.assert_array_equal(outs[False], outs[True])

    def window(p):
        t0 = time.perf_counter()
        for _ in range(FWD_ITERS):
            p.set_input("data", X)
            p.forward()
            p.get_output(0)
        return (time.perf_counter() - t0) / FWD_ITERS

    fused_ms, unfused_ms, ratios = [], [], []
    for w in range(WINDOWS):
        feed("fusion-step")
        u = window(preds[False])
        f = window(preds[True])
        unfused_ms.append(u * 1e3)
        fused_ms.append(f * 1e3)
        ratios.append(u / f)
    # latencies publish the best (minimum) window; the speedup publishes
    # the MEDIAN ratio, not the peak — on a host where XLA already fuses
    # the epilogue the true ratio is ~1.0 and a peak statistic would
    # publish the noise ceiling, making bench_gate flap round to round
    import json as _json
    nodes = {fuse: sum(1 for nd in
                       _json.loads(p.symbol.tojson())["nodes"]
                       if nd["op"] != "null")
             for fuse, p in preds.items()}
    return {
        "fused_step_ms": round(min(fused_ms), 3),
        "unfused_step_ms": round(min(unfused_ms), 3),
        "fused_step_speedup": round(sorted(ratios)[len(ratios) // 2], 3),
        # the graph-size win is deterministic and host-independent: the
        # nodes XLA/trace/calibration never have to visit
        "fused_graph_shrink": round(nodes[False] / float(nodes[True]), 2),
    }


def serve_leg(feed=lambda *_: None, threads=SERVE_THREADS,
              reqs_per_thread=SERVE_REQS):
    """serve_qps_fused: closed-loop load on a fused-pipeline engine,
    outputs parity-checked against the unfused engine."""
    from mxnet_tpu.serve import ServeEngine

    net, args = _wide_model()
    shapes = {"data": (1, IN_F), "softmax_label": (1,)}
    n = threads * reqs_per_thread
    X = np.random.RandomState(5).rand(n, IN_F).astype(np.float32)
    buckets = tuple(b for b in (1, 2, 4, 8) if b <= threads)
    feed("fusion-serve-warmup")
    ref = ServeEngine(net, dict(args), shapes, batch_buckets=buckets,
                      max_delay_ms=2.0, deadline_ms=60000.0,
                      name="bench-unfused", fuse=False)
    eng = ServeEngine(net, dict(args), shapes, batch_buckets=buckets,
                      max_delay_ms=2.0, deadline_ms=60000.0,
                      name="bench-fused", fuse=True)
    results = [None] * n
    try:
        # parity on a sample before any qps means anything
        for i in range(0, n, max(1, n // 40)):
            np.testing.assert_allclose(eng.predict(X[i], timeout=60),
                                       ref.predict(X[i], timeout=60),
                                       atol=1e-6)
        errors = []

        def client(t):
            try:
                for j in range(reqs_per_thread):
                    i = t * reqs_per_thread + j
                    results[i] = eng.predict(X[i], timeout=120)
            except Exception as e:               # pragma: no cover
                errors.append(e)

        rates = []
        for w in range(3):
            feed("fusion-serve")
            workers = [threading.Thread(target=client, args=(t,))
                       for t in range(threads)]
            t0 = time.perf_counter()
            for wk in workers:
                wk.start()
            for wk in workers:
                wk.join()
            if errors:
                raise errors[0]
            rates.append(n / (time.perf_counter() - t0))
    finally:
        eng.close()
        ref.close()
    return {"serve_qps_fused": round(_peak(rates), 1)}


def autotune_leg(feed=lambda *_: None):
    """autotune_superstep_k / autotune_speedup on a dispatch-bound MLP.
    The speedup is read from the tuner's OWN measurement log (per-step
    cost at K=1 over cost at the winner), so the published number is
    exactly the evidence the decision was made from."""
    import mxnet_tpu as mx
    from mxnet_tpu import autotune as at

    feed("fusion-autotune")
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="afc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="afc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.rand(64, 32).astype(np.float32)
    y = rng.randint(0, CLASSES, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    k = at.tune_superstep(mod, candidates=(1, 2, 4, 8), trials=3,
                          persist=False)
    out = {"autotune_superstep_k": k}
    # the tuner's own measurement log — matched by NAME, not [-1]: an
    # ambient MXNET_AUTOTUNE=1 can register serve:pipeline runs in this
    # process, and an early-returned tune (blocked Ks) registers nothing
    stats = next((s for s in reversed(at._kept_stats)
                  if s.name == "fit:superstep"), None)
    if stats is not None:
        log = {c["superstep"]: s for c, s in stats.trials}
        if 1 in log and k in log and log[k] > 0:
            out["autotune_speedup"] = round(log[1] / log[k], 2)
    return out


def run(feed=lambda *_: None):
    """Returns the fusion/autotune bench metrics; each sub-leg degrades
    independently (a failed optional leg must not sink the others)."""
    import sys
    out = {}
    for leg in (step_leg, serve_leg, autotune_leg):
        try:
            out.update(leg(feed=feed))
        except Exception as e:            # pragma: no cover
            sys.stderr.write("bench_fusion: %s failed (%s)\n"
                             % (leg.__name__, e))
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    import json
    print(json.dumps(run()))
