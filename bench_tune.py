"""Joint-autotune + kernel-search bench legs (ISSUE 20).

Two questions, measured:

1. **Does the joint tuner beat the defaults, and how fast does it pay
   for itself?**  A fresh 3-layer tanh MLP (dispatch-bound — the
   regime the fit-side superstep x unroll x remat space exists for)
   tuned with a FRESH cost model in an isolated store:

     autotune_joint_speedup   per-step cost at the K=1 defaults over
                              the joint winner's measured cost — both
                              read through the SAME measurement helper
                              the tuner used, so the ratio is exactly
                              the evidence the decision was made from
     autotune_search_s        wall seconds the whole joint search
                              spent (lower is better; the shortlist is
                              the lever — the 40-candidate space is
                              ranked, only MXNET_AUTOTUNE_SHORTLIST
                              candidates ever run)
     autotune_amortize_steps  search cost / per-step win: training
                              steps until the search has paid for
                              itself (lower is better)

2. **Did any searched Pallas tiling break bitwise parity?**  A full
   kernel-search sweep (flash / fc epilogue / paged) in interpret
   mode:

     kernelsearch_parity_fail  parity_fail_total() after the sweep —
                               ZERO-floor gated: a candidate that is
                               not bitwise-equal to its jnp twin must
                               never appear, anywhere, ever
"""
import os
import shutil
import tempfile
import time

import numpy as np

IN_F = 32
HIDDEN_F = 64
CLASSES = 10
BATCH = 32
TRIALS = 3


def _mlp_module():
    import mxnet_tpu as mx
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN_F, name="jfc1")
    net = mx.sym.Activation(net, act_type="tanh", name="jact1")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN_F, name="jfc2")
    net = mx.sym.Activation(net, act_type="tanh", name="jact2")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="jfc3")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.rand(2 * BATCH, IN_F).astype(np.float32)
    y = rng.randint(0, CLASSES, 2 * BATCH).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    return mod


def joint_leg(feed=lambda *_: None):
    """autotune_joint_speedup / autotune_search_s /
    autotune_amortize_steps on a dispatch-bound MLP with a fresh store
    and a fresh (untrained) cost model — the cold-host number."""
    from mxnet_tpu import autotune as at
    from mxnet_tpu.autotune import costmodel as cm
    from mxnet_tpu.autotune.joint import tune_fit_joint

    feed("tune-joint")
    mod = _mlp_module()
    mod._fused_ensure_state()
    # the defaults' cost, through the SAME helper the tuner measures
    # with (warm program, state copy) — an apples-to-apples baseline
    base_s = at._measure_superstep(mod, 1, TRIALS, unroll=1)
    t0 = time.perf_counter()
    cfg = tune_fit_joint(mod, trials=TRIALS, persist=True)
    search_s = time.perf_counter() - t0
    stats = next((s for s in reversed(at._kept_stats)
                  if s.name == "fit:joint"), None)
    out = {"autotune_search_s": round(search_s, 2),
           "autotune_joint_k": int(cfg["superstep"]),
           "autotune_joint_unroll": int(cfg["unroll"])}
    win_s = stats.best_cost_s if stats is not None else None
    if win_s and win_s > 0:
        out["autotune_joint_speedup"] = round(base_s / win_s, 2)
        gain = base_s - win_s
        if gain > 0:
            out["autotune_amortize_steps"] = int(round(search_s / gain))
    # the model trained from this run's own audit log
    rep = cm.report()
    out["autotune_costmodel_samples"] = int(rep["samples"])
    return out


def kernelsearch_leg(feed=lambda *_: None):
    """kernelsearch_parity_fail after a full search sweep.  Every
    candidate runs the interpret-mode kernel against its bitwise jnp
    twin; the metric is the count of candidates that failed that gate
    (zero-floor: one failure anywhere is a numerics regression)."""
    from mxnet_tpu.autotune import kernelsearch as ks

    feed("tune-kernelsearch")
    before = ks.parity_fail_total()
    t0 = time.perf_counter()
    ks.search_flash(1, 96, 2, 8, causal=True, trials=2)
    ks.search_flash(1, 64, 2, 8, causal=False, trials=2)
    ks.search_fc(8, 128, 256, act_type="relu", trials=2)
    ks.search_fc(8, 128, 256, act_type="relu", out_scale=0.05, trials=2)
    ks.search_paged(2, 2, 2, 8, n_blocks=6, bt=16, trials=2)
    return {"kernelsearch_parity_fail": ks.parity_fail_total() - before,
            "kernelsearch_sweep_s": round(time.perf_counter() - t0, 2)}


def run(feed=lambda *_: None):
    """Returns the joint-autotune bench metrics; runs in an ISOLATED
    store so the published numbers are always the cold-host search (a
    warm store would measure nothing), and each sub-leg degrades
    independently."""
    import sys
    tmp = tempfile.mkdtemp(prefix="bench_tune_store_")
    saved = os.environ.get("MXNET_AUTOTUNE_DIR")
    os.environ["MXNET_AUTOTUNE_DIR"] = tmp
    from mxnet_tpu.autotune import costmodel as cm
    with cm._model_lock:
        cm._MODELS.clear()                # fresh model for the fresh store
    out = {}
    try:
        for leg in (joint_leg, kernelsearch_leg):
            try:
                out.update(leg(feed=feed))
            except Exception as e:        # pragma: no cover
                sys.stderr.write("bench_tune: %s failed (%s)\n"
                                 % (leg.__name__, e))
    finally:
        if saved is None:
            os.environ.pop("MXNET_AUTOTUNE_DIR", None)
        else:
            os.environ["MXNET_AUTOTUNE_DIR"] = saved
        with cm._model_lock:
            cm._MODELS.clear()
        shutil.rmtree(tmp, ignore_errors=True)
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    import json
    print(json.dumps(run()))
