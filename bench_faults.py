"""Robustness benchmark leg (ISSUE 15): what recovery actually costs.

Three promises, three numbers, all gated by tools/bench_gate.py:

  train_recovery_s        elastic-supervisor recovery: wall seconds
                          from a training child's death (SIGKILL mid-
                          commit, injected by the fault plane) to the
                          RESTARTED child committing a step past the
                          pre-crash high water — i.e. training provably
                          moving again, backoff included
  serve_failover_dropped  requests lost in a closed-loop flood against
                          a 2-replica ServeRouter while the fault plane
                          fails a fraction of dispatches (gate: 0 —
                          the retry budget + breaker absorb everything)
  serve_failover_qps      throughput of that flood (the price of
                          riding through failures, for the trend line)
  chaos_overhead_frac     fractional steps/s cost of the fault plane on
                          the fused train loop: plan ARMED at rate=0
                          (every point consulted, none fire) vs
                          MXNET_FAULTS unset (gate: ~0 — disabled
                          points are one `is None` check)
  faults_point_ns         nanoseconds per disabled faults.point() call
                          (the microcost behind that fraction)
"""
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

_RECOVERY_CHILD = """
import os, sys
sys.path.insert(0, %(root)r)
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import faults

store = sys.argv[1]
faults.install(faults.FaultPlan([
    # attempt 0: SIGKILL between shards-written and rename on the 2nd
    # save — a torn commit the restarted attempt must skip past
    faults.Rule(points="checkpoint.commit@shards_written", kinds="crash",
                attempts=[0], after=1, max_faults=1),
], seed=13))

rng = np.random.RandomState(0)
X = rng.rand(512, 64).astype(np.float32)
y = rng.randint(0, 8, 512).astype(np.float32)
it = mx.io.NDArrayIter(X, y, batch_size=64)
mx.random.seed(11)
net = mx.sym.Variable("data")
net = mx.sym.FullyConnected(net, num_hidden=64, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")
mod = mx.mod.Module(net, context=mx.cpu(0))
mod.fit(it, num_epoch=3, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05},
        checkpoint=store, checkpoint_every=4, resume=True)
sys.exit(0)
"""


def recovery_leg(feed=lambda *_: None):
    """train_recovery_s: supervised crash-and-resume, commit-to-commit."""
    from mxnet_tpu import faults
    out = {}
    tmp = tempfile.mkdtemp(prefix="bench-faults-")
    try:
        script = os.path.join(tmp, "recovery_child.py")
        with open(script, "w") as f:
            f.write(_RECOVERY_CHILD % {"root": ROOT})
        store = os.path.join(tmp, "store")
        feed("faults-recovery")
        sup = faults.Supervisor(
            [sys.executable, script, store],
            max_restarts=3,
            backoff=faults.Backoff(base_s=0.05, jitter=0.0),
            timeout_s=300.0, checkpoint_dir=store,
            # the bench parent may hold the chip; a chip has one process
            env={"JAX_PLATFORMS": "cpu"},
            name="bench-recovery")
        rc = sup.run()
        rep = sup.stats.report()
        if rc == 0 and rep["restarts"] >= 1 and rep["last_recovery_s"] > 0:
            out["train_recovery_s"] = round(rep["last_recovery_s"], 3)
            out["train_recovery_restarts"] = rep["restarts"]
            out["train_recovery_backend"] = "cpu"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def failover_leg(requests=300, feed=lambda *_: None):
    """serve_failover_dropped/qps: router flood under injected faults."""
    import mxnet_tpu as mx
    from mxnet_tpu import faults
    from mxnet_tpu.serve import ServeEngine, ServeRouter
    out = {}
    in_dim, classes = 16, 4
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"),
                              num_hidden=classes, name="fc"),
        name="softmax")
    rng = np.random.RandomState(3)
    params = {"fc_weight": rng.randn(classes, in_dim).astype(np.float32),
              "fc_bias": np.zeros(classes, np.float32)}
    shapes = {"data": (1, in_dim), "softmax_label": (1,)}

    def factory(i):
        return ServeEngine(net, dict(params), shapes,
                           batch_buckets=(1, 2, 4), max_delay_ms=1.0,
                           name="failover-rep%d" % i)

    feed("faults-failover")
    router = ServeRouter(factory, replicas=2, unhealthy_after=4,
                         retries=6, probe_after_s=0.05,
                         name="bench-failover")
    try:
        X = rng.randn(requests, in_dim).astype(np.float32)
        ref = router.predict(X[0], timeout=60)        # warm, fault-free
        faults.install(
            "seed=29,rate=0.05,kinds=error,points=serve.dispatch")
        dropped = 0
        window = 16                 # closed-loop: bounded in-flight set
        t0 = time.perf_counter()
        inflight = []
        for i in range(requests):
            inflight.append(router.submit(X[i % len(X)]))
            if len(inflight) >= window:
                try:
                    inflight.pop(0).result(timeout=120)
                except Exception:
                    dropped += 1
        for f in inflight:
            try:
                f.result(timeout=120)
            except Exception:
                dropped += 1
        dt = time.perf_counter() - t0
        faults.clear()
        out["serve_failover_dropped"] = dropped
        out["serve_failover_qps"] = round(requests / dt, 1)
        assert ref is not None
    finally:
        faults.clear()
        router.close()
    return out


def overhead_leg(steps=400, feed=lambda *_: None):
    """chaos_overhead_frac: armed-at-rate-0 vs unset, same fused loop."""
    import mxnet_tpu as mx
    from mxnet_tpu import faults
    out = {}
    rng = np.random.RandomState(0)
    X = rng.rand(256, 64).astype(np.float32)
    y = rng.randint(0, 8, 256).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Activation(
                mx.sym.FullyConnected(mx.sym.Variable("data"),
                                      num_hidden=64, name="fc1"),
                act_type="relu"),
            num_hidden=8, name="fc2"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Uniform(0.05))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    batch = next(iter(it))

    def loop(n):
        t0 = time.perf_counter()
        for _ in range(n):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        return time.perf_counter() - t0

    feed("faults-overhead")
    loop(50)                                   # warm the compiled step
    faults.clear()
    t_off = min(loop(steps) for _ in range(3))
    faults.install("rate=0,kinds=error")       # armed, never fires
    t_armed = min(loop(steps) for _ in range(3))
    faults.clear()
    out["chaos_overhead_frac"] = round(
        max(0.0, (t_armed - t_off) / t_off), 4)

    n = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n):
        faults.point("bench.hot")
    out["faults_point_ns"] = round(
        (time.perf_counter() - t0) / n * 1e9, 1)
    return out


def run(feed=lambda *_: None):
    """Returns the faults bench metrics; each sub-leg degrades
    independently (a failed optional leg must not sink the others)."""
    out = {}
    for leg in (overhead_leg, failover_leg, recovery_leg):
        try:
            out.update(leg(feed=feed))
        except Exception as e:                    # pragma: no cover
            sys.stderr.write("bench_faults: %s failed (%s)\n"
                             % (leg.__name__, e))
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    print(json.dumps(run(), indent=1))
