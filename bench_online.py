"""Online-loop benchmark leg (ISSUE 17): how fresh can the model be?

The continuous-training promise is a latency promise: traffic served
NOW shapes the weights serving soon.  Four numbers, gated by
tools/bench_gate.py:

  online_freshness_s            wall seconds from the last captured
                                request to the retrained weights
                                serving live — capture flush, fine-tune
                                round, gate decision and the zero-drop
                                rolling promotion, end to end
  online_freshness_chaos_s      the same loop re-measured with an
                                absorbable fault plan armed (errored
                                dispatches the router's retry budget
                                eats) — the freshness cost of riding
                                through faults
  online_promote_dropped        requests lost by a closed-loop flood
                                running THROUGH the promotion
                                (ZERO_FLOOR: rolling_restart drains,
                                nothing may drop)
  online_capture_overhead_frac  fractional cost of the capture seam on
                                router flood throughput, sampling
                                enabled vs no capture at all
                                (ABS_CEILING 0.02: capture must stay
                                invisible to serving)
"""
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

_IN, _CLASSES = 16, 4


def _net():
    import mxnet_tpu as mx
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"),
                              num_hidden=_CLASSES, name="fc"),
        name="softmax")


def _params(seed=3):
    rng = np.random.RandomState(seed)
    return {"fc_weight": rng.randn(_CLASSES, _IN).astype(np.float32) * 0.1,
            "fc_bias": np.zeros(_CLASSES, np.float32)}


def _factory(net, params, name):
    from mxnet_tpu.serve import ServeEngine

    def factory(i):
        return ServeEngine(net, dict(params), {"data": (8, _IN)},
                           max_delay_ms=1.0, name="%s-rep%d" % (name, i),
                           warmup=False)
    return factory


def _flood(router, X, requests, window=16):
    """Closed-loop windowed flood; -> (elapsed_s, dropped)."""
    dropped = 0
    inflight = []
    t0 = time.perf_counter()
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
    return time.perf_counter() - t0, dropped


def capture_overhead_leg(requests=300, repeats=9, feed=lambda *_: None):
    """online_capture_overhead_frac: the serve-path price of sampling.

    Same windowed flood, capture off vs capture on (sample 0.25, large
    shards so the spill cost amortizes the way production capture
    does).  The two routers live side by side and the trials
    INTERLEAVE (off, on, off, on, ...) so machine drift lands on both
    sides equally, and the metric is the MEDIAN of the per-pair
    fractions ``(on_i - off_i) / off_i`` — pairing cancels the drift
    each adjacent trial shares, and the median throws away the
    scheduler-outlier pairs a mean (or a min-of-N) would gate on.
    What survives is the systematic cost, which is what the ceiling
    is about."""
    from mxnet_tpu import online, serve
    out = {}
    net, params = _net(), _params()
    rng = np.random.RandomState(0)
    X = rng.randn(64, _IN).astype(np.float32)
    tmp = tempfile.mkdtemp(prefix="bench-online-cap-")
    feed("online-capture-overhead")
    try:
        writer = online.CaptureWriter(
            os.path.join(tmp, "cap"), sample=0.25, shard_items=4096,
            fresh=True, transform=lambda d, o: (d, np.argmax(o)))
        plain = serve.ServeRouter(_factory(net, params, "cap-off"),
                                  replicas=2, name="bench-cap-off")
        capped = serve.ServeRouter(_factory(net, params, "cap-on"),
                                   replicas=2, capture=writer,
                                   name="bench-cap-on")
        try:
            _flood(plain, X, requests)                 # warm both
            _flood(capped, X, requests)
            t_off, t_on = [], []
            for _ in range(repeats):
                t_off.append(_flood(plain, X, requests)[0])
                t_on.append(_flood(capped, X, requests)[0])
            capped.capture_sync(timeout=60)
            rep = capped.stats.report()
            assert rep["capture_errors"] == 0, rep
        finally:
            plain.close()
            capped.close()
        writer.flush()
        fracs = [(on - off) / off for on, off in zip(t_on, t_off)]
        out["online_capture_overhead_frac"] = round(
            max(0.0, statistics.median(fracs)), 4)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _freshness_once(chaos, feed):
    """One full loop: flood+capture -> fine-tune -> gate -> promote
    with traffic running through the swap.  -> (freshness_s, dropped)."""
    import mxnet_tpu as mx
    from mxnet_tpu import faults, online, serve
    net, params = _net(), _params()
    rng = np.random.RandomState(1)
    X = rng.randn(128, _IN).astype(np.float32)
    y = rng.randint(0, _CLASSES, 128)
    tmp = tempfile.mkdtemp(prefix="bench-online-fresh-")
    try:
        cap_dir = os.path.join(tmp, "cap")
        ck_dir = os.path.join(tmp, "ck")
        writer = online.CaptureWriter(
            cap_dir, sample=0.5, shard_items=32, fresh=True,
            transform=lambda d, o: (d, np.argmax(o)))
        # 3 replicas + a deep retry budget + fast probes: during a
        # rolling restart one replica is draining, and the chaos plan
        # must not be able to trip the breaker on BOTH others at once
        router = serve.ServeRouter(_factory(net, params, "fresh"),
                                   replicas=3, capture=writer,
                                   unhealthy_after=8, retries=8,
                                   probe_after_s=0.02,
                                   name="bench-fresh")
        if chaos:
            # absorbable: errored dispatches the retry budget eats —
            # the loop must stay zero-drop, only slower
            faults.install(
                "seed=29,rate=0.03,kinds=error,points=serve.dispatch")
        try:
            _t, dropped_flood = _flood(router, X, 192)
            t0 = time.perf_counter()            # last request served
            router.capture_sync(timeout=120)
            writer.flush()
            trainer = online.OnlineTrainer(
                net, cap_dir, ck_dir, batch_size=16,
                optimizer_params=(("learning_rate", 0.05),),
                arg_params={k: mx.nd.array(v) for k, v in params.items()},
                checkpoint_every=2, name="bench-online-trainer")
            cand = trainer.round(num_epoch=1)
            live = np.stack([router.predict(X[i], timeout=60)
                             for i in range(32)])
            # candidate scoring is offline (no router, no retry budget
            # to absorb injected dispatch faults) — the chaos plan
            # covers the serving plane, so it steps aside here
            if chaos:
                faults.clear()
            eng = serve.ServeEngine.from_checkpoint_dir(
                ck_dir, net, {"data": (8, _IN)}, warmup=False,
                name="bench-fresh-cand")
            try:
                cand_scores = np.stack([eng.predict(X[i], timeout=60)
                                        for i in range(32)])
            finally:
                eng.close()
            gate = online.PromotionGate(min_improve=-1.0, max_drift=1.0)
            decision = gate.decide(live, cand_scores, y[:32])
            assert decision["promote"], decision
            if chaos:
                faults.install(
                    "seed=31,rate=0.03,kinds=error,points=serve.dispatch")

            stop = threading.Event()
            drops = {"n": 0}

            def traffic():
                k = 0
                while not stop.is_set():
                    try:
                        router.submit(X[k % len(X)]).result(timeout=120)
                    except Exception:
                        drops["n"] += 1
                    k += 1
            t = threading.Thread(target=traffic, name="bench-promote")
            t.start()
            try:
                gate.apply(decision, router, ck_dir, timeout=120)
            finally:
                stop.set()
                t.join(timeout=120)
            router.predict(X[0], timeout=60)    # new weights serving
            freshness = time.perf_counter() - t0
            assert cand["step"] is not None
            return freshness, drops["n"] + dropped_flood
        finally:
            faults.clear()
            router.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def freshness_leg(feed=lambda *_: None):
    """online_freshness_s / online_promote_dropped, then the chaos
    re-measure (online_freshness_chaos_s)."""
    out = {}
    feed("online-freshness")
    fresh_s, dropped = _freshness_once(chaos=False, feed=feed)
    out["online_freshness_s"] = round(fresh_s, 3)
    out["online_promote_dropped"] = dropped
    feed("online-freshness-chaos")
    chaos_s, chaos_dropped = _freshness_once(chaos=True, feed=feed)
    out["online_freshness_chaos_s"] = round(chaos_s, 3)
    # chaos drops fold into the same zero-floor gate: absorbable means
    # absorbed
    out["online_promote_dropped"] += chaos_dropped
    return out


def run(feed=lambda *_: None):
    """Returns the online-loop bench metrics; each sub-leg degrades
    independently (a failed optional leg must not sink the others)."""
    out = {}
    for leg in (capture_overhead_leg, freshness_leg):
        try:
            out.update(leg(feed=feed))
        except Exception as e:                    # pragma: no cover
            sys.stderr.write("bench_online: %s failed (%s)\n"
                             % (leg.__name__, e))
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    print(json.dumps(run(), indent=1))
