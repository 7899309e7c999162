"""Sharded-embedding bench legs (ISSUE 12): the sparse, memory-bound,
traffic-shaped workload the CNN/LSTM legs never exercise.

Four questions, measured at a realistic duplication rate (4096 ids per
batch drawn from a ~410-id hot set of a 200k-row table — ~10% unique,
the rec-traffic shape):

1. **What does the deduped sparse update buy over the naive path?**
   The naive baseline is what dense training actually does with an
   embedding table (MXNET_EMBED_SPARSE=0, the pre-ISSUE-12 fused step):
   the take-VJP scatter-adds every id occurrence into a full
   ``(vocab, dim)`` dense gradient and the optimizer sweeps the WHOLE
   table.  The sparse path dedups ids, segment-sums grads onto the
   unique rows and updates only those (lazy rows).  Both tables donated
   — the real training layout.

     embed_naive_update_ms    per-occurrence scatter-add + full-table
                              momentum update (lower is better)
     embed_sparse_update_ms   deduped update (lower is better)
     embed_update_speedup     naive / sparse (acceptance >= 2x)
     embed_lookups_per_sec    deduped lookup throughput (ids/s)

2. **Does the win survive the full fused train step?**  A rec model
   (ids -> Embedding -> dense tower) stepped through Module's fused
   path, sparse vs dense, interleaved windows:

     embed_sparse_step_ms / embed_dense_step_ms / embed_step_speedup

3. **How much duplication does the live id stream actually have?**

     embed_dedup_ratio        ids / unique ids per batch, read back
                              from mx.profiler.embed_report()

4. **What does the rec-serve path sustain end to end?**  ids ->
   embedding -> dense tower through a ServeEngine(embed_dedup=True)
   under closed-loop multithreaded load, outputs parity-checked
   against serial batch-1 predict:

     rec_serve_qps
"""
import os
import time

import numpy as np

VOCAB = 200_000
DIM = 64
BATCH_IDS = 4096          # ids per update batch (the acceptance point)
HOT_IDS = 410             # ~10% unique at 4096 draws
UNIQUE_CAP = 512
UPDATE_ITERS = 30

STEP_VOCAB = 200_000     # full-step leg: giant table, same id shape
STEP_DIM = 32
STEP_B, STEP_L = 512, 8   # 4096 ids per step
STEP_WINDOWS = 3
STEP_ITERS = 8

SERVE_VOCAB = 10_000
SERVE_DIM = 32
SERVE_L = 16
SERVE_THREADS = 8
SERVE_REQS = 25


def _hot_ids(rng, n, hot, vocab):
    pool = rng.choice(vocab, hot, replace=False)
    return pool[rng.randint(0, hot, n)].astype(np.int32)


def update_leg(feed=lambda *_: None):
    """Micro leg: deduped sparse update vs the naive per-occurrence
    scatter-add (dense take-VJP) update, donated tables, min-of-trials."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.embed.sparse import dedup_ids, sparse_apply_rows

    lr, mu = 0.1, 0.9
    rng = np.random.RandomState(0)
    ids = jnp.asarray(_hot_ids(rng, BATCH_IDS, HOT_IDS, VOCAB))
    g = jnp.asarray(rng.randn(BATCH_IDS, DIM).astype(np.float32))

    def opt_update(w, grad, mom, _lr, wd, t):
        m = mu * mom - _lr * grad
        return w + m, m

    @partial(jax.jit, donate_argnums=(0, 1))
    def naive(table, mom, ids, g):
        gd = jnp.zeros_like(table).at[ids].add(g, mode="drop")
        m = mu * mom - lr * gd
        return table + m, m

    @partial(jax.jit, donate_argnums=(0, 1))
    def sparse(table, mom, ids, g):
        uniq, inv = dedup_ids(ids, UNIQUE_CAP, sentinel=VOCAB)
        grows = jax.ops.segment_sum(g, inv, num_segments=UNIQUE_CAP)
        return sparse_apply_rows(table, mom, uniq, grows, opt_update,
                                 lr, 0.0, 1)

    @jax.jit
    def lookup(table, ids):
        uniq, inv = dedup_ids(ids, UNIQUE_CAP, sentinel=VOCAB)
        rows = jnp.take(table, uniq, axis=0, mode="clip")
        return jnp.take(rows, inv, axis=0)

    # parity first: one step of each from identical state must land on
    # the same touched rows (plain scatter-add is associative; momentum
    # semantics differ only on UNTOUCHED rows, zero here at t=1)
    t0 = jnp.zeros((VOCAB, DIM), jnp.float32)
    m0 = jnp.zeros((VOCAB, DIM), jnp.float32)
    na, _ = naive(jnp.copy(t0), jnp.copy(m0), ids, g)
    sp, _ = sparse(jnp.copy(t0), jnp.copy(m0), ids, g)
    touched = np.unique(np.asarray(ids))
    np.testing.assert_allclose(np.asarray(na)[touched],
                               np.asarray(sp)[touched],
                               rtol=1e-4, atol=1e-5)

    def bench(f):
        table = jnp.zeros((VOCAB, DIM), jnp.float32)
        mom = jnp.zeros((VOCAB, DIM), jnp.float32)
        table, mom = f(table, mom, ids, g)      # warm (compile)
        table.block_until_ready()
        ts = []
        for _ in range(UPDATE_ITERS):
            t0 = time.perf_counter()
            table, mom = f(table, mom, ids, g)
            table.block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e3

    feed("embed-naive")
    t_naive = bench(naive)
    feed("embed-sparse")
    t_sparse = bench(sparse)

    table = jnp.zeros((VOCAB, DIM), jnp.float32)
    lookup(table, ids).block_until_ready()
    ts = []
    for _ in range(UPDATE_ITERS):
        t0 = time.perf_counter()
        lookup(table, ids).block_until_ready()
        ts.append(time.perf_counter() - t0)
    lk = min(ts)

    return {
        "embed_naive_update_ms": round(t_naive, 3),
        "embed_sparse_update_ms": round(t_sparse, 3),
        "embed_update_speedup": round(t_naive / t_sparse, 2),
        "embed_lookups_per_sec": round(BATCH_IDS / lk),
    }


def _rec_symbol(vocab, dim, hidden, classes, name="embed",
                unique_cap=None):
    import mxnet_tpu as mx
    if unique_cap:
        # the traced dedup buffer size: the sparse step unique-sorts
        # into this many rows instead of the worst-case batch size
        weight = mx.sym.Variable(
            "%s_weight" % name,
            attr={"__embed_unique__": str(unique_cap)})
    else:
        weight = mx.sym.Variable("%s_weight" % name)
    net = mx.sym.Embedding(mx.sym.Variable("ids"), weight=weight,
                           input_dim=vocab, output_dim=dim, name=name)
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="rfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="rfc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def step_leg(feed=lambda *_: None):
    """Full fused train step, sparse vs dense embedding update,
    interleaved windows (host drift must not fake a speedup)."""
    import mxnet_tpu as mx

    rng = np.random.RandomState(1)
    X = _hot_ids(rng, 4 * STEP_B * STEP_L, HOT_IDS,
                 STEP_VOCAB).reshape(4 * STEP_B, STEP_L).astype(np.float32)
    y = (X.sum(axis=1) % 2).astype(np.float32)

    def make_mod(sparse):
        os.environ["MXNET_EMBED_SPARSE"] = "1" if sparse else "0"
        try:
            mx.random.seed(7)
            it = mx.io.NDArrayIter(X, y, batch_size=STEP_B,
                                   data_name="ids")
            mod = mx.mod.Module(
                _rec_symbol(STEP_VOCAB, STEP_DIM, 64, 2,
                            unique_cap=UNIQUE_CAP),
                data_names=("ids",), context=mx.cpu(0))
            mod.bind(it.provide_data, it.provide_label)
            mod.init_params(mx.init.Xavier())
            mod.init_optimizer(optimizer_params={"learning_rate": 0.1,
                                                 "momentum": 0.9})
            assert mod._fused is not None
            assert bool(mod._fused.sparse_embeds) == sparse
            return mod, it
        finally:
            os.environ.pop("MXNET_EMBED_SPARSE", None)

    mods = {s: make_mod(s) for s in (False, True)}
    batches = {}
    for s, (mod, it) in mods.items():
        it.reset()
        batches[s] = next(iter(it))

    def window(mod, batch):
        # steady-state fused steps; block on the live state each window
        import jax
        for _ in range(2):                       # warm the queue
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        leaf = next(iter(mod._fused_state["params"].values()))
        jax.block_until_ready(leaf)
        t0 = time.perf_counter()
        for _ in range(STEP_ITERS):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        leaf = next(iter(mod._fused_state["params"].values()))
        jax.block_until_ready(leaf)
        return (time.perf_counter() - t0) / STEP_ITERS * 1e3

    dense_ms, sparse_ms = [], []
    for w in range(STEP_WINDOWS):
        feed("embed-step-dense")
        dense_ms.append(window(mods[False][0], batches[False]))
        feed("embed-step-sparse")
        sparse_ms.append(window(mods[True][0], batches[True]))
    td, ts = min(dense_ms), min(sparse_ms)
    ratio = mods[True][0]._fused.embed_stats.dedup_ratio()
    return {
        "embed_dense_step_ms": round(td, 2),
        "embed_sparse_step_ms": round(ts, 2),
        "embed_step_speedup": round(td / ts, 2),
        "embed_dedup_ratio": round(ratio, 2),
    }


def rec_serve_leg(feed=lambda *_: None):
    """ids -> embedding -> dense tower through ServeEngine under
    closed-loop multithreaded load; rec_serve_qps counts only if every
    answer matches serial batch-1 predict."""
    import threading

    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serve import ServeEngine

    rng = np.random.RandomState(2)
    net = _rec_symbol(SERVE_VOCAB, SERVE_DIM, 64, 8)
    params = {
        "embed_weight": (rng.randn(SERVE_VOCAB, SERVE_DIM) *
                         0.1).astype(np.float32),
        "rfc1_weight": (rng.randn(64, SERVE_L * SERVE_DIM) *
                        0.05).astype(np.float32),
        "rfc1_bias": np.zeros(64, np.float32),
        "rfc2_weight": (rng.randn(8, 64) * 0.1).astype(np.float32),
        "rfc2_bias": np.zeros(8, np.float32),
    }
    shapes = {"ids": (SERVE_THREADS, SERVE_L),
              "softmax_label": (SERVE_THREADS,)}
    tdict = {"ids": np.int32}
    n = SERVE_THREADS * SERVE_REQS
    reqs = _hot_ids(rng, n * SERVE_L, HOT_IDS,
                    SERVE_VOCAB).reshape(n, SERVE_L)

    feed("rec-serve-warmup")
    eng = ServeEngine(net, dict(params), shapes, type_dict=dict(tdict),
                      embed_dedup=True, max_delay_ms=2.0,
                      deadline_ms=30000.0, name="rec_serve")
    pred = Predictor(net.tojson(), dict(params),
                     {"ids": (1, SERVE_L), "softmax_label": (1,)},
                     type_dict=dict(tdict))
    serial = []
    for i in range(n):
        pred.set_input("ids", reqs[i:i + 1])
        pred.forward()
        serial.append(np.array(pred.get_output(0)[0]))

    results = [None] * n
    errors = []

    def client(t):
        try:
            for j in range(SERVE_REQS):
                i = t * SERVE_REQS + j
                results[i] = eng.predict(reqs[i], timeout=60)
        except Exception as e:                    # pragma: no cover
            errors.append(e)

    feed("rec-serve-load")
    workers = [threading.Thread(target=client, args=(t,))
               for t in range(SERVE_THREADS)]
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    wall = time.perf_counter() - t0
    eng.close()
    if errors:
        raise errors[0]
    for i in range(n):
        if not np.allclose(results[i], serial[i], atol=1e-4):
            raise AssertionError(
                "rec-serve output %d diverges from serial predict" % i)
    return {"rec_serve_qps": round(n / wall, 1)}


def run(feed=lambda *_: None):
    """Returns the embed bench metrics; each sub-leg degrades
    independently (a failed optional leg must not sink the others)."""
    import sys
    out = {}
    for leg in (update_leg, step_leg, rec_serve_leg):
        try:
            out.update(leg(feed=feed))
        except Exception as e:                    # pragma: no cover
            sys.stderr.write("bench_embed: %s failed (%s)\n"
                             % (leg.__name__, e))
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    import json
    print(json.dumps(run()))
