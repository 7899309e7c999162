"""Routed-MoE bench legs (ISSUE 19): sparse scale-up as a workload.

Three questions:

1. **Does routing actually buy compute?**  The FLOP-matched dense
   baseline is the MoE layer's dense equivalent — one FFN with hidden
   ``E * H``, the same parameter count as the E stacked experts — so
   it spends the full model's FLOPs on every token, while the routed
   block spends only ``k/E`` of them (plus gate + dispatch/combine
   overhead, which is the honest cost of routing).  Both through
   Module's fused train step, interleaved windows:

     moe_step_ms / moe_dense_step_ms     (both lower is better)
     moe_step_speedup                    dense / moe

2. **Where does the routed traffic land?**  Per-expert top-k counts of
   the TRAINED router over the bench batch, fed through the fused
   step's ``MoeStats`` (the bench-sampler role — routing is
   data-dependent, so occupancy is sampled, not derived):

     moe_expert_imbalance     max/mean expert hits (1.0 = balanced;
                              absolute ceiling 4.0 in the gate — a
                              collapsed router routes everything to
                              one expert and un-earns the speedup)

3. **What does routed decode sustain?**  tok -> embed -> MoE -> logits
   through DecodeEngine with the serving pass pipeline applied — the
   net is BUILT with a dropping train capacity and ``MoEServeParityPass``
   pins it to no-drop — parity-checked token-for-token against a pure
   numpy top-k reference:

     moe_serve_tok_s
"""
import time

import numpy as np

T, D, H, E, K = 256, 128, 256, 8, 2
CF = 1.25                 # train capacity: C = ceil(cf*T*k/E) = 80
STEP_WINDOWS = 3
STEP_ITERS = 8

SV_VOCAB, SV_EMB, SV_H, SV_E = 17, 16, 32, 4
SV_SLOTS = 4
SV_STREAMS = 8
SV_NEW = 16


def _moe_symbol(cf):
    import mxnet_tpu as mx
    from mxnet_tpu.moe import MoEFeedForward, with_aux_loss
    net = MoEFeedForward(mx.sym.Variable("data"), num_hidden=H,
                         num_experts=E, k=K, capacity_factor=cf,
                         name="moe")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="head")
    return with_aux_loss(mx.sym.SoftmaxOutput(net, name="softmax"))


def _dense_symbol():
    import mxnet_tpu as mx
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=E * H, name="d1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=D, name="d2")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def step_leg(feed=lambda *_: None):
    """Fused train step, routed vs FLOP-matched dense, interleaved
    windows (host drift must not fake a speedup); imbalance of the
    trained router sampled into MoeStats at the end."""
    import mxnet_tpu as mx

    rng = np.random.RandomState(3)
    X = rng.randn(T, D).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)

    def make_mod(sym):
        mx.random.seed(11)
        it = mx.io.NDArrayIter(X, y, batch_size=T)
        mod = mx.mod.Module(sym, context=mx.cpu(0))
        mod.bind(it.provide_data, it.provide_label)
        mod.init_params(mx.init.Xavier())
        mod.init_optimizer(optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
        assert mod._fused is not None
        return mod, next(iter(it))

    moe_mod, moe_batch = make_mod(_moe_symbol(CF))
    dense_mod, dense_batch = make_mod(_dense_symbol())
    assert moe_mod._fused.moe_blocks, "MoE block not detected"

    def window(mod, batch):
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

    moe_ms, dense_ms = [], []
    for _ in range(STEP_WINDOWS):
        feed("moe-step-dense")
        dense_ms.append(window(dense_mod, dense_batch))
        feed("moe-step-routed")
        moe_ms.append(window(moe_mod, moe_batch))
    tm, td = min(moe_ms), min(dense_ms)

    # bench-sampler occupancy: top-k of the TRAINED gate over the bench
    # batch, host-side, into the fused step's MoeStats (see moe.stats)
    args, _ = moe_mod.get_params()
    wg = args["moe_gate_weight"].asnumpy()            # (E, D)
    logits = X @ wg.T
    topk = np.argsort(-logits, axis=1)[:, :K]
    counts = np.bincount(topk.reshape(-1), minlength=E).astype(np.float64)
    stats = moe_mod._fused.moe_stats
    block = next(iter(moe_mod._fused.moe_blocks))
    stats.note_counts(block, counts)

    return {
        "moe_step_ms": round(tm, 2),
        "moe_dense_step_ms": round(td, 2),
        "moe_step_speedup": round(td / tm, 2),
        "moe_expert_imbalance": round(stats.imbalance(block), 2),
    }


def _serve_symbol(cf):
    import mxnet_tpu as mx
    from mxnet_tpu.moe import MoEFeedForward, hit_symbols
    tok = mx.sym.Variable("data")
    hits = mx.sym.Variable("moe_hits")
    emb = mx.sym.Embedding(tok, input_dim=SV_VOCAB, output_dim=SV_EMB,
                           name="emb")
    emb = mx.sym.Flatten(emb)
    net = MoEFeedForward(emb, num_hidden=SV_H, num_experts=SV_E, k=K,
                         capacity_factor=cf, name="smoe")
    logits = mx.sym.FullyConnected(net, num_hidden=SV_VOCAB, name="out")
    return mx.sym.Group([logits, hits + hit_symbols(logits)[0]])


def _serve_params(seed=5):
    rng = np.random.RandomState(seed)

    def g(*s):
        return (rng.randn(*s) * 0.5).astype(np.float32)

    return {"emb_weight": g(SV_VOCAB, SV_EMB),
            "smoe_gate_weight": g(SV_E, SV_EMB),
            "smoe_experts_i2h_weight": g(SV_E, SV_EMB, SV_H),
            "smoe_experts_i2h_bias": np.zeros((SV_E, SV_H), np.float32),
            "smoe_experts_h2o_weight": g(SV_E, SV_H, SV_EMB),
            "smoe_experts_h2o_bias": np.zeros((SV_E, SV_EMB), np.float32),
            "out_weight": g(SV_VOCAB, SV_EMB),
            "out_bias": np.zeros(SV_VOCAB, np.float32)}


def _ref_decode(p, prompt, max_new):
    """Pure numpy greedy decode through the no-drop routed forward —
    the ground truth MoEServeParityPass makes the engine hit."""
    def fwd(tok):
        e = p["emb_weight"][tok]
        gl = p["smoe_gate_weight"] @ e
        gz = np.exp((gl - gl.max()).astype(np.float32))
        gates = (gz / gz.sum()).astype(np.float32)
        out = np.zeros(SV_EMB, np.float32)
        for ex in np.argsort(-gates)[:K]:
            h = np.maximum(e @ p["smoe_experts_i2h_weight"][ex]
                           + p["smoe_experts_i2h_bias"][ex], 0.0)
            out += gates[ex] * (h @ p["smoe_experts_h2o_weight"][ex]
                                + p["smoe_experts_h2o_bias"][ex])
        return p["out_weight"] @ out + p["out_bias"]

    toks = [int(t) for t in prompt]
    out, i, tok = [], 0, toks[0]
    while True:
        logits = fwd(tok)
        if i + 1 < len(toks):
            i += 1
            tok = toks[i]
            continue
        tok = int(np.argmax(logits))
        out.append(tok)
        if len(out) >= max_new:
            return out


def serve_leg(feed=lambda *_: None):
    """Routed decode through DecodeEngine: the net carries its TRAIN
    capacity (dropping) and the serving pipeline's MoEServeParityPass
    pins it to no-drop — moe_serve_tok_s counts only if every stream
    matches the numpy reference token-for-token."""
    from mxnet_tpu.passes import default_inference_pipeline
    from mxnet_tpu.serve import DecodeEngine

    params = _serve_params()
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, SV_VOCAB, 1 + rng.randint(0, 3))
               for _ in range(SV_STREAMS)]
    refs = [_ref_decode(params, pr, SV_NEW) for pr in prompts]

    feed("moe-serve-warmup")
    eng = DecodeEngine(_serve_symbol(0.5), dict(params),
                       num_slots=SV_SLOTS,
                       state_shapes={"moe_hits": (SV_E,)},
                       pipeline=default_inference_pipeline(),
                       moe_hits_state="moe_hits", moe_stats_every=4,
                       name="bench-moe")
    try:
        feed("moe-serve-load")
        t0 = time.perf_counter()
        futs = [eng.submit(pr, max_new_tokens=SV_NEW) for pr in prompts]
        outs = [f.result(timeout=120) for f in futs]
        wall = time.perf_counter() - t0
    finally:
        eng.close()
    for i, (got, ref) in enumerate(zip(outs, refs)):
        if [int(t) for t in got] != ref:
            raise AssertionError(
                "moe-serve stream %d diverges from the numpy no-drop "
                "reference: %s vs %s" % (i, list(got), ref))
    return {"moe_serve_tok_s": round(SV_STREAMS * SV_NEW / wall, 1)}


def run(feed=lambda *_: None):
    """Returns the MoE bench metrics; each sub-leg degrades
    independently (a failed optional leg must not sink the others)."""
    import sys
    out = {}
    for leg in (step_leg, serve_leg):
        try:
            out.update(leg(feed=feed))
        except Exception as e:                    # pragma: no cover
            sys.stderr.write("bench_moe: %s failed (%s)\n"
                             % (leg.__name__, e))
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    import json
    print(json.dumps(run()))
