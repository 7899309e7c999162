"""SmallThinker through the Symbol graph (ISSUE 47, tier-1): the whole
tiny model against ``benchmark/reference/smallthinker-21b-a3b.py`` in
float32 (loss, every gradient, Adam's first step); one test a departure
from the decoder skeleton's defaults (no head norms, rotation on sliding
layers only, a router fed the mixer's rows, ReLU gates); the eight
ranks' shares of one expert layer against the uncut layer; seven query
heads a key/value head under a window against a dense-mask softmax;
``router_data=None`` and an unset ``act_zeros`` lower the tiny OLMoE and
AFMoE steps to the text they had; the ``moe:act_zeros`` counter against
the reference's count where the row bound pads; the FLOP count by hand;
the tiles the kernel visits at the cell's shape; scopes and the
trace-time counters."""
import hashlib
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.executor import _GraphProgram              # noqa: E402
from mxnet_tpu.models import afmoe_lm, olmoe_lm, smallthinker_lm  # noqa: E402
from mxnet_tpu.models import decoder                      # noqa: E402
from mxnet_tpu.moe import MoEFeedForward, find_load_heads  # noqa: E402
from mxnet_tpu.moe.dispatch import held_rows_bound        # noqa: E402
from mxnet_tpu.trace.heads import MOE_ACT_ZEROS           # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, placed_on_rows      # noqa: E402

REF = manifest.load_module("reference", "smallthinker-21b-a3b")
share_rule = sys.modules["mxnet_tpu.moe.dispatch"]

TINY = dict(num_layers=4, hidden_size=32,
            layer_types=["full", "sliding", "sliding", "sliding"],
            num_heads=6, num_kv_heads=2, head_dim=8, window=6,
            rope_theta=1.5e6, num_experts=16, experts_per_tok=3,
            expert_width=24, vocab_size=50, seq_len=16, experts_held=4,
            first_expert=4, rms_eps=1e-6, act_zeros=True)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
BLOCKS = ["l%d_moe_dispatch" % l for l in range(4)]
SHARES = ["l%d_moe_share" % l for l in range(4)]
F32 = jnp.float32
# sha256 of a tiny step's lowered text as the commits before this PR held
# it (tests/test_sdar_moe.py, tests/test_decoder_symbols.py): the graph
# ``MoEFeedForward`` builds without ``router_data`` and ``act_zeros`` is
# the one it built.  The AFMoE text was taken again at PR 66, with
# tests/test_decoder_symbols.py's: a tiny rank's share has no row bound and
# lowers as the one window ``(0, T*k)`` since
STEP_TEXT_WAS = {
    "olmoe":
        "0eeb7a8c80320f85d5aeb07cc83d53e328f9fa006d09ca4ae1083936719a3524",
    # taken again at ISSUE 70, as tests/test_decoder_symbols.py's: q and
    # k pass ``HeadNormRotary``
    "afmoe":
        "9983d99f54b79b1d772042fc37085d6a0b2a292a58ad0f098d0f62407f09a0f4"}


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = smallthinker_lm(**kwargs)
    T = kwargs["seq_len"]
    arg_shapes, _, _ = net.infer_shape(data=(BATCH, T),
                                       softmax_label=(BATCH, T))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gamma"):
            params[name] = (1 + 0.1 * rng.randn(*shape)).astype(np.float32)
        else:
            # wide enough that routing and attention are not flat
            params[name] = (0.2 * rng.randn(*shape)).astype(np.float32)
    tokens = rng.randint(0, kwargs["vocab_size"],
                         (BATCH, T)).astype(np.int32)
    return net, kwargs, params, tokens, np.roll(tokens, -1, axis=1)


def _bound(net, params, tokens, labels, optimizer, optimizer_params):
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", tokens.shape)],
             label_shapes=[("softmax_label", labels.shape)])
    mod.init_params(mx.init.Zero(), arg_params={
        k: mx.nd.array(v) for k, v in params.items()}, allow_missing=True)
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=optimizer_params)
    assert mod._fused is not None
    return mod, mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)], pad=0)


def _sgd_gradients(net, params, tokens, labels, lr=0.125):
    """(outputs, {name: gradient}) through one SGD step of the fused
    train step."""
    mod, batch = _bound(net, params, tokens, labels, "sgd", {
        "learning_rate": lr, "momentum": 0.0, "wd": 0.0,
        "rescale_grad": 1.0})
    mod.forward_backward(batch)
    mod.update()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    after, _ = mod.get_params()
    return outs, {k: (params[k] - after[k].asnumpy()) / lr for k in params}


def _losses(net, params, tokens, labels):
    """The per-token loss head ``(B, T)`` of a forward pass."""
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=tokens.shape,
                          softmax_label=labels.shape)
    for k, v in dict(params, data=tokens, softmax_label=labels).items():
        exe.arg_dict[k][:] = v
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy().reshape(tokens.shape)


def _ref_loss(kwargs, params, tokens, labels):
    return REF.loss_and_grads({"model": {"kwargs": kwargs}}, params, tokens,
                              labels, names=[])["loss"]


# -- the model -----------------------------------------------------------------
def test_the_builder_names_its_heads_and_refuses_what_it_cannot_build():
    net, kwargs, _, _, _ = _tiny(seed=0)
    assert net.list_outputs() == ["lm_output", "moe_load_output",
                                  "moe_act_zeros_output"]
    assert find_load_heads(net) == (1, BLOCKS)
    assert MOE_ACT_ZEROS.find(net) == (2, SHARES)
    assert net.list_auxiliary_states() == []
    args = net.list_arguments()
    for l in range(4):
        for part in ("attn_norm_gamma", "ffn_norm_gamma", "q_proj_weight",
                     "k_proj_weight", "v_proj_weight", "o_proj_weight",
                     "moe_gate_weight", "moe_experts_i2h_gate_weight"):
            assert "l%d_%s" % (l, part) in args
    # no norm over a head's lanes, no bias, no dense MLP, no shared expert
    assert not [a for a in args if "q_norm" in a or "k_norm" in a
                or a.endswith("bias") or "shared" in a or "up_proj" in a]
    # without the counter's head the symbol carries two outputs, and a
    # model that holds every expert has no rank's rows to count
    plain = smallthinker_lm(**dict(kwargs, act_zeros=False))
    assert plain.list_outputs() == ["lm_output", "moe_load_output"]
    assert MOE_ACT_ZEROS.find(plain) is None
    assert "act_zeros" not in plain.tojson()
    for bad in (dict(layer_types=["sliding"] * 3),
                dict(layer_types=["sliding", "window", "full", "full"]),
                dict(num_kv_heads=4), dict(experts_held=0)):
        with pytest.raises(ValueError):
            smallthinker_lm(**dict(kwargs, **bad))


def test_model_matches_reference_loss_gradients_and_adam_step(monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    assert abs(float(outs[0].mean()) - ref["loss"]) <= 1e-5 * ref["loss"]
    for row, block in zip(outs[1], BLOCKS):
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][block]))
        assert row[-1] == 0 and row[:-1].sum() == 16 * BATCH * 3
    for row, share in zip(outs[2], SHARES):
        assert np.array_equal(row, ref["act_zeros"][share])
        assert 0 < row[0] < row[1]
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 2e-4, errors

    names = ["l0_q_proj_weight", "l1_q_proj_weight", "l1_k_proj_weight",
             "l1_moe_gate_weight", "l1_moe_experts_i2h_gate_weight",
             "l2_ffn_norm_gamma", "embed_weight", "lm_head_weight"]
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    assert want["loss"] == ref["loss"]
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, aux = mod.get_params()
    assert not aux
    for name in names:
        got = after[name].asnumpy() - params[name]
        # an element whose gradient is ~0 may flip sign: Adam's first
        # step is lr * sign(g); such elements are a sliver of the norm
        assert _rel(got, want["updates"][name]) <= 0.02, name


# -- the departures from the skeleton's defaults, one test each ----------------
def test_no_norm_lies_over_a_heads_lanes():
    """``q_proj`` times 4 is a softmax four times as sharp: a head norm
    would take the scale back (the other builders' blocks do), this
    block's loss moves, as the reference's."""
    net, kwargs, params, tokens, labels = _tiny(seed=11)
    base = _losses(net, params, tokens, labels)
    assert abs(base.mean() - _ref_loss(kwargs, params, tokens, labels)) \
        <= 1e-5 * base.mean()
    louder = {k: (4.0 * v if k.endswith("q_proj_weight") else v)
              for k, v in params.items()}
    got = _losses(net, louder, tokens, labels)
    assert abs(got.mean() - base.mean()) > 1e-3
    assert abs(got.mean() - _ref_loss(kwargs, louder, tokens, labels)) \
        <= 1e-5 * got.mean()
    # the skeleton's default still norms: the same block with head norms
    # has the gains this one lacks, and takes the scale back
    normed = decoder.gqa_attention(mx.sym.Variable("h"), "l0_", 0, 16, 6, 2,
                                   8, 32, 1e-6)
    assert {"l0_q_norm_gamma", "l0_k_norm_gamma"} \
        <= set(normed.list_arguments())
    bare = decoder.gqa_attention(mx.sym.Variable("h"), "l0_", 0, 16, 6, 2,
                                 8, 32, 1e-6, head_norms=False)
    assert set(normed.list_arguments()) - set(bare.list_arguments()) \
        == {"l0_q_norm_gamma", "l0_k_norm_gamma"}


def test_only_sliding_layers_are_rotated():
    """One block, no rotation: a full layer knows the ORDER of the keys
    through its mask alone, so with tokens 0 and 1 exchanged every
    later position reads the same set of keys and its loss stays.  A
    sliding layer whose window holds the whole sequence has the same
    mask and is rotated: the same exchange moves every later loss."""
    kwargs = dict(TINY, num_layers=1, window=64)
    results = {}
    for kind in ("full", "sliding"):
        net, kw, params, tokens, labels = _tiny(seed=19, **dict(
            kwargs, layer_types=[kind]))
        tokens[:, 1] = (tokens[:, 0] + 1) % kwargs["vocab_size"]
        other = tokens.copy()
        other[:, [0, 1]] = tokens[:, [1, 0]]
        results[kind] = (_losses(net, params, tokens, labels),
                         _losses(net, params, other, labels))
        assert abs(results[kind][0].mean()
                   - _ref_loss(kw, params, tokens, labels)) \
            <= 1e-5 * results[kind][0].mean()
    before, after = results["full"]
    assert np.allclose(after[:, 2:], before[:, 2:], rtol=1e-5, atol=1e-6)
    assert not np.allclose(after[:, :2], before[:, :2], atol=1e-3)
    before, after = results["sliding"]
    assert np.abs(after[:, 2:] - before[:, 2:]).max() > 1e-3


def test_the_router_reads_the_mixers_rows_and_not_the_mlps():
    """The router's logits are ``N1(x) Wr``.  ``N2``'s gain scales the
    rows the experts read and never a logit: with it doubled every
    block's choices stay and an expert's output doubles; ``N1``'s gain
    moves the choices.  A router fed the MLP's rows (the skeleton's
    default) would have it the other way round, and gives another loss
    on the same weights."""
    net, kwargs, params, tokens, labels = _tiny(seed=23, num_layers=1,
                                                layer_types=["full"])
    cfg = {"model": {"kwargs": kwargs}}
    base = REF.loss_and_grads(cfg, params, tokens, labels, names=[])

    def counts(p):
        exe = net.simple_bind(mx.cpu(), grad_req="null", data=tokens.shape,
                              softmax_label=labels.shape)
        for k, v in dict(p, data=tokens, softmax_label=labels).items():
            exe.arg_dict[k][:] = v
        exe.forward(is_train=False)
        return exe.outputs[1].asnumpy()[0, :-1]

    assert np.array_equal(counts(params), base["counts"]["l0_moe_dispatch"])
    rng = np.random.RandomState(1)
    skew = (1 + rng.rand(32)).astype(np.float32)
    ffn = dict(params, l0_ffn_norm_gamma=params["l0_ffn_norm_gamma"] * skew)
    assert np.array_equal(counts(ffn), counts(params))
    mixer = dict(params,
                 l0_attn_norm_gamma=params["l0_attn_norm_gamma"] * skew)
    assert not np.array_equal(counts(mixer), counts(params))
    assert np.array_equal(counts(mixer), REF.loss_and_grads(
        cfg, mixer, tokens, labels, names=[])["counts"]["l0_moe_dispatch"])

    # the same weights under a router that reads the MLP's rows
    def mlp_routed():
        x = decoder.embed(mx.sym.Variable("data"), 50, 32)
        x = decoder.block(
            x, "l0_", 1e-6,
            lambda h: decoder.gqa_attention(h, "l0_", 0, 16, 6, 2, 8, 32,
                                            1e-6, head_norms=False),
            lambda g: decoder.routed_experts(
                g, "l0_", 0, 16, 3, 24, 32, act_type="relu",
                renormalize=True, score="softmax", experts_held=4,
                first_expert=4))
        return decoder.lm_head_loss(x, 50, 1e-6)

    other = mlp_routed()
    assert set(other.list_arguments()) == set(net.list_arguments())
    mine = _losses(net, params, tokens, labels).mean()
    theirs = _losses(other, params, tokens, labels).mean()
    assert abs(mine - base["loss"]) <= 1e-5 * mine
    assert abs(theirs - mine) > 1e-3
    # the dispatch node says which rows its logits came from
    assert '"router_rows": "mixer"' in net.tojson()
    assert "router_rows" not in other.tojson()


def test_the_gate_is_relu_and_not_silu():
    """Where every gate lane is negative a ReGLU expert adds exactly
    nothing and a SwiGLU one something: with ``Wg`` = -|Wg| and rows made
    positive, the expert layers are the identity."""
    T, D, H, E = 12, 8, 10, 8
    rng = np.random.RandomState(2)
    x = np.abs(rng.randn(T, D)).astype(np.float32)
    weights = {"moe_gate_weight": rng.randn(E, D),
               "moe_experts_i2h_gate_weight": -np.abs(rng.randn(E, D, H)),
               "moe_experts_i2h_weight": rng.randn(E, D, H),
               "moe_experts_h2o_weight": rng.randn(E, H, D)}
    outs = {}
    for act in ("relu", "silu"):
        net = decoder.routed_experts(mx.sym.Variable("data"), "", -1, E, 2,
                                     H, D, act_type=act, renormalize=True)
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        exe.arg_dict["data"][:] = x
        for n, v in weights.items():
            exe.arg_dict[n][:] = v.astype(np.float32)
        exe.forward(is_train=False)
        outs[act] = exe.outputs[0].asnumpy()
    assert np.array_equal(outs["relu"], np.zeros((T, D), np.float32))
    assert np.abs(outs["silu"]).max() > 1e-3


# -- one rank's share ----------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """64 experts over 8 ranks of 8, top-6 of logits read from OTHER rows
    than the experts', softmax over the six chosen: each rank's output is
    the reference given the same share, and the eight sum to the
    reference's layer with all experts held (there is no shared expert to
    count once)."""
    E, k, held = 64, 6, 8
    rng = np.random.RandomState(5)
    T, D, H = 40, 12, 10
    g = rng.randn(T, D).astype(np.float32)
    h = rng.randn(T, D).astype(np.float32)
    full = {"moe_gate_weight": rng.randn(E, D),
            "moe_experts_i2h_gate_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D)}
    full = {n: v.astype(np.float32) for n, v in full.items()}
    m = {"num_experts": E, "experts_per_tok": k, "expert_width": H}
    with jax.default_matmul_precision("highest"):
        z = jnp.asarray(h) @ jnp.asarray(full["moe_gate_weight"]).T
        whole, counts, seen = REF.moe(
            {n: jnp.asarray(v) for n, v in full.items()}, "",
            jnp.asarray(g), z, m)
    assert np.asarray(counts).sum() == T * k == np.asarray(seen)[1] / H
    total = np.zeros((T, D), np.float32)
    zeros = lanes = 0.0
    for first in range(0, E, held):
        mine = {n: (v[first:first + held] if "experts" in n else v)
                for n, v in full.items()}
        net = MoEFeedForward(
            mx.sym.Variable("data"), num_hidden=H, num_experts=E, k=k,
            capacity_factor=0.0, name="moe", act_type="relu", gated=True,
            no_bias=True, renormalize=True, output_dim=D,
            experts_held=held, first_expert=first,
            router_data=mx.sym.Variable("mixer_rows"))
        exe = net.simple_bind(mx.cpu(), data=(T, D), mixer_rows=(T, D),
                              grad_req="null")
        exe.arg_dict["data"][:] = g
        exe.arg_dict["mixer_rows"][:] = h
        for n, v in mine.items():
            exe.arg_dict[n][:] = v
        exe.forward(is_train=False)
        out = exe.outputs[0].asnumpy()
        with jax.default_matmul_precision("highest"):
            want, _, part = REF.moe(
                {n: jnp.asarray(v) for n, v in mine.items()}, "",
                jnp.asarray(g), z, dict(m, experts_held=held,
                                        first_expert=first))
        assert np.abs(out - np.asarray(want)).max() \
            <= 1e-4 * np.abs(np.asarray(want)).max()
        total += out
        zeros, lanes = zeros + float(part[0]), lanes + float(part[1])
    assert np.abs(total - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()
    assert (zeros, lanes) == tuple(float(v) for v in seen)


# -- the op at the cell's head grouping ----------------------------------------
def test_seven_query_heads_a_key_head_under_a_window_against_dense_softmax():
    """28 query heads over 4 key/value heads (groups of 7) under a window
    smaller than ``T``, through the op's symbol in several blocks of
    queries: output and all three input gradients against a dense-mask
    softmax with the key/value heads repeated."""
    from mxnet_tpu.ops import transformer as tf_ops
    T, W, dh, h, hkv = 40, 11, 8, 28, 4
    rng = np.random.RandomState(3)
    q, k, v = (rng.randn(2, T, n, dh).astype(np.float32)
               for n in (h, hkv, hkv))
    w = rng.randn(2, T, h, dh).astype(np.float32)
    allowed = REF.window_mask(T, W)
    assert allowed.sum() == REF.allowed_pairs(T, W)

    def dense(q, k, v):
        kr, vr = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * 0.3
        s = jnp.where(jnp.asarray(allowed)[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)

    with jax.default_matmul_precision("highest"):
        want_out, vjp = jax.vjp(dense, *(jnp.asarray(x) for x in (q, k, v)))
        want = [want_out] + list(vjp(jnp.asarray(w)))
    net = mx.sym.CausalSelfAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"),
        scale=0.3, mask="sliding_window", window=W)
    exe = net.simple_bind(mx.cpu(), grad_req="write", q=q.shape, k=k.shape,
                          v=v.shape)
    for name, x in (("q", q), ("k", k), ("v", v)):
        exe.arg_dict[name][:] = x
    was, tf_ops.ATTN_BLOCK_Q = tf_ops.ATTN_BLOCK_Q, 16
    try:
        exe.forward(is_train=True)
        exe.backward([mx.nd.array(w)])
    finally:
        tf_ops.ATTN_BLOCK_Q = was
    got = [exe.outputs[0].asnumpy()] + [exe.grad_dict[n].asnumpy()
                                        for n in ("q", "k", "v")]
    for g, r in zip(got, want):
        assert np.abs(g - np.asarray(r)).max() \
            <= 2e-5 * max(1.0, np.abs(np.asarray(r)).max())
    # the reference's attention in blocks of queries is the same function
    m = dict(num_heads=h, num_kv_heads=hkv, head_dim=dh, window=W,
             rope_theta=1.5e6)
    p = {"%s_proj_weight" % n: jnp.eye(s * dh, h * dh, dtype=F32)
         for n, s in (("q", h), ("k", hkv), ("v", hkv), ("o", h))}
    x = jnp.asarray(rng.randn(2, T, h * dh), F32)
    was, REF.QUERY_BLOCK = REF.QUERY_BLOCK, 8
    try:
        with jax.default_matmul_precision("highest"):
            blocked = REF.attention(p, "", x, m, "full")
            REF.QUERY_BLOCK = 1024
            whole = REF.attention(p, "", x, m, "full")
    finally:
        REF.QUERY_BLOCK = was
    assert np.abs(np.asarray(blocked) - np.asarray(whole)).max() <= 1e-5


def test_the_tiles_the_kernel_visits_at_the_cells_shape():
    """8 x 8 tiles of 1024 over 8192 rows under a window of 4096: 30 hold
    an allowed pair (at most 5 key tiles a query tile) and 12 of them are
    partial, for 24.0 tiles' worth of pairs; the causal mask visits 36 (8
    partial) for 32.0."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)
    from mxnet_tpu.ops import transformer as tf_ops

    def tiles(mask, T, tile):
        some = whole = 0
        for i in range(0, T, tile):
            for j in range(0, T, tile):
                part = mask[i:i + tile, j:j + tile]
                some += bool(part.any())
                whole += bool(part.all())
        return some, some - whole

    window = tf_ops._splash_mask()(8192, ("sliding_window", 4096))
    assert tiles(window, 8192, 1024) == (30, 12)
    assert tiles(sm.CausalMask((8192, 8192)), 8192, 1024) == (36, 8)
    assert REF.allowed_pairs(8192, 4096) / 1024 ** 2 == pytest.approx(
        24.0, abs=2e-3)
    assert REF.allowed_pairs(8192) / 1024 ** 2 == pytest.approx(32.0,
                                                                abs=4e-3)


def test_the_attention_at_the_cells_shape_lowers_to_the_kernel_on_a_tpu():
    """bfloat16 ``[1, 8192, 28, 128]`` over 4 key/value heads, lowered
    for a TPU, is the splash kernel, forward and fused backward, under
    the window of 4096 as under the causal mask, nothing padded or
    repeated; the track names the key/value heads, the mask and its
    window."""
    from mxnet_tpu.ops import transformer as tf_ops
    q = jax.ShapeDtypeStruct((1, 8192, 28, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)
    tracks = {}
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        for mask in (dict(mask="sliding_window", window=4096), {}):
            fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
                q, k, v, 128 ** -0.5, **mask).astype(F32).sum(),
                argnums=(0, 1, 2)))
            mark = time.perf_counter_ns()
            text = jax.export.export(fn, platforms=["tpu"])(q, kv, kv) \
                .mlir_module()
            event = mx.trace.counter_events(["attn:lowering"],
                                            since_ns=mark)[0]
            assert text.count("tpu_custom_call") == 2
            assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
            assert "stablehlo.pad" not in text
            assert event["args"]["kernel"] == 1
            tracks[bool(mask)] = event["id"]
    finally:
        mx.trace.set_enabled(was)
    assert tracks == {
        True: "bfloat16[1, 8192, 28, 128]/kv4/sliding_window4096",
        False: "bfloat16[1, 8192, 28, 128]/kv4"}


# -- the programs of the cells that are there ----------------------------------
@pytest.mark.parametrize("case", ["olmoe", "afmoe"])
def test_the_tiny_steps_of_the_other_builders_lower_to_what_they_did(case):
    """``router_data=None`` and an unset ``act_zeros`` are the graph that
    was: a tiny OLMoE step (the whole layer's three nodes) and a tiny
    AFMoE step (a rank's share node) lower to the text the commits before
    this PR gave."""
    if case == "olmoe":
        net = olmoe_lm(num_layers=2, hidden_size=32, num_heads=2,
                       num_experts=8, experts_per_tok=2, expert_width=16,
                       vocab_size=64, seq_len=16)
    else:
        with mx.name.NameManager():
            net = afmoe_lm(
                num_layers=4, hidden_size=32,
                layer_types=["sliding", "sliding", "sliding", "full"],
                dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
                window=6, rope_theta=1e4, dense_width=48, num_experts=16,
                experts_per_tok=4, expert_width=24, shared_width=24,
                route_scale=2.826, vocab_size=50, seq_len=16,
                embed_scale=32 ** 0.5, experts_held=4, first_expert=4,
                bias_rate=1e-3, rms_eps=1e-5)
    inputs = dict(data=(2, 16), softmax_label=(2, 16))
    shapes, _, aux_shapes = net.infer_shape(**inputs)
    args = {n: jax.ShapeDtypeStruct(s, jnp.int32 if n in inputs else F32)
            for n, s in zip(net.list_arguments(), shapes)}
    aux = {n: jax.ShapeDtypeStruct(s, F32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    prog = _GraphProgram(net, {}, None, do_mirror=False)

    def loss(a, x):
        outs = prog.eval(a, x, jax.random.PRNGKey(0), True)[0]
        return sum(jnp.sum(o.astype(F32)) for o in outs)

    params = {k: v for k, v in args.items() if k not in inputs}
    # each held text's own signature: the function's name and arguments
    # are part of the lowered module
    if case == "olmoe":
        def step(p, d, l):
            return jax.value_and_grad(
                lambda p: loss(dict(p, data=d, softmax_label=l), {}))(p)
        text = jax.jit(step).lower(params, args["data"],
                                   args["softmax_label"]).as_text()
    else:
        def step(p, x, d, l):
            return jax.value_and_grad(
                lambda p: loss(dict(p, data=d, softmax_label=l), x))(p)
        text = jax.jit(step).lower(params, aux, args["data"],
                                   args["softmax_label"]).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == STEP_TEXT_WAS[case]


# -- the counters --------------------------------------------------------------
class _Train:
    is_train = True


@pytest.mark.parametrize("case, held_rows", [
    ("under", 400), ("exactly_full", 1024), ("overflow", 1600)])
def test_act_zeros_counts_the_rows_really_held_when_the_bound_pads(
        case, held_rows, monkeypatch):
    """512 tokens x 4 choices over 32 experts, 4 held: the node runs over
    a bound of 1024 sorted rows (and over the rest where they overflow
    it).  ``(zeros, lanes)`` count the held rows' gate lanes and never a
    row behind them, on both sides of the bound: against a count written
    out expert by expert."""
    monkeypatch.setattr(share_rule, "BOUND_WORTH_ROWS", 0)
    T, K, E, HELD, FIRST, D, H = 512, 4, 32, 4, 5, 32, 48
    assert held_rows_bound(T * K, E, HELD) == 1024
    share = dict(experts_held=HELD, first_expert=FIRST)
    ffn = dict(num_hidden=H, output_dim=D, act_type="relu", no_bias=True,
               gated=True, layer=47, **share)
    dispatch, node = (mx.ops.get_op(n) for n in ("_moe_dispatch",
                                                 "_moe_share_ffn"))
    dp = dispatch.parse_params(dict(num_experts=E, k=K, capacity_factor=0.0,
                                    renormalize=True, layer=47, **share))
    counting = node.parse_params(dict(ffn, act_zeros=True))
    plain = node.parse_params(ffn)
    assert node.list_outputs(counting) == ["output", "act_zeros"]
    assert node.list_outputs(plain) == ["output"]
    assert node.infer_shape(counting, [(T, D), (T, K), None, None, (E,)]
                            + [None] * 3)[1] == [(T, D), (2,)]
    rng = np.random.RandomState(held_rows)
    logits = rng.randn(T, E).astype(np.float32)
    logits[:, FIRST:FIRST + HELD] = 0.1 * logits[:, FIRST:FIRST + HELD] - 12
    logits[:held_rows // HELD, FIRST:FIRST + HELD] += 24.0
    x = rng.randn(T, D).astype(np.float32)
    ws = [jnp.asarray(rng.randn(HELD, *s) / 6, F32)
          for s in ((D, H), (D, H), (H, D))]

    @jax.jit
    def run(x, logits, *ws):
        d = dispatch.forward(dp, [x, logits], [], _Train)
        ins = [x, d[1], d[2], d[7], d[4]] + list(ws)
        return (node.forward(counting, ins, [], _Train),
                node.forward(plain, ins, [], _Train), d[4])

    (out, seen), (same,), counts = run(jnp.asarray(x), jnp.asarray(logits),
                                       *ws)
    assert np.array_equal(np.asarray(out), np.asarray(same))
    assert float(np.asarray(counts)[FIRST:FIRST + HELD].sum()) == held_rows
    chosen = np.argsort(-logits, axis=1, kind="stable")[:, :K]
    zeros = 0
    for e in range(HELD):
        rows = x[(chosen == FIRST + e).any(axis=1)]
        zeros += int((np.maximum(rows @ np.asarray(ws[0][e]), 0) == 0).sum())
    assert [float(v) for v in seen] == [zeros, held_rows * H]
    assert 0.3 < zeros / (held_rows * H) < 0.7
    # no gradient flows through the count

    def counted(x, *ws):
        d = dispatch.forward(dp, [x, jnp.asarray(logits)], [], _Train)
        return node.forward(counting, [x, d[1], d[2], d[7], d[4]] + list(ws),
                            [], _Train)[1].sum()

    for g in jax.grad(counted, argnums=(0, 1))(jnp.asarray(x), *ws):
        assert not np.asarray(g).any()


def _fit(net, tokens, labels, steps=3):
    X = np.concatenate([tokens] * steps)
    Y = np.concatenate([labels] * steps)
    mod = mx.mod.Module(net, context=mx.cpu(0))
    since = time.perf_counter_ns()
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=BATCH), num_epoch=1,
            eval_metric=mx.metric.OutputMean(0), optimizer="adam",
            initializer=mx.init.Normal(0.02), optimizer_params=dict(ADAM))
    counters = mx.trace.counter_events(
        ["moe:act_zeros", "moe:load", "moe:router_rows"], since_ns=since)
    spans = mx.trace.span_events(
        names=["fit:step", "fit:moe_act_zeros", "fit:moe_load",
               "fit:update_metric"], since_ns=since)
    return mod, counters, spans


def test_fit_records_the_zeros_once_a_step_and_block():
    net, kwargs, _, tokens, labels = _tiny(seed=3)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(net, tokens, labels)
    finally:
        mx.trace.reset()         # the ring is the process's: leave none
        mx.trace.set_enabled(was)
    assert mod._fused.head("moe_act_zeros") == (2, SHARES)
    seen = [e for e in counters if e["name"] == "moe:act_zeros"]
    assert [e["id"] for e in seen] == SHARES * 3
    load = [e["args"] for e in counters if e["name"] == "moe:load"]
    for e, held in zip(seen, load):
        a = e["args"]
        assert set(a) == {"zeros", "lanes"}
        # the lanes are the held rows' (moe:load's ``held``), not the
        # rows of a bound
        assert a["lanes"] == held["held"] * kwargs["expert_width"]
        assert 0 < a["zeros"] < a["lanes"]
    # each trace of a dispatch node says which rows its router read
    rows = [e for e in counters if e["name"] == "moe:router_rows"]
    assert rows and {e["id"] for e in rows} == {"l0", "l1", "l2", "l3"}
    assert all(e["args"] == {"mixer": 1, "ffn": 0} for e in rows)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append((e["ts"],
                                                  e["ts"] + e["dur"]))
    assert len(by_name["fit:moe_act_zeros"]) == len(by_name["fit:moe_load"]) \
        == 3

    def inside(span, others):
        return any(a <= span[0] and span[1] <= b for a, b in others)

    for span in by_name["fit:moe_act_zeros"]:
        assert inside(span, by_name["fit:step"])
        assert not inside(span, by_name["fit:update_metric"])
        assert not inside(span, by_name["fit:moe_load"])


def test_nothing_is_recorded_without_the_head_or_while_tracing_is_off():
    net, kwargs, _, tokens, labels = _tiny(seed=3, act_zeros=False)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(net, tokens, labels)
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert mod._fused.head("moe_act_zeros") is None
    assert not [e for e in counters if e["name"] == "moe:act_zeros"]
    assert not [e for e in spans if e["name"] == "fit:moe_act_zeros"]
    assert [e for e in counters if e["name"] == "moe:load"]
    net, _, _, tokens, labels = _tiny(seed=3)
    mx.trace.set_enabled(False)
    try:
        mod, counters, spans = _fit(net, tokens, labels)
    finally:
        mx.trace.set_enabled(was)
    assert mod._fused.head("moe_act_zeros") == (2, SHARES)
    assert not [e for e in counters if e["name"] == "moe:act_zeros"]
    assert not [e for e in spans if e["name"] == "fit:moe_act_zeros"]
    # a router that reads the experts' rows says so
    olmoe = olmoe_lm(num_layers=1, hidden_size=16, num_heads=2,
                     num_experts=4, experts_per_tok=2, expert_width=12,
                     vocab_size=40, seq_len=16)
    rng = np.random.RandomState(0)
    X = rng.randint(0, 40, (BATCH, 16)).astype(np.int32)
    mx.trace.set_enabled(True)
    try:
        _, counters, _ = _fit(olmoe, X, np.roll(X, -1, 1))
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    rows = [e for e in counters if e["name"] == "moe:router_rows"]
    assert rows and all(e["args"] == {"mixer": 0, "ffn": 1} for e in rows)


def test_device_scopes_and_the_lowering_counter_name_both_kinds():
    net, kwargs, params, tokens, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(tokens), softmax_label=jnp.asarray(labels))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.jit(lambda a: prog.eval(a, {}, jax.random.PRNGKey(0),
                                           True)[0]).lower(args) \
            .as_text(debug_info=True)
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    for scope in ("attn_proj.l0", "attn.l0", "attn.l3", "moe_experts.l0",
                  "moe_route.l2", "moe_combine.l3", "lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope
    assert "attn_gate" not in text
    # the full layer, then three window layers: a sample an op
    assert [e["id"] for e in events] == ["float32[2, 16, 6, 8]/kv2"] \
        + ["float32[2, 16, 6, 8]/kv2/sliding_window6"] * 3


# -- counts --------------------------------------------------------------------
def test_reference_flops_are_the_hand_count():
    """ISSUE 47's arithmetic, a forward token: projections 4 x 41.94 M,
    allowed pairs 3 x 44.04 M + 58.73 M, four routers 1.31 M, the held
    share of six choices (6 x 8 / 64 = 0.75 expert a token) 4 x 8.85 M,
    the head 97.24 M: 492.5 M, three times that a trained token."""
    kwargs = dict(num_layers=4, hidden_size=2560,
                  layer_types=["full", "sliding", "sliding", "sliding"],
                  num_heads=28, num_kv_heads=4, head_dim=128, window=4096,
                  num_experts=64, experts_per_tok=6, expert_width=768,
                  vocab_size=18992, seq_len=8192, experts_held=8)
    proj = 2 * 2560 * 128 * (28 + 4 + 4 + 28)
    assert proj == 41_943_040
    window_pairs = 4096 * 4097 // 2 + 4096 * 4096
    causal_pairs = 8192 * 8193 // 2
    assert (window_pairs, causal_pairs) == (25_167_872, 33_558_528)
    assert REF.allowed_pairs(8192, 4096) == window_pairs
    assert REF.allowed_pairs(8192) == REF.allowed_pairs(8192, 8192) \
        == causal_pairs
    scores = 4 * 128 * 28 * (3 * window_pairs + causal_pairs) / 8192
    routers = 4 * 2 * 2560 * 64
    held = 4 * 0.75 * 6 * 2560 * 768
    head = 2 * 2560 * 18992
    forward = 4 * proj + scores + routers + held + head
    assert forward == pytest.approx(492.5e6, rel=1e-3)
    got = REF.train_flops_per_sample({"model": {"kwargs": kwargs}})
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert got == pytest.approx(1.478e9, rel=1e-3)
    assert got * 8192 == pytest.approx(12.10e12, rel=1e-3)
    assert scores / forward == pytest.approx(0.387, abs=0.003)
    assert (4 * proj + scores) / forward == pytest.approx(0.728, abs=0.003)
    # all experts held: six experts a token
    whole = REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kwargs, experts_held=0)}})
    assert whole - got == pytest.approx(3 * 4 * 5.25 * 6 * 2560 * 768,
                                        rel=1e-12)
    # at the other cells' 4096 tokens the window is the causal mask
    assert REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kwargs, seq_len=4096)}}) \
        == REF.train_flops_per_sample({"model": {"kwargs": dict(
            kwargs, seq_len=4096, layer_types=["full"] * 4)}})


# -- ISSUE 70: q's and k's norm and rotation, one node on the rows ---------
def test_q_and_k_are_placed_by_one_node_on_the_rows():
    """This model has no head norms: a sliding layer's q and k are
    rotated by ONE ``HeadNormRotary`` (``norm`` off, no weight) under
    ``attn_proj.l<i>``; the full layer, which has no positions either,
    has no node between its projections and attention."""
    net = smallthinker_lm(**TINY)
    sliding = [l for l, kind in enumerate(TINY["layer_types"])
               if kind == "sliding"]
    placed = placed_on_rows(net)
    assert [(name, scope, ins) for name, scope, _, ins in placed] == [
        ("l%d_%s_rotary" % (l, x), "attn_proj.l%d" % l,
         ["l%d_%s_proj" % (l, x)]) for l in sliding for x in "qk"]
    for _, _, how, _ in placed:
        assert (how["head_dim"], how["norm"], how["seq_len"],
                how["theta"]) == (TINY["head_dim"], False, TINY["seq_len"],
                                  TINY["rope_theta"])
    assert not nodes(net, "RotaryEmbedding")
    assert not [a for a in net.list_arguments() if a.endswith("norm_gamma")
                and a[3:] in ("q_norm_gamma", "k_norm_gamma")]
