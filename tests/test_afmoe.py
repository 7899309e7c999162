"""AFMoE (Trinity-Mini) through the Symbol graph (ISSUE 41, tier-1): the
sliding-window mask against its definition written out pair by pair, in
both lowerings and against the library's own local mask; the op under
the window against a dense-mask softmax; the whole tiny model against
``benchmark/reference/trinity-mini.py`` in float32 (loss, every gradient,
Adam's first step, the selection bias's first move); one test a
departure from a plain pre-norm decoder (the output gate, each
post-norm, the embedding's scale, rotation on sliding layers only); the
eight ranks' shares of one expert layer against the uncut layer; the
FLOP count by hand; the tiny SDAR step's lowered text as it was; the TPU
lowering of the attention at the cell's shape."""
import functools
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
from mxnet_tpu.models import afmoe_lm, sdar_moe_lm        # noqa: E402
from mxnet_tpu.moe import find_load_heads                 # noqa: E402
from mxnet_tpu.moe.layer import MoEFeedForward            # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, placed_on_rows      # noqa: E402

REF = manifest.load_module("reference", "trinity-mini")

TINY = dict(num_layers=4, hidden_size=32, layer_types=["sliding", "sliding",
                                                       "sliding", "full"],
            dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
            window=6, rope_theta=1e4, dense_width=48, num_experts=16,
            experts_per_tok=4, expert_width=24, shared_width=24,
            route_scale=2.826, vocab_size=50, seq_len=16,
            embed_scale=32 ** 0.5, experts_held=4, first_expert=4,
            bias_rate=1e-3, rms_eps=1e-5)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
BLOCKS = ["l1_moe_dispatch", "l2_moe_dispatch", "l3_moe_dispatch"]
F32, BF16 = jnp.float32, jnp.bfloat16
# sha256 of a tiny SDAR step's lowered text at the commit before the op
# took a third mask (96d8256): under ``block_diffusion`` the mask's choice
# and the plain blocks lower to what they did.  Taken again at PR 66 (at
# whose parent it read what it did): the tiny rank's share has no row bound
# and lowers as the one window ``(0, T*k)`` since.  And at ISSUE 70, which
# meant to move it: q and k pass ``HeadNormRotary``, at these 8-lane heads
# the old statements between two more reshapes
SDAR_STEP_TEXT = \
    "6d75c6c781731597ec0d91120f5704b2a671389a1ca8191b6a047890fe93c226"


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _by_hand(T, W):
    """The mask of ISSUE 41, one pair at a time: query ``i`` reads its
    own position and the ``W - 1`` before it."""
    allowed = np.zeros((T, T), bool)
    for i in range(T):
        for j in range(T):
            allowed[i, j] = 0 <= i - j < W
    return allowed


def _dense(q, k, v, scale, allowed):
    """Dense float64 attention, the key/value heads repeated."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = np.where(allowed[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)


# -- the mask ------------------------------------------------------------------
@pytest.mark.parametrize("T, W", [(16, 4), (24, 7), (13, 5), (16, 1)])
def test_the_window_mask_is_its_definition_written_out_pair_by_pair(T, W):
    want = _by_hand(T, W)
    ids = np.arange(T)
    assert np.array_equal(
        tf_ops.sliding_window_allowed(ids[:, None], ids[None, :], W), want)
    assert np.array_equal(REF.window_mask(T, W), want)
    # traced ids give the same; every row sees itself and at most W keys
    got = jax.jit(lambda i: tf_ops.sliding_window_allowed(
        i[:, None], i[None, :], W))(jnp.arange(T))
    assert np.array_equal(np.asarray(got), want)
    assert want.diagonal().all() and want.sum(axis=1).max() == min(W, T)
    assert want.sum() == REF.allowed_pairs(T, W) \
        == W * (W + 1) // 2 + (T - W) * W
    # the function both lowerings are made of, and the kernel's object
    assert np.array_equal(tf_ops._mask_function(("sliding_window", W), T)(
        ids[:, None], ids[None, :]), want)
    if T % 2 == 0:      # the library's slices want a shape its shards split
        splash = tf_ops._splash_mask()(T, ("sliding_window", W))
        assert np.array_equal(splash[0:T, 0:T], want)


@pytest.mark.parametrize("W", [16, 17, 4096])
def test_a_window_of_the_whole_sequence_is_the_causal_mask(W):
    T = 16
    causal = np.tril(np.ones((T, T), bool))
    assert np.array_equal(_by_hand(T, W), causal)
    assert np.array_equal(REF.window_mask(T, W), causal)
    assert np.array_equal(REF.window_mask(T), causal)
    assert REF.allowed_pairs(T, W) == REF.allowed_pairs(T) == T * (T + 1) // 2
    # ... and lowers, and is counted, as it
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, T, 2, 8), F32) for _ in range(3))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        got = jax.make_jaxpr(lambda q, k, v: tf_ops.causal_attention(
            q, k, v, 0.3, "sliding_window", window=W))(q, k, v)
        want = jax.make_jaxpr(lambda q, k, v: tf_ops.causal_attention(
            q, k, v, 0.3))(q, k, v)
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert str(got) == str(want)
    assert [e["id"] for e in events] == ["float32[1, 16, 2, 8]"] * 2


def test_the_kernels_mask_object_is_the_librarys_local_mask_tile_by_tile():
    """The computable mask over the repo's own function against the
    library's ``LocalMask(window_size=(W - 1, 0))`` on every tile of 128
    over 1024 rows; equal masks are one kernel's, another window or
    another kind is another's."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)
    T, W, tile = 1024, 300, 128
    mine = tf_ops._splash_mask()(T, ("sliding_window", W))
    theirs = sm.LocalMask((T, T), window_size=(W - 1, 0), offset=0)
    for i in range(0, T, tile):
        for j in range(0, T, tile):
            assert np.array_equal(mine[i:i + tile, j:j + tile],
                                  theirs[i:i + tile, j:j + tile]), (i, j)
    assert mine == tf_ops._splash_mask()(T, ("sliding_window", W))
    assert hash(mine) == hash(tf_ops._splash_mask()(T, ("sliding_window", W)))
    assert mine != tf_ops._splash_mask()(T, ("sliding_window", W + 1))
    assert mine != tf_ops._splash_mask()(T, ("block_diffusion", 4))
    assert mine != sm.CausalMask((T, T))


def test_the_tiles_the_kernel_visits_at_the_cells_shape():
    """4 x 4 tiles of 1024 over 4096 rows under a window of 2048: 9 hold
    an allowed pair and 6 of them are partial, for 6.0 tiles' worth of
    pairs; the causal mask visits 10 (4 partial); tiles of 512 would
    visit 30 of 64 for 24.0 of theirs."""
    def tiles(mask, T, tile):
        some = whole = 0
        for i in range(0, T, tile):
            for j in range(0, T, tile):
                part = mask[i:i + tile, j:j + tile]
                some += bool(part.any())
                whole += bool(part.all())
        return some, some - whole

    window = tf_ops._splash_mask()(4096, ("sliding_window", 2048))
    assert tiles(window, 4096, 1024) == (9, 6)
    assert tiles(window, 4096, 512) == (30, 12)
    assert REF.allowed_pairs(4096, 2048) == 6292480
    assert REF.allowed_pairs(4096, 2048) / 1024 ** 2 == pytest.approx(
        6.0, abs=2e-3)
    assert REF.allowed_pairs(4096, 2048) / 512 ** 2 == pytest.approx(
        24.0, abs=8e-3)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)
    assert tiles(sm.CausalMask((4096, 4096)), 4096, 1024) == (10, 4)


# -- the op --------------------------------------------------------------------
@pytest.mark.parametrize("h, hkv", [(8, 1), (2, 2)],
                         ids=["8-over-1", "equal-heads"])
def test_the_op_under_the_window_against_a_dense_mask_softmax(h, hkv):
    """The plain lowering (rows in several blocks of queries) through
    the op's symbol: output and all three input gradients against dense
    float64 attention under the mask written out by hand."""
    T, W, dh, dv = 40, 11, 8, 6
    rng = np.random.RandomState(3)
    q, k, v = (rng.randn(2, T, n, d).astype(np.float32)
               for n, d in ((h, dh), (hkv, dh), (hkv, dv)))
    w = rng.randn(2, T, h, dv).astype(np.float32)
    allowed = _by_hand(T, W)

    def dense(q, k, v):
        group = h // hkv
        kr, vr = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * 0.3
        s = jnp.where(jnp.asarray(allowed)[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)

    with jax.default_matmul_precision("highest"):
        want_out, vjp = jax.vjp(dense, *(jnp.asarray(x) for x in (q, k, v)))
        want = [want_out] + list(vjp(jnp.asarray(w)))
    assert np.allclose(want_out, _dense(q, k, v, 0.3, allowed), atol=1e-5)

    net = mx.sym.CausalSelfAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"),
        scale=0.3, mask="sliding_window", window=W)
    exe = net.simple_bind(mx.cpu(), grad_req="write", q=q.shape, k=k.shape,
                          v=v.shape)
    for name, x in (("q", q), ("k", k), ("v", v)):
        exe.arg_dict[name][:] = x
    # more than one block of queries
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


def test_the_op_refuses_a_window_that_reads_nothing():
    q = mx.sym.Variable("q")
    for window in (0, -3):
        net = mx.sym.CausalSelfAttention(q, q, q, mask="sliding_window",
                                         window=window)
        with pytest.raises(mx.MXNetError):
            net.infer_shape(q=(2, 8, 2, 4))
        x = jnp.zeros((1, 12, 2, 4))
        with pytest.raises(mx.MXNetError):
            tf_ops.causal_attention(x, x, x, 0.5, "sliding_window",
                                    window=window)
    with pytest.raises(mx.MXNetError):
        mx.sym.CausalSelfAttention(q, q, q, mask="local", window=4)
    # the window is no part of the other masks' checks, nor theirs of it
    net = mx.sym.CausalSelfAttention(q, q, q, mask="sliding_window",
                                     window=3)
    assert net.infer_shape(q=(2, 8, 2, 4))[1] == [(2, 8, 2, 4)]
    assert mx.sym.CausalSelfAttention(q, q, q).infer_shape(
        q=(2, 8, 2, 4))[1] == [(2, 8, 2, 4)]


def test_the_kernel_under_the_window_interpreted(monkeypatch):
    """The library kernel the TPU lowering runs, interpreted on the CPU
    at tiles of 128 over 512 rows under a window of 200, 4 query heads
    over 1 key/value head: output and the three input gradients against
    the plain blocks in float32, inside bfloat16's rounding; a key that
    has left a query's window does not move it."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(sk, "make_splash_mha_single_device",
                        functools.partial(sk.make_splash_mha_single_device,
                                          interpret=True))
    monkeypatch.setattr(tf_ops, "ATTN_KERNEL_BLOCK", 128)
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 512, h, 128), BF16)
               for h in (4, 1, 1))
    w = jnp.asarray(rng.randn(1, 512, 4, 128), F32)
    assert tf_ops._kernel_takes(q, k, v)
    kind, scale = ("sliding_window", 200), 128 ** -0.5

    def run(fn, *args):
        out, vjp = jax.vjp(lambda *a: fn(*a, scale, kind).astype(F32),
                           *args)
        return [np.asarray(x, np.float32) for x in (out,) + vjp(w)]

    got = run(tf_ops._flash_attention, q, k, v)
    want = run(tf_ops._plain_attention, *(x.astype(F32) for x in (q, k, v)))
    assert np.allclose(want[0], _dense(q, k, v, scale, _by_hand(512, 200)),
                       atol=1e-4)
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 0.02 * np.abs(r).max()
    # keys 100..103: rows 100..302 read at least one, rows from 303 none
    k2, v2 = k.at[:, 100:104].add(1.0), v.at[:, 100:104].add(-1.0)
    moved = np.asarray(tf_ops._flash_attention(q, k2, v2, scale, kind),
                       np.float32)
    assert np.array_equal(moved[:, :100], got[0][:, :100])
    assert np.array_equal(moved[:, 303:], got[0][:, 303:])
    assert not np.array_equal(moved[:, 100:303], got[0][:, 100:303])


def test_the_attention_at_the_cells_shape_lowers_to_the_kernel_on_a_tpu():
    """bfloat16 ``[1, 4096, 32, 128]`` over 4 key/value heads under the
    window of 2048, lowered for a TPU, is the splash kernel, forward and
    fused backward, nothing padded or repeated; the track names the
    key/value heads, the mask and its window."""
    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), BF16)
    kv = jax.ShapeDtypeStruct((1, 4096, 4, 128), BF16)
    fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, 128 ** -0.5, "sliding_window", window=2048)
        .astype(F32).sum(), argnums=(0, 1, 2)))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(fn, platforms=["tpu"])(q, kv, kv) \
            .mlir_module()
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert text.count("tpu_custom_call") == 2
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert "stablehlo.pad" not in text
    assert events[0]["args"] == {"kernel": 1, "plain": 0, "pair": "rows",
                                 "mask_form": "function"}
    assert events[0]["id"] == \
        "bfloat16[1, 4096, 32, 128]/kv4/sliding_window2048"


# -- the programs of the cells that are there ----------------------------------
def test_the_sdar_symbols_lowered_text_is_what_it_was():
    """The ``block_diffusion`` branch is statement for statement the
    program it was: a tiny SDAR step (forward and every gradient) lowers
    to the text the commit before this PR gave.  (The causal branch is
    held by the tiny OLMoE step in ``tests/test_sdar_moe.py``.)"""
    net = sdar_moe_lm(num_layers=2, hidden_size=32, num_heads=4,
                      num_kv_heads=2, head_dim=8, num_experts=16,
                      experts_per_tok=4, expert_width=24, vocab_size=50,
                      seq_len=16, block_len=4, rope_theta=1e6, rms_eps=1e-6,
                      aux_coef=0.001, experts_held=4, first_expert=4)
    shapes, _, _ = net.infer_shape(data=(2, 32), softmax_label=(2, 2, 16))
    inputs = ("data", "softmax_label")
    args = {n: jax.ShapeDtypeStruct(s, jnp.int32 if n == "data" else F32)
            for n, s in zip(net.list_arguments(), shapes)}
    prog = _GraphProgram(net, {}, None, do_mirror=False)

    def loss(a):
        outs = prog.eval(a, {}, jax.random.PRNGKey(0), True)[0]
        return sum(jnp.sum(o.astype(F32)) for o in outs)

    def step(p, d, l):
        return jax.value_and_grad(
            lambda p: loss(dict(p, data=d, softmax_label=l)))(p)

    params = {k: v for k, v in args.items() if k not in inputs}
    text = jax.jit(step).lower(params, args["data"],
                               args["softmax_label"]).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == SDAR_STEP_TEXT


# -- the model -----------------------------------------------------------------
def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = afmoe_lm(**kwargs)
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


def test_the_builder_names_its_heads_and_refuses_what_it_cannot_build():
    net, kwargs, _, _, _ = _tiny(seed=0)
    assert net.list_outputs() == ["lm_output", "moe_load_output"]
    assert find_load_heads(net) == (1, BLOCKS)
    assert sorted(net.list_auxiliary_states()) \
        == [b + "_select_bias" for b in BLOCKS]
    args = net.list_arguments()
    # a dense lead, then expert layers; every layer the gate and four norms
    assert "l0_gate_proj_weight" in args and "l0_moe_gate_weight" not in args
    assert "l1_moe_gate_weight" in args and "l1_gate_proj_weight" not in args
    for l in range(4):
        for part in ("attn_gate_proj_weight", "attn_norm_gamma",
                     "attn_post_norm_gamma", "ffn_norm_gamma",
                     "ffn_post_norm_gamma", "q_norm_gamma", "k_norm_gamma"):
            assert "l%d_%s" % (l, part) in args
    for bad in (dict(layer_types=["sliding"] * 3),
                dict(layer_types=["sliding", "window", "full", "full"]),
                dict(num_kv_heads=3)):
        with pytest.raises(ValueError):
            afmoe_lm(**dict(kwargs, **bad))


def test_model_matches_reference_loss_gradients_adam_step_and_bias_move(
        monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    assert abs(float(outs[0].mean()) - ref["loss"]) <= 1e-5 * ref["loss"]
    for row, block in zip(outs[1], BLOCKS):
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][block]))
        assert row[-1] == 0 and row[:-1].sum() == 16 * BATCH * 4
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 2e-4, errors

    # the configuration's optimizer: Adam's first step and the bias
    names = ["l1_q_proj_weight", "l1_attn_gate_proj_weight",
             "l3_q_proj_weight", "l3_k_proj_weight", "l1_moe_gate_weight",
             "l1_moe_experts_i2h_weight", "l2_attn_post_norm_gamma",
             "embed_weight", "lm_head_weight"]
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    assert want["loss"] == ref["loss"]
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, aux = mod.get_params()
    for name in names:
        got = after[name].asnumpy() - params[name]
        # an element whose gradient is ~0 may flip sign: Adam's first
        # step is lr * sign(g); such elements are a sliver of the norm
        assert _rel(got, want["updates"][name]) <= 0.02, name
    assert sorted(aux) == sorted(b + "_select_bias" for b in BLOCKS)
    for block in BLOCKS:
        moved = aux[block + "_select_bias"].asnumpy()
        assert np.allclose(moved, want["bias_moves"][block], atol=1e-9)
        assert np.allclose(np.abs(moved)[moved != 0], 1e-3)


# -- the departures from a plain pre-norm decoder, one test each ---------------
def test_the_output_gate_scales_the_heads_before_the_output_projection():
    """With ``Wg`` = 0 the gate is 1/2 everywhere: the attention branch
    is ``o_proj`` of half the heads' outputs, which the post-norm takes
    back, so the loss is that of ``Wg`` = 0 and ``2 Wo``; a random ``Wg``
    gives another, the reference's."""
    net, kwargs, params, tokens, labels = _tiny(seed=11)
    cfg = {"model": {"kwargs": kwargs}}
    base = _losses(net, params, tokens, labels)
    assert abs(base.mean() - REF.loss_and_grads(
        cfg, params, tokens, labels, names=[])["loss"]) <= 1e-5 * base.mean()
    flat = {k: (np.zeros_like(v) if "attn_gate_proj" in k else v)
            for k, v in params.items()}
    halves = _losses(net, flat, tokens, labels)
    assert abs(halves.mean() - REF.loss_and_grads(
        cfg, flat, tokens, labels, names=[])["loss"]) <= 1e-5 * halves.mean()
    assert abs(halves.mean() - base.mean()) > 1e-3
    # without the post-norm's eps, a gate of 1/2 is a scale the norm
    # removes: the elementwise product is before o_proj, not after it
    turned = {k: (v[:, ::-1].copy() if "attn_gate_proj" in k else v)
              for k, v in params.items()}
    assert abs(_losses(net, turned, tokens, labels).mean()
               - base.mean()) > 1e-4


@pytest.mark.parametrize("branch, out_weights", [
    ("attn", ("o_proj_weight",)),
    ("ffn", ("down_proj_weight", "moe_experts_h2o_weight",
             "moe_shared_h2o_weight"))])
def test_a_post_norm_takes_the_scale_of_its_branch(branch, out_weights):
    """``x + N(f(x))``: a branch whose output weights are all multiplied
    by 8 adds what it added (the norm divides the scale out again, up to
    its eps), which no pre-norm block does; the post-norm's own gain
    does move the loss, as the reference's."""
    net, kwargs, params, tokens, labels = _tiny(seed=13)
    cfg = {"model": {"kwargs": kwargs}}
    base = _losses(net, params, tokens, labels)
    louder = {k: (8.0 * v if k.endswith(out_weights) else v)
              for k, v in params.items()}
    assert any(k.endswith(out_weights) for k in params)
    assert np.allclose(_losses(net, louder, tokens, labels), base,
                       rtol=2e-3, atol=2e-3)
    gained = {k: (3.0 * v if k.endswith(branch + "_post_norm_gamma") else v)
              for k, v in params.items()}
    got = _losses(net, gained, tokens, labels)
    assert abs(got.mean() - base.mean()) > 1e-2
    assert abs(got.mean() - REF.loss_and_grads(
        cfg, gained, tokens, labels, names=[])["loss"]) <= 1e-5 * got.mean()


def test_the_embeddings_scale_is_on_the_residual_stream():
    """``embed_scale = s`` with the table ``E`` is ``embed_scale = 1``
    with the table ``s E``, and is not the table ``E`` alone: the first
    norm removes the scale from its branch only."""
    net, kwargs, params, tokens, labels = _tiny(seed=17)
    s = kwargs["embed_scale"]
    plain = afmoe_lm(**dict(kwargs, embed_scale=1.0))
    scaled = dict(params, embed_weight=np.float32(s) * params["embed_weight"])
    base = _losses(net, params, tokens, labels)
    assert np.allclose(_losses(plain, scaled, tokens, labels), base,
                       rtol=1e-4, atol=1e-5)
    left_out = _losses(plain, params, tokens, labels)
    assert abs(left_out.mean() - base.mean()) > 1e-2
    cfg = {"model": {"kwargs": dict(kwargs, embed_scale=1.0)}}
    assert abs(left_out.mean() - REF.loss_and_grads(
        cfg, params, tokens, labels, names=[])["loss"]) \
        <= 1e-5 * left_out.mean()


def test_only_sliding_layers_are_rotated():
    """One block, no rotation: a full layer knows the ORDER of the keys
    through its mask alone, so with tokens 0 and 1 exchanged every
    later position reads the same set of keys and its loss stays.  A
    sliding layer whose window holds the whole sequence has the same
    mask and is rotated: the same exchange moves every later loss."""
    kwargs = dict(TINY, num_layers=1, dense_layers=0, window=64)
    results = {}
    for kind in ("full", "sliding"):
        net, _, params, tokens, labels = _tiny(seed=19, **dict(
            kwargs, layer_types=[kind]))
        tokens[:, 1] = (tokens[:, 0] + 1) % kwargs["vocab_size"]
        other = tokens.copy()
        other[:, [0, 1]] = tokens[:, [1, 0]]
        results[kind] = (_losses(net, params, tokens, labels),
                         _losses(net, params, other, labels))
        cfg = {"model": {"kwargs": dict(kwargs, layer_types=[kind])}}
        assert abs(results[kind][0].mean() - REF.loss_and_grads(
            cfg, params, tokens, labels, names=[])["loss"]) \
            <= 1e-5 * results[kind][0].mean()
    before, after = results["full"]
    assert np.allclose(after[:, 2:], before[:, 2:], rtol=1e-5, atol=1e-6)
    assert not np.allclose(after[:, :2], before[:, :2], atol=1e-3)
    before, after = results["sliding"]
    assert np.abs(after[:, 2:] - before[:, 2:]).max() > 1e-3


# -- one rank's share ----------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """128 experts over 8 ranks of 16 under the sigmoid router with its
    selection bias, the weights normalized over all 8 chosen and scaled
    by 2.826: each rank's output (its held experts' part plus the shared
    expert), summed with the shared expert counted once, is the
    reference's layer with all experts held; and each rank's output is
    the reference given the same share."""
    E, k, held, scale = 128, 8, 16, 2.826
    rng = np.random.RandomState(5)
    T, D, H = 40, 12, 10
    x = rng.randn(T, D).astype(np.float32)
    full = {"moe_gate_weight": rng.randn(E, D),
            "moe_experts_i2h_gate_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D),
            "moe_shared_i2h_gate_weight": 0.5 * rng.randn(H, D),
            "moe_shared_i2h_weight": 0.5 * rng.randn(H, D),
            "moe_shared_h2o_weight": 0.5 * rng.randn(D, H)}
    full = {n: v.astype(np.float32) for n, v in full.items()}
    bias = (0.3 * rng.randn(E)).astype(np.float32)
    m = {"num_experts": E, "experts_per_tok": k, "route_scale": scale}
    state = {"moe_dispatch_select_bias": jnp.asarray(bias)}
    p = dict({n: jnp.asarray(v) for n, v in full.items()}, **state)
    with jax.default_matmul_precision("highest"):
        whole, counts = REF.moe(p, "", jnp.asarray(x), m)
        shared = np.asarray(REF.swiglu(jnp.asarray(x), *(
            p["moe_shared_%s_weight" % n]
            for n in ("i2h_gate", "i2h", "h2o"))))
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, held):
        mine = {n: (v[first:first + held] if "experts" in n else v)
                for n, v in full.items()}
        net = MoEFeedForward(
            mx.sym.Variable("data"), num_hidden=H, num_experts=E, k=k,
            capacity_factor=0.0, name="moe", act_type="silu", gated=True,
            no_bias=True, renormalize=True, output_dim=D, score="sigmoid",
            scale=scale, bias_rate=1e-3, shared_hidden=H,
            experts_held=held, first_expert=first)
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        exe.arg_dict["data"][:] = x
        for n, v in mine.items():
            exe.arg_dict[n][:] = v
        exe.aux_dict["moe_dispatch_select_bias"][:] = bias
        exe.forward(is_train=False)
        out = exe.outputs[0].asnumpy()
        with jax.default_matmul_precision("highest"):
            want = np.asarray(REF.moe(
                dict({n: jnp.asarray(v) for n, v in mine.items()}, **state),
                "", jnp.asarray(x), dict(m, experts_held=held,
                                         first_expert=first))[0])
        assert np.abs(out - want).max() <= 1e-4 * np.abs(want).max()
        total += out - shared
    total += shared
    assert np.asarray(counts).sum() == T * k
    assert np.abs(total - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()


# -- counts --------------------------------------------------------------------
def test_reference_flops_are_the_hand_count():
    """ISSUE 41's arithmetic at the sizes it priced (16 held experts; the
    cell holds 8, ``tests/benchmark/test_cell_trinity.py`` has its
    count), a forward token:
    projections 5 x 54.53 M, scores 4 x 25.17 M + 33.56 M, the dense MLP
    75.50 M, four routers 2.10 M, four shared experts 50.33 M, the held
    routed share (8 x 16 / 128 = 1 expert a token) 50.33 M, the head
    102.50 M: 687.5 M, three times that a trained token."""
    kwargs = dict(num_layers=5, hidden_size=2048,
                  layer_types=["sliding"] * 4 + ["full"], dense_layers=1,
                  num_heads=32, num_kv_heads=4, head_dim=128, window=2048,
                  dense_width=6144, num_experts=128, experts_per_tok=8,
                  expert_width=1024, shared_width=1024, vocab_size=25024,
                  seq_len=4096, experts_held=16)
    proj = 2 * 2048 * 128 * (32 + 4 + 4 + 32 + 32)
    window_pairs = 2048 * 2049 // 2 + 2048 * 2048
    causal_pairs = 4096 * 4097 // 2
    assert (window_pairs, causal_pairs) == (6292480, 8390656)
    scores = 4 * 128 * 32 * (4 * window_pairs + causal_pairs) / 4096
    dense = 6 * 2048 * 6144
    routers = 4 * 2 * 2048 * 128
    shared = held = 4 * 6 * 2048 * 1024
    head = 2 * 2048 * 25024
    forward = 5 * proj + scores + dense + routers + shared + held + head
    assert forward == pytest.approx(687.5e6, rel=1e-3)
    got = REF.train_flops_per_sample({"model": {"kwargs": kwargs}})
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert got == pytest.approx(2.063e9, rel=1e-3)
    assert got * 4096 == pytest.approx(8.45e12, rel=1e-3)
    # all experts held: 8 experts a token, 7 more than the share's 1
    whole = REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kwargs, experts_held=0)}})
    assert whole - got == pytest.approx(3 * 7 * held / 4 * 4, rel=1e-12)
    # a window the sequence fits in counts as the causal mask
    assert REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kwargs, window=4096)}}) \
        == REF.train_flops_per_sample(
            {"model": {"kwargs": dict(kwargs, layer_types=["full"] * 5)}})


# -- scopes and the counter ----------------------------------------------------
def test_device_scopes_and_the_lowering_counter_name_both_kinds():
    net, kwargs, params, tokens, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(tokens), softmax_label=jnp.asarray(labels))
    aux = {name: jnp.zeros((kwargs["num_experts"],), F32)
           for name in net.list_auxiliary_states()}
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.jit(lambda a: prog.eval(a, aux, jax.random.PRNGKey(0),
                                           True)[0]).lower(args) \
            .as_text(debug_info=True)
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    for scope in ("attn_proj.l0", "attn_gate.l0", "attn_gate.l3", "attn.l1",
                  "attn.l3", "moe_experts.l1", "moe_route.l2",
                  "moe_combine.l3", "lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope
    assert "moe_experts.l0" not in text           # the dense lead
    # three window layers, then the full one: a sample an op
    assert [e["id"] for e in events] == \
        ["float32[2, 16, 4, 8]/kv2/sliding_window6"] * 3 \
        + ["float32[2, 16, 4, 8]/kv2"]
    assert all(e["args"] == {"kernel": 0, "plain": 1, "pair": "none",
                            "mask_form": "none"} for e in events)


# -- ISSUE 70: q's and k's norm and rotation, one node on the rows ---------
def test_q_and_k_are_placed_by_one_node_on_the_rows():
    """A sliding layer's q and k are normed and rotated, a full layer's
    normed and nothing else, each by ONE ``HeadNormRotary`` under
    ``attn_proj.l<i>`` on the rows as the projection writes them, under
    the weights' old names."""
    net = afmoe_lm(**TINY)
    placed = placed_on_rows(net)
    assert [(name, scope, ins) for name, scope, _, ins in placed] == [
        ("l%d_%s_norm" % (l, x), "attn_proj.l%d" % l,
         ["l%d_%s_proj" % (l, x), "l%d_%s_norm_gamma" % (l, x)])
        for l in range(TINY["num_layers"]) for x in "qk"]
    for name, _, how, _ in placed:
        sliding = TINY["layer_types"][int(name[1])] == "sliding"
        assert (how["head_dim"], how["norm"], how["seq_len"],
                how["eps"]) == (TINY["head_dim"], True,
                                TINY["seq_len"] if sliding else 0,
                                TINY["rms_eps"])
        assert not sliding or how["theta"] == TINY["rope_theta"]
    assert not nodes(net, "RotaryEmbedding")
    assert not [n for n in nodes(net, "RMSNorm")
                if n.name[3:] in ("q_norm", "k_norm")]
