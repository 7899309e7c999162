"""SDAR-MoE through the Symbol graph (ISSUE 39, tier-1): the block mask
against its definition written out by hand, grouped query heads against
the same heads repeated into multi-head, rotary positions with a period,
both masks through the library kernel interpreted on the CPU, the whole
tiny model against ``benchmark/reference/sdar-30b-a3b.py`` in float32
(loss, every gradient, Adam's first step), that a noised row never sees
its own clean token, the weighted loss at chance, the OLMoE symbol's
lowered text as it was, the TPU lowering of the attention at the cell's
shape, and the noise counter and span in ``fit``."""
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
from mxnet_tpu.models import olmoe_lm, sdar_moe_lm        # noqa: E402
from mxnet_tpu.moe import find_load_heads                 # noqa: E402
from mxnet_tpu.trace.heads import DIFFUSION_NOISE, MTP_LOSS   # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, placed_on_rows      # noqa: E402

REF = manifest.load_module("reference", "sdar-30b-a3b")
GEN = manifest.load_module("generators", "token_block_noised")

TINY = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, num_experts=16, experts_per_tok=4, expert_width=24,
            vocab_size=50, seq_len=16, block_len=4, rope_theta=1e6,
            rms_eps=1e-6, aux_coef=0.001, experts_held=4, first_expert=4)
BATCH = 2
MASK = TINY["vocab_size"] - 1
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
BLOCKS = ["l0_moe_dispatch", "l1_moe_dispatch"]
F32, BF16 = jnp.float32, jnp.bfloat16
# sha256 of the tiny OLMoE step's lowered text at the commit before this
# op took a second mask and grouped heads (832ac5d)
OLMOE_STEP_TEXT = \
    "0eeb7a8c80320f85d5aeb07cc83d53e328f9fa006d09ca4ae1083936719a3524"


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _by_hand(T, beta):
    """The mask of ISSUE 39, quadrant by quadrant, one pair at a time."""
    allowed = np.zeros((2 * T, 2 * T), bool)
    for n in range(2 * T):
        for m in range(2 * T):
            bn, bm = (n % T) // beta, (m % T) // beta
            if n < T:                 # a noised query
                allowed[n, m] = (m < T and bm == bn) or (m >= T and bm < bn)
            else:                     # a clean query
                allowed[n, m] = m >= T and bm <= bn
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


# -- the mask, the groups, the period ------------------------------------------
def test_the_block_mask_is_its_definition_written_out_by_hand():
    want = _by_hand(16, 4)
    ids = np.arange(32)
    assert np.array_equal(tf_ops.block_diffusion_allowed(
        ids[:, None], ids[None, :], 16, 4), want)
    assert np.array_equal(REF.block_mask(16, 4), want)
    # sizes that are no powers of two take the divisions, not the shifts
    ids24 = np.arange(24)
    assert np.array_equal(tf_ops.block_diffusion_allowed(
        ids24[:, None], ids24[None, :], 12, 3), _by_hand(12, 3))
    assert np.array_equal(REF.block_mask(12, 3), _by_hand(12, 3))
    # traced ids give the same, and every row sees something
    got = jax.jit(lambda i: tf_ops.block_diffusion_allowed(
        i[:, None], i[None, :], 16, 4))(jnp.arange(32))
    assert np.array_equal(np.asarray(got), want) and want.any(axis=1).all()
    # a noised row: its block both ways, earlier clean blocks, never its
    # own clean token; a clean row never a noised one
    assert want[5, 4:8].all() and not want[5, :4].any()
    assert want[5, 16:20].all() and not want[5, 20:].any()
    assert not want[16:, :16].any() and want[21, 16:24].all()
    assert want.sum() == REF.allowed_pairs(16, 4) == 16 * 16 + 16 * 4
    # the library's mask object says the same tile by tile
    splash = tf_ops._splash_mask()(32, ("block_diffusion", 4))
    assert np.array_equal(splash[0:32, 0:32], want)
    assert splash == tf_ops._splash_mask()(32, ("block_diffusion", 4))
    assert splash != tf_ops._splash_mask()(32, ("block_diffusion", 2))
    assert hash(splash) \
        == hash(tf_ops._splash_mask()(32, ("block_diffusion", 4)))


def test_the_tiles_the_kernel_visits_at_the_cells_shape():
    """8 x 8 tiles of 1024 over 8192 rows: 24 hold an allowed pair, 12
    of them whole."""
    splash = tf_ops._splash_mask()(8192, ("block_diffusion", 4))
    some = whole = 0
    for i in range(8):
        for j in range(8):
            tile = splash[i * 1024:(i + 1) * 1024, j * 1024:(j + 1) * 1024]
            some += bool(tile.any())
            whole += bool(tile.all())
    assert (some, whole) == (24, 12)


# -- the form the kernel gets the mask in --------------------------------------
@pytest.mark.parametrize("half, block", [(16, 4), (256, 4), (256, 64)])
def test_the_row_codes_are_the_function_over_every_pair(half, block):
    """``block_diffusion_codes``: the query's side made once on the host,
    the keys' side a shift and an xor in the kernel; equal to
    ``block_diffusion_allowed`` pair for pair, on numpy ids (the host's
    tile classification) and on traced ones (the kernel's tiles)."""
    ids = np.arange(2 * half, dtype=np.int32)
    want = tf_ops.block_diffusion_allowed(ids[:, None], ids[None, :], half,
                                          block)
    assert np.array_equal(want, _by_hand(half, block))
    codes, allowed = tf_ops.block_diffusion_codes(half, block)
    assert codes.dtype == np.int32 and codes.shape == (2 * half,)
    # a row's code: its block's index, the clean copy's first
    nb = half // block
    assert np.array_equal(codes[:half], nb + ids[:half] // block)
    assert np.array_equal(codes[half:], ids[:half] // block)
    assert np.array_equal(allowed(codes[:, None], ids[None, :]), want)
    got = jax.jit(lambda c, k: allowed(c[:, None], k[None, :]))(
        jnp.asarray(codes), jnp.asarray(ids))
    assert got.dtype == jnp.bool_ and np.array_equal(np.asarray(got), want)
    # what the kernel does with them: the codes tiled along the keys
    # against an iota from a tile's first key
    tile = jax.jit(lambda c: allowed(
        jnp.tile(c[:, None], (1, half)),
        half + jax.lax.broadcasted_iota(jnp.int32, (2 * half, half), 1)))(
        jnp.asarray(codes))
    assert np.array_equal(np.asarray(tile), want[:, half:])
    # the mask object hands the kernel exactly these
    splash = tf_ops._splash_mask()(2 * half, ("block_diffusion", block))
    assert tf_ops.kernel_mask(("block_diffusion", block),
                              2 * half)[0] == "codes"
    assert np.array_equal(splash.q_sequence, codes)
    assert np.array_equal(splash[0:2 * half, 0:2 * half], want)
    assert np.array_equal(
        splash.mask_function(splash.q_sequence[:, None], ids[None, :]), want)


@pytest.mark.parametrize("i", range(8))
def test_the_mask_object_at_the_cells_shape_tile_by_tile(i):
    """Row ``i`` of the 8 x 8 tiles of 1024 over the cell's 8192 rows
    (two copies of 4096 in blocks of 4): what ``__getitem__`` hands the
    library's tile classification is the function as it is written, on
    every pair of every tile."""
    splash = tf_ops._splash_mask()(8192, ("block_diffusion", 4))
    assert splash.q_sequence.shape == (8192,)
    rows = np.arange(i * 1024, (i + 1) * 1024)
    visited = []
    for j in range(8):
        cols = np.arange(j * 1024, (j + 1) * 1024)
        tile = splash[i * 1024:(i + 1) * 1024, j * 1024:(j + 1) * 1024]
        assert np.array_equal(tile, tf_ops.block_diffusion_allowed(
            rows[:, None], cols[None, :], 4096, 4)), (i, j)
        if tile.any():
            visited.append((j, bool(tile.all())))
    # a noised row of tiles: its own diagonal tile (partial), the clean
    # tiles of earlier blocks (whole) and the clean diagonal one
    # (partial); a clean row: the clean tiles to its diagonal
    if i < 4:
        want = [(i, False)] + [(4 + j, True) for j in range(i)] \
            + [(4 + i, False)]
    else:
        want = [(j, True) for j in range(4, i)] + [(i, False)]
    assert visited == want


@pytest.mark.parametrize("half, block", [(12, 3), (24, 8), (48, 6),
                                         (192, 4)])
def test_sizes_that_are_no_powers_of_two_keep_the_function(half, block):
    """The coded form is the shift branch's: where ``half`` or ``block``
    is no power of two the kernel's mask object carries the row ids and
    ``block_diffusion_allowed`` itself, divisions and all."""
    t, kind = 2 * half, ("block_diffusion", block)
    assert tf_ops.kernel_mask(kind, t)[0] == "function"
    splash = tf_ops._splash_mask()(t, kind)
    ids = np.arange(t)
    assert np.array_equal(splash.q_sequence, ids)
    assert splash.q_sequence.dtype == np.int32
    want = _by_hand(half, block)
    assert np.array_equal(splash[0:t, 0:t], want)
    assert np.array_equal(
        splash.mask_function(ids[:, None], ids[None, :]), want)
    # and the other kinds' form does not depend on their size
    assert tf_ops.kernel_mask(("causal", 0), t)[0] == "library"
    assert tf_ops.kernel_mask(("sliding_window", block), t)[0] == "function"
    assert np.array_equal(
        tf_ops._splash_mask()(t, ("sliding_window", block)).q_sequence, ids)


def test_one_kernel_pair_a_kind_a_process_under_the_coded_form():
    """Two mask objects of one ``(t, kind)`` are equal and hash alike
    whatever their form, so the library's ``process_mask`` answers the
    second layer's from its cache with the FIRST one's function: one
    static argument, one kernel pair a kind a process."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm, splash_attention_mask_info as mi)
    make = tf_ops._splash_mask()
    # shapes no other test of this file hands to process_mask
    coded = [make(1024, ("block_diffusion", 8)) for _ in range(2)]
    plain = [make(768, ("block_diffusion", 8)) for _ in range(2)]
    for a, b in (coded, plain):
        assert a is not b and a == b and hash(a) == hash(b)
        assert a.mask_function is not b.mask_function
    assert coded[0] != plain[0]
    assert coded[0] != make(1024, ("block_diffusion", 4))
    assert coded[0] != make(1024, ("sliding_window", 8))
    for a, b in (coded, plain):
        before = mi._process_mask.cache_info()
        first = mi.process_mask(sm.MultiHeadMask([a] * 4), (128, 128))
        again = mi.process_mask(sm.MultiHeadMask([b] * 4), (128, 128))
        after = mi._process_mask.cache_info()
        assert after.misses == before.misses + 1
        assert after.hits == before.hits + 1
        assert again[1] is first[1] is a.mask_function
        assert np.array_equal(first[0].q_sequence, a.q_sequence)
        # a computable mask: nothing to load, whatever the form
        assert first[0].partial_mask_blocks is None
        assert first[0].mask_next is None


@pytest.mark.parametrize("mask, size, t, dtype, form", [
    ("causal", 0, 512, "bfloat16", "library"),
    ("sliding_window", 128, 512, "bfloat16", "function"),
    ("block_diffusion", 4, 512, "bfloat16", "codes"),
    ("block_diffusion", 4, 384, "bfloat16", "function"),
    ("block_diffusion", 3, 384, "bfloat16", "function"),
    ("block_diffusion", 4, 512, "float32", "none"),
    ("block_diffusion", 4, 32, "bfloat16", "none"),
    ("causal", 0, 512, "float32", "none")])
def test_the_lowering_counter_names_the_masks_form(mask, size, t, dtype,
                                                   form):
    """``attn:lowering`` says in which form the kernel gets the mask
    (``library`` / ``function`` / ``codes``), ``none`` where the op runs
    the plain blocks on every platform; ``kernel``, ``plain`` and the
    track are what they were."""
    q = jax.ShapeDtypeStruct((1, t, 4, 128), jnp.dtype(dtype))
    kv = jax.ShapeDtypeStruct((1, t, 2, 128), jnp.dtype(dtype))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        jax.eval_shape(lambda q, k, v: tf_ops.causal_attention(
            q, k, v, 0.1, mask, block=size, window=size), q, kv, kv)
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    kernel = int(form != "none")
    assert [e["args"] for e in events] == [
        {"kernel": kernel, "plain": 1 - kernel,
         "pair": "rows" if kernel else "none", "mask_form": form}]
    assert events[0]["id"] == "%s[1, %d, 4, 128]/kv2%s" % (
        dtype, t, "" if mask == "causal" else "/%s%d" % (mask, size))


@pytest.mark.parametrize("mask, block", [("causal", 0),
                                         ("block_diffusion", 4)])
def test_grouped_heads_are_the_same_heads_repeated(mask, block):
    """4 query heads over 2 key/value heads against multi-head attention
    on the key/value heads repeated: outputs alike, and the repeated
    form's key/value gradients sum over a group to the grouped form's."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(2, 32, h, d), F32)
               for h, d in ((4, 8), (2, 8), (2, 6)))
    w = jnp.asarray(rng.randn(2, 32, 4, 6), F32)

    def run(k, v):
        out, vjp = jax.vjp(lambda q, k, v: tf_ops.causal_attention(
            q, k, v, 0.3, mask, block), q, k, v)
        return (out,) + vjp(w)

    got = run(k, v)
    want = run(jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
    assert np.allclose(got[0], want[0], atol=1e-6)
    assert np.allclose(got[1], want[1], atol=1e-5)
    for g, r in zip(got[2:], want[2:]):
        r = np.asarray(r).reshape(2, 32, 2, 2, -1).sum(axis=3)
        assert np.allclose(g, r, atol=1e-5)
    allowed = _by_hand(16, 4) if block else np.tril(np.ones((32, 32), bool))
    assert np.allclose(got[0], _dense(q, k, v, 0.3, allowed), atol=1e-5)


def test_the_op_takes_fewer_key_heads_and_refuses_what_is_no_group():
    q, k = mx.sym.Variable("q"), mx.sym.Variable("k")
    net = mx.sym.CausalSelfAttention(q, k, mx.sym.Variable("v"))
    _, outs, _ = net.infer_shape(q=(2, 8, 6, 4), k=(2, 8, 2, 4),
                                 v=(2, 8, 2, 5))
    assert outs == [(2, 8, 6, 5)]
    for bad in (dict(k=(2, 8, 4, 4), v=(2, 8, 4, 5)),      # 6 over 4
                dict(k=(2, 8, 2, 3), v=(2, 8, 2, 5)),      # key head size
                dict(k=(2, 8, 2, 4), v=(2, 8, 3, 5)),      # value heads
                dict(k=(2, 7, 2, 4), v=(2, 7, 2, 5))):     # rows
        with pytest.raises(mx.MXNetError):
            net.infer_shape(q=(2, 8, 6, 4), **bad)
    with pytest.raises(mx.MXNetError):
        mx.sym.CausalSelfAttention(q, k, k, mask="sliding")
    x = jnp.zeros((1, 12, 2, 4))
    for block in (0, 4, 5):         # no length, 6 rows in blocks of 4, 5
        with pytest.raises(mx.MXNetError):
            tf_ops.causal_attention(x, x, x, 0.5, "block_diffusion", block)


def test_rotary_positions_with_a_period():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 24, 3, 8), F32)
    plain = tf_ops.rotary_embedding(x, 1e4)
    assert np.array_equal(tf_ops.rotary_embedding(x, 1e4, 0), plain)
    got = tf_ops.rotary_embedding(x, 1e4, 12)
    assert np.array_equal(got[:, :12], plain[:, :12])
    # row n of the second copy turns as row n - 12 does
    assert np.allclose(got[:, 12:], tf_ops.rotary_embedding(x[:, 12:], 1e4),
                       atol=1e-6)
    assert np.allclose(got, REF.rotate(x, 1e4, 12), atol=1e-6)
    net = mx.sym.RotaryEmbedding(mx.sym.Variable("x"), theta=1e4, period=12)
    exe = net.simple_bind(mx.cpu(), grad_req="null", x=(2, 24, 3, 8))
    exe.arg_dict["x"][:] = np.asarray(x)
    exe.forward(is_train=False)
    assert np.allclose(exe.outputs[0].asnumpy(), got, atol=1e-6)


def test_the_kernel_under_the_block_mask_interpreted(monkeypatch):
    """The library kernel the TPU lowering runs, interpreted on the CPU
    at tiles of 128 over 512 rows (two copies of 256), 4 query heads
    over 2 key/value heads: output and the three input gradients against
    the plain blocks in float32, inside bfloat16's rounding; a clean
    token of a block does not reach its noised rows."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(sk, "make_splash_mha_single_device",
                        functools.partial(sk.make_splash_mha_single_device,
                                          interpret=True))
    monkeypatch.setattr(tf_ops, "ATTN_KERNEL_BLOCK", 128)
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 512, h, 128), BF16)
               for h in (4, 2, 2))
    w = jnp.asarray(rng.randn(1, 512, 4, 128), F32)
    assert tf_ops._kernel_takes(q, k, v)
    kind, scale = ("block_diffusion", 4), 128 ** -0.5

    def run(fn, *args):
        out, vjp = jax.vjp(lambda *a: fn(*a, scale, kind).astype(F32),
                           *args)
        return [np.asarray(x, np.float32) for x in (out,) + vjp(w)]

    got = run(tf_ops._flash_attention, q, k, v)
    want = run(tf_ops._plain_attention, *(x.astype(F32) for x in (q, k, v)))
    assert np.allclose(want[0], _dense(q, k, v, scale, _by_hand(256, 4)),
                       atol=1e-4)
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 0.02 * np.abs(r).max()
    # clean rows 256 + 8 .. 256 + 11 are block 2's: its noised rows 8..11
    # do not move, later blocks' noised rows and the clean rows do
    k2, v2 = k.at[:, 264:268].add(1.0), v.at[:, 264:268].add(-1.0)
    moved = np.asarray(tf_ops._flash_attention(q, k2, v2, scale, kind),
                       np.float32)
    assert np.array_equal(moved[:, :12], got[0][:, :12])
    assert not np.array_equal(moved[:, 12:16], got[0][:, 12:16])
    assert not np.array_equal(moved[:, 264:268], got[0][:, 264:268])


def test_the_kernel_keeps_the_function_where_the_sizes_are_no_powers_of_two(
        monkeypatch):
    """384 rows are two copies of 192, no power of two: the interpreted
    kernel runs ``block_diffusion_allowed`` itself on row ids (the form
    every size had before the codes) and agrees with the plain blocks,
    forward and backward."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(sk, "make_splash_mha_single_device",
                        functools.partial(sk.make_splash_mha_single_device,
                                          interpret=True))
    monkeypatch.setattr(tf_ops, "ATTN_KERNEL_BLOCK", 128)
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, 384, h, 128), BF16)
               for h in (2, 1, 1))
    w = jnp.asarray(rng.randn(1, 384, 2, 128), F32)
    assert tf_ops._kernel_takes(q, k, v)
    kind, scale = ("block_diffusion", 4), 128 ** -0.5
    assert tf_ops.kernel_mask(kind, 384)[0] == "function"

    def run(fn, *args):
        out, vjp = jax.vjp(lambda *a: fn(*a, scale, kind).astype(F32),
                           *args)
        return [np.asarray(x, np.float32) for x in (out,) + vjp(w)]

    got = run(tf_ops._flash_attention, q, k, v)
    want = run(tf_ops._plain_attention, *(x.astype(F32) for x in (q, k, v)))
    assert np.allclose(want[0], _dense(q, k, v, scale, _by_hand(192, 4)),
                       atol=1e-4)
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 0.02 * np.abs(r).max()


# -- the program of the cells that are there -----------------------------------
def test_the_olmoe_symbols_lowered_text_is_what_it_was():
    """Causal attention over as many key heads as query heads is node for
    node the program it was: the tiny OLMoE step (forward and every
    gradient) lowers to the text the commit before this PR gave."""
    net = olmoe_lm(num_layers=2, hidden_size=32, num_heads=2, num_experts=8,
                   experts_per_tok=2, expert_width=16, vocab_size=64,
                   seq_len=16)
    shapes, _, _ = net.infer_shape(data=(2, 16), softmax_label=(2, 16))
    inputs = ("data", "softmax_label")
    args = {n: jax.ShapeDtypeStruct(s, jnp.int32 if n in inputs else F32)
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
    assert hashlib.sha256(text.encode()).hexdigest() == OLMOE_STEP_TEXT


def test_the_attention_at_the_cells_shape_lowers_to_the_kernel_on_a_tpu():
    """bfloat16 ``[1, 8192, 32, 128]`` over 4 key/value heads under the
    block mask, lowered for a TPU, is the splash kernel, forward and
    fused backward, nothing padded or repeated; the track names the
    key/value heads and the mask."""
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), BF16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), BF16)
    fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, 128 ** -0.5, "block_diffusion", 4).astype(F32).sum(),
        argnums=(0, 1, 2)))
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
                                 "mask_form": "codes"}
    assert events[0]["id"] == \
        "bfloat16[1, 8192, 32, 128]/kv4/block_diffusion4"


# -- the model -----------------------------------------------------------------
def _noised(rng, clean, eps=1e-3):
    noised, target, weight = GEN.block_noise(rng, clean, TINY["block_len"],
                                             eps, MASK)
    return (np.concatenate([noised, clean], axis=1),
            np.stack([target, weight], axis=1))


def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = sdar_moe_lm(**kwargs)
    T = kwargs["seq_len"]
    arg_shapes, _, _ = net.infer_shape(data=(BATCH, 2 * T),
                                       softmax_label=(BATCH, 2, T))
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
    clean = rng.randint(0, MASK, (BATCH, T)).astype(np.int32)
    data, labels = _noised(rng, clean, eps=0.2)
    return net, kwargs, params, data, labels


def _bound(net, params, data, labels, optimizer, optimizer_params):
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", data.shape)],
             label_shapes=[("softmax_label", labels.shape)])
    mod.init_params(mx.init.Zero(), arg_params={
        k: mx.nd.array(v) for k, v in params.items()}, allow_missing=True)
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=optimizer_params)
    assert mod._fused is not None
    return mod, mx.io.DataBatch(data=[mx.nd.array(data, dtype=np.int32)],
                                label=[mx.nd.array(labels)], pad=0)


def _sgd_gradients(net, params, data, labels, lr=0.125):
    """(outputs, {name: gradient}) through one SGD step of the fused
    train step."""
    mod, batch = _bound(net, params, data, labels, "sgd", {
        "learning_rate": lr, "momentum": 0.0, "wd": 0.0,
        "rescale_grad": 1.0})
    mod.forward_backward(batch)
    mod.update()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    after, _ = mod.get_params()
    return outs, {k: (params[k] - after[k].asnumpy()) / lr for k in params}


def test_the_heads_are_found_by_what_they_are():
    net, kwargs, _, _, _ = _tiny(seed=0)
    assert net.list_outputs() == [
        "lm_output", "l0_moe_dispatch_aux_output",
        "l1_moe_dispatch_aux_output", "moe_load_output",
        "diffusion_noise_output"]
    assert find_load_heads(net) == (3, BLOCKS)
    assert DIFFUSION_NOISE.find(net) == 4
    assert MTP_LOSS.find(net) is None                # one per-token loss
    assert DIFFUSION_NOISE.find(mx.sym.Group([net[4], net[0]])) == 0
    assert DIFFUSION_NOISE.find(net[0]) is None
    # no load-balance head where its coefficient is 0
    assert sdar_moe_lm(**dict(kwargs, aux_coef=0.0)).list_outputs() == [
        "lm_output", "moe_load_output", "diffusion_noise_output"]
    with pytest.raises(ValueError):
        sdar_moe_lm(**dict(kwargs, num_kv_heads=3))
    with pytest.raises(ValueError):
        sdar_moe_lm(**dict(kwargs, seq_len=18))


def test_model_matches_reference_loss_gradients_and_adam_step(monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, data, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, data, labels)
    outs, grads = _sgd_gradients(net, params, data, labels)
    target, weight = labels[:, 0].reshape(-1), labels[:, 1].reshape(-1)
    assert abs(float(outs[0].mean()) - ref["loss"]) <= 1e-5 * ref["loss"]
    # a position that is not masked reads exactly 0; the masked ones > 0
    assert np.array_equal(outs[0] > 0, target >= 0)
    assert 0 < (target >= 0).sum() < target.size
    for got, want in zip(outs[1:3], ref["aux"]):
        assert got == pytest.approx(want, rel=1e-5)
    for row, block in zip(outs[3], BLOCKS):
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][block]))
        assert row[-1] == 0 and row[:-1].sum() == 2 * 16 * BATCH * 4
    masked = target >= 0
    assert np.allclose(outs[4], [masked.sum(), target.size,
                                 weight[masked].sum()], rtol=1e-6)
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 2e-4, errors

    # the configuration's optimizer: Adam's first step
    names = ["l1_q_proj_weight", "l1_k_proj_weight", "l1_v_proj_weight",
             "l1_o_proj_weight", "l1_q_norm_gamma", "l1_moe_gate_weight",
             "l1_moe_experts_i2h_weight", "embed_weight", "lm_head_weight"]
    want = REF.reference_step(cfg, params, {"data": data},
                              {"softmax_label": labels}, ADAM, names)
    assert want["loss"] == ref["loss"]
    mod, batch = _bound(net, params, data, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, aux = mod.get_params()
    assert not aux
    for name in names:
        got = after[name].asnumpy() - params[name]
        # an element whose gradient is ~0 may flip sign: Adam's first
        # step is lr * sign(g); such elements are a sliver of the norm
        assert _rel(got, want["updates"][name]) <= 0.02, name


def test_a_noised_row_never_sees_its_own_clean_token():
    """Another clean token inside block ``b`` moves no logit's loss of
    noised block ``b`` (nor of an earlier block), and does move the
    blocks after it."""
    net, kwargs, params, data, labels = _tiny(seed=11)
    T, beta = kwargs["seq_len"], kwargs["block_len"]
    labels[:, 0] = data[:, T:]                 # score every position
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=data.shape,
                          softmax_label=labels.shape,
                          type_dict={"data": np.int32})

    def losses(data):
        for k, v in dict(params, data=data, softmax_label=labels).items():
            exe.arg_dict[k][:] = v
        exe.forward(is_train=False)
        return exe.outputs[0].asnumpy().reshape(BATCH, T)

    base = losses(data)
    b = 1
    other = data.copy()
    at = slice(T + b * beta, T + (b + 1) * beta)
    other[:, at] = (other[:, at] + 7) % MASK
    moved = losses(other)
    assert np.array_equal(moved[:, :(b + 1) * beta], base[:, :(b + 1) * beta])
    assert not np.allclose(moved[:, (b + 1) * beta:], base[:, (b + 1) * beta:])


def test_the_weighted_loss_reads_ln_v_at_chance_over_many_draws():
    """With uniform predictions every masked row's CE is ``ln V``, and
    the mean over positions of ``[masked] / t`` is 1 in expectation:
    over many blocks the loss reads ``ln V``; a step's counts are what
    the noise head reports."""
    rng = np.random.RandomState(3)
    clean = rng.randint(0, MASK, (64, 4096)).astype(np.int32)
    noised, target, weight = GEN.block_noise(rng, clean, 4, 1e-3, MASK)
    masked = target >= 0
    assert np.array_equal(noised[masked], np.full(masked.sum(), MASK))
    assert np.array_equal(noised[~masked], clean[~masked])
    assert np.array_equal(target[masked], clean[masked].astype(np.float32))
    assert abs(masked.mean() - 0.5) < 0.01
    assert weight.min() >= 1.0 and weight.max() <= 1000.0
    # one t a block
    assert np.array_equal(weight.reshape(64, -1, 4).min(-1),
                          weight.reshape(64, -1, 4).max(-1))
    assert (masked * weight).mean() == pytest.approx(1.0, abs=0.02)
    V = 50
    net = mx.sym.MakeLoss(mx.sym.SoftmaxCELoss(
        mx.sym.Variable("x"), mx.sym.Variable("t"), use_ignore=True,
        ignore_label=-1) * mx.sym.Variable("w"), normalization="batch")
    exe = net.simple_bind(mx.cpu(), grad_req="null", x=(4096, V), t=(4096,),
                          w=(4096,))
    exe.arg_dict["x"][:] = 0.0
    exe.arg_dict["t"][:] = np.where(masked[0], clean[0] % V, -1)
    exe.arg_dict["w"][:] = weight[0]
    exe.forward(is_train=False)
    row = exe.outputs[0].asnumpy()
    assert row.mean() == pytest.approx(
        np.log(V) * (masked[0] * weight[0]).mean(), rel=1e-5)
    assert np.array_equal(row > 0, masked[0])


# -- the counter and the span --------------------------------------------------
def _fit(net, data, labels, steps=4):
    X = np.concatenate([data] * (steps * BATCH // len(data)))
    Y = np.concatenate([labels] * (steps * BATCH // len(labels)))
    mod = mx.mod.Module(net, context=mx.cpu(0))
    since = time.perf_counter_ns()
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=BATCH), num_epoch=1,
            eval_metric=mx.metric.OutputMean(0), optimizer="adam",
            initializer=mx.init.Normal(0.02), optimizer_params=dict(ADAM))
    counters = mx.trace.counter_events(
        ["diffusion:noise", "moe:load"], since_ns=since)
    spans = mx.trace.span_events(
        names=["fit:step", "fit:diffusion_noise", "fit:moe_load",
               "fit:update_metric"], since_ns=since)
    return mod, counters, spans


def test_fit_records_the_noise_once_a_step():
    net, kwargs, _, data, labels = _tiny(seed=3)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(net, data, labels)
    finally:
        mx.trace.reset()         # the ring is the process's: leave none
        mx.trace.set_enabled(was)
    assert mod._fused.head("diffusion_noise") == 4
    noise = [e["args"] for e in counters if e["name"] == "diffusion:noise"]
    assert len(noise) == 4
    masked = labels[:, 0] >= 0
    for a in noise:
        assert a == {"masked": float(masked.sum()),
                     "positions": float(masked.size),
                     "weight_sum": pytest.approx(
                         float(labels[:, 1][masked].sum()), rel=1e-6)}
    assert len([e for e in counters if e["name"] == "moe:load"]) == 8
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append((e["ts"],
                                                  e["ts"] + e["dur"]))
    assert len(by_name["fit:diffusion_noise"]) \
        == len(by_name["fit:moe_load"]) == 4

    def inside(span, others):
        return any(a <= span[0] and span[1] <= b for a, b in others)

    for span in by_name["fit:diffusion_noise"]:
        assert inside(span, by_name["fit:step"])
        assert not inside(span, by_name["fit:update_metric"])
        assert not inside(span, by_name["fit:moe_load"])


def test_nothing_is_recorded_for_the_olmoe_symbol_or_while_tracing_is_off():
    olmoe = olmoe_lm(num_layers=1, hidden_size=16, num_heads=2,
                     num_experts=4, experts_per_tok=2, expert_width=12,
                     vocab_size=40, seq_len=16)
    rng = np.random.RandomState(0)
    X = rng.randint(0, 40, (2 * BATCH, 16)).astype(np.int32)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(olmoe, X, np.roll(X, -1, 1))
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert mod._fused.head("diffusion_noise") is None
    assert not [e for e in counters if e["name"] == "diffusion:noise"]
    assert not [e for e in spans if e["name"] == "fit:diffusion_noise"]
    assert [e for e in counters if e["name"] == "moe:load"]
    net, _, _, data, labels = _tiny(seed=3)
    mx.trace.set_enabled(False)
    try:
        mod, counters, spans = _fit(net, data, labels)
    finally:
        mx.trace.set_enabled(was)
    assert mod._fused.head("diffusion_noise") == 4
    assert not counters and not spans
    assert mod._fused.moe_stats.report()["blocks"]     # MoeStats still fed


def test_device_scopes_name_the_blocks_parts():
    net, kwargs, params, data, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(data), softmax_label=jnp.asarray(labels))
    text = jax.jit(lambda a: prog.eval(a, {}, jax.random.PRNGKey(0),
                                       True)[0]).lower(args) \
        .as_text(debug_info=True)
    for scope in ("attn_proj.l0", "attn.l1", "moe_experts.l1",
                  "moe_route.l0", "moe_combine.l1", "lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope


# -- ISSUE 70: q's and k's norm and rotation, one node on the rows ---------
def test_q_and_k_are_placed_by_one_node_on_the_rows():
    """Every layer's q and k leave their projections through ONE
    ``HeadNormRotary`` under ``attn_proj.l<i>``, the rows of both copies
    at positions ``n mod T``; the weights are still ``l<i>_{q,k}_norm_gamma``
    of a head's width and no ``RMSNorm`` or ``RotaryEmbedding`` stands
    over the heads."""
    net = sdar_moe_lm(**TINY)
    rows = 2 * TINY["seq_len"]
    placed = placed_on_rows(net)
    assert [(name, scope, ins) for name, scope, _, ins in placed] == [
        ("l%d_%s_norm" % (l, x), "attn_proj.l%d" % l,
         ["l%d_%s_proj" % (l, x), "l%d_%s_norm_gamma" % (l, x)])
        for l in range(TINY["num_layers"]) for x in "qk"]
    for _, _, how, _ in placed:
        assert (how["head_dim"], how["norm"], how["seq_len"], how["period"],
                how["theta"], how["eps"]) == (
            TINY["head_dim"], True, rows, TINY["seq_len"],
            TINY["rope_theta"], TINY["rms_eps"])
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(BATCH, rows), softmax_label=(BATCH, 2, TINY["seq_len"]))[0]))
    assert shapes["l1_q_norm_gamma"] == shapes["l1_k_norm_gamma"] \
        == (TINY["head_dim"],)
    assert not nodes(net, "RotaryEmbedding")
    assert not [n for n in nodes(net, "RMSNorm") if "_norm" in n.name
                and n.name[3:] in ("q_norm", "k_norm")]
