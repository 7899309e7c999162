"""Kimi Linear through the Symbol graph (ISSUE 31, tier-1): the chunked
gated delta rule against the token recurrence, latent attention's
unequal head sizes, the sigmoid router with its selection bias, one
expert-parallel rank's share against the uncut layer, the whole tiny
model (loss, every gradient, Adam's first step, the bias's first move)
against ``benchmark/reference/kimi-linear-48b-a3b.py`` in float32, and
what ``Module`` holds and hands back while the fused step is live."""
import contextlib
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
from jax import lax                                       # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.models import kimi_linear_lm               # noqa: E402
from mxnet_tpu.moe import MoEFeedForward                  # noqa: E402
from mxnet_tpu.moe.router import route_sorted             # noqa: E402
from mxnet_tpu.ops import linear_attention as kda_ops     # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402
from check_utils import check_symbolic_forward, jaxpr_eqns  # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, signature             # noqa: E402

REF = manifest.load_module("reference", "kimi-linear-48b-a3b")

TINY = dict(num_layers=5, hidden_size=32, full_attn_layers=[4, 8],
            dense_layers=1, kda_heads=2, kda_head_dim=8, conv_kernel=4,
            mla_heads=2, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
            v_head_dim=8, dense_width=64, num_experts=16, experts_per_tok=4,
            expert_width=24, shared_width=24, routed_scale=2.446,
            vocab_size=50, seq_len=72, experts_held=4, first_expert=4,
            bias_rate=1e-3, rms_eps=1e-5)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}


def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


# -- the ops -----------------------------------------------------------------

@pytest.mark.parametrize("lo,hi", [(-0.01, -1e-4), (-40.0, -5.0),
                                   (-3.0, -0.01)],
                         ids=["decay-near-1", "decay-near-0", "mixed"])
def test_chunked_delta_rule_matches_the_token_recurrence(lo, hi):
    """Forward and all five gradients, T = 150 (two whole chunks of 64
    and a tail of 22), log-decay from nearly none to exp(-40) a token."""
    rng = np.random.RandomState(0)
    b, t, h, dk, dv = 2, 150, 3, 16, 8
    q, k = (jnp.asarray(_unit(rng.randn(b, t, h, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)
    g = jnp.asarray(rng.uniform(lo, hi, (b, t, h, dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.99, (b, t, h)), jnp.float32)
    w = jnp.cos(jnp.arange(dv, dtype=jnp.float32))
    scale = dk ** -0.5

    def run(fn):
        return jax.value_and_grad(
            lambda *a: (fn(*a) * w).sum(), argnums=(0, 1, 2, 3, 4))(
                q, k, v, g, beta)

    with jax.default_matmul_precision("highest"):
        got = kda_ops.gated_delta_rule(q, k, v, g, beta, scale)
        want = REF.delta_rule(q, k, v, g, beta)
        assert np.abs(np.asarray(got - want)).max() \
            <= 1e-5 * np.abs(np.asarray(want)).max()
        (_, grads), (_, ref_grads) = run(
            lambda *a: kda_ops.gated_delta_rule(*a, scale)), \
            run(REF.delta_rule)
    for x, y in zip(grads, ref_grads):
        assert np.abs(np.asarray(x - y)).max() \
            <= 2e-4 * np.abs(np.asarray(y)).max()


def test_kda_op_gates_scope_and_counter():
    """The op against the reference's pieces (L2 norm, both gates), and
    its ``kda:lowering`` sample."""
    rng = np.random.RandomState(1)
    b, t, h, d = 1, 70, 2, 8
    shapes = {"query": (b, t, h, d), "key": (b, t, h, d),
              "value": (b, t, h, d), "decay": (b, t, h, d),
              "beta": (b, t, h), "a_log_bias": (h,), "dt_bias": (h * d,)}
    vals = {n: rng.randn(*s).astype(np.float32) for n, s in shapes.items()}
    sym = mx.sym.KimiDeltaAttention(*[mx.sym.Variable(n) for n in shapes],
                                    layer=3)
    assert sym.infer_shape(query=(b, t, h, d), value=(b, t, h, d))[1] \
        == [(b, t, h, d)]
    x = {n: jnp.asarray(v) for n, v in vals.items()}
    g = -jnp.exp(x["a_log_bias"])[:, None] * jax.nn.softplus(
        x["decay"] + x["dt_bias"].reshape(h, d))
    want = REF.delta_rule(REF.l2norm(x["query"]), REF.l2norm(x["key"]),
                          x["value"], g, jax.nn.sigmoid(x["beta"]))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        check_symbolic_forward(sym, vals, [np.asarray(want)], 1e-4)
        events = mx.trace.counter_events(["kda:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    # heads of 8 are not the kernels': the plain chunks on every platform
    assert events and events[0]["args"] == {"chunked": 1, "chunk": 64,
                                            "kernel": 0, "plain": 1}
    assert events[0]["id"] == "float32%s" % [b, t, h, d]
    op = mx.ops.get_op("KimiDeltaAttention")
    text = jax.jit(lambda *a: op.forward(
        op.parse_params({"layer": 3}), list(a), [], None)[0]).lower(
            *x.values()).as_text(debug_info=True)
    assert "kda.l3" in text



# -- the two lowerings of the delta rule (ISSUE 33) ---------------------------

def _kda_inputs(t, lo, hi, heads=2, seed=0, dk=128, dv=128):
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(_unit(rng.randn(1, t, heads, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, t, heads, dv), jnp.float32)
    decay = jnp.asarray(rng.uniform(lo, hi, (1, t, heads, dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.99, (1, t, heads)), jnp.float32)
    return q, k, v, decay, beta


@pytest.mark.parametrize("t", [128, 320], ids=["t128", "t320"])
@pytest.mark.parametrize("lo,hi", [(-0.01, -1e-4), (-3.0, -0.01),
                                   (-40.0, -5.0), (-40.0, -1e-4)],
                         ids=["decay-near-1", "mixed", "decay-near-0",
                              "wide"])
def test_kernels_match_the_plain_chunks(lo, hi, t):
    """``kda_chunk_fwd`` / ``kda_chunk_bwd`` in Pallas interpret mode
    against the plain chunks at HIGHEST precision: the output and the
    cotangents of q, k, v, g and beta, heads of 128, two chunks and five
    (the carried state and its cotangent cross four boundaries)."""
    args = _kda_inputs(t, lo, hi)
    w = jnp.asarray(np.random.RandomState(1).randn(*args[2].shape),
                    jnp.float32)
    scale = 128 ** -0.5
    with jax.default_matmul_precision("highest"):
        got, states = kda_ops._kernel_rule(*args, scale, interpret=True)
        grads = kda_ops._kernel_rule_vjp(*args, states, w, scale,
                                         interpret=True)
        want, vjp = jax.vjp(
            lambda *a: kda_ops.gated_delta_rule(*a, scale), *args)
        plain_grads = vjp(w)
    assert np.abs(np.asarray(got - want)).max() \
        <= 1e-4 * np.abs(np.asarray(want)).max()
    for x, y in zip(grads, plain_grads):
        assert np.abs(np.asarray(x - y)).max() \
            <= 5e-4 * np.abs(np.asarray(y)).max()


def test_kernel_lowering_carries_the_gradient_through_the_gates():
    """The op's body under its kernel lowering against the plain one:
    the output and all seven gradients (q and k through the L2 norm,
    ``decay``, ``a_log`` and ``dt_bias`` through ``kda_gates`` and the
    kernels' ``dg``, beta through the sigmoid), bfloat16 values."""
    rng = np.random.RandomState(2)
    q, k, v, decay, beta = _kda_inputs(128, -2.0, 2.0, seed=2)
    q, k = 3.0 * q, 0.5 * k
    v = v.astype(jnp.bfloat16)
    a_log = jnp.asarray(rng.uniform(-1, 1, (2,)), jnp.float32)
    dt_bias = jnp.asarray(rng.uniform(-1, 1, (2 * 128,)), jnp.float32)
    args = (q, k, v, decay, 4.0 * beta - 2.0, a_log, dt_bias)

    def run(fn):
        return jax.value_and_grad(
            lambda *a: jnp.square(fn(*a).astype(jnp.float32)).sum(),
            argnums=tuple(range(7)))(*args)

    with jax.default_matmul_precision("highest"):
        (got, grads), (want, plain_grads) = run(
            lambda *a: kda_ops.kimi_delta_attention(*a, interpret=True)), \
            run(kda_ops._plain_attention)
    assert abs(float(got) - float(want)) <= 1e-3 * float(want)
    for x, y in zip(grads, plain_grads):
        x, y = (np.asarray(z, np.float32) for z in (x, y))
        assert np.abs(x - y).max() <= 4e-3 * np.abs(y).max()


def _kernel_dot_eqns(jaxpr):
    """The ``dot_general``s of a jaxpr and of every jaxpr inside it (a
    ``pallas_call``'s kernel body)."""
    return jaxpr_eqns(jaxpr, "dot_general")


def _kernel_dots(jaxpr):
    return sum(1 for _ in _kernel_dot_eqns(jaxpr))


def _streamed_rows(eqn):
    """The rows of a ``dot_general``'s left operand that are neither
    contracted nor a batch (one head's): what one pass streams."""
    (contract, _), (batch, _) = eqn.params["dimension_numbers"]
    return int(np.prod([x for i, x in enumerate(eqn.invars[0].aval.shape)
                        if i not in contract and i not in batch]))


def _kernel_row_cycles(jaxpr):
    """The rows every ``dot_general`` of a kernel body streams through the
    MXU, summed, so that an exact product counts once a pass.  What
    ``PERF.md`` §7 counts by hand, and what the kernels' time follows."""
    return sum(map(_streamed_rows, _kernel_dot_eqns(jaxpr)))


# MXU passes a chunk in ``kda_chunk_bwd``'s body: with the scores and the
# inverse formed again (the commit before ISSUE 45: 14 + 30 passes, and 3
# for an output nobody read), with the three read back (before ISSUE 48),
# and with an exact product over ``C`` rows of contraction in two passes
# for three (levels 0-3's two each, ``u``, ``dy``)
BWD_DOTS_BEFORE_PR45, BWD_DOTS_BEFORE_PR48, BWD_DOTS = 103, 56, 46
# rows x passes a chunk-head, forward and backward kernel: with a level's
# two half-empty operands side by side and every exact product three
# passes (the commit before ISSUE 48); with the levels packed (3520 and
# 3712), the forward's level 0 on the VPU (3328) and the exact products
# ``C`` deep in two passes
ROW_CYCLES_BEFORE_PR48 = {"fwd": 4416, "bwd": 4608}
ROW_CYCLES = {"fwd": 2624, "bwd": 3072}


def _kernel_jaxpr(which, b, t, h, d, jitted=True, heads_decay=False):
    """-> the jaxpr of ``_kda_fwd`` / ``_kda_bwd`` traced for ``(B, T, H,
    D)`` inputs, the decay a lane's or (``heads_decay``) a head's, a
    chunk a row; ``jitted`` False traces the function's body anew
    whatever the process has traced."""
    seq = jax.ShapeDtypeStruct((b, t, h * d), jnp.float32)
    beta = jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)
    rows = jax.ShapeDtypeStruct((b, h, t // 64, 1, 64), jnp.float32)
    fn = {"fwd": kda_ops._kda_fwd, "bwd": kda_ops._kda_bwd}[which]
    fn = fn if jitted else fn.__wrapped__
    args = (seq, seq, seq, rows if heads_decay else seq, beta) + (
        () if which == "fwd" else
        (*kda_ops._kept_shapes(b, t, h, d), seq))
    return jax.make_jaxpr(
        lambda *a: fn(*a, scale=0.1, interpret=False))(*args).jaxpr


def _bwd_kernel_dots(b, t, h, d):
    """-> the passes of the backward kernel traced for ``(B, T, H, D)``
    inputs."""
    return _kernel_dots(_kernel_jaxpr("bwd", b, t, h, d))


# -- the statements the kernels held before ISSUE 48: the reference ----------

def _unpacked_level_scores(l, pair, kl, ql, kr):
    c = kl.shape[1]
    both = kda_ops._dot(jnp.concatenate([kl, ql], 1), kr, kda_ops._NT,
                        exact=l < kda_ops._FINE_LEVELS)
    return (jnp.where(pair, both[:, :c], 0.0),
            jnp.where(pair, both[:, c:], 0.0))


def _unpacked_level_transposes(l, pair, dA, dBs, kl, ql, kr):
    n = kl.shape[1]
    exact = l < kda_ops._FINE_LEVELS
    dAB = jnp.concatenate([jnp.where(pair, dA, 0.0),
                           jnp.where(pair, dBs, 0.0)], 1)
    dleft = kda_ops._dot(dAB, kr, kda_ops._NN, exact)       # (H, 2C, Dk)
    dkr = kda_ops._dot(dAB, jnp.concatenate([kl, ql], 1), kda_ops._TN, exact)
    return dleft[:, :n], dleft[:, n:], dkr


def _three_pass_dot(a, b, dims, exact=False):
    if not exact:
        return lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)
    (ah, al), (bh, bl) = (kda_ops._bf16_parts(x, 2) for x in (a, b))
    return kda_ops._one_pass(ah, bh, dims) + (
        kda_ops._one_pass(ah, bl, dims) + kda_ops._one_pass(al, bh, dims))


@pytest.fixture
def statements_before(monkeypatch):
    """-> ``before(rows_only)``, a context under which the kernels' bodies
    hold the statements of the commit before ISSUE 48 (for
    ``fn.__wrapped__``: a jitted kernel function keeps what it traced): a
    level's two operands side by side and, unless ``rows_only``, the
    forward's level 0 on the MXU and every exact product in three
    passes.  ``rows_only`` undoes the packing alone, of the levels the
    MXU forms."""
    now = kda_ops._level_scores

    @contextlib.contextmanager
    def before(rows_only=False):
        vpu = kda_ops.KDA_VPU_LEVELS if rows_only else 0
        with monkeypatch.context() as m:
            m.setattr(kda_ops, "_level_scores", lambda l, *a: (
                now if l < vpu else _unpacked_level_scores)(l, *a))
            m.setattr(kda_ops, "_level_transposes",
                      _unpacked_level_transposes)
            if not rows_only:
                m.setattr(kda_ops, "_dot", _three_pass_dot)
            yield
    return before


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_a_level_streams_only_the_rows_that_carry_pairs(which,
                                                        statements_before):
    """The row-cycle count of each kernel body (ISSUE 48): a level's
    score product and its two transposes stream ``C`` rows a pass, not
    the ``2 C`` of which half were zeros (896 row-cycles a chunk-head
    less in each kernel); the forward's level 0 forms its 32 pairs on
    the VPU; an exact product whose contraction is ``C`` deep takes two
    passes."""
    shape = (1, 64, 1, 128)
    c = kda_ops.KDA_CHUNK
    got = _kernel_jaxpr(which, *shape, jitted=False)
    with statements_before():
        before = _kernel_jaxpr(which, *shape, jitted=False)
    with statements_before(rows_only=True):
        unpacked = _kernel_jaxpr(which, *shape, jitted=False)
    assert _kernel_row_cycles(before) == ROW_CYCLES_BEFORE_PR48[which]
    assert _kernel_row_cycles(got) <= ROW_CYCLES[which] \
        < ROW_CYCLES_BEFORE_PR48[which] - 896
    # the packing alone: the same passes over half the rows (and in the
    # backward kernel kr's cotangent sums over C rows, not 2 C: a pass
    # less at each of levels 0-3)
    assert _kernel_dots(unpacked) - _kernel_dots(got) \
        == {"fwd": 0, "bwd": 4}[which]
    assert 2 * c in map(_streamed_rows, _kernel_dot_eqns(unpacked))
    assert 2 * c in map(_streamed_rows, _kernel_dot_eqns(before))
    if which == "bwd":
        assert (_kernel_dots(before), _kernel_dots(got)) \
            == (BWD_DOTS_BEFORE_PR48, BWD_DOTS)
        # what is left above C rows a pass reads the states: Dk rows
        assert max(map(_streamed_rows, _kernel_dot_eqns(got))) \
            == kda_ops.KDA_KERNEL_DIM


# rows x passes a chunk-head under a HEAD's decay (ISSUE 51): no level;
# k and q stacked against k^T exactly (3 x 128) where the forward's levels
# streamed 704 and the lanes' running sum 192, and in the backward kernel
# the two cotangents stacked against k (2 x 128) and, transposed, against
# [k; q] (3 x 64) where the levels streamed 1792 and two running sums 384
HEAD_ROW_CYCLES = {"fwd": 2112, "bwd": 1472}


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_a_heads_decay_forms_the_scores_without_a_level(which):
    """The kernels traced with a head's decay, ``(B, H, N, 1, C)``, a
    chunk a row (ISSUE 51): the row-cycle count of each body, pinned; no roll
    and no exponential over the key lanes (the levels' own), every
    exponential over ``(H, C, C)`` or a column; the same function traced
    with a lane's decay ``(B, T, H * D)`` streams what it did."""
    shape = (1, 64, 1, 128)
    c = kda_ops.KDA_CHUNK
    head = _kernel_jaxpr(which, *shape, jitted=False, heads_decay=True)
    lane = _kernel_jaxpr(which, *shape, jitted=False)
    assert _kernel_row_cycles(head) == HEAD_ROW_CYCLES[which]
    assert _kernel_row_cycles(lane) == ROW_CYCLES[which]
    # the widest product is the two stacked scores' (or cotangents'), 2 C
    # rows a pass; nothing reads the states with more rows than Dk
    assert max(map(_streamed_rows, _kernel_dot_eqns(head))) == 2 * c \
        == kda_ops.KDA_KERNEL_DIM
    exps = [e.outvars[0].aval.shape for e in jaxpr_eqns(head, "exp")]
    assert exps and all(s[-1] in (1, c) for s in exps), exps
    assert any(s[-1] == kda_ops.KDA_KERNEL_DIM
               for s in (e.outvars[0].aval.shape for e in jaxpr_eqns(lane, "exp")))
    assert not list(jaxpr_eqns(head, "roll")) and list(jaxpr_eqns(lane, "roll"))
    # the decay enters and its cotangent leaves a chunk a row, 64 lanes
    call, = jaxpr_eqns(head, "pallas_call")
    assert call.invars[3].aval.shape == (1, 1, 1, 1, 64)
    if which == "bwd":
        assert call.outvars[3].aval.shape == (1, 1, 1, 1, 64)


@pytest.mark.parametrize("precision", ["highest", "default"])
@pytest.mark.parametrize("lo,hi", [(-3.0, -0.01), (-40.0, -1e-4),
                                   (-0.01, -1e-4)],
                         ids=["mixed", "wide", "decay-near-1"])
def test_packed_levels_equal_the_unpacked_statements(lo, hi, precision,
                                                     statements_before):
    """Both kernels under the interpreter against the same kernels with
    the statements they held before ISSUE 48.  With the packing alone
    undone the output, ``A``, ``Bs``, ``T`` and the cotangents of q, v
    and beta are equal bit for bit (only rows moved); k's and g's take
    the transposed product over kr, whose sum over a block's pairs runs
    in another order, so they agree to float32's rounding.  With level 0
    on the MXU and three-pass exact products besides, all agree inside
    ``test_kernels_match_the_plain_chunks``' tolerances: the forward's
    level 0 forms float32 products now, and the two passes over b's
    upper half add up in one accumulator.  Three chunks of two heads."""
    t, heads, d = 192, 2, 128
    args = _kda_inputs(t, lo, hi, heads=heads, seed=48)
    w = jnp.asarray(np.random.RandomState(3).randn(*args[2].shape),
                    jnp.float32)
    scale = d ** -0.5

    def run():
        lay = kda_ops._kernel_layout(*args)
        o, states, kept = kda_ops._kda_fwd.__wrapped__(
            *lay, scale=scale, interpret=True)
        grads = kda_ops._kda_bwd.__wrapped__(
            *lay, states, kept, w.reshape(1, t, -1), scale=scale,
            interpret=True)
        return [np.asarray(x) for x in (o, states, kept) + tuple(grads)]

    with jax.default_matmul_precision(precision):
        got = run()
        with statements_before(rows_only=True):
            unpacked = run()
        with statements_before():
            before = run()
    names = ("o", "states", "kept", "dq", "dk", "dv", "dg", "dbeta")
    for name, x, y, z in zip(names, got, unpacked, before):
        assert np.abs(y).max() > 0, name
        if name in ("dk", "dg"):
            assert np.abs(x - y).max() <= 1e-6 * np.abs(y).max(), name
        else:
            assert np.array_equal(x, y), name
        if precision == "highest":
            # at the default precision one bfloat16 rounding that falls
            # the other way is 2**-9 of a product
            assert np.abs(x - z).max() <= (
                1e-4 if name in ("o", "states", "kept") else 5e-4) \
                * np.abs(z).max(), name


@pytest.mark.parametrize("lo,hi", [(-3.0, -0.01), (-40.0, -1e-4)],
                         ids=["mixed", "wide"])
def test_kept_chunk_products_are_the_ones_formed_again(lo, hi):
    """What ``kda_chunk_fwd`` keeps for ``kda_chunk_bwd`` (ISSUE 45) is,
    bit for bit, what the chunk algebra's own statements give from the
    inputs (``_chunk_sums``, ``_chunk_scores_and_inverse``, called here
    outside any kernel over every chunk-head at once), and so are the
    five cotangents the backward kernel makes of either: the split of
    the algebra between the kernels moved statements and changed none.
    Three chunks of two heads; float32 and exact products throughout."""
    t, heads, c, d = 192, 2, kda_ops.KDA_CHUNK, 128
    args = _kda_inputs(t, lo, hi, heads=heads, seed=45)
    w = jnp.asarray(np.random.RandomState(2).randn(*args[2].shape),
                    jnp.float32)
    scale = d ** -0.5

    @jax.jit
    def formed_again(q, k, g, beta):
        def chunks(x):          # (1, T, H, ..) -> (H * N, C, ..)
            x = x.reshape((t // c, c, heads) + x.shape[3:])
            return jnp.moveaxis(x, 2, 0).reshape((-1, c) + x.shape[3:])
        q, k, g, beta = chunks(q), chunks(k), chunks(g), chunks(beta[..., None])
        G, masks = kda_ops._chunk_sums(g)
        kept = jnp.stack(kda_ops._chunk_scores_and_inverse(
            q, k, g, beta, G, scale, masks), 1)          # (H * N, 3, C, C)
        # a head's three side by side, as the forward kernel lays them
        kept = kept.reshape(heads, t // c, 3, c, c).transpose(1, 3, 0, 2, 4)
        return kept.reshape(1, t, heads * 3 * c)

    with jax.default_matmul_precision("highest"):
        _, (states, kept) = kda_ops._kernel_rule(*args, scale,
                                                 interpret=True)
        again = formed_again(args[0], args[1], args[3], args[4])
        assert kept.shape == again.shape == (1, t, heads * 3 * c)
        assert np.array_equal(np.asarray(kept), np.asarray(again))
        assert np.abs(np.asarray(kept)).max() > 0
        grads, grads_again = (
            kda_ops._kernel_rule_vjp(*args, (states, x), w, scale,
                                     interpret=True) for x in (kept, again))
    for x, y in zip(grads, grads_again):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("t,dk,dv,dtype,kernel", [
    (128, 128, 128, "bfloat16", True), (128, 128, 128, "float32", True),
    (96, 128, 128, "bfloat16", False), (128, 64, 128, "bfloat16", False),
    (128, 128, 64, "bfloat16", False), (128, 128, 128, "float16", False),
    (32, 128, 128, "bfloat16", False)],
    ids=["takes-bf16", "takes-f32", "ragged-t", "dk-64", "dv-64", "f16",
         "short"])
def test_delta_rule_lowering_is_chosen_from_shape_and_dtype(t, dk, dv, dtype,
                                                            kernel):
    """What the kernels do not take runs the plain chunks on every
    platform, and ``kda:lowering`` says so; what they take is the kernel
    pair in a program lowered for a TPU and the plain chunks in one for
    the CPU."""
    q, k, v, decay, beta = _kda_inputs(t, -1.0, 1.0, heads=1, dk=dk, dv=dv)
    args = (q, k, v.astype(dtype), decay, beta, jnp.zeros((1,)),
            jnp.zeros((dk,)))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        fn = jax.jit(kda_ops.kimi_delta_attention)
        tpu = jax.export.export(fn, platforms=["tpu"])(*args).mlir_module()
        cpu = fn.lower(*args).as_text()
        events = mx.trace.counter_events(["kda:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert events[0]["args"] == {"chunked": 1, "chunk": min(64, t),
                                 "kernel": int(kernel),
                                 "plain": int(not kernel)}
    assert events[0]["id"] == "%s%s" % (dtype, [1, t, 1, dk])
    assert ("tpu_custom_call" in tpu) == kernel
    assert ("kda_chunk_fwd" in tpu) == kernel
    assert "tpu_custom_call" not in cpu
    if not kernel:
        qn, kn, g, b = kda_ops._normalized_and_gated(q, k, decay, beta,
                                                     *args[5:])
        want = REF.delta_rule(qn, kn, args[2].astype(jnp.float32), g, b)
        got = fn(*args).astype(jnp.float32)
        assert np.abs(np.asarray(got - want)).max() \
            <= 2e-2 * np.abs(np.asarray(want)).max()


# one trace of each kernel a process; the backward one says how many chunk
# matrices it takes from the forward one (A, Bs, T) and does not form again
# and (ISSUE 51) whose decay it was traced for: a key lane's here, so the
# six levels, 64 packed rows each
KERNEL_TRACES = [{"fwd": 1, "bwd": 0, "decay": "lane", "level_rows": 64,
                  "vpu_levels": 1},
                 {"fwd": 0, "bwd": 1, "kept_products": 3, "decay": "lane",
                  "level_rows": 64, "vpu_levels": 0}]


def test_the_tpu_program_traces_each_kernel_once():
    """The same count on the path the chip takes (no interpreter: the
    choice by ``lax.platform_dependent``, two ops under ``jax.grad``,
    exported for a TPU): one ``fwd`` and one ``bwd`` trace, one function
    each with two call sites.  With the ``custom_vjp`` inside the choice
    the forward function was traced twice, once for the branch and once
    for its forward rule."""
    q, k, v, decay, beta = _kda_inputs(256, -1.0, 1.0, heads=1)
    args = (q, k, v.astype(jnp.bfloat16), decay, beta, jnp.zeros((1,)),
            jnp.zeros((128,)))

    def loss(*a):
        once = kda_ops.kimi_delta_attention(*a)
        twice = kda_ops.kimi_delta_attention(a[0], a[1], once, *a[3:])
        return jnp.square(twice.astype(jnp.float32)).sum()

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(
            jax.jit(jax.grad(loss, argnums=tuple(range(7)))),
            platforms=["tpu"])(*args).mlir_module()
        traces = mx.trace.counter_events(["kda:kernel_trace"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert [e["args"] for e in traces] == KERNEL_TRACES
    for fn in ("_kda_fwd", "_kda_bwd"):
        assert len(re.findall(r"func\.func private @%s\b" % fn, text)) == 1
        assert len(re.findall(r"call @%s\b" % fn, text)) == 2, fn
    assert "triangular_solve" not in text and "stablehlo.while" not in text
    # the backward kernel this program holds forms no score level and no
    # ``T - T (X T)`` again: 47 passes a chunk fewer than it had (ISSUE
    # 45), then 10 fewer (ISSUE 48)
    assert _bwd_kernel_dots(*q.shape) == BWD_DOTS \
        == BWD_DOTS_BEFORE_PR45 - 14 - 30 - 3 - 10


def test_kernels_are_traced_once_a_process_and_lowered_once_a_program(
        monkeypatch):
    """Two ``Module``s of the same shapes (as the harness's reference
    check and ``fit`` are), four KDA layers each, heads of 128, the
    kernels through interpret mode: ``kda:kernel_trace`` counts one
    ``fwd`` and one ``bwd`` for the process, and the step's lowered text
    holds each kernel's function once with four call sites."""
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    body = kda_ops.kimi_delta_attention
    monkeypatch.setattr(kda_ops, "kimi_delta_attention",
                        lambda *a: body(*a, interpret=True))
    # shapes no other test of this process has traced the kernels at
    over = dict(kda_heads=1, kda_head_dim=128, seq_len=192,
                full_attn_layers=[4])
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        texts = []
        for seed in (11, 12):
            net, kwargs, params, tokens, labels = _tiny(seed, **over)
            mod, batch = _bound(net, params, tokens, labels, "adam",
                                dict(ADAM))
            mod.forward_backward(batch)
            mod.update()
            assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()
            fused = mod._fused
            texts.append(fused._step._jit.lower(
                mod._fused_state, fused.make_batch(batch),
                jnp.asarray(ADAM["learning_rate"], jnp.float32),
                mod._fused_key).as_text())
        traces = mx.trace.counter_events(["kda:kernel_trace"], since_ns=mark)
        choices = mx.trace.counter_events(["kda:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert [e["args"] for e in traces] == KERNEL_TRACES
    assert len(choices) >= 8 and all(
        e["args"]["kernel"] == 1 for e in choices)
    for text in texts:
        for fn in ("_kda_fwd", "_kda_bwd"):
            assert len(re.findall(r"func\.func private @%s\b" % fn,
                                  text)) == 1, fn
            assert len(re.findall(r"call @%s\b" % fn, text)) == 4, fn
    assert _bwd_kernel_dots(BATCH, over["seq_len"], over["kda_heads"],
                            over["kda_head_dim"]) == BWD_DOTS


def test_causal_conv_and_silu():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 9, 5).astype(np.float32)
    w = rng.randn(5, 4).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    sym = mx.sym.CausalConv1D(mx.sym.Variable("data"),
                              mx.sym.Variable("weight"), kernel=4)
    assert sym.infer_shape(data=x.shape)[0][1] == (5, 4)
    check_symbolic_forward(sym, {"data": x, "weight": w}, [want], 1e-5)
    assert np.allclose(np.asarray(REF.causal_conv(jnp.asarray(x),
                                                  jnp.asarray(w))), want,
                       atol=1e-5)
    silu = mx.sym.Activation(mx.sym.Variable("data"), act_type="silu")
    check_symbolic_forward(silu, {"data": x}, [x / (1 + np.exp(-x))], 1e-5)


def test_attention_takes_value_heads_of_another_size():
    """Latent attention's shapes: q and k of 12 a head, v of 8, against
    dense per-head attention; which input differed is named."""
    rng = np.random.RandomState(3)
    b, t, h = 2, 40, 3
    q, k = (rng.randn(b, t, h, 12).astype(np.float32) for _ in range(2))
    v = rng.randn(b, t, h, 8).astype(np.float32)
    sym = mx.sym.CausalSelfAttention(*[mx.sym.Variable(n) for n in "qkv"],
                                     scale=0.3)
    assert sym.infer_shape(q=q.shape, k=k.shape, v=v.shape)[1] == [v.shape]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
    check_symbolic_forward(sym, {"q": q, "k": k, "v": v}, [want], 1e-4)
    with pytest.raises(mx.MXNetError, match="key .* differs from query"):
        sym.infer_shape(q=q.shape, k=(b, t, h, 8), v=v.shape)
    with pytest.raises(mx.MXNetError, match="value .* differs from query"):
        sym.infer_shape(q=q.shape, k=k.shape, v=(b, t + 1, h, 8))


@pytest.mark.parametrize("dqk,kernel", [(192, True), (128, True),
                                        (96, False)],
                         ids=["192-padded", "128", "96-plain"])
def test_latent_attention_lowering_on_a_tpu(dqk, kernel):
    """bfloat16 q, k of 192 a head against v of 128, lowered for a TPU,
    is the splash kernel (q and k padded to 256 inside its wrapper);
    under 128 it is the plain blocks.  ``attn:lowering`` names v's size
    where it differs."""
    q = jax.ShapeDtypeStruct((1, 256, 2, dqk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, 0.07).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(fn, platforms=["tpu"])(q, q, v) \
            .mlir_module()
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert text.count("tpu_custom_call") == (2 if kernel else 0)
    assert events[0]["args"] == {"kernel": int(kernel),
                                 "plain": int(not kernel),
                                 "pair": "library" if kernel else "none",
                                 "mask_form": "library" if kernel else "none"}
    assert events[0]["id"] == "bfloat16%s%s" % (
        [1, 256, 2, dqk], "" if dqk == 128 else "x128")


# -- the router and the share -------------------------------------------------

def test_sigmoid_router_bias_moves_the_choice_and_not_the_weights():
    rng = np.random.RandomState(4)
    T, E, k = 24, 16, 4
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))

    def want(bias):
        top = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
        picked = np.take_along_axis(s, top, -1)
        return top, 2.446 * picked / picked.sum(-1, keepdims=True)

    plain = route_sorted(logits, k, renormalize=True, score="sigmoid",
                         scale=2.446, select_bias=jnp.zeros(E))
    top0, w0 = want(np.zeros(E))
    assert np.allclose(np.asarray(plain.weight), w0, rtol=1e-5)
    assert np.array_equal(np.asarray(plain.counts),
                          np.bincount(top0.ravel(), minlength=E))
    # a bias large enough to pull expert 3 into every token's choice
    bias = np.zeros(E, np.float32)
    bias[3] = 2.0
    moved = route_sorted(logits, k, renormalize=True, score="sigmoid",
                         scale=2.446, select_bias=jnp.asarray(bias))
    top1, w1 = want(bias)
    assert np.asarray(moved.counts)[3] == T > np.asarray(plain.counts)[3]
    # ... and the weights are the chosen experts' own scores, bias-free
    assert np.allclose(np.asarray(moved.weight), w1, rtol=1e-5)
    assert np.allclose(np.asarray(moved.weight).sum(-1), 2.446, rtol=1e-5)
    grad = jax.grad(lambda b: route_sorted(
        logits, k, renormalize=True, score="sigmoid",
        select_bias=b).weight.sum())(jnp.asarray(bias))
    assert not np.asarray(grad).any()


def _share_block(E, k, H, D, held, first, scale):
    """One rank's share; ``scale`` None is the renormalised softmax
    router with no selection bias and no shared expert."""
    router = dict(score="softmax") if scale is None else dict(
        score="sigmoid", scale=scale, bias_rate=1e-3, shared_hidden=H)
    return MoEFeedForward(mx.sym.Variable("data"), num_hidden=H,
                          num_experts=E, k=k, capacity_factor=0.0,
                          name="moe", act_type="silu", gated=True,
                          no_bias=True, renormalize=True, output_dim=D,
                          experts_held=held, first_expert=first, **router)


@pytest.mark.parametrize("config,E,k,held,scale", [
    ("kimi-linear-48b-a3b", 16, 4, 4, 2.446),
    ("glm-4.7-flash", 16, 2, 2, 1.8),
    ("sdar-30b-a3b", 128, 8, 16, None),
    ("keye-vl-2.0-30b-a3b", 128, 8, 16, None)],
    ids=["kimi-4-ranks-of-4", "glm-8-ranks-of-2", "sdar-8-ranks-of-16",
         "keye-8-ranks-of-16"])
def test_the_shares_add_up_to_the_uncut_layer(config, E, k, held, scale):
    """E = 16 experts over 4 ranks of 4 (8 ranks of 2, as the GLM
    configuration's eight; 128 over 8 ranks of 16 under a renormalised
    softmax with neither bias nor shared expert, as the SDAR and the Keye
    configurations'): each rank's output (its held experts' part plus
    the shared expert), summed, with the shared expert counted once, is
    that configuration's reference layer with all experts held; and each
    rank's output is the reference given the same share."""
    REF = manifest.load_module("reference", config)
    rng = np.random.RandomState(5)
    T, D, H = 40, 12, 10
    x = rng.randn(T, D).astype(np.float32)
    full = {"moe_gate_weight": rng.randn(E, D),
            "moe_experts_i2h_gate_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D)}
    if scale is not None:
        full.update({"moe_shared_i2h_gate_weight": 0.5 * rng.randn(H, D),
                     "moe_shared_i2h_weight": 0.5 * rng.randn(H, D),
                     "moe_shared_h2o_weight": 0.5 * rng.randn(D, H)})
    full = {n: v.astype(np.float32) for n, v in full.items()}
    bias = (0.3 * rng.randn(E)).astype(np.float32)
    m = {"num_experts": E, "experts_per_tok": k, "routed_scale": scale}
    state = {} if scale is None else \
        {"moe_dispatch_select_bias": jnp.asarray(bias)}
    p = dict({n: jnp.asarray(v) for n, v in full.items()}, **state)
    shared = np.zeros((T, D), np.float32)
    with jax.default_matmul_precision("highest"):
        # (output, ..., choices per expert), whatever lies between
        whole, *_, counts = REF.moe(p, "", jnp.asarray(x), m)
        if scale is not None:
            shared = REF.swiglu(jnp.asarray(x), *(
                p["moe_shared_%s_weight" % n]
                for n in ("i2h_gate", "i2h", "h2o")))
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, held):
        mine = {n: (v[first:first + held] if "experts" in n else v)
                for n, v in full.items()}
        net = _share_block(E, k, H, D, held, first, scale)
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        exe.arg_dict["data"][:] = x
        for n, v in mine.items():
            exe.arg_dict[n][:] = v
        if scale is not None:
            exe.aux_dict["moe_dispatch_select_bias"][:] = bias
        exe.forward(is_train=False)
        out = exe.outputs[0].asnumpy()
        with jax.default_matmul_precision("highest"):
            want = REF.moe(
                dict({n: jnp.asarray(v) for n, v in mine.items()}, **state),
                "", jnp.asarray(x), dict(m, experts_held=held,
                                         first_expert=first))[0]
        assert np.abs(out - np.asarray(want)).max() \
            <= 1e-4 * np.abs(np.asarray(want)).max()
        # evaluation does not move the bias
        if scale is not None:
            assert np.array_equal(
                exe.aux_dict["moe_dispatch_select_bias"].asnumpy(), bias)
        total += out - np.asarray(shared)
    total += np.asarray(shared)
    assert np.asarray(counts).sum() == T * k
    assert np.abs(total - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()


# -- the whole model -----------------------------------------------------------

def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = kimi_linear_lm(**kwargs)
    arg_shapes, _, _ = net.infer_shape(
        data=(BATCH, kwargs["seq_len"]),
        softmax_label=(BATCH, kwargs["seq_len"]))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gamma"):
            params[name] = (1 + 0.1 * rng.randn(*shape)).astype(np.float32)
        elif name.endswith("a_log_bias"):
            params[name] = rng.uniform(-1, 1, shape).astype(np.float32)
        else:
            # wide enough that routing, gates and attention are not flat
            params[name] = (0.2 * rng.randn(*shape)).astype(np.float32)
    tokens = rng.randint(0, kwargs["vocab_size"],
                         (BATCH, kwargs["seq_len"])).astype(np.int32)
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


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def test_model_matches_reference_gradients_adam_step_and_bias_move(
        monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)

    # every gradient, through one SGD step of the fused train step
    lr = 0.125
    mod, batch = _bound(net, params, tokens, labels, "sgd", {
        "learning_rate": lr, "momentum": 0.0, "wd": 0.0,
        "rescale_grad": 1.0})
    mod.forward_backward(batch)
    mod.update()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    assert abs(float(outs[0].mean()) - ref["loss"]) <= 1e-5 * ref["loss"]
    blocks = ["l%d_moe_dispatch" % l for l in (2, 3, 4, 5)]
    for row, block in zip(outs[-1], blocks):
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][block]))
        assert row[-1] == 0
    after, aux = mod.get_params()
    errors = {k: _rel((params[k] - after[k].asnumpy()) / lr, ref["grads"][k])
              for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 2e-4, errors

    # the configuration's optimizer: Adam's first step and the bias
    names = ["l1_q_proj_weight", "l2_kda_a_log_bias", "l3_kda_dt_bias",
             "l4_kv_b_proj_weight", "l5_moe_gate_weight",
             "l3_moe_experts_i2h_weight", "embed_weight"]
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, aux = mod.get_params()
    for name in names:
        got = after[name].asnumpy() - params[name]
        # an element whose gradient is ~0 may flip sign: Adam's first
        # step is lr * sign(g); such elements are a sliver of the norm
        assert _rel(got, want["updates"][name]) <= 0.02, name
    for block in blocks:
        moved = aux[block + "_select_bias"].asnumpy()
        assert np.allclose(moved, want["bias_moves"][block], atol=1e-9)
        assert np.allclose(np.abs(moved)[moved != 0], 1e-3)


def test_reference_flops_count_the_held_share():
    """At the published widths, depth 5, 8 of 256 experts, 20 480
    vocabulary rows: the held share of the routed experts is a quarter
    of an expert a token."""
    kw = dict(num_layers=5, hidden_size=2304, full_attn_layers=[4, 8],
              dense_layers=1, kda_heads=32, kda_head_dim=128,
              conv_kernel=4, mla_heads=32, kv_lora_rank=512,
              qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
              dense_width=9216, num_experts=256, experts_per_tok=8,
              expert_width=1024, shared_width=1024, vocab_size=20480,
              seq_len=4096, experts_held=8)
    per_token = REF.train_flops_per_sample({"model": {"kwargs": kw}})
    D, W = 2304, 4096
    kda = 8 * D * W + 4 * (D * 128 + 128 * W) + 2 * D * 32 + 24 * W \
        + 6 * 32 * 128 * 128
    mla = 2 * D * 32 * 192 + 2 * D * 576 + 2 * 512 * 32 * 256 \
        + 2 * W * D + 4096 * 32 * 320
    sparse = 2 * D * 256 + 6 * D * 1024 + 0.25 * 6 * D * 1024
    assert per_token == 3 * (4 * kda + mla + 6 * D * 9216 + 4 * sparse
                             + 2 * D * 20480)
    whole = REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kw, experts_held=0)}})
    assert whole - per_token == 3 * 4 * 7.75 * 6 * D * 1024


# -- what Module holds while the fused step is live ----------------------------

def test_fused_module_holds_no_executor_arrays_and_hands_weights_back(
        tmp_path):
    """Once the fused state exists the executor group's argument and
    gradient arrays are gone; ``get_params``, a checkpoint round trip
    and ``_disable_fused`` still see the trained weights, and the
    classic path goes on from them."""
    net, kwargs, params, tokens, labels = _tiny(seed=9, num_layers=2)
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    assert mod._exec_group.execs          # bound, nothing trained yet
    for _ in range(2):
        mod.forward_backward(batch)
        mod.update()
    assert mod._fused_state is not None
    assert mod._exec_group.execs == [] \
        and mod._exec_group.param_arrays is None
    trained = {k: np.asarray(v) for k, v in
               mod._fused_state["params"].items()}
    assert any(np.abs(trained[k] - params[k]).max() > 0 for k in params)
    got, aux = mod.get_params()
    assert all(np.array_equal(got[k].asnumpy(), trained[k])
               for k in trained)
    assert mod._exec_group.execs == []    # reading does not bind
    # evaluation on the live weights, still without executor arrays
    mod.forward(batch, is_train=False)
    assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()
    assert mod._exec_group.execs == []

    prefix = str(tmp_path / "kimi")
    mod.save_checkpoint(prefix, 1)
    _, args, auxs = mx.model.load_checkpoint(prefix, 1)
    assert all(np.array_equal(args[k].asnumpy(), trained[k])
               for k in trained)
    assert all(np.array_equal(auxs[k].asnumpy(), aux[k].asnumpy())
               for k in aux)
    fresh = mx.mod.Module(net, context=mx.cpu(0))
    fresh.bind(data_shapes=[("data", tokens.shape)],
               label_shapes=[("softmax_label", labels.shape)])
    fresh.init_params(mx.init.Zero())
    fresh.init_optimizer(optimizer="adam", optimizer_params=dict(ADAM))
    mx.checkpoint.restore_module(
        mx.checkpoint.CheckpointManager(prefix + "-ckpt"), fresh)
    assert all(np.array_equal(np.asarray(fresh._fused_state["params"][k]),
                              trained[k]) for k in trained)

    # both modules take the same third step: one fused, one classic
    fresh.forward_backward(batch)
    fresh.update()
    mod._disable_fused("test")
    assert mod._fused is None and mod._exec_group.execs
    for name, block in zip(mod._param_names, mod._exec_group.param_arrays):
        assert np.array_equal(block[0].asnumpy(), trained[name])
    mod.forward_backward(batch)
    mod.update()
    classic = mod.get_params()[0]
    fused = fresh.get_params()[0]
    for k in trained:
        assert np.abs(classic[k].asnumpy() - fused[k].asnumpy()).max() \
            <= 1e-5 + 1e-3 * np.abs(fused[k].asnumpy() - trained[k]).max()


def test_set_params_and_a_new_optimizer_bind_the_executor_arrays_again():
    net, kwargs, params, tokens, labels = _tiny(seed=10, num_layers=2)
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    assert mod._exec_group.execs == []
    trained, aux = mod.get_params()
    trained = {k: v.asnumpy() for k, v in trained.items()}
    mod.init_optimizer(optimizer="sgd", force_init=True, optimizer_params={
        "learning_rate": 0.01, "rescale_grad": 1.0})
    assert mod._fused_state is None and mod._exec_group.execs
    for name, block in zip(mod._param_names, mod._exec_group.param_arrays):
        assert np.array_equal(block[0].asnumpy(), trained[name])
    mod.forward_backward(batch)
    mod.update()
    assert mod._exec_group.execs == []
    mod.set_params({k: mx.nd.array(v) for k, v in params.items()}, aux)
    assert mod._fused_state is None and mod._exec_group.execs
    for name, block in zip(mod._param_names, mod._exec_group.param_arrays):
        assert np.array_equal(block[0].asnumpy(), params[name])


def test_fit_feeds_the_held_share_to_moe_load():
    net, kwargs, params, tokens, labels = _tiny(seed=3)
    rng = np.random.RandomState(0)
    X = rng.randint(0, kwargs["vocab_size"],
                    (8, kwargs["seq_len"])).astype(np.int32)
    it = mx.io.NDArrayIter(X, np.roll(X, -1, 1), batch_size=BATCH)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        since = time.perf_counter_ns()
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1, eval_metric=mx.metric.OutputMean(0),
                optimizer="adam", initializer=mx.init.Normal(0.02),
                optimizer_params=dict(ADAM))
        events = mx.trace.counter_events(["moe:load"], since_ns=since)
    finally:
        mx.trace.set_enabled(was)
    blocks = ["l%d_moe_dispatch" % l for l in (2, 3, 4, 5)]
    assert mod._fused.head("moe_load")[1] == blocks
    assert len(events) == 4 * len(blocks)
    routed = BATCH * kwargs["seq_len"] * kwargs["experts_per_tok"]
    for e in events:
        assert e["args"]["routed"] == routed and e["args"]["dropped"] == 0
        assert 0 < e["args"]["held"] < routed
    assert [n for n in mod._aux_names] == [b + "_select_bias"
                                           for b in blocks]


# sha256 of the symbol's arguments, outputs and states (names and shapes,
# in order: ``common/symbol_signature.py``) at the parent of ISSUE 68,
# which made the mixers' output stage one node and meant to move nothing
# a checkpoint or the reference's weights map by
SIGNATURE_WAS = {
    "cell": "61b87bedb5279d790b41c5ea1c3ba0ea93dbc6c5e89e19da501bce92d5b67302",
    "tiny": "0681ed4ac795d882aa07dd954774479f8ad19594adf9997ca629f1452f5b731a",
}


@pytest.mark.parametrize("case", sorted(SIGNATURE_WAS))
def test_the_output_stage_is_one_node_under_the_names_it_had(case):
    """Every KDA layer ends in ONE ``GatedRMSNorm`` with a sigmoid gate,
    fed the gate's up-projection as it comes; its weight is still
    ``l<i>_o_norm_gamma`` of a head's width, and the symbol's arguments,
    outputs and states are the parent's, name for name and shape for
    shape."""
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cell = json.load(f)["model"]["kwargs"]
    kwargs, batch = (cell, 1) if case == "cell" else (TINY, BATCH)
    net = kimi_linear_lm(**kwargs)
    shape = (batch, kwargs["seq_len"])
    assert signature(net, data=shape, softmax_label=shape) \
        == SIGNATURE_WAS[case]
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=shape, softmax_label=shape)[0]))
    mixers = [l for l in range(1, kwargs["num_layers"] + 1)
              if l not in kwargs["full_attn_layers"]]
    stages = nodes(net, "GatedRMSNorm")
    assert [n.name for n in stages] == ["l%d_o_norm" % l for l in mixers]
    for l, node in zip(mixers, stages):
        assert shapes["l%d_o_norm_gamma" % l] == (kwargs["kda_head_dim"],)
        assert node.params["act_type"] == "sigmoid"
        assert [i[0].name for i in node.inputs][1:] == [
            "l%d_o_norm_gamma" % l, "l%d_g_up" % l]
    assert not [n for n in nodes(net, "RMSNorm") if "o_norm" in n.name]
    assert not [n for n in nodes(net, "Activation")
                if n.params["act_type"] == "sigmoid"]


def test_a_checkpoint_written_before_pr36_still_loads():
    """One rank's share (4 of 16 experts held, rows behind the groups):
    parameters, saved graph and loss are the commit before's
    (``tests/common/old_checkpoint.py``)."""
    from old_checkpoint import check_checkpoint_written_before_pr36
    net, _, _, tokens, labels = _tiny(seed=36, num_layers=2)
    check_checkpoint_written_before_pr36("kimi", net, tokens, labels,
                                         dict(ADAM))
