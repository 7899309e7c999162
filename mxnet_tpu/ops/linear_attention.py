"""Linear-attention block ops: ``KimiDeltaAttention`` (the gated delta
rule with a per-channel decay, "KDA": Kimi Linear, arXiv:2510.26692),
``GatedDeltaNet`` (the same rule with ONE decay a head and token and
fewer key heads than value heads: Gated DeltaNet, arXiv:2412.06464) and
the depthwise ``CausalConv1D`` in front of them (``ops/causal_conv.py``).

Per head the layer keeps a ``(Dk, Dv)`` state and, token by token,

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

with ``g_t <= 0`` a log-decay per key channel and ``beta_t`` in (0, 1).
``gated_delta_rule`` computes exactly that in chunks of ``KDA_CHUNK``
tokens (the WY form): inside a chunk the corrections ``u_i = beta_i (v_i
- S_{i-1}^T diag(exp g_i) k_i)`` solve one unit lower-triangular system
in float32, whose matrix holds ``k_i . k_j exp(G_i - G_j)`` (``G`` the
running sum of ``g`` inside the chunk); between chunks a ``lax.scan``
carries the state.  ``exp(G_i - G_j)`` is never split into ``exp(G_i)
exp(-G_j)`` across more than the chunk's decay allows: blocks of
``KDA_SUB`` tokens take a reference point between them (both factors
<= 1), and a block against itself is computed channel by channel.  So a
decay of ``exp(-40)`` a token is as safe as one of 1.

The ops' body (normalize q and k, the two gates, the rule) is one
algorithm behind two front ends, ``kimi_delta_attention`` and
``gated_delta_net``.  What tells them apart is the rank of the decay's
projection, read in one place (``kda_gates``, ``_normalized_and_gated``):
``(B, T, H, Dk)`` is a key lane's, ``(B, T, Hv)`` a head's one number,
under which the ``Hk`` key heads are repeated for their value heads
inside the ``custom_vjp``'s body, so that a step keeps the op's own
inputs and nothing of their size times ``Dk``.  A head's decay stays one
number a head and token wherever it can: the plain chunks take it handed
over the key lanes (``_plain_attention``), the kernels as it is, a chunk
a row (below).  The body has two lowerings, chosen from what
the code observes as ``causal_attention`` chooses.  The plain ``lax`` /
``jnp`` chunks above, differentiable by autodiff, run on every platform
and are the parity oracle.  Where the program is LOWERED for a TPU and
the inputs are ones the kernels take (``_kernel_takes``: whole chunks,
heads of 128), two Pallas kernels run the rule instead,
``kda_chunk_fwd`` and ``kda_chunk_bwd``, over a grid of (batch, a few
heads, chunk): the heads' states and the chunk's float32 products stay
in VMEM and the state is carried along the sequential chunk axis in
scratch.  The forward kernel writes out every chunk's entry state
(``f32[B, H, N, Dv, Dk]``) and the three ``(C, C)`` matrices that cost
it most MXU passes, the scores ``A`` and ``Bs`` and the triangular
inverse ``T`` (``f32[B, T, H * 3 * C]``, a head's three side by side);
the backward kernel reads them back and computes again, from q, k, v, g
and beta, only the cheap rest: the running sum, the decays, the residual
against the entry state and the corrections ``u = T (beta r)``
(``_chunk_entry``, which both kernels run).  Inside the kernels ``exp(G_i
- G_j)`` is kept below 1 by halving: at level ``l`` a block of ``2 << l``
tokens takes the running sum at the end of its first half as the
reference point of its lower-left quarter, so every pair ``j < i`` is
formed once, by a matmul, at the level where it first falls into two
halves; the unit triangular system is inverted by the same halving (``T
<- T - T X T``).  A level's products stream only the rows that carry
pairs: k and q under the decay from the reference point are zero on every
first-half row of a block, so the level packs them into one ``(C, Dk)``
operand, k's rows where they lie and q's ``s = 1 << l`` rows above their
own (one sublane roll, ``_packed``); one product with the first half's
rows gives ``A``'s part in the second-half rows and ``Bs``'s in the
first-half ones, which a roll brings back down (``_level_scores``), and
the backward kernel packs the two scores' cotangents the same way
(``_level_transposes``).  Level 0's blocks are two rows, one pair a
packed row: the forward kernel forms them as float32 row products on the
VPU.  Pairs less than ``KDA_SUB`` apart, the inverse, its
products with the right-hand sides and the running sums keep float32's
digits whatever the matmul precision in force (three bfloat16 products
a pair, ``_dot``: in two MXU passes where the contraction is ``C`` deep,
half a pass's depth, in three where it is ``Dk``), as the plain chunks
form them in float32; every other product follows the precision in
force, as the plain chunks' matmuls do.

All of that halving is a LANE's decay's: ``exp(G_i - G_j)`` a key lane
does not leave the dot product ``k_i . k_j``.  A head's decay does: it
is one number a pair, so ``A = tril(K K^T, -1) * D`` and ``Bs = scale *
tril(Q K^T) * D`` with ``D[i, j] = exp(G_i - G_j)`` on ``j <= i``, a
``(C, C)`` matrix whose every entry is <= 1 (``exp(G_i)`` and ``exp(-
G_j)`` are never formed apart), and there is no reference point, no
level and no packing.  The same two kernels hold both forms, and what
decides is the decay's rank where ``_kernel_layout`` hands it over: a
lane's as ``(B, T, H * Dk)`` float32 beside q and k, a head's as ``(B,
H, T / C, 1, C)``, a chunk a row of lanes (``_kernel_rule`` /
``_kernel_rule_vjp`` carry it, and its cotangent, as they carry
beta's; not in beta's ``(B, H, T, 1)``, which lies on the chip with its
last dimension padded to 128 lanes: the bytes of the decay on every
key lane).  The forms share the grid, the block specs, what is kept
(``_kept_shapes``), the inverse (``_chunk_scores_and_inverse``'s second
half), ``_chunk_entry``, the output, the state and the backward kernel
down to the states' cotangent; under a head's decay the decays there
are ``(H, C, 1)`` columns that scale rows.  They differ in how the
chunk's running sum, ``A``, ``Bs`` and their transposes are formed: a lane's by
``_sum_rows``, ``_lane_scores`` and the level loop of ``_bwd_kernel``; a
head's by a float32 sum of 64 numbers on the VPU (``_chunk_sums``), by
k and q stacked, ``2 C`` rows against ``k^T`` in ONE exact product under
``D`` (``_head_scores``: no pair at a lower precision than the levels
give it), and in the backward kernel by the two cotangents under ``D``
stacked against k and, transposed, against ``[k; q]``
(``_head_cotangents``); ``G``'s cotangent from the scores is ``rowsum(P)
- colsum(P)`` of ``P = dA A + dBs Bs`` off the diagonal, which adds up
to nothing over a chunk by construction, the states' terms are summed over
the lanes inside the kernel, and ``dg`` leaves a chunk a row, as g came.

The lowering differentiates itself (``jax.custom_vjp`` around the op's
body, which keeps the op's inputs, the entry states and ``A``, ``Bs``,
``T`` and nothing else: ``_kept_shapes``) through two module-level
``jax.jit`` functions of arrays and static sizes only, so a process
traces each kernel once and a program holds each once, whatever the
number of layers and modules that call them: the counter
``kda:kernel_trace`` (``fwd`` / ``bwd``; the ``bwd`` event's
``kept_products`` is the number of chunk matrices that backward takes
from the forward kernel, 3; both events' ``decay`` whose decay the
kernel was traced for, ``lane`` or ``head``, ``level_rows`` the rows a
level's products stream, 64, and ``vpu_levels`` the levels that kernel
forms off the MXU, 1 and 0; under a head's decay both read 0: no level)
fires from inside their bodies and so counts traces, not calls: one a
kernel, process and decay's kind. The counter ``kda:lowering``
(``gdn:lowering`` for ``GatedDeltaNet``, with its ``key_heads`` and ``value_heads``) records
the choice per traced op (``kernel`` / ``plain``) as ``attn:lowering``
does for attention, and either op's body runs under ``kda.l<layer>``, the
scope of the rule and its kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..base import MXNetError
from .causal_conv import biased_conv, causal_conv, causal_conv1d, gated_conv
from .nn import ACTIVATIONS
from .pallas_kernels import _kernel_on_tpu, pl
from .registry import OpDef, Param, register_op
from .transformer import layer_scope

__all__ = ["causal_conv1d", "gated_delta_net", "gated_delta_rule",
           "kda_gates", "kimi_delta_attention"]

# tokens a chunk: one triangular system and one step of the state's scan
KDA_CHUNK = 64
# tokens a block inside a chunk: its own (KDA_SUB, KDA_SUB, Dk) products
# are formed channel by channel, every other block by a matmul
KDA_SUB = 16
# the one head size the kernels take: a row of lanes, a square state
KDA_KERNEL_DIM = 128


def _l2norm(x, eps=1e-6):
    """``x / sqrt(sum(x**2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def kda_gates(decay, beta, a_log, dt_bias):
    """The layer's two gates from their projections, in float32: ``g =
    -exp(a_log[head]) * softplus(decay + dt_bias)`` in ``decay``'s shape
    and ``beta = sigmoid(beta)`` per head.  ``decay``'s rank says whose
    the decay is: ``(B, T, H, Dk)`` with ``dt_bias`` ``(H * Dk,)`` is a
    key lane's (KDA), ``(B, T, H)`` with ``dt_bias`` ``(H,)`` a head's
    one number (Gated DeltaNet)."""
    f32 = jnp.float32
    a = jnp.exp(a_log.astype(f32))
    if decay.ndim == 4:
        a = a[:, None]
    g = -a * jax.nn.softplus(
        decay.astype(f32) + dt_bias.astype(f32).reshape(decay.shape[2:]))
    return g, jax.nn.sigmoid(beta.astype(f32))


@jax.checkpoint
def _chunk_scores(q, k, G):
    """Inside every chunk: ``A[i, j] = k_i . k_j exp(G_i - G_j)`` for
    ``j < i`` and ``B[i, j] = q_i . k_j exp(G_i - G_j)`` for ``j <= i``,
    zero elsewhere; all of ``(..., C, Dk)`` -> two ``(..., C, C)``.
    Checkpointed: the ``(KDA_SUB, KDA_SUB, Dk)`` products of the diagonal
    blocks are formed again in the backward pass, not kept."""
    c = q.shape[-2]
    s = min(KDA_SUB, c)
    low = jnp.tril(jnp.ones((s, s), bool))
    rows_a, rows_b = [], []
    for lo in range(0, c, s):
        Gi, ki, qi = (x[..., lo:lo + s, :] for x in (G, k, q))
        n = Gi.shape[-2]
        e = jnp.exp(jnp.where(low[:n, :n, None],
                              Gi[..., :, None, :] - Gi[..., None, :, :],
                              -jnp.inf))                     # (.., s, s, Dk)
        kj = ki[..., None, :, :] * e
        a = [jnp.tril((ki[..., :, None, :] * kj).sum(-1), -1)]
        b = [(qi[..., :, None, :] * kj).sum(-1)]
        if lo:
            # against the blocks before it: the reference point is the
            # running sum just before this block, so both factors decay
            ref = G[..., lo - 1:lo, :]
            right = k[..., :lo, :] * jnp.exp(ref - G[..., :lo, :])
            left = jnp.exp(Gi - ref)
            a.insert(0, jnp.einsum("...ik,...jk->...ij", ki * left, right))
            b.insert(0, jnp.einsum("...ik,...jk->...ij", qi * left, right))
        tail = ((0, 0),) * (q.ndim - 1) + ((0, c - lo - n),)
        rows_a.append(jnp.pad(jnp.concatenate(a, -1), tail))
        rows_b.append(jnp.pad(jnp.concatenate(b, -1), tail))
    return jnp.concatenate(rows_a, -2), jnp.concatenate(rows_b, -2)


def gated_delta_rule(q, k, v, g, beta, scale: float, chunk: int = KDA_CHUNK):
    """The recurrence of the module docstring over ``(B, T, H, Dk)`` q, k
    and g (float32 log-decay), ``(B, T, H, Dv)`` v and ``(B, T, H)``
    beta, from a zero state -> ``(B, T, H, Dv)`` in v's dtype.  Chunked;
    T need not be a multiple of the chunk (the tail is padded with
    tokens that write nothing).  The plain chunks: the lowering of every
    platform, and the kernels' parity twin."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    n = -(-t // chunk)
    pad = n * chunk - t
    f32 = jnp.float32

    def blocks(x):                      # (B, T, H, ..) -> (N, B, H, C, ..)
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    out_dtype = v.dtype
    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                      # (N, B, H, C, Dk)
    A, Bq = _chunk_scores(q, k, G)
    decayed = jnp.exp(G)
    bcol = beta[..., None]
    # u = Wv - Wk S0 for the chunk's entry state S0: both from one solve
    W = jax.scipy.linalg.solve_triangular(
        jnp.eye(chunk, dtype=f32) + bcol * A,
        jnp.concatenate([bcol * v, bcol * k * decayed], -1),
        lower=True, unit_diagonal=True)
    Gc = G[..., -1:, :]
    xs = (W[..., :dv], W[..., dv:], q * decayed * scale, Bq * scale,
          k * jnp.exp(Gc - G), jnp.exp(Gc[..., 0, :]))

    def one_chunk(S, x):
        wv, wk, qd, bq, kc, dec = x
        u = wv - jnp.einsum("bhck,bhkv->bhcv", wk, S)
        o = jnp.einsum("bhck,bhkv->bhcv", qd, S) \
            + jnp.einsum("bhcj,bhjv->bhcv", bq, u)
        S = dec[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", kc, u)
        return S, o

    _, o = lax.scan(one_chunk, jnp.zeros((b, h, dk, dv), f32), xs)
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)    # (B, N, C, H, Dv)
    return o.reshape(b, n * chunk, h, dv)[:, :t].astype(out_dtype)


# -- the TPU lowering ---------------------------------------------------------

# heads a grid step: their chains of small dependent matmuls interleave
KDA_KERNEL_HEADS = 4
# the (C, C) chunk matrices the forward kernel keeps for the backward one:
# the scores A and Bs and the triangular inverse T
KDA_KEPT = 3
_NN = (((2,), (1,)), ((0,), (0,)))    # a @ b, a batch of heads
_NT = (((2,), (2,)), ((0,), (0,)))    # a @ b.T
_TN = (((1,), (1,)), ((0,), (0,)))    # a.T @ b
# the contraction a pass of a v5e's MXU takes whole
_MXU_DEPTH = 128


def _bf16_parts(x, n):
    """x as a sum of n bfloat16 arrays, each the bfloat16 of what the
    ones before it left: two hold 16 of float32's 24 digits, three all."""
    parts = []
    for _ in range(n):
        parts.append(x.astype(jnp.bfloat16))
        x = x - parts[-1].astype(jnp.float32)
    return parts


def _one_pass(a, b, dims):
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _dot(a, b, dims, exact=False):
    """A batch of float32 products on the MXU: at the precision in force
    (one bfloat16 pass at the default one), or ``exact``, to 2**-16 of
    each product whatever is in force, by three passes over the
    operands' bfloat16 halves.  Where the contraction is ``C`` deep, half
    of what a pass takes, the two passes over b's upper half are one:
    a's two halves side by side along the contraction against b's upper
    half twice."""
    if not exact:
        return lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)
    (ah, al), (bh, bl) = _bf16_parts(a, 2), _bf16_parts(b, 2)
    ((ca,), (cb,)), _ = dims
    if 2 * a.shape[ca] <= _MXU_DEPTH:
        return _one_pass(jnp.concatenate([ah, al], ca),
                         jnp.concatenate([bh, bh], cb), dims) \
            + _one_pass(ah, bl, dims)
    return _one_pass(ah, bh, dims) + (_one_pass(ah, bl, dims)
                                      + _one_pass(al, bh, dims))


def _sum_rows(ones, x):
    """``ones @ x`` for a ``(C, C)`` matrix of zeros and ones, exactly:
    the ones are whole in bfloat16, so three passes over x's three
    bfloat16 parts lose nothing."""
    ones = jnp.broadcast_to(ones.astype(jnp.bfloat16),
                            x.shape[:1] + ones.shape)
    return sum(_one_pass(ones, part, _NN) for part in _bf16_parts(x, 3))


def _level(l, q, k, g, G, scale, row):
    """Level ``l`` of the halving (blocks of ``2 << l`` tokens, halves of
    ``s = 1 << l``): rows of a block's second half decayed from the
    block's reference point, the running sum at the end of its first
    half, and rows of the first half decayed up to it; both factors are
    <= 1.  -> k and q times the first, k times the second, the factors."""
    from jax.experimental.pallas import tpu as pltpu
    c = q.shape[1]
    s = 1 << l
    second = ((row >> l) & 1) == 1                       # (C, 1)
    if l == 0:
        x = jnp.where(second, g, 0.0)
    elif l == 1:
        at = row & 3
        x = jnp.where(at == 0, pltpu.roll(g, c - 1, 1), 0.0) \
            + jnp.where(at >= 2, g, 0.0) \
            + jnp.where(at == 3, pltpu.roll(g, 1, 1), 0.0)
    else:
        ref = jnp.concatenate(
            [jnp.broadcast_to(G[:, lo + s - 1:lo + s], g.shape[:1]
                              + (2 * s,) + g.shape[2:])
             for lo in range(0, c, 2 * s)], 1)
        x = jnp.where(second, G - ref, ref - G)
    f = jnp.exp(jnp.minimum(x, 0.0))
    left, right = jnp.where(second, f, 0.0), jnp.where(second, 0.0, f)
    return k * left, q * left * scale, k * right, left, right


def _roll_rows(x, shift):
    """``x`` ``(H, C, ..)`` with row ``i`` moved to row ``i + shift`` (mod
    C): a sublane roll."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift % x.shape[1], 1)


def _packed(second, moved, l):
    """Two operands of level ``l`` that are zero outside the rows of a
    block's second half, in one of their size: ``second`` where it lies
    and ``moved``'s rows ``s = 1 << l`` above their own, in the first
    half of the same block, which both leave empty.  A product over the
    packed rows streams the rows that carry pairs and no others."""
    return second + _roll_rows(moved, -(1 << l))


# the levels whose scores the forward kernel forms off the MXU: level 0,
# whose blocks are two rows, so that a packed row meets one row of kr, the
# even row of its block
KDA_VPU_LEVELS = 1


def _level_scores(l, pair, kl, ql, kr):
    """Level ``l``'s part of ``A`` and ``Bs`` from ``_level``'s rows: one
    product of the packed ``(H, C, Dk)`` rows with kr gives ``kl kr^T``
    in a block's second-half rows and, ``s`` rows above their own, ``ql
    kr^T``; ``pair`` keeps each in its block.  Level 0's one pair a row
    is a float32 row product on the VPU, a lane sum."""
    z = _packed(kl, ql, l)
    if l < KDA_VPU_LEVELS:
        both = jnp.sum(z * (kr + _roll_rows(kr, 1)), axis=2, keepdims=True)
    else:
        both = _dot(z, kr, _NT, l < _FINE_LEVELS)
    return (jnp.where(pair, both, 0.0),
            jnp.where(pair, _roll_rows(both, 1 << l), 0.0))


def _level_transposes(l, pair, dA, dBs, kl, ql, kr):
    """``_level_scores`` transposed: the cotangents of kl, ql and kr from
    ``A``'s and ``Bs``'s, packed as the rows were (every level by the
    MXU: two passes ``C`` deep cost the backward kernel what level 0's
    row products would).  dkl and dql hold the other's rows where their
    own are not: every reader multiplies them by ``left``, kl or ql,
    which are zero there."""
    exact = l < _FINE_LEVELS
    dAB = _packed(jnp.where(pair, dA, 0.0), jnp.where(pair, dBs, 0.0), l)
    dkl = _dot(dAB, kr, _NN, exact)                     # (H, C, Dk)
    dkr = _dot(dAB, _packed(kl, ql, l), _TN, exact)
    return dkl, _roll_rows(dkl, 1 << l), dkr


def _pair_masks(c):
    """-> the row and column indices ``(C, 1)`` / ``(1, C)`` and, a
    level, the ``(C, C)`` mask of pairs whose row lies in the second and
    whose column in the first half of one block of that level."""
    row = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, c), 1)
    return row, col, [((row >> (l + 1)) == (col >> (l + 1)))
                      & (((row >> l) & 1) == 1) & (((col >> l) & 1) == 0)
                      for l in range(c.bit_length() - 1)]


# levels whose pairs are less than KDA_SUB apart: exact whatever the
# precision in force, as the plain chunks form them channel by channel
_FINE_LEVELS = KDA_SUB.bit_length() - 1


def _heads_decay(G) -> bool:
    """Whose a chunk's log-decay is, from its running sum's shape: ``(H,
    C, 1)`` is a head's one number a token, ``(H, C, Dk)`` a key
    lane's."""
    return G.shape[2] == 1


def _as_row(x, row, col):
    """A column a head ``(H, C, 1)`` as a row ``(H, 1, C)``, the same
    bits: the diagonal of its spread over the lanes, summed over the
    sublanes (every other term is a zero)."""
    return jnp.sum(jnp.where(row == col, x, 0.0), axis=1, keepdims=True)


def _as_col(x, row, col):
    """``_as_row`` the other way: ``(H, 1, C)`` -> ``(H, C, 1)``."""
    return jnp.sum(jnp.where(row == col, x, 0.0), axis=2, keepdims=True)


def _chunk_sums(g):
    """The running sum ``G`` of a chunk's log-decay and the chunk's
    ``_pair_masks``: what every other part of the chunk algebra starts
    from.  -> G, (row, col, pairs).  A lane's decay ``(H, C, Dk)`` is
    summed by the MXU into its own shape; a head's, a row a head ``(H,
    1, C)``, in float32 on the VPU (64 numbers a head) into a column
    ``(H, C, 1)``, which scales rows wherever a lane's ``G`` scales
    elements."""
    head = g.shape[1] == 1
    masks = _pair_masks(g.shape[2 if head else 1])
    row, col, _ = masks
    if head:
        return jnp.sum(jnp.where(row >= col, g, 0.0), axis=2,
                       keepdims=True), masks
    return _sum_rows(row >= col, g), masks


def _head_decays(G, row, col):
    """``D[i, j] = exp(G_i - G_j)`` on ``j <= i`` and zero above, ``(H,
    C, C)`` from a head's running sum ``(H, C, 1)``: every entry <= 1
    since ``g <= 0``, the diagonal exactly 1 (the row is the column's
    bits).  What a head's decay leaves of the levels: it is one number a
    pair, so it stands outside the pair's sum over the lanes."""
    return jnp.where(row >= col, jnp.exp(jnp.minimum(
        G - _as_row(G, row, col), 0.0)), 0.0)


def _head_scores(q, k, G, scale, masks):
    """``A`` and ``Bs`` of a chunk under a head's decay: k and q
    stacked, ``2 C`` rows against ``k^T`` in one exact product, times
    ``_head_decays``."""
    row, col, _ = masks
    c = q.shape[1]
    D = _head_decays(G, row, col)
    S = _dot(jnp.concatenate([k, q], 1), k, _NT, True)
    return jnp.where(row > col, S[:, :c] * D, 0.0), S[:, c:] * D * scale


def _lane_scores(q, k, g, G, scale, masks):
    """``A`` and ``Bs`` of a chunk under a lane's decay: the six halving
    levels, each from its own reference point, and ``Bs``'s diagonal."""
    c = q.shape[1]
    row, col, pairs = masks
    A = Bs = jnp.zeros((c, c), jnp.float32)
    for l, pair in enumerate(pairs):
        kl, ql, kr, _, _ = _level(l, q, k, g, G, scale, row)
        a, bs = _level_scores(l, pair, kl, ql, kr)
        A, Bs = A + a, Bs + bs
    Bs = Bs + jnp.where(row == col, jnp.sum(q * k, axis=2, keepdims=True)
                        * scale, 0.0)
    return A, Bs


def _chunk_scores_and_inverse(q, k, g, beta, G, scale, masks):
    """One chunk of a few heads, everything float32: q, k ``(H, C,
    Dk)``, g and its running sum (a lane's, both ``(H, C, Dk)``, or a
    head's, ``(H, 1, C)`` and ``(H, C, 1)``: ``_chunk_sums``), beta
    ``(H, C, 1)`` -> ``A`` and ``Bs``, the scores of ``_chunk_scores``
    (``Bs`` times ``scale``), and ``T``, the inverse of ``I + beta A``,
    each ``(H, C, C)``.  The scores are formed as the decay's shape
    admits (``_head_scores`` / ``_lane_scores``); the inverse is one
    statement for both.  The forward kernel's alone: the backward kernel
    reads the three back."""
    row, col, pairs = masks
    if _heads_decay(G):
        A, Bs = _head_scores(q, k, G, scale, masks)
    else:
        A, Bs = _lane_scores(q, k, g, G, scale, masks)

    M = beta * A
    T = jnp.where(row == col, 1.0, 0.0) - jnp.where(pairs[0], M, 0.0)
    for pair in pairs[1:]:          # blocks of 2s from blocks of s
        X = jnp.where(pair, M, 0.0)
        T = T - _dot(T, _dot(X, T, _NN, True), _NN, True)
    return A, Bs, T


def _chunk_entry(q, k, v, beta, St, scale, G, T):
    """What both kernels form of a chunk from its inputs, its running
    sum, its inverse ``T`` and the entry states transposed ``(H, Dv,
    Dk)``: the decays from the chunk's start and to its end, k and q
    under them, the residual ``r`` of v against the entry state and the
    corrections ``u``, by name.  The decays take the running sum's
    shape: under a head's ``(H, C, 1)`` they scale rows."""
    c = q.shape[1]
    decayed = jnp.exp(G)
    Gc = G[:, c - 1:c]
    to_end = jnp.exp(Gc - G)
    kd = k * decayed
    r = v - _dot(kd, St, _NT)
    return dict(decayed=decayed, to_end=to_end, kd=kd,
                qd=q * decayed * scale, kc=k * to_end,
                ec=jnp.exp(Gc),                    # (H, 1, Dk or 1)
                r=r, u=_dot(T, beta * r, _NN, True))


def _head_cotangents(q, k, G, A, Bs, dA, dBs, scale, row, col):
    """``_head_scores`` transposed: what the cotangents of ``A`` (zero
    on and above the diagonal) and of ``Bs`` (zero above it) give q, k
    and the running sum ``G``.  The two under the decay, stacked, meet k
    in one product (``A``'s rows for k, ``Bs``'s for q) and ``[k; q]``
    in one transposed product (for k); every product exact, as the
    scores.  ``G_i`` moves row ``i`` of ``P = dA A + dBs Bs`` (the KEPT
    scores) up and column ``i`` down, so its cotangent is ``rowsum(P) -
    colsum(P)`` off the diagonal, which adds up to nothing over the
    chunk whatever the products lost.  -> dq, dk ``(H, C, Dk)``, dG
    ``(H, C, 1)``."""
    c = q.shape[1]
    D = _head_decays(G, row, col)
    under = jnp.concatenate([dA * D, dBs * D * scale], 1)    # (H, 2C, C)
    rows = _dot(under, k, _NN, True)                         # (H, 2C, Dk)
    dk = rows[:, :c] + _dot(under, jnp.concatenate([k, q], 1), _TN, True)
    P = jnp.where(row > col, dA * A + dBs * Bs, 0.0)
    dG = jnp.sum(P, axis=2, keepdims=True) \
        - _as_col(jnp.sum(P, axis=1, keepdims=True), row, col)
    return rows[:, c:], dk, dG


def _heads(ref, d):
    """A ``(C, H * D)`` block as ``(H, C, D)`` float32."""
    return jnp.stack([ref[:, i * d:(i + 1) * d].astype(jnp.float32)
                      for i in range(ref.shape[1] // d)])


def _put_heads(ref, x):
    d = x.shape[2]
    for i in range(x.shape[0]):
        ref[:, i * d:(i + 1) * d] = x[i].astype(ref.dtype)


def _decay_block(ref, d):
    """A grid step's log-decay, float32: a head's block, a row a head
    ``(heads, 1, C)``, as it lies; a lane's ``(C, heads * D)`` by
    heads."""
    return ref[...] if len(ref.shape) == 3 else _heads(ref, d)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, kept_ref,
                state, *, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    d = state.shape[-1]
    St = state[...]
    s_ref[...] = St
    q, k, g, beta = _heads(q_ref, d), _heads(k_ref, d), \
        _decay_block(g_ref, d), b_ref[...]
    G, masks = _chunk_sums(g)
    A, Bs, T = _chunk_scores_and_inverse(q, k, g, beta, G, scale, masks)
    # a head's three side by side: (C, heads * 3 * C), whole rows of lanes
    _put_heads(kept_ref,
               jnp.stack([A, Bs, T], 1).reshape((-1,) + T.shape[1:]))
    c = _chunk_entry(q, k, _heads(v_ref, d), beta, St, scale, G, T)
    _put_heads(o_ref, _dot(c["qd"], St, _NT) + _dot(Bs, c["u"], _NN))
    state[...] = St * c["ec"] + _dot(c["u"], c["kc"], _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, kept_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, scale):
    """The chunks in reverse: the scores and the inverse as the forward
    kernel wrote them, the decays, ``r`` and ``u`` again from the inputs
    and the entry states (``_chunk_entry``), then the chunk's transpose;
    ``dstate`` carries the states' cotangent (transposed) to the chunk
    before."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    d = dstate.shape[-1]
    q, k, g = _heads(q_ref, d), _heads(k_ref, d), _decay_block(g_ref, d)
    beta, St, dS1t, do = b_ref[...], s_ref[...], dstate[...], _heads(do_ref, d)
    n = q.shape[1]
    kept = _heads(kept_ref, n).reshape(-1, KDA_KEPT, n, n)
    A, Bs, T = (kept[:, i] for i in range(KDA_KEPT))
    G, (row, col, pairs) = _chunk_sums(g)
    c = _chunk_entry(q, k, _heads(v_ref, d), beta, St, scale, G, T)
    u, kd, qd, kc = c["u"], c["kd"], c["qd"], c["kc"]

    dqd = _dot(do, St, _NN)
    dBs = jnp.where(row >= col, _dot(do, u, _NT), 0.0)
    du = _dot(Bs, do, _TN) + _dot(kc, dS1t, _NT)
    dkc = _dot(u, dS1t, _NN)
    dGc = jnp.sum(dS1t * St, axis=1, keepdims=True) * c["ec"] \
        + jnp.sum(dkc * kc, axis=1, keepdims=True)
    dy = _dot(T, du, _TN, True)
    dM = jnp.where(row > col, -_dot(dy, u, _NT), 0.0)
    dA = beta * dM
    dr = beta * dy
    db_ref[...] = jnp.sum(dM * A, axis=2, keepdims=True) \
        + jnp.sum(dy * c["r"], axis=2, keepdims=True)
    _put_heads(dv_ref, dr)
    dkd = -_dot(dr, St, _NN)
    dstate[...] = _dot(do, qd, _TN) + dS1t * c["ec"] - _dot(dr, kd, _TN)

    if _heads_decay(G):
        dq, dk, dG = _head_cotangents(q, k, G, A, Bs, dA, dBs, scale, row,
                                      col)
        dG = dG + jnp.sum(dkd * kd + dqd * qd - dkc * kc
                          + jnp.where(row == n - 1, dGc, 0.0), axis=2,
                          keepdims=True)
        _put_heads(dq_ref, dq + dqd * c["decayed"] * scale)
        _put_heads(dk_ref, dk + dkd * c["decayed"] + dkc * c["to_end"])
        # g's cotangent is the running sum of G's from the chunk's end:
        # a column's, summed over the sublanes into g's row
        dg_ref[...] = jnp.sum(jnp.where(row >= col, dG, 0.0), axis=1,
                              keepdims=True)
        return
    diag = jnp.sum(jnp.where(row == col, dBs, 0.0), axis=2,
                   keepdims=True) * scale
    dq = dqd * c["decayed"] * scale + diag * k
    dk = dkd * c["decayed"] + dkc * c["to_end"] + diag * q
    dG = dkd * kd + dqd * qd - dkc * kc + jnp.where(row == n - 1, dGc, 0.0)
    for l, pair in enumerate(pairs):
        exact = l < _FINE_LEVELS
        kl, ql, kr, left, right = _level(l, q, k, g, G, scale, row)
        dkl, dql, dkr = _level_transposes(l, pair, dA, dBs, kl, ql, kr)
        dq = dq + dql * left * scale
        dk = dk + dkl * left + dkr * right
        moved = dkl * kl + dql * ql - dkr * kr
        if not exact:
            # a block's rows move G by amounts that add up to nothing but
            # for what the products at the precision in force lost; the
            # reference row takes that, as autodiff of the plain chunks
            # gives it to theirs, or the running sum below would carry
            # it to every row before the block
            s = 1 << l
            moved = moved - _sum_rows(
                row == ((col >> (l + 1)) << (l + 1)) + s - 1, moved)
        dG = dG + moved
    _put_heads(dq_ref, dq)
    _put_heads(dk_ref, dk)
    # g's cotangent is the running sum of G's from the chunk's end
    _put_heads(dg_ref, _sum_rows(row <= col, dG))


def _kernel_specs(b, t, h, d, flip, heads_decay):
    """The grid over (batch, heads, chunk) and the blocks of one step:
    ``(C, heads * D)`` of a ``(B, T, H * D)`` array, ``(heads, C, 1)`` of
    beta's ``(B, H, T, 1)``, ``(heads, D, D)`` states of ``(B, H, N, D,
    D)``, ``(C, heads * 3 * C)`` of the kept matrices' ``(B, T, H * 3 *
    C)``; the decay's is a lane's, q's block, or (``heads_decay``)
    ``(heads, 1, C)`` of a head's ``(B, H, N, 1, C)``.  ``flip`` walks
    the chunks from the last."""
    from jax.experimental.pallas import tpu as pltpu
    chunk, n = KDA_CHUNK, t // KDA_CHUNK
    heads = next(x for x in range(min(KDA_KERNEL_HEADS, h), 0, -1)
                 if h % x == 0)
    at = (lambda i: n - 1 - i) if flip else (lambda i: i)
    seq = pl.BlockSpec((None, chunk, heads * d),
                       lambda i, j, m: (i, at(m), j))
    return dict(
        grid=(b, h // heads, n),
        seq=seq,
        col=pl.BlockSpec((None, heads, chunk, 1),
                         lambda i, j, m: (i, j, at(m), 0)),
        decay=pl.BlockSpec((None, heads, None, 1, chunk),
                           lambda i, j, m: (i, j, at(m), 0, 0))
        if heads_decay else seq,
        state=pl.BlockSpec((None, heads, None, d, d),
                           lambda i, j, m: (i, j, at(m), 0, 0)),
        kept=pl.BlockSpec((None, chunk, heads * KDA_KEPT * chunk),
                          lambda i, j, m: (i, at(m), j)),
        scratch=[pltpu.VMEM((heads, d, d), jnp.float32)],
        # the unrolled levels of a few heads spill more than the 16 MiB
        # a kernel is given unasked; a v5e core has 128 MiB
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
    )


def _kept_shapes(b, t, h, d):
    """What the kernel lowering keeps of the forward pass beside the op's
    inputs: the chunks' entry states and their ``A``, ``Bs``, ``T``
    (134 MB + 100.7 MB a layer at 4096 tokens of 32 heads)."""
    f32 = jnp.float32
    return [jax.ShapeDtypeStruct((b, h, t // KDA_CHUNK, d, d), f32),
            jax.ShapeDtypeStruct((b, t, h * KDA_KEPT * KDA_CHUNK), f32)]


def _trace_fields(head: bool, vpu_levels: int):
    """What ``kda:kernel_trace`` says of the form a kernel was traced
    in: whose the decay is, and the halving levels' rows and VPU levels,
    none under a head's decay."""
    if head:
        return dict(decay="head", level_rows=0, vpu_levels=0)
    return dict(decay="lane", level_rows=KDA_CHUNK, vpu_levels=vpu_levels)


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, there so that every call site shares one traced jaxpr and one
# lowered function; the step that holds it goes through the cache
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kda_fwd(q, k, v, g, beta, *, scale, interpret):
    """``kda_chunk_fwd`` over ``(B, T, H * D)`` q, k (float32) and v,
    ``(B, H, T, 1)`` beta and the log-decay g, a lane's ``(B, T, H *
    D)`` or a head's, a chunk a row ``(B, H, N, 1, C)`` (its rank says
    which, and with it how the kernel forms the scores) -> the output in
    v's layout and dtype and what ``_kda_bwd`` wants back: every chunk's
    entry state ``(B, H, N, D, D)``, transposed, and its scores and
    inverse ``A``, ``Bs``, ``T``, a head's three side by side ``(B, T, H
    * 3 * C)``, float32.  A forward-only caller runs the same kernel and
    drops them.  Module-level and free of per-call objects: traced once
    a process and decay's kind."""
    b, t, hd = q.shape
    h = beta.shape[1]
    d = hd // h
    head = g.ndim == 5
    trace.counter("kda:kernel_trace", cat="ops",
                  track="%s%s" % (v.dtype.name, [b, t, h, d]), fwd=1, bwd=0,
                  **_trace_fields(head, KDA_VPU_LEVELS))
    sp = _kernel_specs(b, t, h, d, False, head)
    # lint: allow(raw-pallas-call) — one lowering of this op, a pair with
    # its own vjp: ops/pallas_kernels holds forward kernels behind the
    # kernel search's bitwise gate, which a pair chosen by platform and
    # held to the plain chunks by tolerance (tests/test_kimi_linear.py,
    # tests/tpu) cannot ride
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=sp["grid"],
        in_specs=[sp["seq"]] * 3 + [sp["decay"], sp["col"]],
        out_specs=[sp["seq"], sp["state"], sp["kept"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)]
        + _kept_shapes(b, t, h, d),
        scratch_shapes=sp["scratch"], compiler_params=sp["params"],
        interpret=interpret, name="kda_chunk_fwd",
    )(q, k, v, g, beta)


# lint: allow(raw-jit) — as _kda_fwd
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kda_bwd(q, k, v, g, beta, states, kept, do, *, scale, interpret):
    """``kda_chunk_bwd``: the cotangents of q, k, v, g and beta from the
    forward kernel's inputs, its entry states, its kept ``A``, ``Bs``,
    ``T`` and the output's cotangent, all in ``_kda_fwd``'s layouts.
    Traced once a process."""
    b, t, hd = q.shape
    h = beta.shape[1]
    d = hd // h
    head = g.ndim == 5
    trace.counter("kda:kernel_trace", cat="ops",
                  track="%s%s" % (v.dtype.name, [b, t, h, d]), fwd=0, bwd=1,
                  kept_products=KDA_KEPT, **_trace_fields(head, 0))
    sp = _kernel_specs(b, t, h, d, True, head)
    f32 = jax.ShapeDtypeStruct(q.shape, jnp.float32)
    # lint: allow(raw-pallas-call) — as _kda_fwd
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=sp["grid"],
        in_specs=[sp["seq"]] * 3 + [sp["decay"], sp["col"], sp["state"],
                                    sp["kept"], sp["seq"]],
        out_specs=[sp["seq"]] * 3 + [sp["decay"], sp["col"]],
        out_shape=[f32, f32, jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, jnp.float32),
                   jax.ShapeDtypeStruct(beta.shape, jnp.float32)],
        scratch_shapes=sp["scratch"], compiler_params=sp["params"],
        interpret=interpret, name="kda_chunk_bwd",
    )(q, k, v, g, beta, states, kept, do)


def _kernel_layout(q, k, v, g, beta):
    """``(B, T, H, D)`` -> ``(B, T, H * D)`` (free), q, k and a lane's g
    in float32; beta ``(B, T, H)`` -> ``(B, H, T, 1)``; a head's g ``(B,
    T, H)`` -> ``(B, H, N, 1, C)``, a chunk a row of lanes: a float32
    array whose last dimension is 1 lies on the chip with that dimension
    padded to a row of 128 lanes, ``(B, H, T, 1)`` in the 67 MB a layer
    of the decay handed over the key lanes."""
    b, t = q.shape[:2]
    f32 = jnp.float32
    if g.ndim == 3:
        g = g.astype(f32).transpose(0, 2, 1).reshape(
            b, -1, t // KDA_CHUNK, 1, KDA_CHUNK)
    return (q.astype(f32).reshape(b, t, -1), k.astype(f32).reshape(b, t, -1),
            v.reshape(b, t, -1),
            g if g.ndim == 5 else g.astype(f32).reshape(b, t, -1),
            beta.astype(f32).transpose(0, 2, 1)[..., None])


def _kernel_rule(q, k, v, g, beta, scale: float, interpret: bool = False):
    """``gated_delta_rule`` by ``kda_chunk_fwd`` for inputs
    ``_kernel_takes`` accepts -> the output and what the forward kernel
    kept (``_kept_shapes``), which ``_kernel_rule_vjp`` wants back."""
    o, *kept = _kda_fwd(*_kernel_layout(q, k, v, g, beta), scale=scale,
                        interpret=interpret)
    return o.reshape(v.shape), kept


def _kernel_rule_vjp(q, k, v, g, beta, kept, do, scale: float,
                     interpret: bool = False):
    """The cotangents of ``_kernel_rule``'s five inputs for the output's
    cotangent ``do``, by ``kda_chunk_bwd``."""
    b, t = q.shape[:2]
    dq, dk, dv, dg, db = _kda_bwd(
        *_kernel_layout(q, k, v, g, beta), *kept, do.reshape(b, t, -1),
        scale=scale, interpret=interpret)
    if g.ndim == 3:
        dg = dg.reshape(b, -1, t).transpose(0, 2, 1)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), db[..., 0].transpose(0, 2, 1))


def _normalized_and_gated(q, k, decay, beta, a_log, dt_bias):
    """What the op does before the rule, all float32: q and k
    L2-normalized a head, at the VALUE's heads, the log-decay in the
    rank of its projection (``kda_gates``: a lane's ``(B, T, H, Dk)``, a
    head's ``(B, T, Hv)``) and the write gate.  Under a head's decay
    ``Hk`` key heads under ``Hv`` value heads are repeated here (value
    head ``j`` reads key head ``j // (Hv / Hk)``): inside the
    ``custom_vjp``'s body, so that they are not kept across the step."""
    q, k = _l2norm(q), _l2norm(k)
    g, beta = kda_gates(decay, beta, a_log, dt_bias)
    if g.ndim == 3:
        group = g.shape[2] // q.shape[2]
        q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)
    return q, k, g, beta


def _scale(q) -> float:
    return float(q.shape[-1]) ** -0.5


def _plain_attention(q, k, v, decay, beta, a_log, dt_bias):
    """The op's body on every platform: the plain chunks, which take the
    decay a key lane: a head's is handed over its lanes here."""
    q, k, g, beta = _normalized_and_gated(q, k, decay, beta, a_log, dt_bias)
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], q.shape)
    return gated_delta_rule(q, k, v, g, beta, _scale(q))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _two_lowerings(q, k, v, decay, beta, a_log, dt_bias, interpret: bool):
    """The op's body for inputs the kernels take: the kernels where the
    program is lowered for a TPU, the plain chunks elsewhere
    (``_kernel_on_tpu``), in the forward and in the backward pass.  The
    backward pass keeps the op's own inputs and, from the kernels, the
    chunks' entry states and their scores and inverse ``A``, ``Bs``,
    ``T`` (134 MB + 100.7 MB a layer at 4096 tokens of 32 heads) and
    computes everything else again: the normalized q and k and the gates
    (0.27 GB a layer in float32, one elementwise pass), the chunks'
    running sums, decays and corrections inside the backward kernel; the
    plain chunks are computed again whole and keep zeros in the kernels'
    shapes.  The choice lies inside the two rules, so that neither
    lowering is differentiated through the choice."""
    return _two_lowerings_fwd(q, k, v, decay, beta, a_log, dt_bias,
                              interpret)[0]


def _two_lowerings_fwd(q, k, v, decay, beta, a_log, dt_bias, interpret):
    args = (q, k, v, decay, beta, a_log, dt_bias)

    def kernels(*args):
        qn, kn, g, b = _normalized_and_gated(*args[:2], *args[3:])
        return _kernel_rule(qn, kn, args[2], g, b, _scale(qn), interpret)

    def plain(*args):
        return _plain_attention(*args), [
            jnp.zeros(x.shape, x.dtype) for x in _kept_shapes(*args[2].shape)]

    o, kept = _kernel_on_tpu(kernels, plain, interpret, *args)
    return o, (args, kept)


def _two_lowerings_bwd(interpret, res, do):
    # as jax.checkpoint ties what it computes again to the cotangent's
    # arrival: without it XLA is free to form every layer's float32 q, k
    # and g at the start of the backward pass and hold them
    (args, kept), do = lax.optimization_barrier((res, do.reshape(*do.shape[:2], -1)))

    def kernels(do, kept, *args):
        v = args[2]
        (qn, kn, g, b), before = jax.vjp(_normalized_and_gated, *args[:2],
                                         *args[3:])
        dqn, dkn, dv, dg, db = _kernel_rule_vjp(qn, kn, v, g, b, kept, do,
                                                _scale(qn), interpret)
        dq, dk, ddecay, dbeta, da_log, ddt_bias = before((dqn, dkn, dg, db))
        return dq, dk, dv, ddecay, dbeta, da_log, ddt_bias

    def plain(do, kept, *args):
        return jax.vjp(_plain_attention, *args)[1](do.reshape(args[2].shape))

    return _kernel_on_tpu(kernels, plain, interpret, do, kept, *args)


_two_lowerings.defvjp(_two_lowerings_fwd, _two_lowerings_bwd)


def _delta_attention(counter, track, args, interpret, **fields):
    """The op's body behind either front end: one algorithm, two
    lowerings.  Inputs the kernels take (``_kernel_takes``) run them
    where the program is LOWERED for a TPU (anywhere under ``interpret``,
    the Pallas interpreter: the tests) and the plain chunks on any other
    platform; every other input runs the plain chunks everywhere.  Each
    trace records which, as ``counter``: ``kernel`` 1 means this op's TPU
    lowering is the kernels (the lowered text of a CPU program holds the
    plain chunks all the same), ``plain`` 1 the plain chunks on every
    platform."""
    q, v = args[0], args[2]
    kernel = _kernel_takes(q, v)
    trace.counter(counter, cat="ops", track=track,
                  chunked=1, chunk=min(KDA_CHUNK, q.shape[1]),
                  kernel=int(kernel), plain=int(not kernel), **fields)
    if not kernel:
        return _plain_attention(*args)
    return _two_lowerings(*args, interpret)


def kimi_delta_attention(q, k, v, decay, beta, a_log, dt_bias,
                         interpret: bool = False):
    """Kimi Delta Attention of ``KimiDeltaAttentionOp``'s seven inputs:
    normalize, gate, ``gated_delta_rule`` with a decay a key lane
    (``_delta_attention``).  The counter is ``kda:lowering``; the track
    names dtype and shape."""
    return _delta_attention(
        "kda:lowering", "%s%s" % (v.dtype.name, list(q.shape)),
        (q, k, v, decay, beta, a_log, dt_bias), interpret)


def gated_delta_net(q, k, v, decay, beta, a_log, dt_bias,
                    interpret: bool = False):
    """Gated DeltaNet of ``GatedDeltaNetOp``'s seven inputs: the same
    body (``_delta_attention``) with a head's one decay, ``decay`` ``(B,
    T, Hv)``, and ``Hk`` key heads under ``Hv`` value heads; what tells
    the two front ends apart is the decay's rank, read in one place
    (``kda_gates``, ``_normalized_and_gated``), and the same rank says
    which factorisation of the chunk's scores the kernels run
    (``_kernel_layout``).  The counter is
    ``gdn:lowering`` with ``key_heads`` and ``value_heads``; the track is
    ``<dtype>[B, T, Hv, D]/k<Hk>``."""
    hk, hv = q.shape[2], v.shape[2]
    return _delta_attention(
        "gdn:lowering", "%s%s/k%d" % (v.dtype.name, list(v.shape), hk),
        (q, k, v, decay, beta, a_log, dt_bias), interpret,
        key_heads=hk, value_heads=hv)


def _kernel_takes(q, v) -> bool:
    """What the kernels' tiling accepts: sequences of whole chunks and
    key and value heads of one 128-lane row (the state is one 128 x 128
    tile); v in bfloat16 or float32."""
    return (q.shape[1] % KDA_CHUNK == 0
            and q.shape[3] == v.shape[3] == KDA_KERNEL_DIM
            and v.dtype in (jnp.bfloat16, jnp.float32))


@register_op("CausalConv1D", hint="causalconv1d")
class CausalConv1DOp(OpDef):
    """Depthwise causal convolution over time of ``(B, T, C)``: a ``kernel``-tap filter a channel
    (``weight`` ``(C, kernel)``; output ``t`` reads inputs ``t - kernel + 1 .. t``), plus ``bias``
    ``(C,)`` where ``no_bias`` is False (unset: no such input), then ``act_type``.  ``lanes``: of
    ``(B, T, G, Dw)`` a group's leading lanes; ``gated``: ``C * conv(B * u)`` (``causal_conv``)."""
    params = [Param("kernel", int, default=4), Param("lanes", "shape"),
              Param("act_type", str, enum=list(ACTIVATIONS)), Param("gated", bool),
              Param("no_bias", bool)]

    def list_arguments(self, p):
        return ["data", "weight"] + ["bias"] * (p.no_bias is False)

    def list_outputs(self, p):
        return ["output", "rest"] if p.lanes else ["output"]

    def infer_shape(self, p, in_shapes):
        d, taken, wide = in_shapes[0], sum(p.lanes or ()), 3 if p.gated else 1
        if d is None:
            return in_shapes, [None] * (2 if p.lanes else 1), []
        if len(d) != (4 if p.lanes else 3) or taken > d[-1] or d[2] % wide \
                or p.gated and (p.lanes or p.act_type or p.no_bias is False):
            raise MXNetError("CausalConv1D: data (batch, seq, channels) or, "
                             "with lanes, (.., groups, width); got %r" % (d,))
        outs = [tuple(d[:2]) + (d[2] * n,) for n in (taken, d[3] - taken)] \
            if p.lanes else [tuple(d[:2]) + (d[2] // wide,)]
        return [d, (outs[0][2], p.kernel)] + [outs[0][2:]] * (p.no_bias is False), outs, []

    def forward(self, p, inputs, aux, ctx):
        if len(inputs) > 2:
            return biased_conv(*inputs, p.act_type, p.lanes)
        return causal_conv(*inputs, act_type=p.act_type, lanes=p.lanes) \
            if not p.gated else [gated_conv(*inputs)]

@register_op("KimiDeltaAttention", hint="kda")
class KimiDeltaAttentionOp(OpDef):
    """Kimi Delta Attention over ``(B, T, H, Dk)`` query and key, ``(B,
    T, H, Dv)`` value: q and k are L2-normalized per head, the decay gate
    is ``g = -exp(a_log[head]) * softplus(decay + dt_bias)`` per channel
    from its projection ``decay`` ``(B, T, H, Dk)``, the write gate is
    ``sigmoid(beta)`` from ``(B, T, H)``, and the output is the gated
    delta rule's ``S_t^T q_t * Dk**-0.5`` (see ``gated_delta_rule``),
    ``(B, T, H, Dv)``.  The state starts at zero and runs through the
    whole sequence.  ``layer`` names the trace scope."""
    params = [Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["query", "key", "value", "decay", "beta", "a_log_bias",
                "dt_bias"]

    def infer_shape(self, p, in_shapes):
        q, v = in_shapes[0], in_shapes[2]
        if q is None or v is None:
            return in_shapes, [None], []
        if len(q) != 4 or len(v) != 4 or tuple(q[:3]) != tuple(v[:3]):
            raise MXNetError("KimiDeltaAttention: query (batch, seq, heads, "
                             "key_dim) and value (batch, seq, heads, "
                             "value_dim), got %r and %r" % (q, v))
        b, t, h, dk = q
        return [q, q, v, q, (b, t, h), (h,), (h * dk,)], [v], []

    def forward(self, p, inputs, aux, ctx):
        with layer_scope("kda", p.layer):
            return [kimi_delta_attention(*inputs)]


@register_op("GatedDeltaNet", hint="gdn")
class GatedDeltaNetOp(OpDef):
    """Gated DeltaNet (arXiv:2412.06464) over ``(B, T, Hk, Dk)`` query
    and key and ``(B, T, Hv, Dv)`` value, ``Hk`` a whole divisor of
    ``Hv`` (value head ``j`` reads key head ``j // (Hv / Hk)``): q and k
    are L2-normalized per head, the decay is ONE number a value head and
    token, ``g = -exp(a_log[head]) * softplus(decay + dt_bias[head])``
    from its projection ``decay`` ``(B, T, Hv)``, the write gate is
    ``sigmoid(beta)`` from ``(B, T, Hv)``, and the output is the gated
    delta rule's ``S_t^T q_t * Dk**-0.5`` (``gated_delta_rule`` with
    ``g`` equal over a head's key lanes), ``(B, T, Hv, Dv)``.  The same
    body and the same two lowerings as ``KimiDeltaAttention``: the plain
    chunks are fed the decay on every key lane, and where the kernels
    run they are ``kda_chunk_fwd`` / ``kda_chunk_bwd`` in the form a
    head's decay admits (it enters a chunk a row, one number a head and
    token, and the chunk's scores are one product under a ``(C, C)``
    decay matrix, where a lane's decay takes six halving levels; the
    inverse, the corrections, the state and what a step keeps are the
    same statements), so the op's body runs under ``kda.l<layer>``, the
    scope of the rule."""
    params = [Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["query", "key", "value", "decay", "beta", "a_log_bias",
                "dt_bias"]

    def infer_shape(self, p, in_shapes):
        q, v = in_shapes[0], in_shapes[2]
        if q is None or v is None:
            return in_shapes, [None], []
        if len(q) != 4 or len(v) != 4 or tuple(q[:2]) != tuple(v[:2]) \
                or q[2] < 1 or v[2] % q[2]:
            raise MXNetError("GatedDeltaNet: query (batch, seq, key_heads, "
                             "key_dim) and value (batch, seq, value_heads, "
                             "value_dim) with key_heads a whole divisor of "
                             "value_heads, got %r and %r" % (q, v))
        gate = tuple(v[:3])
        return [q, q, v, gate, gate, (v[2],), (v[2],)], [v], []

    def forward(self, p, inputs, aux, ctx):
        with layer_scope("kda", p.layer):
            return [gated_delta_net(*inputs)]
