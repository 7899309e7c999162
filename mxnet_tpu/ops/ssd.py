"""The state-space mixer's scan, ``SSDScan``: the linear recurrence of
Mamba-2 (state-space duality, arXiv:2405.21060) as one op.

Per head the layer keeps an ``(N, P)`` state and, token by token,

    S_t = a_t S_{t-1} + dt_t B_t x_t^T        a_t = exp(dt_t A)
    y_t = C_t^T S_t + D x_t

with ``dt_t = softplus(dt + dt_bias) > 0`` and ``A = -exp(A_log) < 0``
one number a head (and token), ``x_t`` the head's ``P`` lanes and ``B_t``,
``C_t`` ``N`` lanes shared by the ``H / G`` heads of a group.  It is the
gated delta rule of ``ops/linear_attention.py`` WITHOUT the correction:
nothing is read back from the state before it is written, so a chunk has
no triangular system; what is left is that file's head form, a chunk's
pairs under one ``(C, C)`` decay matrix ``exp(G_i - G_j)`` whose every
entry is <= 1 (``G`` the running sum of ``g = dt A`` inside the chunk),
and its entry states carried by a scan.  The decay's chain (the
softplus, the exponentials, the running sums, the state) is float32
whatever the compute dtype.

``ssd_chunked`` computes exactly that in chunks of ``SSD_CHUNK`` tokens
in plain ``jnp`` (every platform, float32, autodiff: the parity twin):
``y = (C B^T * D * dt) x`` inside a chunk, the group's ``C B^T`` formed
once a group and not once a head, ``exp(G) C S`` from the entry state,
``S' = exp(G_c) S + B^T (exp(G_c - G) dt x)``.  Where the program is
LOWERED for a TPU and the inputs are ones the kernels take
(``_kernel_takes``: bfloat16, whole chunks, an even number of 64-lane
heads a group, a 128-lane state), two Pallas kernels run the rule,
``ssd_chunk_fwd`` and ``ssd_chunk_bwd``, over a grid of (batch, a few
heads of ONE group, chunk).  They read x as ``(T, H * P)`` rows, where the
projection's matmul left it: TWO 64-lane heads fill a 128-lane tile and
are handled as one, each under its own decay matrix, the other's lanes
zeroed in the operand (the MXU passes are the ones a 128-lane head would
take; nothing is cut or shifted along the lanes), and a tile's two
states lie side by side in one ``(N, 128)`` float32 tile, carried along
the sequential chunk axis in scratch.  A step's heads share the chunk's
``C B^T``: they are heads of one group (``_step_heads`` cuts a step to a
group's heads), and the step's ``B`` and ``C`` blocks are that group's
``N`` lanes of the ``(B, T, G * N)`` rows, found by the block index
alone, so ``G`` groups (Nemotron-H's eight under 64 heads, PR 71) run
the kernel bodies one group runs (Granite's), which hold no group.  The
decay and the step enter as ``ops/linear_attention.py``'s head form
takes a head's decay, a chunk a row ``(B, H, T / C, 1, C)``,
and that file's statements turn rows into running sums and decay
matrices (``_chunk_sums``, ``_head_decays``, ``_as_row``, ``_as_col``).
The forward kernel writes every chunk's entry states (``f32[B, H / 2, T
/ C, N, 2 P]``); the backward kernel walks the chunks from the last,
forms the chunk again from them and carries the states' cotangent.

The lowering differentiates itself (``jax.custom_vjp`` around the op's
body, which keeps the op's inputs and the entry states) through two
module-level ``jax.jit`` functions, traced once a process:
``ssd:kernel_trace`` (``fwd`` / ``bwd``, the chunk, the heads a lane
tile and a grid step) counts traces, ``ssd:lowering`` (``kernel`` /
``plain``) the choice a traced op, as ``kda:lowering`` does; the op's
body runs under ``ssm_scan.l<layer>``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..base import MXNetError
from .linear_attention import _as_col, _as_row, _chunk_sums, _head_decays
from .pallas_kernels import _kernel_on_tpu, pl
from .registry import OpDef, Param, register_op
from .transformer import layer_scope

__all__ = ["ssd_chunked", "ssd_gates", "ssd_scan"]

# tokens a chunk: one (C, C) decay matrix a head, one step of the state's
# scan (the published mamba_chunk_size 256 is a tile size too: no chunk
# size enters the mathematics).  The pair alone at (1, 4096, 64, 64) on a
# v5e, forward / forward + backward ms a layer (PR 67): chunks of 64
# 0.59 / 1.89, of 128 0.41 / 1.43, of 256 0.44 / 1.53
SSD_CHUNK = 128
# heads a grid step of the kernels, whole lane tiles of two: 4 read
# 0.51 / 1.84, 8 0.41 / 1.43, 16 0.37 / 1.38 (the chunk's C B^T and its
# B and C blocks are a step's, so more heads a step share them further)
SSD_KERNEL_HEADS = 8
# the sizes the kernels take: a head's lanes, two a tile; the state's
SSD_HEAD_DIM, SSD_STATE = 64, 128


def ssd_gates(dt, a_log, dt_bias):
    """The step and the log-decay from the step's projection ``(B, T,
    H)``, float32: ``dt = softplus(dt + dt_bias)`` and ``g = -exp(a_log)
    dt``, one number a head and token."""
    f32 = jnp.float32
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    return dt, -jnp.exp(a_log.astype(f32)) * dt


def ssd_chunked(x, bm, cm, dt, g, chunk: int = SSD_CHUNK):
    """The recurrence of the module docstring without the skip: ``(B, T,
    H, P)`` x, ``(B, T, G, N)`` bm and cm, float32 ``(B, T, H)`` step dt
    and log-decay g, from a zero state -> float32 ``(B, T, H, P)``.
    Chunked; T need not be a multiple of the chunk (the tail is padded
    with tokens that write nothing).  The plain chunks: the lowering of
    every platform, and the kernels' parity twin."""
    b, t, h, p = x.shape
    grp, n = bm.shape[2:]
    k = h // grp
    chunk = min(chunk, t)
    z = -(-t // chunk)
    f32 = jnp.float32

    def blocks(a, tail):                 # (B, T, ..) -> (B, Z, C, *tail)
        a = jnp.pad(a.astype(f32), ((0, 0), (0, z * chunk - t))
                    + ((0, 0),) * (a.ndim - 2))
        return a.reshape((b, z, chunk) + tail)

    x, bm, cm = blocks(x, (grp, k, p)), blocks(bm, (grp, n)), \
        blocks(cm, (grp, n))
    dt, g = blocks(dt, (grp, k)), blocks(g, (grp, k))
    G = jnp.cumsum(g, axis=2)                              # (B, Z, C, G, K)
    low = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    D = jnp.where(low, jnp.exp(jnp.minimum(
        G[:, :, :, None] - G[:, :, None], 0.0)), 0.0)      # (B, Z, i, j, G, K)
    cb = jnp.einsum("bzign,bzjgn->bzijg", cm, bm)
    M = cb[..., None] * D * dt[:, :, None]
    y = jnp.einsum("bzijgk,bzjgkp->bzigkp", M, x)
    Gc = G[:, :, -1:]
    wrote = jnp.einsum("bzjgn,bzjgk,bzjgkp->bzgknp", bm,
                       jnp.exp(Gc - G) * dt, x)
    kept = jnp.exp(Gc[:, :, 0])                            # (B, Z, G, K)

    def one_chunk(S, c):
        w, e = c
        return e[..., None, None] * S + w, S

    _, entry = lax.scan(one_chunk, jnp.zeros((b, grp, k, n, p), f32),
                        (jnp.moveaxis(wrote, 1, 0), jnp.moveaxis(kept, 1, 0)))
    y = y + jnp.einsum("bzign,zbgknp,bzigk->bzigkp", cm, entry, jnp.exp(G))
    return y.reshape(b, z * chunk, h, p)[:, :t]


def _plain_scan(x, bm, cm, dt, a_log, dt_bias, d):
    """The op's body on every platform: the gates, the plain chunks, the
    skip; rounded once, to x's dtype."""
    dt, g = ssd_gates(dt, a_log, dt_bias)
    y = ssd_chunked(x, bm, cm, dt, g) \
        + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)


# -- the TPU lowering ---------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_TN = (((0,), (0,)), ((), ()))        # a.T @ b


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product of the kernels' operands, float32 out, ONE MXU pass
    whatever precision is in force: under a "highest" default Mosaic
    refuses bfloat16 operands ("Bad lhs type"), as it does
    ``moe/gmm.py``'s."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _chunk_decays(g_ref, dt_ref):
    """What both kernels form of a grid step's heads from their rows of
    log-decay and step ``(heads, 1, C)``: the running sum and its masks
    (``_chunk_sums``), the ``(heads, C, C)`` decay matrix, and as ``(heads,
    C, 1)`` columns the decay from the chunk's start ``e``, to its end
    ``to_end`` and the step; ``ec`` ``(heads, 1, 1)`` is a whole chunk's
    decay."""
    g, dt = g_ref[...], dt_ref[...]
    G, (row, col, _) = _chunk_sums(g)
    Gc = G[:, G.shape[1] - 1:]
    return dict(G=G, row=row, col=col, dt=dt, D=_head_decays(G, row, col),
                e=jnp.exp(G), to_end=jnp.exp(Gc - G), ec=jnp.exp(Gc),
                dtc=_as_col(dt, row, col))


def _tiles(ref, p):
    """(tile index, its lanes, the two heads in it) of a ``(C, heads *
    P)`` block: two heads a 128-lane tile."""
    return [(i, slice(2 * i * p, 2 * (i + 1) * p), (2 * i, 2 * i + 1))
            for i in range(ref.shape[1] // (2 * p))]


def _fwd_kernel(x_ref, b_ref, c_ref, g_ref, dt_ref, d_ref, y_ref, s_ref,
                state):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f32, mm = jnp.float32, x_ref.dtype
    p = state.shape[2] // 2
    s_ref[...] = state[...]
    k = _chunk_decays(g_ref, dt_ref)
    bm, cm = b_ref[...], c_ref[...]
    M = (_mm(cm, bm, _NT)[None] * k["D"] * k["dt"]).astype(mm)
    w = k["to_end"] * k["dtc"]
    first = lax.broadcasted_iota(jnp.int32, (1, 2 * p), 1) < p
    for i, lanes, (a, b) in _tiles(x_ref, p):
        x2, S2 = x_ref[:, lanes], state[i]
        none = jnp.zeros_like(x2)
        y2 = _mm(M[a], jnp.where(first, x2, none)) \
            + _mm(M[b], jnp.where(first, none, x2)) \
            + jnp.where(first, k["e"][a], k["e"][b]) * _mm(cm, S2.astype(mm)) \
            + d_ref[:, lanes] * x2.astype(f32)
        y_ref[:, lanes] = y2.astype(y_ref.dtype)
        xw = (x2.astype(f32) * jnp.where(first, w[a], w[b])).astype(mm)
        state[i] = S2 * jnp.where(first, k["ec"][a], k["ec"][b]) \
            + _mm(bm, xw, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, g_ref, dt_ref, d_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dg_ref, ddt_ref, dstate):
    """The chunks in reverse: the decays and ``C B^T`` again from the
    inputs, the chunk's transpose against the entry states the forward
    kernel wrote; ``dstate`` carries the states' cotangent to the chunk
    before.  ``G_i`` moves row ``i`` of ``P = dM * M`` up and column
    ``i`` down, ``e_i`` and ``to_end_i`` with it; g's cotangent is the
    running sum of G's from the chunk's end."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    f32, mm = jnp.float32, x_ref.dtype
    p = dstate.shape[2] // 2
    k = _chunk_decays(g_ref, dt_ref)
    row, col, n = k["row"], k["col"], k["G"].shape[1]
    bm, cm = b_ref[...], c_ref[...]
    CBD = _mm(cm, bm, _NT)[None] * k["D"]
    M = (CBD * k["dt"]).astype(mm)
    w = k["to_end"] * k["dtc"]
    first = lax.broadcasted_iota(jnp.int32, (1, 2 * p), 1) < p
    dCB = jnp.zeros(CBD.shape[1:], f32)
    dB = jnp.zeros(bm.shape, f32)
    dC = jnp.zeros(cm.shape, f32)
    moved, stepped = {}, {}      # a head's dG (C, 1) and ddt (1, C)
    for i, lanes, heads in _tiles(x_ref, p):
        x2, dy2 = x_ref[:, lanes], dy_ref[:, lanes]
        S2, dS2 = s_ref[i], dstate[i]
        none = jnp.zeros_like(dy2)
        dys = (jnp.where(first, dy2, none), jnp.where(first, none, dy2))
        # a tile's two heads' columns side by side over their lanes
        w2, e2, ec2 = (jnp.where(first, v[heads[0]], v[heads[1]])
                       for v in (w, k["e"], k["ec"]))
        BdS, CS = _mm(bm, dS2.astype(mm)), _mm(cm, S2.astype(mm))
        read, wrote = dy2.astype(f32) * CS, x2.astype(f32) * BdS
        held = dS2 * S2
        dx2 = w2 * BdS + d_ref[:, lanes] * dy2.astype(f32)
        for h, dyh, mine in zip(heads, dys, (first, ~first)):
            dM = _mm(dyh, x2, _NT)                            # (C, C)
            Q = dM * CBD[h]
            dCB = dCB + dM * k["D"][h] * k["dt"][h]
            dx2 = dx2 + _mm(M[h], dyh, _TN)
            ddt = jnp.sum(Q, axis=0, keepdims=True)           # (1, C)
            de = jnp.sum(jnp.where(mine, read, 0.0), axis=1, keepdims=True)
            dw = jnp.sum(jnp.where(mine, wrote, 0.0), axis=1, keepdims=True)
            dec = jnp.sum(jnp.sum(jnp.where(mine, held, 0.0), axis=1,
                                  keepdims=True), axis=0, keepdims=True)
            dww = dw * w[h]
            dG = jnp.sum(Q * k["dt"][h], axis=1, keepdims=True) \
                - _as_col((ddt * k["dt"][h])[None], row, col)[0] \
                + de * k["e"][h] - dww
            moved[h] = dG + jnp.where(
                row == n - 1, jnp.sum(dww, axis=0, keepdims=True)
                + dec * k["ec"][h], 0.0)
            stepped[h] = ddt + _as_row((dw * k["to_end"][h])[None], row,
                                       col)[0]
        dx_ref[:, lanes] = dx2.astype(dx_ref.dtype)
        dye = (dy2.astype(f32) * e2).astype(mm)
        xw = (x2.astype(f32) * w2).astype(mm)
        dC = dC + _mm(dye, S2.astype(mm), _NT)
        dB = dB + _mm(xw, dS2.astype(mm), _NT)
        dstate[i] = dS2 * ec2 + _mm(cm, dye, _TN)
    dCB = dCB.astype(mm)
    dc_ref[...] = dC + _mm(dCB, bm)
    db_ref[...] = dB + _mm(dCB, cm, _TN)
    heads = range(len(moved))
    dG = jnp.stack([moved[h] for h in heads])                 # (heads, C, 1)
    dg_ref[...] = jnp.sum(jnp.where(row >= col, dG, 0.0), axis=1,
                          keepdims=True)
    ddt_ref[...] = jnp.stack([stepped[h] for h in heads])


def _step_heads(h: int, groups: int = 1) -> int:
    """Heads a grid step takes of ``h`` in ``groups`` groups: the most
    up to ``SSD_KERNEL_HEADS`` that divide a group's, in whole tiles of
    two, so that a step's heads read one ``B`` and one ``C``."""
    k = h // groups
    return next(x for x in range(min(SSD_KERNEL_HEADS, k), 0, -2)
                if k % x == 0)


def _kernel_specs(b, t, h, p, n, flip, groups=1):
    """The grid over (batch, heads, chunk) and the blocks of one step:
    ``(C, heads * P)`` of a ``(B, T, H * P)`` array, ``(C, N)`` of the
    ``(B, T, G * N)`` rows of ``B`` or ``C``, the lanes of the step's
    group (``part``: of a step's share ``(B, H / heads, T, N)``),
    ``(heads, 1, C)`` of a head's rows ``(B, H, T / C, 1, C)``, ``(1,
    heads * P)`` of the skip's lanes, ``(heads / 2, N, 2 P)`` states of
    ``(B, H / 2, T / C, N, 2 P)``.  ``flip`` walks the chunks from the
    last."""
    from jax.experimental.pallas import tpu as pltpu
    chunk, z = SSD_CHUNK, t // SSD_CHUNK
    heads = _step_heads(h, groups)
    at = (lambda i: z - 1 - i) if flip else (lambda i: i)
    steps_a_group = h // groups // heads
    return dict(
        grid=(b, h // heads, z), heads=heads,
        seq=pl.BlockSpec((None, chunk, heads * p),
                         lambda i, j, m: (i, at(m), j)),
        group=pl.BlockSpec((None, chunk, n),
                           lambda i, j, m: (i, at(m), j // steps_a_group)),
        part=pl.BlockSpec((None, None, chunk, n),
                          lambda i, j, m: (i, j, at(m), 0)),
        rows=pl.BlockSpec((None, heads, None, 1, chunk),
                          lambda i, j, m: (i, j, at(m), 0, 0)),
        skip=pl.BlockSpec((1, heads * p), lambda i, j, m: (0, j)),
        state=pl.BlockSpec((None, heads // 2, None, n, 2 * p),
                           lambda i, j, m: (i, j, at(m), 0, 0)),
        scratch=[pltpu.VMEM((heads // 2, n, 2 * p), jnp.float32)],
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
    )


def _states_shape(b, t, h, p, n):
    return jax.ShapeDtypeStruct((b, h // 2, t // SSD_CHUNK, n, 2 * p),
                                jnp.float32)


def _note_trace(x, rows, groups, n, **which):
    b, t, hp = x.shape
    h = rows.shape[1]
    trace.counter("ssd:kernel_trace", cat="ops",
                  track="%s%s/g%dn%d" % (x.dtype.name, [b, t, h, hp // h],
                                         groups, n),
                  chunk=SSD_CHUNK, heads_a_tile=2,
                  heads_a_step=_step_heads(h, groups), lowering="kernel",
                  **which)


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, there so that every layer's call shares one traced jaxpr and one
# lowered function; the step that holds it goes through the cache
@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _ssd_fwd(x, bm, cm, g, dt, d, *, groups, interpret):
    """``ssd_chunk_fwd`` over ``(B, T, H * P)`` x, ``(B, T, G * N)`` bm
    and cm, the log-decay and the step a chunk a row ``(B, H, T / C, 1,
    C)`` and the skip over x's lanes ``(1, H * P)`` -> y in x's layout
    and dtype and every chunk's entry states (``_states_shape``), which
    ``_ssd_bwd`` wants back."""
    b, t, hp = x.shape
    h, n = g.shape[1], bm.shape[2] // groups
    p = hp // h
    _note_trace(x, g, groups, n, fwd=1, bwd=0)
    sp = _kernel_specs(b, t, h, p, n, False, groups)
    # lint: allow(raw-pallas-call) — one lowering of this op, a pair with
    # its own vjp, chosen by platform and held to the plain chunks by
    # tolerance (tests/test_granite_hybrid.py, tests/tpu): not a forward
    # kernel behind the kernel search's bitwise gate
    return pl.pallas_call(
        _fwd_kernel, grid=sp["grid"],
        in_specs=[sp["seq"], sp["group"], sp["group"], sp["rows"],
                  sp["rows"], sp["skip"]],
        out_specs=[sp["seq"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   _states_shape(b, t, h, p, n)],
        scratch_shapes=sp["scratch"], compiler_params=sp["params"],
        interpret=interpret, name="ssd_chunk_fwd",
    )(x, bm, cm, g, dt, d)


# lint: allow(raw-jit) — as _ssd_fwd
@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _ssd_bwd(x, bm, cm, g, dt, d, states, dy, *, groups, interpret):
    """``ssd_chunk_bwd``: the cotangents of x (its dtype), of bm and cm
    (float32, a grid step's heads' share each: ``(B, H / heads, T, N)``,
    summed a group by the caller, ``_group_sums``), of the log-decay and
    of the step (float32, a chunk a row) from ``_ssd_fwd``'s inputs, its
    entry states and the output's cotangent."""
    b, t, hp = x.shape
    h, n = g.shape[1], bm.shape[2] // groups
    p = hp // h
    _note_trace(x, g, groups, n, fwd=0, bwd=1)
    sp = _kernel_specs(b, t, h, p, n, True, groups)
    share = jax.ShapeDtypeStruct((b, h // sp["heads"], t, n), jnp.float32)
    rows = jax.ShapeDtypeStruct(g.shape, jnp.float32)
    # lint: allow(raw-pallas-call) — as _ssd_fwd
    return pl.pallas_call(
        _bwd_kernel, grid=sp["grid"],
        in_specs=[sp["seq"], sp["group"], sp["group"], sp["rows"],
                  sp["rows"], sp["skip"], sp["state"], sp["seq"]],
        out_specs=[sp["seq"], sp["part"], sp["part"], sp["rows"],
                   sp["rows"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), share, share,
                   rows, rows],
        scratch_shapes=sp["scratch"], compiler_params=sp["params"],
        interpret=interpret, name="ssd_chunk_bwd",
    )(x, bm, cm, g, dt, d, states, dy)


def _kernel_layout(x, bm, cm, dt, g, d):
    """``(B, T, H, P)`` -> ``(B, T, H * P)`` and the groups' ``(B, T,
    G, N)`` -> ``(B, T, G * N)`` (free); a head's rows ``(B, T, H)`` ->
    ``(B, H, T / C, 1, C)``, a chunk a row of lanes, as
    ``ops/linear_attention.py`` hands a head's decay over; the skip
    ``(H,)`` over its head's lanes ``(1, H * P)``."""
    b, t, h, p = x.shape

    def rows(a):
        return a.transpose(0, 2, 1).reshape(b, h, t // SSD_CHUNK, 1,
                                            SSD_CHUNK)

    return (x.reshape(b, t, h * p), bm.reshape(b, t, -1),
            cm.reshape(b, t, -1), rows(g), rows(dt),
            jnp.repeat(d.astype(jnp.float32), p)[None])


def _from_rows(a):
    """``_kernel_layout``'s rows back: ``(B, H, Z, 1, C)`` -> ``(B, T,
    H)``."""
    b, h = a.shape[:2]
    return a.reshape(b, h, -1).transpose(0, 2, 1)


def _group_sums(share, like):
    """The backward kernel's shares of ``B``'s or ``C``'s cotangent ``(B,
    H / heads, T, N)``, a grid step's heads each, summed over the steps
    of a group -> ``like``'s ``(B, T, G, N)`` and dtype."""
    b, t, groups, n = like.shape
    return share.reshape(b, groups, -1, t, n).sum(2).transpose(0, 2, 1, 3) \
        .astype(like.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _two_lowerings(x, bm, cm, dt, a_log, dt_bias, d, interpret: bool):
    """The op's body for inputs the kernels take: the kernels where the
    program is lowered for a TPU, the plain chunks elsewhere
    (``_kernel_on_tpu``), in both passes.  The backward pass keeps the
    op's own inputs and, from the forward kernel, the chunks' entry
    states (67 MB a layer at 4096 tokens of 64 heads) and computes the
    gates again; the plain chunks are computed again whole and keep
    zeros in the states' shape."""
    return _two_lowerings_fwd(x, bm, cm, dt, a_log, dt_bias, d,
                              interpret)[0]


def _two_lowerings_fwd(x, bm, cm, dt, a_log, dt_bias, d, interpret):
    args = (x, bm, cm, dt, a_log, dt_bias, d)

    def kernels(x, bm, cm, dt, a_log, dt_bias, d):
        dt, g = ssd_gates(dt, a_log, dt_bias)
        y, states = _ssd_fwd(*_kernel_layout(x, bm, cm, dt, g, d),
                             groups=bm.shape[2], interpret=interpret)
        return y.reshape(x.shape), states

    def plain(*args):
        b, t, h, p = args[0].shape
        shape = _states_shape(b, t, h, p, args[1].shape[3])
        return _plain_scan(*args), jnp.zeros(shape.shape, shape.dtype)

    y, states = _kernel_on_tpu(kernels, plain, interpret, *args)
    return y, (args, states)


def _two_lowerings_bwd(interpret, res, dy):
    # as ops/linear_attention.py's: what is computed again waits for the
    # cotangent's arrival
    (args, states), dy = lax.optimization_barrier((res, dy))

    def kernels(dy, states, x, bm, cm, dt, a_log, dt_bias, d):
        b, t, h, p = x.shape
        (dtf, g), before = jax.vjp(ssd_gates, dt, a_log, dt_bias)
        dx, dbm, dcm, dg, ddt = _ssd_bwd(
            *_kernel_layout(x, bm, cm, dtf, g, d), states,
            dy.reshape(b, t, -1), groups=bm.shape[2], interpret=interpret)
        ddt, da_log, ddt_bias = before((_from_rows(ddt), _from_rows(dg)))
        dd = jnp.sum(dy.astype(jnp.float32) * x.astype(jnp.float32),
                     axis=(0, 1, 3))
        return (dx.reshape(x.shape), _group_sums(dbm, bm),
                _group_sums(dcm, cm), ddt, da_log, ddt_bias,
                dd.astype(d.dtype))

    def plain(dy, states, *args):
        return jax.vjp(_plain_scan, *args)[1](dy)

    return _kernel_on_tpu(kernels, plain, interpret, dy, states, *args)


_two_lowerings.defvjp(_two_lowerings_fwd, _two_lowerings_bwd)


def _kernel_takes(x, bm) -> bool:
    """What the kernels compute and tile: bfloat16 (float32 runs the
    plain chunks, which keep its digits), sequences of whole chunks,
    ``B`` and ``C`` of one 128-lane row a group, each group under an even
    number of 64-lane heads (a grid step's heads lie in one group:
    ``_step_heads``)."""
    groups = bm.shape[2]
    return (x.dtype == jnp.bfloat16 and x.shape[1] % SSD_CHUNK == 0
            and x.shape[2] % (2 * groups) == 0
            and x.shape[3] == SSD_HEAD_DIM and bm.shape[3] == SSD_STATE)


def ssd_scan(x, bm, cm, dt, a_log, dt_bias, d, interpret: bool = False):
    """``SSDScan`` of its seven inputs: the gates, the recurrence, the
    skip.  One algorithm, two lowerings (see the module docstring); each
    trace records which as ``ssd:lowering`` (track ``<dtype>[B, T, H,
    P]/g<G>n<N>``): ``kernel`` 1 means the op's TPU lowering is the
    kernel pair (a CPU program holds the plain chunks all the same),
    ``plain`` 1 the plain chunks on every platform."""
    kernel = _kernel_takes(x, bm)
    trace.counter("ssd:lowering", cat="ops",
                  track="%s%s/g%dn%d" % ((x.dtype.name, list(x.shape))
                                         + tuple(bm.shape[2:])),
                  chunked=1, chunk=min(SSD_CHUNK, x.shape[1]),
                  kernel=int(kernel), plain=int(not kernel))
    if not kernel:
        return _plain_scan(x, bm, cm, dt, a_log, dt_bias, d)
    return _two_lowerings(x, bm, cm, dt, a_log, dt_bias, d, interpret)


@register_op("SSDScan", hint="ssd")
class SSDScanOp(OpDef):
    """The state-space scan of Mamba-2 over ``(B, T, H, P)`` data, ``(B,
    T, G, N)`` ``b`` and ``c`` (``G`` a whole divisor of ``H``: head
    ``j`` reads group ``j // (H / G)``) and the step's projection ``dt``
    ``(B, T, H)``: ``S_t = exp(dt_t A) S_{t-1} + dt_t b_t x_t^T``, ``y_t
    = c_t^T S_t + d x_t`` a head, with ``dt_t = softplus(dt + dt_bias)``
    and ``A = -exp(a_log)`` one number a head, the decay's chain in
    float32 (``ops/ssd.py``) -> ``(B, T, H, P)``.  The state starts at
    zero and runs through the whole sequence.  ``layer`` names the trace
    scope ``ssm_scan.l<layer>``."""
    params = [Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["data", "b", "c", "dt", "a_log_bias", "dt_bias", "d_gamma"]

    def infer_shape(self, p, in_shapes):
        x, bm = in_shapes[0], in_shapes[1]
        if x is None or bm is None:
            return in_shapes, [None], []
        if len(x) != 4 or len(bm) != 4 or tuple(x[:2]) != tuple(bm[:2]) \
                or bm[2] < 1 or x[2] % bm[2]:
            raise MXNetError("SSDScan: data (batch, seq, heads, head_dim) "
                             "and b, c (batch, seq, groups, state) with "
                             "groups a whole divisor of heads, got %r and %r"
                             % (x, bm))
        h = (x[2],)
        return [x, bm, bm, tuple(x[:3]), h, h, h], [x], []

    def forward(self, p, inputs, aux, ctx):
        with layer_scope("ssm_scan", p.layer):
            return [ssd_scan(*inputs)]
