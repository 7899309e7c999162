"""The forward attend pass of ``IndexedSelfAttention`` as a Pallas kernel
of this repo: softmax attention of one sequence under a selection that is
DATA (a ``(T, T)`` mask no function computes), grouped queries.

All ``G = H / Hkv`` query heads of one key/value head share one
selection, so a grid step holds them together: ``G x bq`` query rows
against one ``(bkv, Dh)`` key tile, one ``(bkv, Dv)`` value tile and ONE
``(bq, bkv)`` tile of the selection as int8, a byte a pair, broadcast
over the heads in VMEM.  (The library's splash attention takes a dynamic
mask as ``bool``, which Mosaic holds as int32, and loads a tile once for
EACH head: for 1024 x 1024 pairs of the Keye cell's 32 heads over 4 that
is 32 x 4 MiB where this kernel loads 4 x 1 MiB.)  The rest is a flash
forward: float32 scores, the running maximum, sum and accumulator in
scratch, bfloat16 operands on the MXU, key tiles beyond a query tile's
last row never visited.

The operands go in as the op has them but for one reshape, ``(T, H *
Dh)`` rows with a head's lanes side by side (a grid step cuts its heads
out of whole lane blocks; XLA lays q out once for it, as it did for the
library's head-major form), and the output comes back the same way; the
log-sum-exp leaves as ``(H, T)``, what the target pass and the library's
backward kernel read.

The kernel's name on the device is ``splash_mha_fwd_selected``: the
benchmark's ``dsa_attn_roofline`` divides the work of BOTH attend passes
by the time of the operations named ``splash_mha*`` (the backward kernel
is the library's), so the forward keeps the prefix until that reader goes
by scope (ROADMAP D12(h)).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selected_attention_fwd", "forward_tiles"]

LANES = 128
# what masks a score: the library's value, so a row's log-sum-exp over a
# tile that holds none of its keys is the number the backward kernel forms
MASK_VALUE = -0.7 * float(np.finfo(np.dtype("float32")).max)
# rows a grid step (heads * bq), keys a tile and keys a matmul: a piece's
# float32 scores are ROWS x PIECE x 4 bytes (4 MiB), what Mosaic's default
# scoped VMEM (16 MiB on a v5e) holds beside the blocks (q and the output
# 1 MiB each, twice) and the scratch (6 MiB).  Measured on a v5e at 32
# heads over 4, 8192 rows (PERF.md, PR 58): 4096 x 512 x 256 3.89 ms a
# call, 2048 x 1024 x 512 4.65, 4096 x 1024 x 256 3.83 but 5 s to compile
# (the pieces are unrolled) where this takes 2
ROWS, BLOCK_KV, PIECE = 4096, 512, 256


def _whole(t: int, most: int) -> int:
    """The largest multiple of LANES within ``most`` that divides ``t``."""
    return next(n for n in range(min(most, t) // LANES * LANES, 0, -LANES)
                if t % n == 0)


def forward_tiles(t: int, group: int, lanes: int):
    """``(heads, bq, bkv, piece)`` for sequences of ``t`` rows (whole
    128s), ``group`` query heads a key/value head and heads of ``lanes``
    lanes (the wider of Dh, Dv): the heads a step holds under one mask
    tile (the whole group where its rows fit, else its largest divisor
    that does), the query rows a head (within the step's rows and a key
    tile), the keys a tile and the keys a matmul, each a whole divisor of
    the one before in whole 128s.  A step's rows are ROWS at 128 lanes
    and fewer at wider heads, as the blocks and the scratch grow."""
    rows = ROWS * LANES // lanes
    heads = next(n for n in range(min(group, rows // LANES), 0, -1)
                 if group % n == 0)
    bkv = _whole(t, BLOCK_KV)
    return (heads, _whole(t, min(rows // heads, bkv)), bkv,
            _whole(bkv, PIECE))


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_ref, l_ref,
            acc_ref, *, heads, bq, bkv, piece, dh, dv):
    i, j = pl.program_id(1), pl.program_id(2)
    last_row = (i + 1) * bq - 1
    last_tile = last_row // bkv

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_piece(lo):
        # the heads' rows one under another: (heads * bq, Dh)
        q = jnp.concatenate([q_ref[:, g * dh:(g + 1) * dh]
                             for g in range(heads)], axis=0)
        s = jax.lax.dot_general(q, k_ref[lo:lo + piece, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        keep = mask_ref[:, lo:lo + piece].astype(jnp.int32) != 0
        s = jnp.where(keep[None], s.reshape(heads, bq, piece),
                      MASK_VALUE).reshape(heads * bq, piece)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - jnp.tile(m_next, (1, piece // LANES)))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_next
        pv = jnp.dot(p.astype(v_ref.dtype), v_ref[lo:lo + piece, :],
                     preferred_element_type=jnp.float32)
        acc_ref[...] = jnp.tile(alpha, (1, dv // LANES)) * acc_ref[...] + pv

    for n in range(bkv // piece):
        # a piece whose first key lies beyond the query tile's last row
        # holds no causal pair: the diagonal tile runs only the pieces it
        # needs, the tiles behind it none
        pl.when(j * bkv + n * piece <= last_row)(
            functools.partial(one_piece, n * piece))

    @pl.when(j == last_tile)
    def _():
        l = l_ref[...]
        out = acc_ref[...] * jnp.tile(1.0 / l, (1, dv // LANES))
        lse = m_ref[...] + jnp.log(l)
        # a row's value stands in every lane: the diagonal of a 128 x 128
        # square, summed down its sublanes, lays 128 rows' values along the
        # lanes
        square = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        for g in range(heads):
            o_ref[:, g * dv:(g + 1) * dv] = \
                out[g * bq:(g + 1) * bq].astype(o_ref.dtype)
            for at in range(0, bq, LANES):
                lse_ref[g:g + 1, at:at + LANES] = jnp.sum(
                    jnp.where(square, lse[g * bq + at:g * bq + at + LANES],
                              0.0), axis=0, keepdims=True)


def selected_attention_fwd(q, k, v, mask, interpret: bool = False):
    """Softmax attention of one sequence over the pairs ``mask`` selects:
    ``(T, H, Dh)`` scaled queries, ``(T, Hkv, Dh)`` keys, ``(T, Hkv,
    Dv)`` values (heads of whole 128 lanes, ``T`` of whole 128s) and the
    selection ``(T, T)`` (bool; causal, and every row selects at least
    one key) -> ``(T, H, Dv)`` in the inputs' dtype and the float32
    log-sum-exp ``(H, T)``."""
    t, h, dh = q.shape
    hkv, dv = k.shape[1], v.shape[2]
    heads, bq, bkv, piece = forward_tiles(t, h // hkv, max(dh, dv))
    steps = h // hkv // heads       # of one key/value head's query heads

    def keys_of(i, j):
        # a step beyond the diagonal keeps the diagonal's blocks: nothing
        # is fetched for it
        return jnp.minimum(j, ((i + 1) * bq - 1) // bkv)

    # lint: allow(raw-pallas-call) — one lowering of the op's attend pass,
    # chosen by platform and held to the plain blocks by tolerance
    # (tests/test_sparse_attention.py, tests/tpu/test_keye_tpu.py): not a
    # forward kernel behind the kernel search's bitwise gate
    out, lse = pl.pallas_call(
        functools.partial(_kernel, heads=heads, bq=bq, bkv=bkv, piece=piece,
                          dh=dh, dv=dv),
        grid=(h // heads, t // bq, t // bkv),
        in_specs=[
            pl.BlockSpec((bq, heads * dh), lambda n, i, j: (i, n)),
            pl.BlockSpec((bkv, dh),
                         lambda n, i, j: (keys_of(i, j), n // steps)),
            pl.BlockSpec((bkv, dv),
                         lambda n, i, j: (keys_of(i, j), n // steps)),
            pl.BlockSpec((bq, bkv), lambda n, i, j: (i, keys_of(i, j)))],
        out_specs=[
            pl.BlockSpec((bq, heads * dv), lambda n, i, j: (i, n)),
            pl.BlockSpec((None, heads, bq), lambda n, i, j: (n, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((t, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((h // heads, heads, t),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads * bq, LANES), jnp.float32),
                        pltpu.VMEM((heads * bq, LANES), jnp.float32),
                        pltpu.VMEM((heads * bq, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="splash_mha_fwd_selected",
    )(q.reshape(t, h * dh), k.reshape(t, hkv * dh), v.reshape(t, hkv * dv),
      mask.astype(jnp.int8))
    return out.reshape(t, h, dv), lse.reshape(h, t)
