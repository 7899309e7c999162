"""The attend pass, forward and backward, and the target pass of
``IndexedSelfAttention`` as three Pallas kernels of this repo, none of
the library's, all under a selection that is DATA (a ``(T, T)`` mask no
function computes) over grouped queries; and the attend pair once more
under a mask that is a FUNCTION of row and key, ``causal_attention``'s
TPU lowering for grouped 128-lane heads (``computed_attention_fwd`` /
``computed_attention_bwd``, the last section below).

**The forward attend pass** (``selected_attention_fwd``): softmax
attention of one sequence under the selection.

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
log-sum-exp leaves as ``(H, T)``, what the target pass and the backward
kernel read.

The kernel's name on the device is ``splash_mha_fwd_selected``: the
benchmark's ``dsa_attn_roofline`` divides the work of BOTH attend passes
by the time of the operations named ``splash_mha*``, so both kernels
keep the prefix until that reader goes by scope (ROADMAP D12(h)).

**The backward attend pass** (``selected_attention_bwd``, PR 62;
``splash_mha_dkv_selected`` on the device): the cotangents of the scaled
q, of k and of v from the forward's output and log-sum-exp, on the
forward's plan.  A grid step holds the query heads of one key/value
head, ``G x bq`` rows, against one key tile under ONE ``(bq, bkv)`` int8
tile of the selection, transposed once in VMEM for all of them.  The
scores are formed TRANSPOSED, keys down and the heads' rows side by side
along the lanes (``k q^T``, ``(bkv, G * bq)``), so a row's log-sum-exp
and ``di = rowsum(out * d_out)`` are lane vectors (``di`` is formed in
the kernel from the tiles it holds, once a query tile) and four of the
five products need no transpose: ``p = exp(s - lse)`` under the tile,
``dp = v d_out^T``, ``ds = p (dp - di)``, ``dv += p d_out`` and ``dk +=
ds q`` as ONE contraction each over the group's ``G * bq`` rows, ``dq +=
ds^T k``; bfloat16 operands on the MXU into float32.  With the query
tile outermost ``dq`` of a step's rows accumulates in float32 scratch
over its causal key tiles and is written once, rounded once; ``dk`` and
``dv`` of one key/value head accumulate over all its query tiles and its
group's heads in two float32 ``(T, D)`` scratches (8 MiB at 8192 x 128;
the grid runs in order on the chip's one core) and leave a key tile at
a time during the head's last query tile, which reads every key.  Key
tiles beyond a query tile's last row are never visited, a diagonal tile
runs only the pieces its rows reach.  q, the output, its cotangent and
``dq`` are ``(T, H * Dh)`` rows, k, v, ``dk``, ``dv`` ``(T, Hkv * D)``
rows: no ``MaskInfo``, no int32 block a head, no head-major copy and no
partial ``dq`` plane ``(key tiles, H, T, Dh)`` to sum.  The call is one
inner ``jax.jit``, as the target pass's.

**The target pass** (``selected_target``, PR 60): the index loss ``KL(p ||
softmax over the selection of I)`` with ``p`` the heads' mean
probabilities, and its gradient by the indexer's inputs.  A grid step is
one ``(bq, bk)`` tile of pairs under the SAME int8 tile of the selection:
the query heads of each key/value head one under another against its key
tile (bfloat16 on the MXU into float32), ``exp(s - lse)`` in float32
ADDED into one float32 ``(bq, bk)`` accumulator, so no ``(H, rows, keys)``
array exists; the indexer's scores of the tile as ``indexer_scores``
forms them (TARGET_HEADS heads a matmul); the row's ``sum p``, the
selected scores' running log-sum-exp and the loss's terms in ``(bq,
128)`` scratch.  The gradient of a pair, ``d_score = (sum_k p) softmax(
score) - p``, needs two statistics of its whole row, so a row block
keeps ``p`` and the selected scores of its causal tiles in VMEM (two
float32 ``(bq, T)``: 16 MiB at 256 rows of 8192) and sweeps its key
tiles a SECOND time: the indexer's products again, ``d_z`` a head in
bfloat16, ``d_qi`` and ``d_w`` summed a row block, ``d_ki`` over the
whole call in ONE float32 ``(T, Di)`` scratch (the grid runs in order on
the chip's one core); all three leave as the mean row loss's gradient
in their inputs' dtypes.  q and ``qi`` come in as ``(T, H * Dh)`` and
``(T, Hi * Di)`` rows, what they are but for a reshape, and a row block
cuts its heads out of the lanes once; ``d_qi`` leaves the same way.  The
call is one inner ``jax.jit``, so the layers and modules of a process
share one traced kernel.  Its device names are ``dsa_target_grads`` and
``dsa_target_loss``: not ``splash_mha*``, which ``dsa_attn_roofline``'s
work function does not count it under.

**The attend pair under a computed mask** (``computed_attention_fwd`` /
``computed_attention_bwd``, PR 64; ``splash_mha_fwd_computed`` /
``splash_mha_dkv_computed`` on the device, the prefix every
``*attn_roofline`` reader sums): the SAME two step bodies (``_kernel``,
``_backward_step``), which take where a tile's allowed pairs come from as
a parameter: ``_Loaded`` is the int8 tile above, ``_Computed`` evaluates
``allowed(rows' entries, key ids)`` (``ops/transformer.py``
``kernel_mask``: causal, ``sliding_window``, ``block_diffusion`` through
its host-made row codes) in VMEM from the rows' int32 entries and an iota
of the tile's keys, once for the heads of a step, where the library's
kernels evaluate it once a head.  Nothing ``(T, T)`` exists.  Which key
tiles a query tile visits is a table made on the host at trace time from
the mask itself (``visit_plan``) and prefetched as scalars: only tiles
that hold an allowed pair, a tile's empty pieces skipped, a query tile's
``dq`` written at its last visit and a key tile's ``dk`` / ``dv`` at the
last visit any query tile pays it (under a window or the block mask the
last query tile does not read every key).  The operands are ``(B, T, H *
Dh)`` and ``(B, T, Hkv * D)`` rows, the batch a grid axis.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selected_attention_fwd", "forward_tiles",
           "selected_attention_bwd", "backward_tiles", "selected_target",
           "target_tiles", "computed_attention_fwd", "computed_attention_bwd",
           "visit_plan"]

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


def _attend_tiles(t: int, group: int, lanes: int, rows: int, block_kv: int,
                  piece: int):
    """``(heads, bq, bkv, piece)`` of an attend kernel whose step holds
    ``rows`` rows at 128 lanes (fewer at wider heads, as the blocks and
    the scratch grow) against ``block_kv`` keys read ``piece`` a matmul:
    the heads a step holds under one mask tile (the whole group where its
    rows fit, else its largest divisor that does), the query rows a head
    (within the step's rows and a key tile), the keys a tile and the keys
    a matmul, each a whole divisor of the one before in whole 128s."""
    rows = rows * LANES // lanes
    heads = next(n for n in range(min(group, rows // LANES), 0, -1)
                 if group % n == 0)
    bkv = _whole(t, block_kv)
    return (heads, _whole(t, min(rows // heads, bkv)), bkv,
            _whole(bkv, piece))


def forward_tiles(t: int, group: int, lanes: int):
    """``(heads, bq, bkv, piece)`` of the forward attend kernel for
    sequences of ``t`` rows (whole 128s), ``group`` query heads a
    key/value head and heads of ``lanes`` lanes (the wider of Dh, Dv):
    ``_attend_tiles`` of ROWS, BLOCK_KV and PIECE."""
    return _attend_tiles(t, group, lanes, ROWS, BLOCK_KV, PIECE)


class _Loaded:
    """Where a step of the attend kernels gets its allowed pairs when the
    mask is DATA: an int8 ``(bq, bkv)`` tile of the selection, loaded a
    step, under the grid (heads' steps, query tiles, key tiles) of a
    selection that is causal and nothing more: key tile ``j`` is the
    grid's, the tiles behind a query tile's last row are never run and
    the last query tile reads every key."""

    def __init__(self, refs, bq, bkv, backward):
        self.refs = refs
        self.mask_ref = refs[6 if backward else 3]
        if backward:
            self.n = pl.program_id(0)
        self.i, self.j = pl.program_id(1), pl.program_id(2)
        self.last_row = (self.i + 1) * bq - 1
        self.last_tile = self.last_row // bkv
        self.tile, self.bkv, self.backward = self.j, bkv, backward

    @property
    def first(self):
        return self.j == 0

    @property
    def last(self):
        return self.j == self.last_tile

    def runs(self, n, lo):
        """Whether piece ``n``, keys ``lo`` on of the tile, holds a pair
        at all: its first key is not beyond the last row."""
        return self.j * self.bkv + lo <= self.last_row

    def keep(self, lo, piece):
        """The piece's allowed pairs ``(bq, piece)``; keys down in the
        backward pass, where the tile is transposed in VMEM."""
        tile = self.mask_ref[:, lo:lo + piece]
        if self.backward:
            return tile.astype(jnp.float32).T != 0.0
        return tile.astype(jnp.int32) != 0

    def closes(self, steps):
        """Whether this step's key tile has its whole ``dk`` and ``dv``:
        the last query tile of a key/value head's last step reads every
        key tile."""
        return (self.n % steps == steps - 1) \
            & (self.i == pl.num_programs(1) - 1)


class _Computed:
    """Where a step gets its allowed pairs when the mask is a FUNCTION of
    row and key: ``allowed(rows' entries, key ids)`` (``ops/transformer.py``
    ``kernel_mask``) evaluated in VMEM on the rows' int32 entries, loaded
    a query tile (a column in the forward pass, a row of lanes in the
    backward one), and an iota of the tile's keys, once for the heads of
    the step.  The grid is (batch, heads' steps, query tiles, VISITS): a
    query tile's ``j``-th visit is to the key tile the prefetched
    ``visit_plan`` names, and the plan says which of its pieces run, when
    a query tile has seen its last key and when a key tile its last
    query."""

    def __init__(self, refs, bq, bkv, backward, allowed):
        plan_ref, *self.refs = refs
        self.codes_ref = self.refs[6 if backward else 3]
        self.n, self.i, self.j = (pl.program_id(a) for a in (1, 2, 3))
        self.tile, self.pieces, self.flags = (
            plan_ref[field, self.i, self.j] for field in range(3))
        self.bq, self.bkv, self.backward = bq, bkv, backward
        self.allowed = allowed

    @property
    def first(self):
        return self.j == 0

    @property
    def last(self):
        return self.flags & LAST_VISIT != 0

    def runs(self, n, lo):
        return (self.pieces >> n) & 1 != 0

    def keep(self, lo, piece):
        first_key = self.tile * self.bkv + lo
        if self.backward:
            shape = (piece, self.bq)
            codes = jnp.broadcast_to(self.codes_ref[:1, :], shape)
        else:
            shape = (self.bq, piece)
            codes = jnp.tile(self.codes_ref[...], (1, piece // LANES))
        return self.allowed(codes, first_key + jax.lax.broadcasted_iota(
            jnp.int32, shape, 0 if self.backward else 1))

    def closes(self, steps):
        return (self.n % steps == steps - 1) & (self.flags & CLOSES != 0)


# ``visit_plan``'s flags: the query tile's last visit, and the last visit
# that the key tile gets from any query tile
LAST_VISIT, CLOSES = 1, 2


def visit_plan(rows, allowed, bq: int, bkv: int, piece: int):
    """The visits of the computed-mask kernels, made on the host at trace
    time from the mask itself: int32 ``(4, T / bq, visits)`` for ``rows``
    (the mask's int32 entry a row, ``T`` of them) and ``allowed(rows'
    entries, key ids)``.  Field 0: the key tile of a query tile's
    ``j``-th visit, only tiles that hold an allowed pair, in order; the
    visits behind the last repeat it, so nothing is fetched for them.
    Field 1: bit ``n`` says piece ``n`` of that tile holds an allowed
    pair (0 behind the last visit: nothing runs).  Field 2: LAST_VISIT on
    a query tile's last visit, CLOSES on the visit after which no later
    step of the grid (query tiles outermost) reads the key tile again.
    Field 3: the key tile whose ``dk`` and ``dv`` blocks are the current
    ones at a step: the last to have closed, or the first that will."""
    t = len(rows)
    nq, nk, pieces = t // bq, t // bkv, bkv // piece
    keys = np.arange(t, dtype=np.int32)[None, :]
    some = np.stack([
        np.asarray(allowed(rows[at:at + bq, None], keys)).reshape(
            bq, t // piece, piece).any(axis=(0, 2))
        for at in range(0, t, bq)]).reshape(nq, nk, pieces)
    if not (some.any(axis=(1, 2)).all() and some.any(axis=(0, 2)).all()):
        raise ValueError("a query tile or a key tile without one allowed pair")
    bits = (some.astype(np.int32) << np.arange(pieces, dtype=np.int32)).sum(2)
    visits = [np.flatnonzero(row) for row in bits]
    plan = np.zeros((4, nq, max(len(v) for v in visits)), np.int32)
    for i, tiles in enumerate(visits):
        plan[0, i] = tiles[-1]
        plan[0, i, :len(tiles)] = tiles
        plan[1, i, :len(tiles)] = bits[i, tiles]
        plan[2, i, len(tiles) - 1] = LAST_VISIT
    closed = []
    for i, tiles in enumerate(visits):
        for j, tile in enumerate(tiles):
            if not bits[i + 1:, tile].any():
                plan[2, i, j] |= CLOSES
                closed.append((i, j, tile))
    plan[3] = closed[0][2]
    for i, j, tile in closed:
        plan[3, i, j:] = tile
        plan[3, i + 1:] = tile
    return plan


def _kernel(*refs, source, heads, bq, bkv, piece, dh, dv):
    at = source(refs, bq, bkv, False)
    q_ref, k_ref, v_ref, _, o_ref, lse_ref, m_ref, l_ref, acc_ref = at.refs

    @pl.when(at.first)
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
        s = jnp.where(at.keep(lo, piece)[None], s.reshape(heads, bq, piece),
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
        # only the pieces that hold an allowed pair: the diagonal tile
        # runs the pieces its rows reach, the tiles behind it none
        pl.when(at.runs(n, n * piece))(functools.partial(one_piece, n * piece))

    @pl.when(at.last)
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
        functools.partial(_kernel, source=_Loaded, heads=heads, bq=bq,
                          bkv=bkv, piece=piece, dh=dh, dv=dv),
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


# The backward attend pass: rows a grid step (heads * bq), keys a tile and
# keys a matmul, as the forward's; a piece's float32 scores and their
# cotangents are 2 x ROWS x PIECE x 4 bytes and ``dk`` / ``dv`` of one
# key/value head stay in float32 VMEM over all its query tiles
# (BACKWARD_KEPT bounds them: 2 x T x D x 4 bytes, 8 MiB at 8192 x 128),
# so the kernel asks for more than Mosaic's default scoped VMEM, as the
# target kernel does.  Measured on a v5e at 32 heads over 4, 8192 rows
# under a top-2048, ms a kernel / s to the first call (PERF.md, PR 62):
# 4096 x 512 x 512 8.49 / 4.0, 4096 x 1024 x 512 8.38 / 7.2, 2048 x 512 x
# 512 8.74 / 2.6, 4096 x 512 x 256 8.59, 2048 x 1024 x 512 8.55, 2048 x
# 256 x 256 9.08, 1024 x 512 x 512 9.49, 8192 x 1024 x 512 12.68 / 15.7
BACKWARD_ROWS, BACKWARD_BLOCK_KV, BACKWARD_PIECE = 4096, 512, 512
BACKWARD_KEPT = 32 << 20
BACKWARD_VMEM = 100 << 20


def backward_tiles(t: int, group: int, lanes: int):
    """``(heads, bq, bkv, piece)`` of the backward attend kernel, as
    ``forward_tiles`` gives the forward's, or None where the kernel does
    not take the sequence: ``t`` not in whole 128s, or so long that one
    key/value head's float32 ``dk`` and ``dv`` pass BACKWARD_KEPT."""
    if t % LANES or 8 * t * lanes > BACKWARD_KEPT:
        return None
    return _attend_tiles(t, group, lanes, BACKWARD_ROWS, BACKWARD_BLOCK_KV,
                         BACKWARD_PIECE)


def _backward_step(*refs, source, heads, steps, bq, bkv, piece, dh, dv):
    at = source(refs, bq, bkv, True)
    (q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, _, dq_ref, dk_ref, dv_ref,
     q_st, do_st, di_st, dq_acc, dk_acc, dv_acc) = at.refs
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))

    @pl.when((at.n % steps == 0) & (at.i == 0) & (at.j == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(at.first)
    def _():
        # the query tile's operands as the key tiles read them: the heads'
        # rows one under another, and ``di = rowsum(out * d_out)`` a head
        # along the lanes (the diagonal of a 128 x 128 square summed down
        # its sublanes lays 128 rows' values along the lanes)
        dq_acc[...] = jnp.zeros_like(dq_acc)
        square = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        for g in range(heads):
            rows = slice(g * bq, (g + 1) * bq)
            d_out = do_ref[:, g * dv:(g + 1) * dv]
            q_st[rows] = q_ref[:, g * dh:(g + 1) * dh]
            do_st[rows] = d_out
            di = jnp.sum(o_ref[:, g * dv:(g + 1) * dv].astype(f32)
                         * d_out.astype(f32), axis=1, keepdims=True)
            for at in range(0, bq, LANES):
                di_st[g:g + 1, at:at + LANES] = jnp.sum(
                    jnp.where(square, di[at:at + LANES], 0.0), axis=0,
                    keepdims=True)

    def one_piece(lo):
        # TRANSPOSED scores, keys down and the heads' rows side by side
        # along the lanes, so a row's log-sum-exp and ``di`` are lane
        # vectors and four of the five products need no transpose
        k_p, v_p = k_ref[lo:lo + piece, :], v_ref[lo:lo + piece, :]
        s = jax.lax.dot_general(k_p, q_st[...], nt,
                                preferred_element_type=f32)
        dp = jax.lax.dot_general(v_p, do_st[...], nt,
                                 preferred_element_type=f32)
        # the tile's pairs keys down, once for the group's heads
        keep = at.keep(lo, piece)
        p, ds = [], []
        for g in range(heads):
            rows = slice(g * bq, (g + 1) * bq)
            p_g = jnp.where(keep, jnp.exp(s[:, rows] - lse_ref[g:g + 1, :]),
                            0.0)
            ds.append((p_g * (dp[:, rows] - di_st[g:g + 1, :])).astype(
                k_ref.dtype))
            p.append(p_g.astype(do_st.dtype))
        p, ds = jnp.concatenate(p, axis=1), jnp.concatenate(ds, axis=1)
        keys = pl.ds(pl.multiple_of(at.tile * bkv + lo, piece), piece)
        dv_acc[keys, :] += jnp.dot(p, do_st[...], preferred_element_type=f32)
        dk_acc[keys, :] += jnp.dot(ds, q_st[...], preferred_element_type=f32)
        dq_acc[...] += jax.lax.dot_general(ds, k_p, (((0,), (0,)), ((), ())),
                                           preferred_element_type=f32)

    for n, lo in enumerate(range(0, bkv, piece)):
        # as the forward: only the pieces that hold an allowed pair
        pl.when(at.runs(n, lo))(functools.partial(one_piece, lo))

    @pl.when(at.last)
    def _():
        for g in range(heads):
            dq_ref[:, g * dh:(g + 1) * dh] = \
                dq_acc[g * bq:(g + 1) * bq].astype(dq_ref.dtype)

    # a key tile leaves as its sum closes: at the last visit it gets from
    # a key/value head's last step
    @pl.when(at.closes(steps))
    def _():
        keys = pl.ds(pl.multiple_of(at.tile * bkv, bkv), bkv)
        dk_ref[...] = dk_acc[keys, :].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[keys, :].astype(dv_ref.dtype)


def selected_attention_bwd(q, k, v, mask, out, lse, d_out,
                           interpret: bool = False):
    """The cotangents of ``selected_attention_fwd``'s inputs: its ``(T, H,
    Dh)`` scaled queries, ``(T, Hkv, Dh)`` keys, ``(T, Hkv, Dv)`` values
    and selection ``(T, T)``, its output ``(T, H, Dv)`` and float32
    log-sum-exp ``(H, T)`` and the output's cotangent -> ``(dq, dk, dv)``
    in their inputs' shapes and dtypes (the module's docstring has the
    kernel).  ``backward_tiles`` must take ``T``."""
    return _selected_attention_bwd(
        q, k, v, mask, out, lse, d_out, interpret=interpret,
        tiles=backward_tiles(q.shape[0], q.shape[1] // k.shape[1],
                             max(q.shape[2], v.shape[2])))


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, as ``_selected_target``'s, so that every layer and module shares
# one traced kernel and one lowered function
@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def _selected_attention_bwd(q, k, v, mask, out, lse, d_out, *, interpret,
                            tiles):
    t, h, dh = q.shape
    hkv, dv = k.shape[1], v.shape[2]
    heads, bq, bkv, piece = tiles
    steps = h // hkv // heads       # of one key/value head's query heads
    rows = heads * bq

    def keys_of(i, j):
        # a step beyond the diagonal keeps the diagonal's blocks
        return jnp.minimum(j, ((i + 1) * bq - 1) // bkv)

    def closing(n, i, j):
        # dk and dv leave a key tile at a time in the head's last sweep
        # and stay at tile 0, unwritten and unfetched, before it
        return jnp.where((n % steps == steps - 1) & (i == t // bq - 1), j, 0)

    def a_head(width):
        return pl.BlockSpec((bq, heads * width), lambda n, i, j: (i, n))

    def a_key_tile(width):
        return pl.BlockSpec((bkv, width),
                            lambda n, i, j: (keys_of(i, j), n // steps))

    def closed(width):
        return pl.BlockSpec((bkv, width),
                            lambda n, i, j: (closing(n, i, j), n // steps))

    # lint: allow(raw-pallas-call) — one lowering of the op's attend pass,
    # chosen by platform and held to the plain blocks by tolerance
    # (tests/test_sparse_attention.py, tests/tpu/test_keye_tpu.py)
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(_backward_step, source=_Loaded, heads=heads,
                          steps=steps, bq=bq, bkv=bkv, piece=piece, dh=dh,
                          dv=dv),
        grid=(h // heads, t // bq, t // bkv),
        in_specs=[
            a_head(dh), a_key_tile(dh), a_key_tile(dv), a_head(dv),
            a_head(dv),
            pl.BlockSpec((None, heads, bq), lambda n, i, j: (n, 0, i)),
            pl.BlockSpec((bq, bkv), lambda n, i, j: (i, keys_of(i, j)))],
        out_specs=[a_head(dh), closed(dh), closed(dv)],
        out_shape=[jax.ShapeDtypeStruct((t, h * dh), q.dtype),
                   jax.ShapeDtypeStruct((t, hkv * dh), k.dtype),
                   jax.ShapeDtypeStruct((t, hkv * dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, dh), q.dtype),
                        pltpu.VMEM((rows, dv), d_out.dtype),
                        pltpu.VMEM((heads, bq), jnp.float32),
                        pltpu.VMEM((rows, dh), jnp.float32),
                        pltpu.VMEM((t, dh), jnp.float32),
                        pltpu.VMEM((t, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=BACKWARD_VMEM),
        interpret=interpret,
        name="splash_mha_dkv_selected",
    )(q.reshape(t, h * dh), k.reshape(t, hkv * dh), v.reshape(t, hkv * dv),
      out.reshape(t, h * dv), d_out.reshape(t, h * dv),
      lse.reshape(h // heads, heads, t), mask.astype(jnp.int8))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape)


def _computed_specs(heads, steps, bq, bkv):
    """The block specs of the computed-mask kernels over ``(B, T, lanes)``
    rows: ``(a step's heads, the visited key tile of a key/value head,
    the log-sum-exp of a step's heads)``, each by its lanes a head."""
    def a_head(width):
        return pl.BlockSpec((None, bq, heads * width),
                            lambda b, n, i, j, plan: (b, i, n))

    def a_key_tile(width):
        return pl.BlockSpec(
            (None, bkv, width),
            lambda b, n, i, j, plan: (b, plan[0, i, j], n // steps))

    lse = pl.BlockSpec((None, None, heads, bq),
                       lambda b, n, i, j, plan: (b, n, 0, i))
    return a_head, a_key_tile, lse


def computed_attention_fwd(q, k, v, rows, allowed, interpret: bool = False):
    """Softmax attention under a mask that is a function: ``(B, T, H, Dh)``
    scaled queries, ``(B, T, Hkv, Dh)`` keys, ``(B, T, Hkv, Dv)`` values
    (heads of whole 128 lanes, ``T`` of whole 128s), the mask's int32
    entry a row ``rows`` (numpy, ``T``) and ``allowed(rows' entries, key
    ids)`` (every row reads at least itself) -> ``(B, T, H, Dv)`` in the
    inputs' dtype and the float32 log-sum-exp ``(B, H, T)``.  The kernel
    is ``selected_attention_fwd``'s (``_kernel``) with the tile's pairs
    from ``_Computed``; on the device ``splash_mha_fwd_computed``."""
    b, t, h, dh = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    heads, bq, bkv, piece = forward_tiles(t, h // hkv, max(dh, dv))
    steps = h // hkv // heads
    plan = visit_plan(rows, allowed, bq, bkv, piece)
    a_head, a_key_tile, lse_spec = _computed_specs(heads, steps, bq, bkv)
    codes = jnp.broadcast_to(jnp.asarray(rows)[:, None], (t, LANES))
    # lint: allow(raw-pallas-call) — one lowering of causal_attention,
    # chosen by platform and shape and held to the plain blocks by
    # tolerance (tests/test_rows_attention.py, tests/tpu/test_sdar_tpu.py)
    out, lse = pl.pallas_call(
        functools.partial(
            _kernel, source=functools.partial(_Computed, allowed=allowed),
            heads=heads, bq=bq, bkv=bkv, piece=piece, dh=dh, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // heads, t // bq, plan.shape[2]),
            in_specs=[a_head(dh), a_key_tile(dh), a_key_tile(dv),
                      pl.BlockSpec((bq, LANES),
                                   lambda b, n, i, j, plan: (i, 0))],
            out_specs=[a_head(dv), lse_spec],
            scratch_shapes=[pltpu.VMEM((heads * bq, LANES), jnp.float32),
                            pltpu.VMEM((heads * bq, LANES), jnp.float32),
                            pltpu.VMEM((heads * bq, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h // heads, heads, t),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",)),
        interpret=interpret,
        name="splash_mha_fwd_computed",
    )(jnp.asarray(plan), q.reshape(b, t, h * dh), k.reshape(b, t, hkv * dh),
      v.reshape(b, t, hkv * dv), codes)
    return out.reshape(b, t, h, dv), lse.reshape(b, h, t)


def computed_attention_bwd(q, k, v, rows, allowed, out, lse, d_out,
                           interpret: bool = False):
    """The cotangents of ``computed_attention_fwd``'s scaled queries, keys
    and values from its output ``(B, T, H, Dv)``, its log-sum-exp ``(B, H,
    T)`` and the output's cotangent -> ``(dq, dk, dv)`` in their inputs'
    shapes and dtypes: ``selected_attention_bwd``'s kernel
    (``_backward_step``) with the tile's pairs from ``_Computed``, a key
    tile leaving at the visit the plan says is its last; on the device
    ``splash_mha_dkv_computed``.  ``backward_tiles`` must take ``T``."""
    b, t, h, dh = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    heads, bq, bkv, piece = backward_tiles(t, h // hkv, max(dh, dv))
    steps = h // hkv // heads
    plan = visit_plan(rows, allowed, bq, bkv, piece)
    a_head, a_key_tile, lse_spec = _computed_specs(heads, steps, bq, bkv)
    group_rows = heads * bq

    def closed(width):
        # the key tile that closed last, or before a key/value head's last
        # step the first that will: unwritten and unfetched until it does
        return pl.BlockSpec(
            (None, bkv, width), lambda b, n, i, j, plan: (
                b, jnp.where(n % steps == steps - 1, plan[3, i, j],
                             plan[3, 0, 0]), n // steps))

    codes = jnp.broadcast_to(jnp.asarray(rows)[None, :], (8, t))
    # lint: allow(raw-pallas-call) — as computed_attention_fwd's
    dq, dk, dv_ = pl.pallas_call(
        functools.partial(
            _backward_step,
            source=functools.partial(_Computed, allowed=allowed),
            heads=heads, steps=steps, bq=bq, bkv=bkv, piece=piece, dh=dh,
            dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // heads, t // bq, plan.shape[2]),
            in_specs=[a_head(dh), a_key_tile(dh), a_key_tile(dv), a_head(dv),
                      a_head(dv), lse_spec,
                      pl.BlockSpec((8, bq), lambda b, n, i, j, plan: (0, i))],
            out_specs=[a_head(dh), closed(dh), closed(dv)],
            scratch_shapes=[pltpu.VMEM((group_rows, dh), q.dtype),
                            pltpu.VMEM((group_rows, dv), d_out.dtype),
                            pltpu.VMEM((heads, bq), jnp.float32),
                            pltpu.VMEM((group_rows, dh), jnp.float32),
                            pltpu.VMEM((t, dh), jnp.float32),
                            pltpu.VMEM((t, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dh), q.dtype),
                   jax.ShapeDtypeStruct((b, t, hkv * dh), k.dtype),
                   jax.ShapeDtypeStruct((b, t, hkv * dv), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=BACKWARD_VMEM),
        interpret=interpret,
        name="splash_mha_dkv_computed",
    )(jnp.asarray(plan), q.reshape(b, t, h * dh), k.reshape(b, t, hkv * dh),
      v.reshape(b, t, hkv * dv), out.reshape(b, t, h * dv),
      d_out.reshape(b, t, h * dv), lse.reshape(b, h // heads, heads, t),
      codes)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape)


# The target pass: a query tile's rows against its causal key tiles, TWICE
# when the indexer's gradient is wanted (the gradient of a pair needs two
# statistics of its whole row).  Rows and keys a tile and the indexer
# heads stacked under one matmul; between the sweeps a row block keeps two
# float32 numbers a pair in VMEM (TARGET_KEPT bounds them: 16 MiB at 256
# rows of 8192 keys), so the kernel asks for more than Mosaic's default
# scoped VMEM (TARGET_VMEM of a v5e's 128 MiB).  Measured on a v5e at 32
# heads over 4 and a 16 x 64 indexer, 8192 rows under a top-2048, ms a
# call with the gradient / loss only (PERF.md, PR 60): 256 x 512 x 16
# 6.30 / 3.59, x 8 6.62 / 3.75, x 4 6.83 / 3.88; 512 x 512 x 8 6.39 / 3.69
# at twice the kept rows (T up to 8192 only) and 7.8 s to the first call
# where 256 x 512 x 16 takes 5; and, while the gradients still left as
# float32 sums (x 4 then 6.95 / 3.90): x 2 7.30 / 4.15, 256 x 256 x 4
# 7.56 / 4.08, 256 x 1024 x 4 7.06 / 4.00
TARGET_BQ, TARGET_BK, TARGET_HEADS = 256, 512, 16
TARGET_KEPT = 16 << 20
TARGET_VMEM = 100 << 20


def target_tiles(t: int, hi: int):
    """``(bq, bk, indexer heads a matmul)`` of the target kernel for
    sequences of ``t`` rows under ``hi`` indexer heads, or None where the
    kernel does not take them (``t`` not in whole 128s, or so long that
    128 rows' kept pairs pass TARGET_KEPT)."""
    rows = TARGET_KEPT // (8 * t) // LANES * LANES if t % LANES == 0 else 0
    if not rows:
        return None
    bk = _whole(t, TARGET_BK)
    return (_whole(bk, min(TARGET_BQ, rows)), bk,
            next(n for n in range(min(TARGET_HEADS, hi), 0, -1)
                 if hi % n == 0))


def _lanes(x, n: int):
    """A row's value in 128 lanes -> in ``n``."""
    return jnp.tile(x, (1, n // LANES))


def _lane_sums(x):
    """``(rows, n)`` -> ``(rows, 128)``: the lane tiles added up, a row's
    sum still spread over 128 lanes."""
    return sum(x[:, at:at + LANES] for at in range(0, x.shape[1], LANES))


def _target_step(q_ref, k_ref, lse_ref, mask_ref, qi_ref, ki_ref, w_ref,
                 loss_ref, *rest, t, h, hkv, hi, bq, bk, stack, grads):
    if grads:
        dqi_ref, dki_ref, dw_ref, *rest = rest
    (q_st, lse_st, qi_st, w_st, top_ref, sum_ref, mass_ref, part_ref,
     *kept) = rest
    if grads:
        p_buf, x_buf, dqi_acc, dki_acc, dw_acc = kept
    i, sweep, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_tile = ((i + 1) * bq - 1) // bk
    group, dh = h // hkv, q_ref.shape[1] // h
    di = qi_ref.shape[1] // hi
    rows_a_loss = np.float32(t)
    f32 = jnp.float32
    factor = np.float32(di ** -0.5 * hi ** -0.5)
    nt = (((1,), (1,)), ((), ()))

    def products(at):
        """The float32 products of ``stack`` indexer heads' rows, one head
        under another, with this tile's keys."""
        rows = qi_st[pl.ds(at, stack)].reshape(stack * bq, di)
        return rows, jax.lax.dot_general(rows, ki_ref[...], nt,
                                         preferred_element_type=f32)

    @pl.when((sweep == 0) & (j == 0))
    def _():
        # the row block's operands as the tiles read them: a key/value
        # head's query heads one under another, a row's log-sum-exp and
        # head weight in every lane
        for n in range(h):
            at = (n // group, slice(n % group * bq, (n % group + 1) * bq))
            q_st[at] = q_ref[:, n * dh:(n + 1) * dh]
            lse_st[at] = jnp.broadcast_to(lse_ref[:, n:n + 1], (bq, LANES))
        for n in range(hi):
            qi_st[n] = qi_ref[:, n * di:(n + 1) * di]
            w_st[n] = jnp.broadcast_to(w_ref[:, n:n + 1].astype(f32),
                                       (bq, LANES))
        top_ref[...] = jnp.full_like(top_ref, MASK_VALUE)
        for ref in (sum_ref, mass_ref, part_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when((sweep == 0) & (j <= last_tile))
    def _():
        def heads(n, acc):
            s = jax.lax.dot_general(q_st[n], k_ref[n], nt,
                                    preferred_element_type=f32)
            a = jnp.exp(s - _lanes(lse_st[n], bk))
            return acc + a.reshape(group, bq, bk).sum(axis=0)

        def indexer(n, acc):
            _, z = products(n * stack)
            for u in range(stack):
                acc = acc + jnp.maximum(z[u * bq:(u + 1) * bq], 0.0) \
                    * _lanes(w_st[n * stack + u], bk)
            return acc

        zeros = jnp.zeros((bq, bk), f32)
        keep = mask_ref[...].astype(jnp.int32) != 0
        p = jnp.where(keep, jax.lax.fori_loop(0, hkv, heads, zeros), 0.0) \
            / np.float32(h)
        score = jax.lax.fori_loop(0, hi // stack, indexer, zeros) * factor
        x = jnp.where(keep, score, MASK_VALUE)
        top = jnp.maximum(top_ref[...], x.max(axis=1, keepdims=True))
        # (a row none of whose keys came yet counts its masked pairs, and
        # the first selected score's factor wipes them: the forward's way)
        sum_ref[...] = jnp.exp(top_ref[...] - top) * sum_ref[...] \
            + jnp.exp(x - _lanes(top, bk)).sum(axis=1, keepdims=True)
        top_ref[...] = top
        mass_ref[...] += p.sum(axis=1, keepdims=True)
        part_ref[...] += jnp.where(p > 0.0, p * (jnp.log(p) - score),
                                   0.0).sum(axis=1, keepdims=True)
        if grads:
            p_buf[j] = p
            x_buf[j] = x

    @pl.when((sweep == 0) & (j == last_tile))
    def _():
        # the selected scores' log-sum-exp in top_ref, the row losses' sum
        top_ref[...] += jnp.log(sum_ref[...])
        rows = part_ref[...] + mass_ref[...] * top_ref[...]
        loss_ref[...] = jnp.broadcast_to(
            jnp.sum(rows[:, :1], axis=0, keepdims=True), loss_ref.shape)

    if not grads:
        return

    @pl.when((i == 0) & (sweep == 0) & (j == 0))
    def _():
        dki_acc[...] = jnp.zeros_like(dki_acc)

    @pl.when((sweep == 1) & (j == 0))
    def _():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    @pl.when((sweep == 1) & (j <= last_tile))
    def _():
        # d loss / d score of the tile's pairs, the scores' factor in it
        d = (_lanes(mass_ref[...], bk)
             * jnp.exp(x_buf[j] - _lanes(top_ref[...], bk))
             - p_buf[j]) * factor

        def indexer(n, carry):
            rows, z = products(n * stack)
            dz = []
            for u in range(stack):
                z_u = z[u * bq:(u + 1) * bq]
                dw_acc[n * stack + u] += _lane_sums(
                    jnp.maximum(z_u, 0.0) * d)
                dz.append(jnp.where(
                    z_u > 0.0, d * _lanes(w_st[n * stack + u], bk),
                    0.0).astype(rows.dtype))
            dz = jnp.concatenate(dz, axis=0)
            dqi_acc[pl.ds(n * stack, stack)] += jnp.dot(
                dz, ki_ref[...], preferred_element_type=f32).reshape(
                    stack, bq, di)
            keys = pl.ds(pl.multiple_of(j * bk, bk), bk)
            dki_acc[keys, :] += jax.lax.dot_general(
                dz, rows, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)
            return carry

        jax.lax.fori_loop(0, hi // stack, indexer, 0)

    # the gradients leave as the MEAN row loss's, in their inputs' dtypes:
    # nothing float32 of them is left for XLA to keep until the backward
    # pass scales them (64 MiB a layer of d_qi as the kernel's sums)
    @pl.when((sweep == 1) & (j == last_tile))
    def _():
        for n in range(hi):
            dqi_ref[:, n * di:(n + 1) * di] = (dqi_acc[n] / rows_a_loss).astype(
                dqi_ref.dtype)
        dw_ref[...] = (jnp.concatenate(
            [dw_acc[n].sum(axis=1, keepdims=True) for n in range(hi)],
            axis=1) / rows_a_loss).astype(dw_ref.dtype)

    @pl.when((i == t // bq - 1) & (sweep == 1) & (j == last_tile))
    def _():
        dki_ref[...] = (dki_acc[...] / rows_a_loss).astype(dki_ref.dtype)


def selected_target(qi, ki, w, q, k, lse, mask, grads: bool,
                    interpret: bool = False):
    """The index loss of one sequence and, with ``grads``, its gradient
    by the indexer's three inputs: ``(T, Hi, Di)`` indexer queries, ``(T,
    Di)`` indexer keys, ``(T, Hi)`` head weights, the attend pass's ``(T,
    H, Dh)`` scaled queries, ``(T, Hkv, Dh)`` keys and float32
    log-sum-exp ``(H, T)``, and the selection ``(T, T)`` (bool or int8)
    -> ``(mean row loss, its gradient by (qi, ki, w) in their dtypes or
    None)`` (the module's docstring has the kernel).  ``target_tiles``
    must take ``T``."""
    return _selected_target(
        qi, ki, w, q, k, lse, mask, grads=grads, interpret=interpret,
        tiles=target_tiles(q.shape[0], qi.shape[1]))


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, there so that every layer and module shares one traced kernel
# and one lowered function (a trace and a lowering of it are ~0.9 s of
# host time on the benchmark's machine, and set-up is judged)
@functools.partial(jax.jit, static_argnames=("grads", "interpret", "tiles"))
def _selected_target(qi, ki, w, q, k, lse, mask, *, grads, interpret, tiles):
    t, h, dh = q.shape
    hkv, hi, di = k.shape[1], qi.shape[1], qi.shape[2]
    bq, bk, stack = tiles
    group = h // hkv
    f32 = jnp.float32

    def diagonal(i):
        return ((i + 1) * bq - 1) // bk

    def keys_of(i, j):
        # a step beyond the diagonal keeps the diagonal's blocks
        return jnp.minimum(j, diagonal(i))

    def first_sweep(i, s, j):
        # what only the first sweep reads stays where that sweep ended
        return jnp.where(s == 0, keys_of(i, j), diagonal(i))

    out_specs = [pl.BlockSpec((None, 8, LANES), lambda i, s, j: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((t // bq, 8, LANES), f32)]
    scratch = [pltpu.VMEM((hkv, group * bq, dh), q.dtype),
               pltpu.VMEM((hkv, group * bq, LANES), f32),
               pltpu.VMEM((hi, bq, di), qi.dtype),
               pltpu.VMEM((hi, bq, LANES), f32)] \
        + [pltpu.VMEM((bq, LANES), f32)] * 4
    if grads:
        out_specs += [
            pl.BlockSpec((bq, hi * di), lambda i, s, j: (i, 0)),
            pl.BlockSpec((t, di), lambda i, s, j: (0, 0)),
            pl.BlockSpec((bq, hi), lambda i, s, j: (i, 0))]
        out_shape += [jax.ShapeDtypeStruct((t, hi * di), qi.dtype),
                      jax.ShapeDtypeStruct(ki.shape, ki.dtype),
                      jax.ShapeDtypeStruct(w.shape, w.dtype)]
        scratch += [pltpu.VMEM((t // bk, bq, bk), f32)] * 2 \
            + [pltpu.VMEM((hi, bq, di), f32), pltpu.VMEM((t, di), f32),
               pltpu.VMEM((hi, bq, LANES), f32)]
    # lint: allow(raw-pallas-call) — one lowering of the op's target pass,
    # chosen by platform and held to the plain blocks by tolerance
    # (tests/test_sparse_attention.py, tests/tpu/test_keye_tpu.py)
    loss, *unit = pl.pallas_call(
        functools.partial(_target_step, t=t, h=h, hkv=hkv, hi=hi, bq=bq,
                          bk=bk, stack=stack, grads=grads),
        grid=(t // bq, 2 if grads else 1, t // bk),
        in_specs=[
            pl.BlockSpec((bq, h * dh), lambda i, s, j: (i, 0)),
            pl.BlockSpec((hkv, bk, dh),
                         lambda i, s, j: (0, first_sweep(i, s, j), 0)),
            pl.BlockSpec((bq, h), lambda i, s, j: (i, 0)),
            pl.BlockSpec((bq, bk),
                         lambda i, s, j: (i, first_sweep(i, s, j))),
            pl.BlockSpec((bq, hi * di), lambda i, s, j: (i, 0)),
            pl.BlockSpec((bk, di), lambda i, s, j: (keys_of(i, j), 0)),
            pl.BlockSpec((bq, hi), lambda i, s, j: (i, 0))],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=TARGET_VMEM),
        interpret=interpret,
        name="dsa_target_grads" if grads else "dsa_target_loss",
    )(q.reshape(t, h * dh), k.transpose(1, 0, 2), lse.T,
      mask.astype(jnp.int8), qi.reshape(t, hi * di), ki, w)
    loss = jnp.sum(loss[:, 0, 0]) / t
    if not grads:
        return loss, None
    return loss, (unit[0].reshape(qi.shape), unit[1], unit[2])
