"""Capacity-bucketed dispatch/combine — THE expert-buffer scatter choke.

Pure-jnp primitives shared by training (inside the fused step's traced
graph) and serving (inside the decode symbol).  All writes into an
expert buffer in this tree go through ``dispatch`` here or the embed
engine's ``embed.sparse`` scatters — enforced by the linter's
``moe-raw-scatter`` rule, because the sentinel-fold bug class (PR 12)
must have exactly one implementation per subsystem.

Sharding: these are plain gathers/scatters with no mesh plumbing.  When
the expert tensors are sharded over an ``ep``/``tp`` axis (layer.py's
``expert_axis=``) and tokens are dp-sharded, GSPMD reshards the buffer
between the token layout and the expert layout — the all-to-all family
in ``multichip_report()``'s collective census.

The drop-free layout (``capacity_factor <= 0``) has no buffer to
scatter into: ``sort_rows`` gathers the ``T*k`` (token, choice) rows in
expert order, ``grouped_matmul`` multiplies each expert's run of rows by
that expert's matrix, ``combine_sorted`` gathers them back.
``grouped_matmul`` is the one primitive here that is not pure jnp
everywhere: a program lowered for a TPU, over one device, with bfloat16
or float32 operands, rows a multiple of 256 and ``K``, ``N`` multiples
of 128 runs the tiled Pallas kernel pair of ``moe/gmm.py`` (the tile
table is ``gmm.tiles_for``, the tile -> group map ``group_tiles``, made
once a layer); every other platform, mesh, dtype and shape runs
``lax.ragged_dot``.  Forward AND
backward are gathers through the plan's permutation and its inverse
(each a ``custom_vjp``): the autodiff transpose of a gather is a
scatter-add, which a TPU executes row by row.  Each is in bounds by
construction and says so (``_rows``), and the plan's ``order`` and
``slot`` are all the backward passes need: no permutation is found
again.  (A rank's window sums on the sorted side: ``held_sum``, below.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..trace import scopes as _scopes
from . import gmm as _gmm

__all__ = ["dispatch", "combine", "sort_rows", "combine_sorted",
           "grouped_matmul", "group_tiles", "held_rows_bound"]

# a rank's sorted layout holds this many times its balanced share of the
# rows before it falls back to all T*k (``held_rows_bound``; PERF.md, PR 40)
HELD_ROWS_SLACK = 4
# ... and is bounded at all only where the bound saves this many rows: the
# device's choice between the bound and all rows is a conditional, at which
# the step's fusions and prefetches stop (~1 ms a layer on a v5e, what the
# layer's passes over about this many rows cost; PERF.md, PR 40)
BOUND_WORTH_ROWS = 16384


def dispatch(x, slot, num_experts: int, capacity: int):
    """Scatter ``(T, D)`` tokens into the ``(E, C, D)`` expert buffer.

    ``slot`` is the routing plan's ``(T, k)`` flat bucket index in
    ``[0, E*C]``.  Slots below the sentinel are unique by construction
    (one position-in-expert per accepted choice), so this is a pure
    ``set`` scatter; the sentinel ``E*C`` is out of range and
    ``mode="drop"`` discards it — a dropped token touches no expert.
    """
    E, C = int(num_experts), int(capacity)
    T, D = x.shape
    k = slot.shape[1]

    buf = jnp.zeros((E * C, D), dtype=x.dtype)
    rows = jnp.broadcast_to(x[:, None, :], (T, k, D)).reshape(T * k, D)
    buf = buf.at[slot.reshape(T * k)].set(rows, mode="drop",
                                          unique_indices=True)
    return buf.reshape(E, C, D)


def combine(expert_out, slot, weight, num_experts: int, capacity: int):
    """Gather ``(E, C, Dout)`` expert outputs back to ``(T, Dout)``.

    The gather clips the sentinel slot to the last real row, then the
    explicit ``slot < E*C`` mask zeroes it — folded tokens read zero by
    construction even if a caller hands in non-zero weights, keeping the
    read side of the sentinel discipline independent of the write side.
    """
    E, C = int(num_experts), int(capacity)
    n = E * C
    T, k = slot.shape

    flat = expert_out.reshape(n, expert_out.shape[-1])
    rows = jnp.take(flat, jnp.minimum(slot, n - 1).reshape(T * k),
                    axis=0).reshape(T, k, -1)
    live = (slot < n)[..., None].astype(flat.dtype)
    w = weight[..., None].astype(flat.dtype)
    return (rows * live * w).sum(axis=1)


# -- the drop-free (sorted) layout -------------------------------------------

def _rows(x, index):
    """Rows ``index`` of ``x``.  Every index of this layout is a value
    of the plan's permutation (``order``, ``slot``), in range by
    construction, and the gather says so (a clip that moves nothing):
    ``take``'s default ``mode="fill"`` would select each gathered row
    against NaN, a whole pass over a ``(T*k, D)`` array that guards
    nothing."""
    return jnp.take(x, index, axis=0, mode="clip")


def _moved(values, place):
    """``out[place[i]] = values[i]`` for ``T*k`` scalars, ``place`` a
    permutation: a key-value sort.  XLA:TPU gathers scalars one at a
    time (1.05 ms for 131 072 on a v5e) and sorts as many pairs in
    0.13 ms (PERF.md, PR 36)."""
    return jax.lax.sort((place, values), num_keys=1)[1]


def held_rows_bound(rows: int, num_experts: int, experts_held: int) -> int:
    """The static row bound ``R`` of one expert-parallel rank's sorted
    layout: ``rows`` = ``T*k`` choices over ``num_experts`` experts of
    which the rank holds ``experts_held`` give it ``B = rows *
    experts_held / num_experts`` rows under a balanced router, and
    ``R`` is ``HELD_ROWS_SLACK * B`` rounded up to whole row tiles of
    the grouped-matmul kernels.  Where that saves fewer than
    ``BOUND_WORTH_ROWS`` of the ``rows`` it is ``rows``: no bound, and the
    op's one window is every row, with no conditional.  One rule from what
    the code sees: the op (``_moe_share_ffn``) sizes its passes by it, and
    ``FusedTrainStep.note_outputs`` reports it as ``bound`` of the
    ``moe:load`` sample.  A rank that holds every expert has ``rows``."""
    rows = int(rows)
    if not experts_held or experts_held >= num_experts:
        return rows
    tile = _gmm.ROW_TILE
    balanced = rows * int(experts_held) / float(num_experts)
    bound = tile * int(math.ceil(HELD_ROWS_SLACK * balanced / tile))
    return bound if rows - bound >= BOUND_WORTH_ROWS else rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sort_rows(x, order, slot, held, lo):
    k = slot.shape[1]
    return _rows(x, order // k)


def _sort_rows_fwd(x, order, slot, held, lo):
    return _sort_rows(x, order, slot, held, lo), (order, slot, held)


def _sort_rows_bwd(lo, res, g):
    order, slot, held = res
    T, k = slot.shape
    # token t's gradient: the sum of its k sorted rows' gradients
    if held is None:
        dx = _rows(g, slot.reshape(T * k)).reshape(T, k, -1).sum(axis=1)
    else:
        # ``g`` is a window of the sorted rows, sorted rows lo .. lo + n -
        # 1: the held ones of it, summed on the sorted side where
        # ``held_sum``'s kernel runs
        dx = held_sum(g, order, slot, held, lo=lo)
    return dx.astype(g.dtype), None, None, None


_sort_rows.defvjp(_sort_rows_fwd, _sort_rows_bwd)


def sort_rows(x, order, slot, held=None, window=None):
    """``(T, D)`` tokens -> the ``(T*k, D)`` rows of a ``SortedPlan``:
    row ``r`` is token ``order[r] // k``.  A rank's share gives ``held``,
    the rows each of its experts got (the plan's first groups; a scalar:
    their sum), and ``window = (lo, n)``: sorted rows ``lo .. lo + n - 1``
    only, and the choices whose row is not a held one of them add
    nothing to the tokens' gradient, which is ``held_sum`` of the rows'
    (below: over the window's held rows where its kernel runs, through
    ``slot`` elsewhere)."""
    lo, n = window if window is not None else (0, order.shape[0])
    return _sort_rows(x, order[lo:lo + n], slot, held, int(lo))


def _picked_sum(rows, slot, weight):
    T, k = slot.shape
    picked = _rows(rows, slot.reshape(T * k)).reshape(T, k, -1)
    return picked, (picked * weight[..., None].astype(rows.dtype)).sum(axis=1)


@jax.custom_vjp
def _combine_sorted(rows, order, slot, weight):
    return _combine_sorted_fwd(rows, order, slot, weight)[0]


def _combine_sorted_fwd(rows, order, slot, weight):
    # the gathered rows are what the backward pass needs of ``rows``:
    # saved, they are not gathered a second time
    picked, out = _picked_sum(rows, slot, weight)
    return out, (picked, order, slot, weight)


def _combine_sorted_bwd(res, g):
    picked, order, slot, weight = res
    T, k = slot.shape
    w_sorted = _moved(weight.reshape(T * k), slot.reshape(T * k))
    d_rows = _rows(g, order // k) * w_sorted[:, None].astype(g.dtype)
    d_weight = (picked.astype(jnp.float32)
                * g[:, None, :].astype(jnp.float32)).sum(axis=-1)
    return d_rows.astype(picked.dtype), None, None, \
        d_weight.astype(weight.dtype)


_combine_sorted.defvjp(_combine_sorted_fwd, _combine_sorted_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine_held(rows, order, slot, weight, held, lo):
    return _combine_held_fwd(rows, order, slot, weight, held, lo)[0]


def _combine_held_fwd(rows, order, slot, weight, held, lo):
    # sorted rows lo .. lo + n - 1, the held ones of them: a choice
    # outside them weighs nothing (``held_sum``, below: on the sorted
    # side where its kernel runs).  Saved: the rows themselves, n of
    # them, and not the T*k gathered ones
    out = held_sum(rows, order[lo:lo + rows.shape[0]], slot, held, weight,
                   lo)
    return out, (rows, order, slot, weight)


def _combine_held_bwd(lo, res, g):
    rows, order, slot, weight = res
    T, k = slot.shape
    hi = lo + rows.shape[0]
    w_sorted = _moved(weight.reshape(T * k), slot.reshape(T * k))[lo:hi]
    # both gradients on the sorted side, over the window's rows: a
    # weight's is its row's product with the token's cotangent, brought
    # to (token, choice) order by the key-value sort; behind the held
    # rows ``rows`` is zero, outside the window the padding: exact zeros
    g_sorted = _rows(g, order[lo:hi] // k)
    d_rows = g_sorted * w_sorted[:, None].astype(g.dtype)
    d_weight = (rows.astype(jnp.float32)
                * g_sorted.astype(jnp.float32)).sum(axis=-1)
    d_weight = _moved(jnp.pad(d_weight, (lo, T * k - hi)), order)
    return d_rows.astype(rows.dtype), None, None, \
        d_weight.reshape(T, k).astype(weight.dtype), None


_combine_held.defvjp(_combine_held_fwd, _combine_held_bwd)


def combine_sorted(rows, order, slot, weight, share_from=None, held=None):
    """``(T*k, O)`` expert outputs in sorted order -> ``(T, O)``: each
    token's k rows, weighted by its gate values and summed.  ``order``
    and ``slot`` are the plan's permutation and its inverse: the forward
    pass gathers through ``slot``, the backward pass through ``order``,
    and no permutation is found again (the backward pass brings the
    ``T*k`` weights, scalars, to sorted order by one key-value sort).

    ``share_from = lo``: a rank's share (``held`` as ``sort_rows``'; None:
    every row), whose held rows come first and whose other rows are zero
    with weight 0, and ``rows`` are sorted rows ``lo .. lo + n - 1`` of
    the ``T*k``: the forward pass is ``held_sum`` (below) of the held
    ones.  The backward pass saves ``rows`` (not the ``T*k`` gathered
    ones) and forms both gradients over the ``n`` sorted rows, so a
    choice outside them, and an absent one, gets 0 for its weight."""
    if share_from is not None:
        return _combine_held(rows, order, slot, weight, held, int(share_from))
    return _combine_sorted(rows, order, slot, weight)


# what a layer hands ``grouped_matmul`` as its group sizes: the sizes with
# the kernels' tile -> group map where they can run, made once a layer
group_tiles = _gmm.group_tiles


def grouped_matmul(rows, w, group_sizes):
    """``rows`` (M, K) in expert order x stacked ``w`` (E, K, N) -> (M, N):
    rows ``sum(group_sizes[:e]) .. + group_sizes[e]`` meet ``w[e]``.
    Exactly M rows of work whatever the routing: an expert that got no
    token costs nothing, one that got them all takes them all.  Rows
    behind the last group are left unwritten on a TPU.

    Two lowerings, chosen from what the code can see (``moe/gmm.py``):
    a program lowered for a TPU, over one device, with bfloat16 or
    float32 operands of ``gmm.tiles_for``'s shapes runs the tiled Pallas
    kernels ``ragged-dot-gmm`` / ``ragged-dot-tgmm`` with their own vjp;
    every other platform, mesh and shape runs ``lax.ragged_dot``.
    ``group_sizes`` may be ``group_tiles``' result."""
    return _gmm.tiled_matmul(rows, w, group_sizes)


# where ``lax.ragged_dot`` stays, XLA:TPU writes its kernels itself and
# names them anew ("ragged-dot-none"): the scope of their one caller,
# ``_moe_expert_ffn`` (this repo's kernels keep the caller's path)
_scopes.adopt("ragged-dot", "moe_experts")


# -- a rank's window brought back to its tokens -------------------------------
#
# One expert-parallel rank's share works on a *window* of the sorted rows
# (``sort_rows(window=)``, ``combine_sorted(share_from=)``), of which only the
# rank's held rows count.  The two passes that bring a window back to the
# tokens (the combine's forward, ``sort_rows``' backward) are one function,
# ``held_sum``, with two forms: through ``slot``, ``T*k`` rows gathered for
# the few that count (every platform but a TPU, float32), and on the sorted
# side, where the repo's ``token-sum`` kernel reads the held rows where they
# lie and moves none (bfloat16 on a TPU, ``moe/gmm.py``).  It stands at the
# end of the file so that no line above it moves: the grouped-matmul kernels'
# Mosaic payloads name ``grouped_matmul``'s line and are part of JAX's cache
# key (a cell that runs none of this keeps its compiled programs).

def held_sum(rows, order, slot, held, weight=None, lo=0, interpret=False):
    """For every token the sum of the window's held rows that are its
    choices, ``(T, D)``: ``out[t] = sum_j [0 <= slot[t, j] - lo < h]
    weight[t, j] * rows[slot[t, j] - lo]``.  ``rows`` ``(n, D)`` are sorted
    rows ``lo .. lo + n - 1`` of the plan, ``order`` ``(n,)`` the plan's
    over them, ``held`` the rows each held expert got (the plan's first
    groups; a scalar: their sum; None: every row of the window is held)
    and ``h`` what of them lies in the window; ``weight`` None weighs
    every choice 1.  It is the combine's forward pass and the backward
    pass of ``sort_rows`` over a rank's window.

    Two forms of the one sum, chosen as ``grouped_matmul``'s lowerings
    are (``gmm.token_sum_tiles`` from dtype and static shapes when the op
    is traced, the platform when the program is lowered):

    * **token side** (every platform but a TPU, a program over more than
      one device, float32, a ``T`` or ``n`` that is no whole number of
      256-row tiles, a ``D`` that is no whole number of lanes, a scalar
      ``held``): gather all ``T*k`` choices' rows through ``slot``, weigh
      the ones outside the held rows by 0, sum over ``k``.  ``T*k`` rows
      moved for the ``h`` that count: 2.2-2.5 ms a call in the SDAR and
      SmallThinker cells, 84 % of it for a weight of 0.
    * **sorted side** (bfloat16 on a TPU): ``gmm.token_sums``.  A held
      expert's rows are in token order, so its rows of one tile of 256
      tokens are consecutive, and the kernel adds each such run to its
      token tile by a product with the matrix of the rows' weights at
      their places in the tile: exact products, float32 sums, one
      rounding, zeros for a token tile that holds no row, no row behind
      the held ones read, no row moved.  The weights come to sorted order
      by ``_moved``, rounded to the rows' dtype as the token side rounds
      them.  On the chip the two forms agree bit for bit (XLA:TPU keeps
      the token side's products in float32 too).  PERF.md, PR 49."""
    from ..ops.pallas_kernels import _kernel_on_tpu
    from ..parallel.mesh import traced_devices
    T, k = slot.shape
    n = rows.shape[0]
    at = slot - lo
    held = jnp.asarray(lo + n if held is None else held)

    def token_side(rows, order, at, held, *weight):
        inside = (at >= 0) & (at < jnp.minimum(held.sum() - lo, n))
        picked = _rows(rows, at.reshape(T * k)).reshape(T, k, -1)
        if weight:
            # a choice outside the held rows reads a clipped row, finite
            # (behind them the experts' output is zero), and weighs 0
            w = jnp.where(inside, weight[0], 0.0)
            return (picked * w[..., None].astype(rows.dtype)).sum(axis=1)
        # a cotangent's rows: whatever lies outside counts for nothing
        return jnp.where(inside[..., None], picked,
                         jnp.zeros((), rows.dtype)).sum(axis=1)

    def sorted_side(rows, order, at, held, *weight):
        w_sorted = _moved(weight[0].reshape(T * k),
                          at.reshape(T * k))[lo:lo + n] if weight else None
        return _gmm.token_sums(rows, order // k, held, lo, T, w_sorted,
                               interpret=interpret)

    args = (rows, order, at, held) + (() if weight is None else (weight,))
    if held.ndim == 0 or traced_devices() > 1 or not _gmm.token_sum_tiles(
            n, rows.shape[1], T, rows.dtype):
        return token_side(*args)
    return _kernel_on_tpu(sorted_side, token_side, interpret, *args)
