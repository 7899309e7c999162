"""The grouped matmul as a tiled Pallas kernel pair (TPU).

``lax.ragged_dot`` on a TPU is a kernel XLA writes itself, with tiles
nobody chooses: OLMoE's nine products ran at 54.8 % of their roofline
(PERF.md, PR 37).  Here the same three products are two kernels of this
repo, after ``jax.experimental.pallas.ops.tpu.megablox``:

* ``ragged-dot-gmm``: ``(M, K)`` rows in expert order x stacked
  ``(E, K, N)`` -> ``(M, N)`` (``gmm``), and with the stacked tensor
  read transposed, ``(M, N) x (E, K, N)^T -> (M, K)``, the backward-data
  product (``gmm_t``).  Grid ``(n tiles, row-tile visits, k tiles)``.
* ``ragged-dot-tgmm``: ``(M, K)^T x (M, N) -> (E, K, N)``, the
  backward-weight product.  Grid ``(n tiles, k tiles, row-tile visits)``;
  an expert that got no row writes zeros.

Both walk one list of *visits*, built from ``group_sizes`` once a layer
(``group_tiles``) and prefetched as scalars: visit ``v`` is row tile ``tile_of[v]`` met with group
``group_of[v]``.  A tile that straddles two groups is visited once a
group, its other rows masked; a tile that lies whole inside its group
(most do) is neither masked nor read back.  No tile behind the last
group is visited: rows that belong to no group cost nothing and are
left unwritten, as XLA:TPU's ``ragged_dot`` leaves them
(``_moe_expert_ffn`` selects them to zero).  Products accumulate in
float32 in VMEM; outputs take the dtypes ``ragged_dot`` and its
autodiff give.

The kernels' names begin ``ragged-dot`` on purpose: they are the HLO
instructions' names (``ragged-dot-gmm.3``), and the benchmark's
``moe_gmm_roofline`` sums the operations so named, whoever wrote them.

Which path runs is chosen from what the code can see, no option:
``tiles_for`` (dtype and static shapes) and ``parallel.mesh.
traced_devices`` (a program over more than one device keeps
``ragged_dot``: a Pallas call is one device's, and the kernel under
``ep`` collectives has never run) when the op is traced, the platform
when the program is lowered (``_kernel_on_tpu``).  The forward and the
backward pass are two module-level ``jax.jit`` functions free of
per-call objects (``_forward``, ``_backward``): a process traces each
once a signature, choice and kernels with it, and a program holds each
once for any number of layers.  The counter ``moe:gmm_trace`` fires
where a kernel is traced, ``moe:gmm_lowering`` once a traced product with
the choice made for it (``tiled_matmul``).  A third kernel,
``token-sum``, and a weight read through its transpose: the file's end.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..ops.pallas_kernels import _kernel_on_tpu, pl
from ..parallel.mesh import traced_devices

__all__ = ["GroupTiles", "group_tiles", "tiles_for", "tiled_matmul",
           "ragged_matmul", "token_sums", "token_sum_tiles"]

ROW_TILE = 256
# a v5e core has 128 MiB of VMEM and gives a kernel 16 unasked; the
# widest tiles below hold 40 MiB (tgmm at 256 x 2048 x 2048 in bfloat16)
VMEM_LIMIT = 64 * 1024 * 1024


class GroupTiles(NamedTuple):
    """One layer's row-tile visits, shared by its nine products."""
    sizes: jax.Array      # (E,) int32: rows a group
    offsets: jax.Array    # (E + 1,) int32: group g is rows offsets[g:g+2]
    group_of: jax.Array   # (M / tm + E - 1,) int32: visit -> group
    tile_of: jax.Array    # (M / tm + E - 1,) int32: visit -> row tile
    visits: jax.Array     # (1,) int32: how many of them to make


def ragged_matmul(rows, w, group_sizes):
    """``lax.ragged_dot``: the path of every platform but a TPU, and of
    every shape ``tiles_for`` refuses."""
    # bfloat16 products are exact at any precision, and XLA:TPU's ragged
    # dot refuses bfloat16 operands under a "highest" default ("Bad lhs
    # type", jax 0.9.0): name the precision they run at anyway
    return lax.ragged_dot(rows, w, group_sizes.astype(jnp.int32),
                          precision=_precision(rows.dtype))


def _precision(dtype):
    return lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _divisor(x: int, most: int) -> int:
    """The largest multiple of 128 that divides ``x`` and is at most
    ``most``; 0 where there is none."""
    return next((t for t in range(min(x, most) // 128 * 128, 0, -128)
                 if x % t == 0), 0)


def _blocks(x: int, most: int, size: int):
    """The tiles ``tiles_for`` tries along a ``K`` or ``N`` of ``x``
    elements of ``size`` bytes: ``_divisor``'s alone where that is one or
    two steps (every shape tuned so far), and in float32, which only
    parity tests run on a chip and which keeps the tiles it had; else
    all of ``x`` as ONE block, which Pallas takes for any multiple of 64
    lanes (a block equal to the array's dimension is legal), and
    ``_divisor``'s three steps or more behind it."""
    t = _divisor(x, most)
    if t and (x // t <= 2 or size > 2):
        return [t]
    return [x] * (x % 64 == 0) + [t] * bool(t)


def _tgmm_vmem_bytes(tk: int, tn: int, size: int) -> int:
    """What ``tgmm``, the fuller kernel, holds in VMEM at tiles
    ``(ROW_TILE, tk, tn)`` of ``size``-byte elements: both inputs' row
    tiles and the output's ``(tk, tn)`` block twice (Pallas
    double-buffers), and the float32 accumulator."""
    return 2 * ROW_TILE * (tk + tn) * size + tk * tn * (2 * size + 4)


def tiles_for(m: int, k: int, n: int, e: int, dtype
              ) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for ``(M, K)`` rows against ``E`` matrices
    ``(K, N)`` (for ``tgmm``: the ``(K, N)`` of its output), or None
    where the kernels do not run and ``ragged_dot`` stays: rows that are
    no whole number of row tiles, a ``K`` or ``N`` that is no whole
    number of 64-lane half tiles or whose blocks no VMEM holds, any dtype
    but bfloat16 and float32.

    ``tm`` depends on nothing but ``M``, so that one ``GroupTiles``
    serves every product of a layer.  The chip numbers behind each
    choice (a v5e, bfloat16, OLMoE's ``M`` = 131 072 rows over 64 groups
    whose fullest is 3.5 x the mean, one product, ms; its roofline is
    2.79; my chip runs, PR 38; ``ragged_dot`` takes 5.07 / 6.31 / 5.31
    for gate's forward, backward-data and backward-weight product):

    * **tm = 256.**  A tile that a group boundary crosses is computed
      once a group, so 63 boundaries cost 63 row tiles more: 25 % of 256
      tiles of 512, 12 % of 512 tiles of 256.  ``gmm`` at ``K, N`` =
      2048, 1024 with ``tk = K``: 3.57 at 128, **3.49 at 256**, 3.73 at
      512, 4.46 at 1024; ``tgmm``: 3.75, **3.67**, 3.83, 4.47; the down
      projection's (1024, 2048): 3.73, **3.58**, 3.81 and 3.74,
      **3.66**, 3.84.  At 128 the grid's 1087 steps cost more than the
      boundaries save.
    * **tk = K and tn = N up to 2048** (bfloat16; 1024 in float32, whose
      tiles are twice the bytes).  With one k step the block index of a
      group's weights does not change between its row tiles and Pallas
      does not fetch them again; with two it alternates and every visit
      fetches all of ``K x N``: ``gmm`` at 256 x 1024 x 1024 takes 4.74
      for 3.49 (7.30 at 128 rows), ``gmm_t`` 3.75 for 3.59, ``tgmm``
      3.89 for 3.67.  Tiles of 256 x 2048 x 2048 hold 28 MiB of VMEM in
      ``gmm`` and 40 in ``tgmm``: hence ``VMEM_LIMIT``.  The library's
      kernels at 512 x 1024 x 1024 (the most Mosaic's 16 MiB allow
      them): 3.95 / 3.73 / 4.00.
    * **A dimension ``_divisor`` cannot cut, or cuts in three steps or
      more, is ONE block** (``_blocks``; PR 71, Nemotron-H's plain
      experts: ``N`` = 1856 = 14.5 x 128 had no tile at all and fell to
      ``ragged_dot``, ``K`` = 2688 = 3 x 896 would fetch a group's
      weights again at every visit) where ``tgmm``'s blocks, the fuller
      kernel's, stay under ``VMEM_LIMIT`` less an eighth
      (``_tgmm_vmem_bytes``; bfloat16 2688 x 1856: 42.5 MiB).  Of the
      pairs that fit, the one with the fewest grid steps along ``K`` and
      ``N`` together: 2688 x 3712, which fits in no one block, is 896 x
      3712 (3 steps) and not 2688 x 128 (29).  On the chip (my chip runs,
      PR 71; 8 groups of 150-230 rows in a window of 6144, ms): the up
      projection's three products 1.51 (forward alone 0.60) against
      ``ragged_dot``'s 4.59 (1.60), the down projection's 0.90 (0.31)
      against 4.42 (1.34), outputs and both gradients ``ragged_dot``'s
      bit for bit (``tests/tpu/test_nemotron_h_tpu.py``).  The state
      holds such a weight with its OTHER dimension on the lanes (24
      transposing copies a step, 12.2 ms), so since PR 72 the pair reads
      it through its transpose: ``reads_turned``, the file's end.
    * ``E`` enters no choice yet: one expert-parallel rank's share
      (Kimi: 1 024 of 32 768 rows in 8 groups; GLM: 2 800 of 16 384) is
      a launch and the weights' fetch whatever ``tm``, as
      ``ragged_dot``'s is, and both cells gain with OLMoE's tiles.
    """
    if dtype not in (jnp.bfloat16, jnp.float32) or m % ROW_TILE:
        return None
    most, size = (2048, 2) if dtype == jnp.bfloat16 else (1024, 4)
    fits = [(tk, tn) for tk in _blocks(k, most, size)
            for tn in _blocks(n, most, size)
            if _tgmm_vmem_bytes(tk, tn, size) <= VMEM_LIMIT // 8 * 7]
    if not fits:
        return None
    return (ROW_TILE,) + min(fits, key=lambda t: (k // t[0]) * (n // t[1]))


# lint: allow(raw-jit) — as _forward below: a jit inside the step program,
# so that a model's routed layers share one trace and one lowered function
@functools.partial(jax.jit, static_argnames=("m", "tm"))
def _visits(sizes, *, m, tm):
    """``GroupTiles`` of ``m`` rows in tiles of ``tm``: group ``g`` visits
    the tiles its rows ``offsets[g] .. offsets[g + 1] - 1`` touch, in
    order; an empty group visits the tile its offset lies in, once.  So a
    tile's visits are consecutive and so are a group's, and there are at
    most ``m / tm + E - 1``.  (The library's ``make_group_metadata`` makes
    the same list of two ``repeat``s and a histogram: 0.2 s of Python a
    trace, twice a run, for what is two cumulative sums and a count.)"""
    e, tiles_m = sizes.shape[0], m // tm
    ends = jnp.cumsum(sizes)
    first = jnp.minimum((ends - sizes) // tm, tiles_m - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - first, 0) + 1
    upto = jnp.cumsum(count)
    v = jnp.arange(tiles_m + e - 1, dtype=jnp.int32)
    # behind the last visit: the last group, never read
    group_of = jnp.minimum((v[:, None] >= upto[None, :]).sum(axis=1), e - 1)
    tile_of = jnp.minimum(
        (first - upto + count)[group_of] + v, tiles_m - 1)
    return GroupTiles(
        sizes, jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
        group_of.astype(jnp.int32), tile_of.astype(jnp.int32), upto[-1:])


def group_tiles(group_sizes, m: int):
    """What a layer hands ``tiled_matmul`` as its group sizes: the visits
    of ``m`` rows in tiles of ``ROW_TILE``, made once for all nine
    products of the layer, or the sizes themselves where no kernel would
    read them: ``m`` is no whole number of tiles, or the program being
    traced spans more than one device.  Empty groups get a visit each
    (``tgmm`` has to write their zeros; ``gmm`` stores nothing there), so
    one list serves both kernels.  The sizes may sum to fewer than ``m``
    rows."""
    sizes = group_sizes.astype(jnp.int32)
    if m % ROW_TILE or traced_devices() > 1:
        return sizes
    return _visits(sizes, m=m, tm=ROW_TILE)


def _row_mask(shape, row0, start, end):
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _gmm_kernel(offsets, group_of, tile_of, lhs, rhs, out, acc, *,
                tm, tiles_k, transposed):
    v, k_i = pl.program_id(1), pl.program_id(2)
    product = lax.dot_general(
        lhs[...], rhs[...],
        (((1,), (1 if transposed else 0,)), ((), ())),
        precision=_precision(lhs.dtype), preferred_element_type=jnp.float32)

    def store(total):
        g = group_of[v]
        start, end, row0 = offsets[g], offsets[g + 1], tile_of[v] * tm
        whole = (start <= row0) & (end >= row0 + tm)

        @pl.when(whole)
        def _():
            out[...] = total.astype(out.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            # the tile's other rows: another group's, stored by its own
            # visit of this tile (visits of a tile are consecutive, the
            # block stays in VMEM), or no group's, left as they are
            out[...] = jnp.where(
                _row_mask(out.shape, row0, start, end), total,
                out[...].astype(jnp.float32)).astype(out.dtype)

    if tiles_k == 1:
        store(product)
        return

    @pl.when(k_i == 0)
    def _():
        acc[...] = product

    @pl.when(k_i > 0)
    def _():
        acc[...] += product

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc[...])


def _tgmm_kernel(offsets, group_of, tile_of, lhs, rhs, out, acc, *, tm):
    v, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group_of[v]
    start, end, row0 = offsets[g], offsets[g + 1], tile_of[v] * tm
    whole = (start <= row0) & (end >= row0 + tm)

    @pl.when((v == 0) | (group_of[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    def add(rows, grads):
        acc[...] += lax.dot_general(
            rows, grads, (((0,), (0,)), ((), ())),
            precision=_precision(rows.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        add(lhs[...], rhs[...])

    @pl.when(jnp.logical_not(whole) & (end > start))
    def _():
        def own(ref):
            # through float32: a v5e selects no bfloat16
            return jnp.where(_row_mask(ref.shape, row0, start, end),
                             ref[...].astype(jnp.float32),
                             0.0).astype(ref.dtype)
        add(own(lhs), own(rhs))

    @pl.when((v == last) | (group_of[jnp.minimum(v + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _note_trace(which, lhs, rhs, tiles):
    trace.counter(
        "moe:gmm_trace", cat="ops",
        track="%s %s%s x %s" % (which, lhs.dtype.name, list(lhs.shape),
                                list(rhs.shape)),
        gmm=int(which == "gmm"), gmm_t=int(which == "gmm_t"),
        tgmm=int(which == "tgmm"), tsum=int(which == "tsum"),
        **dict(zip(("tm", "tk", "tn"), tiles)))


def _gmm(lhs, rhs, offsets, group_of, tile_of, visits, *, tiles,
         transposed, interpret):
    """``ragged-dot-gmm``: ``lhs`` ``(M, K)`` x ``rhs`` ``(E, K, N)``,
    or ``(E, N, K)`` read transposed, -> ``(M, N)`` in ``lhs``'s dtype."""
    from jax.experimental.pallas import tpu as pltpu
    m, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    tm, tk, tn = tiles
    _note_trace("gmm_t" if transposed else "gmm", lhs, rhs, tiles)
    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, tn, tk), lambda n_i, v, k_i, o, g, t: (g[v], n_i, k_i))
    else:
        rhs_spec = pl.BlockSpec(
            (None, tk, tn), lambda n_i, v, k_i, o, g, t: (g[v], k_i, n_i))
    # lint: allow(raw-pallas-call) — one lowering of grouped_matmul, a pair
    # with its own vjp chosen by platform and held to lax.ragged_dot by
    # tolerance (tests/test_moe_gmm.py, tests/tpu): ops/pallas_kernels
    # holds forward kernels behind the kernel search's bitwise gate
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=k // tk,
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n_i, v, k_i, o, g, t: (t[v], k_i)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, o, g, t: (t[v], n_i)),
            grid=(n // tn, visits[0], k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=_compiler_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * (n // tn) + m * n + k * n * group_of.shape[0])),
        interpret=interpret, name="ragged-dot-gmm",
    )(offsets, group_of, tile_of, lhs, rhs)


def _tgmm(lhs, rhs, offsets, group_of, tile_of, visits, *, groups, dtype,
          tiles, interpret):
    """``ragged-dot-tgmm``: ``lhs`` ``(M, K)`` transposed x ``rhs``
    ``(M, N)`` a group -> ``(groups, K, N)`` in ``dtype``."""
    from jax.experimental.pallas import tpu as pltpu
    (m, k), n = lhs.shape, rhs.shape[1]
    tm, tk, tn = tiles
    _note_trace("tgmm", lhs, rhs, tiles)
    # lint: allow(raw-pallas-call) — as _gmm
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n_i, k_i, v, o, g, t: (t[v], k_i)),
                pl.BlockSpec((tm, tn),
                             lambda n_i, k_i, v, o, g, t: (t[v], n_i))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda n_i, k_i, v, o, g, t: (g[v], k_i, n_i)),
            grid=(n // tn, k // tk, visits[0]),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_compiler_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * (n // tn) + m * n * (k // tk))
            + jnp.dtype(dtype).itemsize * groups * k * n),
        interpret=interpret, name="ragged-dot-tgmm",
    )(offsets, group_of, tile_of, lhs, rhs)


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, there so that every call site shares one traced jaxpr and one
# lowered function (nine call sites a routed layer, two modules a
# process); the step that holds it goes through the cache
@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(rows, w, tiles: GroupTiles, *, interpret):
    """``rows`` x ``w`` a group.  Module-level and free of per-call
    objects: traced once a signature, the choice by platform with it."""
    e, k, n = w.shape
    shape = tiles_for(rows.shape[0], k, n, e, rows.dtype)

    def kernels(rows, w, sizes, *visits):
        return _gmm(rows, w, *visits, tiles=shape, transposed=False,
                    interpret=interpret)

    def plain(rows, w, sizes, *visits):
        return ragged_matmul(rows, w, sizes)

    return _kernel_on_tpu(kernels, plain, interpret, rows, w, *tiles)


# lint: allow(raw-jit) — as _forward
@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(g, rows, w, tiles: GroupTiles, *, interpret):
    """The cotangents of ``_forward``'s rows and weights for its
    output's.  Traced once a signature."""
    e, k, n = w.shape
    m = rows.shape[0]

    def kernels(g, rows, w, sizes, *visits):
        d_rows = _gmm(g, w, *visits, tiles=tiles_for(m, n, k, e, g.dtype),
                      transposed=True, interpret=interpret)
        d_w = _tgmm(rows, g, *visits, groups=e, dtype=w.dtype,
                    tiles=tiles_for(m, k, n, e, rows.dtype),
                    interpret=interpret)
        return d_rows, d_w

    def plain(g, rows, w, sizes, *visits):
        return jax.vjp(lambda rows, w: ragged_matmul(rows, w, sizes),
                       rows, w)[1](g)

    return _kernel_on_tpu(kernels, plain, interpret, g, rows, w, *tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _two_lowerings(rows, w, tiles: GroupTiles, interpret: bool):
    """``rows`` x ``w`` a group: the kernels where the program is lowered
    for a TPU, ``ragged_dot`` and its autodiff elsewhere, in the forward
    and in the backward pass.  Saved for the backward pass: the rows, the
    weights and the visits, which is what ``ragged_dot`` saves plus a few
    hundred integers."""
    return _forward(rows, w, tiles, interpret=interpret)


def _two_lowerings_fwd(rows, w, tiles, interpret):
    return _forward(rows, w, tiles, interpret=interpret), (rows, w, tiles)


def _two_lowerings_bwd(interpret, res, g):
    return _backward(g, *res, interpret=interpret) + (None,)


_two_lowerings.defvjp(_two_lowerings_fwd, _two_lowerings_bwd)


def _note_lowering(rows, w, kernel: bool, turned: bool):
    """``moe:gmm_lowering``: one sample a traced grouped product, track
    ``<dtype>[rows] x [E, K, N]``; ``kernel`` 1 where its TPU lowering is
    the kernel pair (a CPU program holds ``ragged_dot`` all the same),
    ``plain`` 1 where ``ragged_dot`` stays on every platform, ``turned``
    1, on that sample alone, where the pair reads ``w`` transposed."""
    trace.counter("moe:gmm_lowering", cat="ops",
                  track="%s[%d] x %s" % (rows.dtype.name, rows.shape[0],
                                         list(w.shape)), kernel=int(kernel),
                  plain=int(not kernel), **({"turned": 1} if turned else {}))


def tiled_matmul(rows, w, group_sizes, interpret: bool = False):
    """``dispatch.grouped_matmul``'s body.  ``group_sizes``: a layer's
    ``GroupTiles``, or plain sizes whose visits are then made here.
    ``interpret``: the kernels in the Pallas interpreter, the tests."""
    tiles = group_sizes if isinstance(group_sizes, GroupTiles) \
        else group_tiles(group_sizes, rows.shape[0])
    e, k, n = w.shape
    # ragged_dot stays for a mesh, rows that are no whole tiles, a dtype or
    # width tiles_for refuses: with K and N swapped too, so ONE question
    kernel = isinstance(tiles, GroupTiles) and rows.dtype == w.dtype \
        and bool(tiles_for(rows.shape[0], k, n, e, rows.dtype))
    turned = kernel and reads_turned(k, n)
    _note_lowering(rows, w, kernel, turned)
    if kernel and not turned:
        return _two_lowerings(rows, w, tiles, interpret)
    if turned:
        return _turned(rows, jnp.swapaxes(w, 1, 2), tiles, interpret)
    return ragged_matmul(rows, w, getattr(tiles, "sizes", tiles))


# -- a rank's window summed a token (``dispatch.held_sum``) -------------------
#
# ``token-sum`` (PR 49): no grouped matmul of an expert FFN (its name does not
# begin ``ragged-dot``) but the same walk turned round, for one rank's window
# of the sorted rows: ``(M, N)`` rows whose groups are each in token order ->
# ``(T, N)``, every token's rows summed.  Later code stands BELOW it and the
# lines above keep their count: a Mosaic payload names its source lines.

# rows a visit of ``token-sum`` reads: a run (one expert's rows of one tile
# of ROW_TILE tokens) is ~20-40 rows in the cells that run it.  A v5e, SDAR's
# window (32 768 rows of 2048, 8 287 held in 16 groups), the combine's
# forward, ms a call: 0.92 at 32 rows, 0.80 at 64, 0.81 at 128, 1.17 at 256;
# the gathers it replaces 2.91 (my chip runs, PR 49)
SUM_ROWS = 64


def token_sum_tiles(m: int, n: int, tokens: int, dtype
                    ) -> Optional[Tuple[int, int]]:
    """``(tm, tn)`` of the ``token-sum`` kernel for ``(M, N)`` rows summed
    into ``tokens`` tokens, or None where it does not run and the caller's
    gathers stay: float32 (a 0/1 left operand moves bfloat16 rows exactly
    -- one MXU pass, float32 accumulation, one rounding at the output --
    while float32 rows would pass through Mosaic's default product, which
    nobody has shown to be exact), rows that are no whole number of row
    tiles, tokens that are no whole number of ``ROW_TILE``-token tiles, an
    ``N`` that is no whole number of 128-lane tiles."""
    tn = _divisor(n, 4096)
    if dtype != jnp.bfloat16 or m % ROW_TILE or tokens % ROW_TILE or not tn:
        return None
    return SUM_ROWS, tn


def _run_visits(token, sizes, lo, *, tokens, tm):
    """The visits of ``token-sum``.  Sorted rows ``lo .. lo + n - 1`` of a
    plan whose first groups have ``sizes`` rows, a group's rows in token
    order (``token`` ``(n,)`` says which): group ``e``'s rows of token
    tile ``g`` are one *run* of consecutive rows, and run ``(g, e)``
    visits the row tiles of ``tm`` it touches, an empty run one tile,
    once (a token tile with no row at all still has its zeros written).
    Runs are walked ``g`` first, so the visits that add to one token tile
    are consecutive.  -> ``(tile_of, out_of, lo_of, hi_of, visits)``: visit
    ``v`` adds rows ``lo_of[v] .. hi_of[v] - 1`` of row tile ``tile_of[v]``
    to token tile ``out_of[v]``; at most ``n / tm + 2 G E`` of them."""
    n, groups, tiles_g = token.shape[0], sizes.shape[0], tokens // ROW_TILE
    runs, tiles_m = groups * tiles_g, n // tm
    ends = jnp.clip(jnp.cumsum(sizes.astype(jnp.int32)) - lo, 0, n)
    row = jnp.arange(n, dtype=jnp.int32)
    group = (row[:, None] >= ends[None, :]).sum(axis=1, dtype=jnp.int32)
    # rows ascend in (group, token tile); behind the groups: no run's
    key = jnp.where(row < ends[-1], group * tiles_g + token // ROW_TILE, runs)
    upto_key = (key[None, :] < jnp.arange(runs + 1, dtype=jnp.int32)[:, None]
                ).sum(axis=1, dtype=jnp.int32)
    # run (g, e) is rows start[g, e] .. stop[g, e] - 1
    start = upto_key[:-1].reshape(groups, tiles_g).T.reshape(runs)
    stop = upto_key[1:].reshape(groups, tiles_g).T.reshape(runs)
    first = jnp.minimum(start // tm, tiles_m - 1)
    count = jnp.where(stop > start, (stop - 1) // tm - first, 0) + 1
    upto = jnp.cumsum(count)
    v = jnp.arange(tiles_m + 2 * runs, dtype=jnp.int32)
    run_of = jnp.minimum(
        (v[:, None] >= upto[None, :]).sum(axis=1, dtype=jnp.int32), runs - 1)
    tile_of = jnp.minimum((first - upto + count)[run_of] + v, tiles_m - 1)
    return (tile_of, run_of // groups, start[run_of], stop[run_of],
            upto[-1:])


def _token_sum_kernel(tile_of, out_of, lo_of, hi_of, token, rows, *rest, tm):
    (weight, out, acc) = rest if len(rest) == 3 else (None,) + rest
    v, last = pl.program_id(1), pl.num_programs(1) - 1
    g = out_of[v]
    start, end, row0 = lo_of[v], hi_of[v], tile_of[v] * tm

    @pl.when((v == 0) | (out_of[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(end > start)
    def _():
        # through float32: a v5e selects no bfloat16
        own = jnp.where(_row_mask(rows.shape, row0, start, end),
                        rows[...].astype(jnp.float32), 0.0)
        # (ROW_TILE, tm): row r of the tile stands at token column
        # token[r] - ROW_TILE g of the token tile, with its weight (1
        # where there is none): the products are exact in float32
        place = (lax.broadcasted_iota(jnp.int32, (ROW_TILE, tm), 0)
                 == token[...] - g * ROW_TILE)
        place = place.astype(jnp.float32) if weight is None else \
            jnp.where(place, weight[...], 0.0)
        acc[...] += lax.dot_general(
            place.astype(rows.dtype), own.astype(rows.dtype),
            (((1,), (0,)), ((), ())), precision=_precision(rows.dtype),
            preferred_element_type=jnp.float32)

    @pl.when((v == last) | (out_of[jnp.minimum(v + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


def token_sums(rows, token, sizes, lo, tokens, weight=None, *, interpret):
    """``token-sum``: for every token the sum of its rows, ``(tokens, N)``
    in ``rows``' dtype.  ``rows`` ``(M, N)`` are sorted rows ``lo .. lo + M
    - 1`` of a plan whose first groups have ``sizes`` rows, each group's
    in token order; ``token`` ``(M,)`` int32 their tokens; ``weight``
    ``(M,)`` weighs each row, rounded to the rows' dtype first.  Rows
    behind the groups are never read.

    No row moves: a group's rows of one tile of ``ROW_TILE`` tokens are
    consecutive (a *run*), and the kernel adds each run to its token tile
    by one product with the matrix that holds each row's weight (1 where
    there is none) at the row's place in the tile and 0 elsewhere, made
    in VMEM from ``token``: a bfloat16 weight times a bfloat16 row is
    exact in float32, the sums are float32 in VMEM over the token tile's
    runs, and the output rounds once.  That is what XLA:TPU's fused
    ``(rows * w).sum()`` gives on the chip too (it keeps the product in
    float32: ``xla_allow_excess_precision``), bit for bit (tests/tpu).
    Grid ``(n tiles, visits)``, ``_run_visits``' list prefetched as
    scalars, as the grouped-matmul kernels walk theirs.  Who calls it:
    ``dispatch.held_sum`` (the combine's forward pass and the backward
    pass of ``sort_rows`` over one expert-parallel rank's window).
    Counter ``moe:gmm_trace`` with ``which`` = ``tsum``."""
    from jax.experimental.pallas import tpu as pltpu
    m, n = rows.shape
    tm, tn = token_sum_tiles(m, n, tokens, rows.dtype)
    _note_trace("tsum", jax.ShapeDtypeStruct((m, ROW_TILE), rows.dtype),
                rows, (tm, ROW_TILE, tn))
    visits = _run_visits(token, sizes, lo, tokens=tokens, tm=tm)
    operands = [token.reshape(m // tm, 1, tm), rows]
    in_specs = [
        pl.BlockSpec((None, 1, tm), lambda n_i, v, t, *_: (t[v], 0, 0)),
        pl.BlockSpec((tm, tn), lambda n_i, v, t, *_: (t[v], n_i))]
    if weight is not None:
        operands.append(weight.astype(rows.dtype).astype(jnp.float32)
                        .reshape(m // tm, 1, tm))
        in_specs.append(in_specs[0])
    # lint: allow(raw-pallas-call) — as _gmm
    return pl.pallas_call(
        functools.partial(_token_sum_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((tokens, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (ROW_TILE, tn), lambda n_i, v, t, o, *_: (o[v], n_i)),
            grid=(n // tn, visits[4][0]),
            scratch_shapes=[pltpu.VMEM((ROW_TILE, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * ROW_TILE * n, transcendentals=0,
            bytes_accessed=rows.dtype.itemsize * (m + tokens) * n),
        interpret=interpret, name="token-sum",
    )(*visits[:4], *operands)


# -- a stacked weight read through its transpose (``tiled_matmul``) -----------
#
# The TPU holds a float32 ``(E, K, N)`` whose ``N`` is no whole number of
# 128-lane tiles while ``K`` is with ``K`` on the lanes (layout ``{1,2,0}``:
# nothing padded), the kernels above want ``N`` there, and so does every
# elementwise neighbour XLA lays out after them: Nemotron-H's step turned
# the up projection ``(8, 2688, 1856)``, Adam's two moments and their three
# updates round the kernels, 24 float32 copies a step (PR 71, docs/moe.md).
# The logical transpose ``(E, N, K)`` of an array held ``{1,2,0}`` IS the
# row-major array of that shape, a bitcast, so such a weight is read as
# ``wt = swapaxes(w, 1, 2)`` by the same kernels with their ``transposed``
# flags turned round: shape for shape the three products of a down
# projection ``(E, N, K)``.  The cotangent of ``wt`` goes back through
# ``swapaxes``' own transpose, a bitcast again.


def reads_turned(k: int, n: int) -> bool:
    """Whether the kernel pair reads a stacked ``(E, K, N)`` through its
    transpose: ``N`` is no whole number of lane tiles and ``K`` is."""
    return bool(n % 128) and not k % 128


# lint: allow(raw-jit) — as _forward
@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward_turned(rows, wt, tiles: GroupTiles, *, interpret):
    """``_forward`` for ``wt`` ``(E, N, K)``.  Traced once a signature."""
    e, n, k = wt.shape
    shape = tiles_for(rows.shape[0], k, n, e, rows.dtype)

    def kernels(rows, wt, sizes, *visits):
        return _gmm(rows, wt, *visits, tiles=shape, transposed=True,
                    interpret=interpret)

    def plain(rows, wt, sizes, *visits):
        return ragged_matmul(rows, jnp.swapaxes(wt, 1, 2), sizes)

    return _kernel_on_tpu(kernels, plain, interpret, rows, wt, *tiles)


# lint: allow(raw-jit) — as _forward
@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward_turned(g, rows, wt, tiles: GroupTiles, *, interpret):
    """``_backward`` for ``wt`` ``(E, N, K)``: the cotangents of the rows
    and of ``wt``.  Traced once a signature."""
    e, n, k = wt.shape
    shape = tiles_for(rows.shape[0], n, k, e, g.dtype)

    def kernels(g, rows, wt, sizes, *visits):
        d_rows = _gmm(g, wt, *visits, tiles=shape, transposed=False,
                      interpret=interpret)
        d_wt = _tgmm(g, rows, *visits, groups=e, dtype=wt.dtype,
                     tiles=shape, interpret=interpret)
        return d_rows, d_wt

    def plain(g, rows, wt, sizes, *visits):
        return jax.vjp(lambda rows, wt: ragged_matmul(
            rows, jnp.swapaxes(wt, 1, 2), sizes), rows, wt)[1](g)

    return _kernel_on_tpu(kernels, plain, interpret, g, rows, wt, *tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turned(rows, wt, tiles: GroupTiles, interpret: bool):
    """``_two_lowerings`` for a weight handed over transposed."""
    return _forward_turned(rows, wt, tiles, interpret=interpret)


def _turned_fwd(rows, wt, tiles, interpret):
    return _forward_turned(rows, wt, tiles, interpret=interpret), (
        rows, wt, tiles)


def _turned_bwd(interpret, res, g):
    return _backward_turned(g, *res, interpret=interpret) + (None,)


_turned.defvjp(_turned_fwd, _turned_bwd)
