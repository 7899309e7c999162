"""The depthwise causal convolution in front of the delta-rule mixers,
with its activation: ``causal_conv`` behind the op ``CausalConv1D``
(``ops/linear_attention.py`` registers it; this file holds the
arithmetic so that the delta-rule kernels' call sites there keep their
lines: a Mosaic payload names them, and it is part of a program's cache
key).

``causal_conv1d`` is the plain form, ``y_t = sum_j w[:, j] x_{t - (W -
1) + j}`` over ``(B, T, C)`` with zeros before the sequence: a padded
copy and ``W`` shifted products, differentiable by autodiff, on every
platform, and the parity oracle.  ``causal_conv`` adds the activation
and a selection of lanes.  Of ``(B, T, G, Dw)`` data, a fused projection
laid out group by group (a key head's ``[q | k | v | z]``), it convolves
the leading ``lanes`` = ``(w_0, w_1, ..)`` of every group and gives
``(B, T, G * sum(w))`` PART by part, ``[part 0 of every group | part 1
of every group | ..]``, which is the order of the weight's rows too (a
published filter's ``[q heads | k heads | v heads]``): the projection is
read where it lies and the channels leave in the checkpoint's order,
with no cut, ``Concat`` or permuted filter in the graph.  Beside them it
hands on the lanes BEHIND the parts (the z of every group), group by
group ``(B, T, G * (Dw - sum(w)))``, so that the projection has one
reader and its cotangent one writer: cut as ``x[..., lo:]`` of the
four-dimensional view, XLA:TPU lays the whole projection out with the
groups on the sublanes for the cut's sake, forward and backward, 100 MB
each way a Qwen3-Next mixer.

With SiLU, whole row tiles and parts in whole 128-lane blocks the op has
a second lowering, chosen as the delta rule's is (``_kernel_on_tpu``:
where the program is LOWERED for a TPU; the plain form elsewhere and for
every other input): two Pallas kernels, ``causal_conv_fwd`` and
``causal_conv_bwd``, one pass over the data each, in the layout a
projection's matmul writes (lanes the channels, sublanes the tokens).
The grid is (lane block, batch, row tile), the row tiles walked in
order; the input's block index map (``_specs``) finds an output block's
lanes in the projection, so nothing is cut or copied in front of the
kernel.  Forward: the last ``HALO`` rows of the previous tile are carried
in scratch (no padded copy, nothing read twice), the taps accumulate in
float32 and ``silu`` is rounded once.  Backward: the same walk from the
last tile, the pre-activation formed AGAIN from x (so that a step keeps
the convolution's input and nothing else of it: no padded copy, no
shifted products, no pre-activation), the next tile's first rows of the
pre-activation's cotangent carried in scratch, ``dx`` in one write
into x's own flat shape, on the lanes the convolution read (the lanes
behind them take their cotangent as it comes, ``_put_rest``: no padded
sum), and ``dw`` summed in float32 scratch over batch and tiles and
written once.  The one thing read twice is a ``HALO``-row block of x in
front of each tile in the backward walk (``HALO`` rows a tile).  A grid
step costs a third of a microsecond before it moves a byte, so a step
takes ``STEP_NUMBERS`` numbers (4096 rows of a 128-lane block) and walks
them in passes of ``PASS_ROWS`` rows of one 128-lane column.

The counter ``conv:lowering`` (track ``<dtype>[B, T, C]/<lanes
taken>``) records the choice a traced op, ``kernel`` 1 or ``plain`` 1,
as ``kda:lowering`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from .nn import ACTIVATIONS
from .pallas_kernels import _kernel_on_tpu, pl

__all__ = ["biased_conv", "causal_conv", "causal_conv1d", "gated_conv"]

# lanes a grid step takes: the largest that divides every part and the
# group (and with them every part's place in its group)
LANE_BLOCKS = (512, 256, 128)
# numbers a grid step takes, rows times lanes: a narrow block is a tall
# one (4096 rows of 128 lanes, 1 MB of bfloat16, twice buffered a stream)
STEP_NUMBERS = 512 * 1024
# one pass inside a step: rows of one 128-lane column, a few float32
# vregs a tap
PASS_ROWS, PASS_LANES = 128, 128
# rows in front of a tile that its first outputs read: a whole sublane
# tile of bfloat16
HALO = 16
# a weight's taps lie on the sublanes of one float32 tile, and the
# cotangent's carried rows are one such tile: the most taps a filter has
SUBLANES = 8


def causal_conv1d(x, w):
    """Depthwise causal convolution over time: ``(B, T, C)`` data, one
    ``W``-tap filter a channel ``(C, W)`` (a bias is its caller's): ``y_t = sum_j
    w[:, j] x_{t - (W - 1) + j}``, positions before 0 read as zero."""
    width = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(x.dtype)
    return sum(xp[:, j:j + t, :] * w[:, j] for j in range(width))


def _starts(parts):
    return [sum(parts[:i]) for i in range(len(parts))]


def _taken(x, parts):
    """The parts of every group of ``(B, T, G, Dw)`` x side by side,
    part by part: ``(B, T, G * sum(parts))``."""
    b, t = x.shape[:2]
    return jnp.concatenate(
        [x[..., lo:lo + n].reshape(b, t, -1)
         for lo, n in zip(_starts(parts), parts)], axis=2)


def _rest(x, parts):
    """The lanes behind the ``parts`` of every group of ``(B, T, G, Dw)``
    x, group by group: ``(B, T, G * (Dw - sum(parts)))``.  Cut out of the
    flat ``(B, T, G * Dw)`` rows, where XLA:TPU keeps the tokens on the
    sublanes as the projection's matmul left them: asked for ``x[...,
    lo:]`` it lays the WHOLE projection out group by sublane first."""
    b, t, g, dw = x.shape
    flat = x.reshape(b, t, g * dw)
    return jnp.concatenate([flat[:, :, i * dw + sum(parts):(i + 1) * dw]
                            for i in range(g)], axis=2)


def _put_rest(dx, drest, groups):
    """``drest``, the cotangent of ``_rest``, written over the lanes of
    flat ``(B, T, G * Dw)`` ``dx`` that the convolution left alone, the
    last ones of every group."""
    dw, n = dx.shape[2] // groups, drest.shape[2] // groups
    for i in range(groups if n else 0):
        dx = lax.dynamic_update_slice(dx, drest[:, :, i * n:(i + 1) * n],
                                      (0, 0, (i + 1) * dw - n))
    return dx


def _plain(x, w, parts, act_type):
    """``causal_conv`` of ``(B, T, G, Dw)`` data by the plain form."""
    y = causal_conv1d(_taken(x, parts), w)
    return (y if act_type is None else ACTIVATIONS[act_type](y),
            _rest(x, parts))


def _shifted(win, s):
    """The rows behind ``win``'s ``HALO`` moved down by ``s``: row ``i``
    is ``win[HALO + i - s]``."""
    from jax.experimental.pallas import tpu as pltpu
    return (pltpu.roll(win, s, 0) if s else win)[HALO:]


def _lifted(win, s, rows):
    """``win``'s first ``rows`` rows moved up by ``s``: row ``i`` is
    ``win[i + s]``."""
    from jax.experimental.pallas import tpu as pltpu
    return (pltpu.roll(win, win.shape[0] - s, 0) if s else win)[:rows]


def _passes(ref, body, first=lambda lanes: None, flip=False):
    """``carry = body(lanes, r0, n, carry)`` over a step's block, a pass
    ``n`` = ``PASS_ROWS`` rows of one ``PASS_LANES``-lane column at a
    time (``flip``: from the last rows), each column's carry starting at
    ``first(lanes)``.  The pass at row 0 reads the rows in front of the
    block and is traced apart, with a Python 0; the others are one
    ``fori_loop``, so a step of 32 passes is traced and lowered as two."""
    rows, width = ref.shape
    n = min(rows, PASS_ROWS)
    count = rows // n

    def others(lanes, c):
        def step(i, c):
            i = count - 1 - i if flip else i + 1
            return body(lanes, pl.multiple_of(i * n, n), n, c)
        return lax.fori_loop(0, count - 1, step, c) if count > 1 else c

    for l0 in range(0, width, PASS_LANES):
        lanes = slice(l0, l0 + PASS_LANES)
        if flip:
            body(lanes, 0, n, others(lanes, first(lanes)))
        else:
            others(lanes, body(lanes, 0, n, first(lanes)))


def _window(x_ref, front, lanes, r0, n):
    """float32 rows ``r0 - HALO .. r0 + n`` of a block's ``lanes``: the
    rows in front of the block (``r0`` a Python 0) are ``front``."""
    if isinstance(r0, int):
        win = jnp.concatenate([front[:, lanes], x_ref[:n, lanes]], axis=0)
    else:
        win = x_ref[pl.ds(r0 - HALO, n + HALO), lanes]
    return win.astype(jnp.float32)


def _taps(w_ref, lanes, width):
    w = w_ref[:, lanes]
    return [w[j:j + 1] for j in range(width)]


def _fwd_kernel(x_ref, w_ref, y_ref, tail_ref, *, width):
    """One (row tile, lane block) of ``silu(conv(x))``; ``tail_ref``
    carries the tile's last ``HALO`` rows to the next one."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    def one(lanes, r0, n, _):
        taps = _taps(w_ref, lanes, width)
        win = _window(x_ref, tail_ref, lanes, r0, n)
        pre = sum(taps[j] * _shifted(win, width - 1 - j)
                  for j in range(width))
        y_ref[pl.ds(r0, n), lanes] = (
            pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)

    _passes(x_ref, one)
    tail_ref[...] = x_ref[x_ref.shape[0] - HALO:, :]


def _bwd_kernel(x_ref, front_ref, w_ref, dy_ref, dx_ref, dw_ref, next_ref,
                acc_ref, *, width, tiles):
    """One tile of the cotangents, the tiles walked from the last:
    ``pre`` again from x and the ``HALO`` rows in front of the tile
    (``front_ref``; zeros in front of the sequence), ``g = dy silu'(pre)``,
    ``dx_t = sum_j w[:, j] g_{t + (W - 1) - j}`` with the first rows of
    the next tile's ``g`` carried in ``next_ref``, ``dw[:, j] = sum_t g_t
    x_{t - (W - 1) + j}`` summed eight rows at a time into ``acc_ref``
    over every batch and tile of this lane block."""
    f32 = jnp.float32
    b, m = pl.program_id(1), pl.program_id(2)

    @pl.when((b == 0) & (m == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m == 0)
    def _():
        next_ref[...] = jnp.zeros_like(next_ref)

    front = front_ref[...]
    front = jnp.where(m == tiles - 1, jnp.zeros_like(front), front)

    def one(lanes, r0, n, after):
        """A pass; ``after`` is the first rows of ``g`` behind it, and its
        own go to the pass in front of it or, from the tile's first
        pass, to the next tile."""
        taps = _taps(w_ref, lanes, width)
        win = _window(x_ref, front, lanes, r0, n)
        xs = [_shifted(win, width - 1 - j) for j in range(width)]
        pre = sum(tap * x for tap, x in zip(taps, xs))
        sig = jax.nn.sigmoid(pre)
        g = (dy_ref[pl.ds(r0, n), lanes].astype(f32)
             * (sig * (1.0 + pre * (1.0 - sig))))
        for j in range(width):
            p = g * xs[j]
            acc_ref[j, :, lanes] += sum(p[i:i + SUBLANES]
                                        for i in range(0, n, SUBLANES))
        gwin = jnp.concatenate([g, after], axis=0)
        dx_ref[pl.ds(r0, n), lanes] = sum(
            taps[j] * _lifted(gwin, width - 1 - j, n)
            for j in range(width)).astype(dx_ref.dtype)
        if isinstance(r0, int):
            next_ref[:, lanes] = g[:SUBLANES]
        return g[:SUBLANES]

    _passes(x_ref, one, first=lambda lanes: next_ref[:, lanes], flip=True)

    @pl.when((b == pl.num_programs(1) - 1) & (m == tiles - 1))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        for j in range(width):
            dw_ref[j:j + 1, :] = jnp.sum(acc_ref[j], axis=0, keepdims=True)


def _tiling(x, parts):
    """(row tile, lane block) of ``(B, T, G, Dw)`` data whose leading
    ``parts`` a group are convolved, or None where the kernels' tiling
    does not take it: lanes in whole blocks, and rows in whole passes (or
    one short pass of whole ``HALO``s)."""
    t, dw = x.shape[1], x.shape[3]
    block = next((c for c in LANE_BLOCKS
                  if dw % c == 0 and all(n % c == 0 for n in parts)), None)
    if block is None or t % (PASS_ROWS if t > PASS_ROWS else HALO):
        return None
    rows = min(t, max(PASS_ROWS, STEP_NUMBERS // block))
    while t % rows:
        rows -= PASS_ROWS
    return rows, block


def _specs(x, parts, flip):
    """The grid (lane block, batch, row tile) and its blocks.  A lane
    block is counted where it LEAVES, part by part: ``out`` is that
    block of a ``(B, T, G * sum(parts))`` array and ``taps`` of ``(8, G *
    sum(parts))``; ``data`` is where its lanes lie in the input ``(B, T,
    G * Dw)`` (group ``i``, the part's place in the group) and ``front``
    the ``HALO`` rows in front of ``data``'s tile.  ``flip`` walks the
    tiles from the last."""
    from jax.experimental.pallas import tpu as pltpu
    b, t, g, dw = x.shape
    rows, block = _tiling(x, parts)
    tiles = t // rows
    at = (lambda m: tiles - 1 - m) if flip else (lambda m: m)

    def lies(j):
        """Where block ``j`` of the part-by-part order lies in x."""
        found = 0
        for lo, n in zip(_starts(parts), parts):
            rel = j - g * lo // block
            here = (rel // (n // block) * (dw // block) + lo // block
                    + rel % (n // block))
            found += jnp.where((rel >= 0) & (rel < g * n // block), here, 0)
        return found

    def rows_of(where):
        return pl.BlockSpec((None, rows, block),
                            lambda j, i, m: (i, at(m), where(j)))

    return dict(
        grid=(g * sum(parts) // block, b, tiles), tiles=tiles, block=block,
        out=rows_of(lambda j: j),
        data=rows_of(lies),
        front=pl.BlockSpec(
            (None, HALO, block),
            lambda j, i, m: (i, jnp.maximum(at(m) * (rows // HALO) - 1, 0),
                             lies(j))),
        taps=pl.BlockSpec((SUBLANES, block), lambda j, i, m: (0, j)),
        scratch=pltpu.VMEM,
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
    )


def _tap_rows(w):
    """``(C, W)`` -> float32 ``(8, C)``: a tap a sublane."""
    return jnp.pad(w.astype(jnp.float32).T,
                   ((0, SUBLANES - w.shape[1]), (0, 0)))


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, so that every layer's call shares one traced jaxpr and one
# lowered function; the step that holds it goes through the cache
@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _conv_fwd(x, w, *, parts, interpret):
    """``causal_conv_fwd``: ``silu`` of the convolution of the leading
    ``parts`` of each group of ``(B, T, G, Dw)`` x under ``(C, W)`` w ->
    ``(B, T, C)`` part by part, in x's dtype."""
    b, t, g, dw = x.shape
    c, width = w.shape
    sp = _specs(x, parts, False)
    # lint: allow(raw-pallas-call) — one lowering of this op, a pair with
    # its own vjp, chosen by platform and held to the plain form by
    # tolerance (tests/test_causal_conv.py, tests/tpu): not a forward
    # kernel behind the kernel search's bitwise gate
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width),
        grid=sp["grid"], in_specs=[sp["data"], sp["taps"]],
        out_specs=sp["out"],
        out_shape=jax.ShapeDtypeStruct((b, t, c), x.dtype),
        scratch_shapes=[sp["scratch"]((HALO, sp["block"]), x.dtype)],
        compiler_params=sp["params"], interpret=interpret,
        name="causal_conv_fwd",
    )(x.reshape(b, t, g * dw), _tap_rows(w))


# lint: allow(raw-jit) — as _conv_fwd
@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _conv_bwd(x, w, dy, *, parts, interpret):
    """``causal_conv_bwd``: the cotangents of ``_conv_fwd``'s x, flat
    ``(B, T, G * Dw)`` with the lanes it did not read left UNWRITTEN
    (``_put_rest`` fills them), and of w, from x, w and the output's
    cotangent."""
    b, t, g, dw = x.shape
    c, width = w.shape
    sp = _specs(x, parts, True)
    flat = x.reshape(b, t, g * dw)
    # lint: allow(raw-pallas-call) — as _conv_fwd
    dx, dtaps = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, tiles=sp["tiles"]),
        grid=sp["grid"],
        in_specs=[sp["data"], sp["front"], sp["taps"], sp["out"]],
        out_specs=[sp["data"], sp["taps"]],
        out_shape=[jax.ShapeDtypeStruct(flat.shape, x.dtype),
                   jax.ShapeDtypeStruct((SUBLANES, c), jnp.float32)],
        scratch_shapes=[
            sp["scratch"]((SUBLANES, sp["block"]), jnp.float32),
            sp["scratch"]((width, SUBLANES, sp["block"]), jnp.float32)],
        compiler_params=sp["params"], interpret=interpret,
        name="causal_conv_bwd",
    )(flat, flat, _tap_rows(w), dy)
    return dx, dtaps[:width].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _two_lowerings(x, w, parts, interpret: bool):
    """``silu(conv(.))`` of x's ``parts`` and the lanes behind them, for
    inputs the kernels take: the kernels where the program is lowered
    for a TPU, the plain form elsewhere, in both passes.  The backward
    pass keeps x and w and nothing else, and gives x's cotangent whole:
    the kernel writes the convolved lanes where they lie and the rest's
    cotangent is written between them, with no padded sum."""
    return _two_lowerings_fwd(x, w, parts, interpret)[0]


def _two_lowerings_fwd(x, w, parts, interpret):
    def kernels(x, w):
        return (_conv_fwd(x, w, parts=parts, interpret=interpret),
                _rest(x, parts))

    out = _kernel_on_tpu(kernels, lambda x, w: _plain(x, w, parts, "silu"),
                         interpret, x, w)
    return out, (x, w)


def _two_lowerings_bwd(parts, interpret, res, cts):
    x, w = res

    def kernels(x, w, dy, drest):
        dx, dw = _conv_bwd(x, w, dy, parts=parts, interpret=interpret)
        return _put_rest(dx, drest, x.shape[2]).reshape(x.shape), dw

    def plain(x, w, dy, drest):
        return jax.vjp(lambda x, w: _plain(x, w, parts, "silu"), x, w)[1](
            (dy, drest))

    return _kernel_on_tpu(kernels, plain, interpret, x, w, *cts)


_two_lowerings.defvjp(_two_lowerings_fwd, _two_lowerings_bwd)


def _kernel_takes(x, w, parts, act_type) -> bool:
    """What the kernel pair computes and tiles: SiLU, bfloat16 or
    float32, a filter's taps on one tile's sublanes, whole row tiles and
    whole lane blocks."""
    return (act_type == "silu" and x.dtype in (jnp.bfloat16, jnp.float32)
            and 2 <= w.shape[1] <= SUBLANES
            and _tiling(x, parts) is not None)


def causal_conv(x, w, act_type=None, lanes=None, interpret: bool = False):
    """``act(causal_conv1d(.))`` of ``(B, T, C)`` data under ``(C, W)``
    w; or, of ``(B, T, G, Dw)`` data, of the leading ``lanes`` (``(w_0,
    w_1, ..)``) of every group, part by part, under ``(G * sum(lanes),
    W)`` w in that order -> ``(B, T, G * sum(lanes))``, and beside it the
    lanes behind them as they are, group by group ``(B, T, G * (Dw -
    sum(lanes)))``; a list of the outputs.  One algorithm, two lowerings (see the module
    docstring); each trace records which as ``conv:lowering``:
    ``kernel`` 1 means the op's TPU lowering is the kernel pair (a CPU
    program holds the plain form all the same), ``plain`` 1 the plain
    form on every platform.  ``biased_conv``, below, adds a bias."""
    grouped = x.ndim == 4
    if not grouped:
        x = x.reshape(x.shape[:2] + (1, -1))
    b, t, g, dw = x.shape
    parts = tuple(lanes) if grouped else (dw,)
    kernel = _kernel_takes(x, w, parts, act_type)
    trace.counter("conv:lowering", cat="ops",
                  track="%s%s/%d" % (x.dtype.name, [b, t, g * dw],
                                     g * sum(parts)),
                  kernel=int(kernel), plain=int(not kernel))
    y, rest = (_two_lowerings(x, w, parts, interpret) if kernel
               else _plain(x, w, parts, act_type))
    return [y, rest] if grouped else [y]


# The gated form, below everything the SiLU form's kernels are called
# through: their Mosaic payloads name those lines (ROADMAP.md D20).
def _plain_gated(x, w):
    """``gated_conv`` by the plain form: the thirds cut, ``causal_conv1d``
    between two products."""
    c = x.shape[2] // 3
    return x[..., c:2 * c] * causal_conv1d(x[..., :c] * x[..., 2 * c:], w)


def _moved(lanes, by):
    return slice(lanes.start + by, lanes.stop + by)


def _gated_bwd_kernel(x_ref, front_ref, w_ref, dy_ref, dx_ref, dw_ref,
                      next_ref, acc_ref, *, width, tiles):
    """One row tile of the cotangents, the tiles walked from the last:
    ``z = B * u`` and its convolution again from x and the ``HALO`` rows
    in front of the tile, ``g = dy * C`` the convolution's cotangent,
    ``dz_t = sum_j w[:, j] g_{t + (W - 1) - j}`` with the first rows of
    the next tile's ``g`` carried in ``next_ref``; ``dx``'s three thirds
    ``[dz * u | dy * conv | dz * B]`` written where x's lie, and ``dw``
    summed as ``_bwd_kernel`` sums it."""
    f32 = jnp.float32
    c = dy_ref.shape[1]
    b, m = pl.program_id(0), pl.program_id(1)

    @pl.when((b == 0) & (m == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m == 0)
    def _():
        next_ref[...] = jnp.zeros_like(next_ref)

    front = front_ref[...]
    front = jnp.where(m == tiles - 1, jnp.zeros_like(front), front)

    def one(lanes, r0, n, after):
        taps = _taps(w_ref, lanes, width)
        rows = pl.ds(r0, n)
        gate_in = _window(x_ref, front, lanes, r0, n)
        u = _window(x_ref, front, _moved(lanes, 2 * c), r0, n)
        z = gate_in * u
        zs = [_shifted(z, width - 1 - j) for j in range(width)]
        dy = dy_ref[rows, lanes].astype(f32)
        g = dy * x_ref[rows, _moved(lanes, c)].astype(f32)
        for j in range(width):
            p = g * zs[j]
            acc_ref[j, :, lanes] += sum(p[i:i + SUBLANES]
                                        for i in range(0, n, SUBLANES))
        gwin = jnp.concatenate([g, after], axis=0)
        dz = sum(taps[j] * _lifted(gwin, width - 1 - j, n)
                 for j in range(width))
        dx_ref[rows, lanes] = (dz * u[HALO:]).astype(dx_ref.dtype)
        dx_ref[rows, _moved(lanes, c)] = (
            dy * sum(tap * z for tap, z in zip(taps, zs))
        ).astype(dx_ref.dtype)
        dx_ref[rows, _moved(lanes, 2 * c)] = (
            dz * gate_in[HALO:]).astype(dx_ref.dtype)
        if isinstance(r0, int):
            next_ref[:, lanes] = g[:SUBLANES]
        return g[:SUBLANES]

    _passes(dy_ref, one, first=lambda lanes: next_ref[:, lanes], flip=True)

    @pl.when((b == pl.num_programs(0) - 1) & (m == tiles - 1))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        for j in range(width):
            dw_ref[j:j + 1, :] = jnp.sum(acc_ref[j], axis=0, keepdims=True)


def _gated_rows(t, c):
    """Rows a grid step of the gated backward kernel takes of ``T``,
    every lane of them (a step reads ``[B | C | u]`` side by side, so its
    block is all ``3 C`` lanes wide and ``STEP_NUMBERS`` count a third's),
    or None where the tiling does not take ``(T, C)``."""
    if c % PASS_LANES or t % (PASS_ROWS if t > PASS_ROWS else HALO):
        return None
    rows = min(t, max(PASS_ROWS, STEP_NUMBERS // c // PASS_ROWS * PASS_ROWS))
    while t % rows:
        rows -= PASS_ROWS
    return rows


# lint: allow(raw-jit) — as _conv_fwd
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_bwd(x, w, dy, *, interpret):
    """``gated_conv_bwd``: the cotangents of ``gated_conv``'s x, every
    lane written once, and of w.  Grid (batch, row tile), the tiles
    walked from the last, blocks as ``_specs`` names them."""
    from jax.experimental.pallas import tpu as pltpu
    (b, t, _), (c, width) = x.shape, w.shape
    rows = _gated_rows(t, c)
    tiles = t // rows

    def at(m):
        return tiles - 1 - m

    data = pl.BlockSpec((None, rows, 3 * c), lambda i, m: (i, at(m), 0))
    taps = pl.BlockSpec((SUBLANES, c), lambda i, m: (0, 0))
    # lint: allow(raw-pallas-call) — as _conv_fwd
    dx, dtaps = pl.pallas_call(
        functools.partial(_gated_bwd_kernel, width=width, tiles=tiles),
        grid=(b, tiles),
        in_specs=[
            data,
            pl.BlockSpec(
                (None, HALO, 3 * c), lambda i, m: (
                    i, jnp.maximum(at(m) * (rows // HALO) - 1, 0), 0)),
            taps,
            pl.BlockSpec((None, rows, c), lambda i, m: (i, at(m), 0))],
        out_specs=[data, taps],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((SUBLANES, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((SUBLANES, c), jnp.float32),
                        pltpu.VMEM((width, SUBLANES, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="gated_conv_bwd",
    )(x, x, _tap_rows(w), dy)
    return dx, dtaps[:width].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated_lowerings(x, w, interpret: bool):
    """``gated_conv`` of inputs the backward kernel takes.  The forward
    is the plain form on every platform: XLA:TPU fuses it into one pass
    over the projection, which a kernel written for it did not beat (PR
    61: the cell's rate and peak memory the same either way).  The
    backward pass keeps x and w and nothing else."""
    return _plain_gated(x, w)


def _gated_lowerings_fwd(x, w, interpret):
    return _plain_gated(x, w), (x, w)


def _gated_lowerings_bwd(interpret, res, dy):
    return _kernel_on_tpu(
        lambda x, w, dy: _gated_bwd(x, w, dy, interpret=interpret),
        lambda x, w, dy: jax.vjp(_plain_gated, x, w)[1](dy),
        interpret, *res, dy)


_gated_lowerings.defvjp(_gated_lowerings_fwd, _gated_lowerings_bwd)


def gated_conv(x, w, interpret: bool = False):
    """The double-gated short convolution ``C * causal_conv1d(B * u)`` of
    ``(B, T, 3 C)`` data ``[B | C | u]``, the three thirds of one
    projection, under ``(C, W)`` w -> ``(B, T, C)``: no activation.  The
    counter of ``causal_conv`` (the track names the lanes as
    ``gated<C>``), and two lowerings of the BACKWARD pass: bfloat16 or
    float32, whole row tiles and whole 128-lane columns run
    ``gated_conv_bwd`` where the program is lowered for a TPU (x and
    ``dy`` read and the projection's cotangent written once, 7 C numbers
    a token, ``B * u`` and its convolution formed again there from the
    kept x and w); the forward is the plain form everywhere."""
    if x.ndim != 3 or x.shape[2] % 3:
        raise ValueError("the gated convolution reads (B, T, 3 C) data; "
                         "got %r" % (x.shape,))
    b, t, c = x.shape[0], x.shape[1], x.shape[2] // 3
    kernel = (x.dtype in (jnp.bfloat16, jnp.float32)
              and 2 <= w.shape[1] <= SUBLANES
              and _gated_rows(t, c) is not None)
    trace.counter("conv:lowering", cat="ops",
                  track="%s%s/gated%d" % (x.dtype.name, [b, t, 3 * c], c),
                  kernel=int(kernel), plain=int(not kernel))
    return _gated_lowerings(x, w, interpret) if kernel \
        else _plain_gated(x, w)


# The biased form, ``act(conv(x) + b)`` with one number a channel (a
# state-space mixer's convolution), below the two forms above for the
# reason the gated form is: their kernels' payloads name those lines.
# The same walk, blocks and passes (``_specs``, ``_passes``, ``_window``);
# the bias rides the taps' operand, on the sublane behind the last tap,
# and its cotangent leaves there, summed as the taps' are.
def _taps_and_bias(w, bias):
    """``_tap_rows`` of ``(C, W)`` w with ``(C,)`` bias as tap ``W``."""
    return _tap_rows(jnp.concatenate(
        [w.astype(jnp.float32), bias.astype(jnp.float32)[:, None]], axis=1))


def _biased_fwd_kernel(x_ref, w_ref, y_ref, tail_ref, *, width):
    """``_fwd_kernel`` with the bias, ``w_ref``'s row ``width``."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        tail_ref[...] = jnp.zeros_like(tail_ref)

    def one(lanes, r0, n, _):
        taps = _taps(w_ref, lanes, width + 1)
        win = _window(x_ref, tail_ref, lanes, r0, n)
        pre = taps[width] + sum(taps[j] * _shifted(win, width - 1 - j)
                                for j in range(width))
        y_ref[pl.ds(r0, n), lanes] = (
            pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)

    _passes(x_ref, one)
    tail_ref[...] = x_ref[x_ref.shape[0] - HALO:, :]


def _biased_bwd_kernel(x_ref, front_ref, w_ref, dy_ref, dx_ref, dw_ref,
                       next_ref, acc_ref, *, width, tiles):
    """``_bwd_kernel`` with the bias: ``pre`` holds it, and ``acc_ref``'s
    row ``width`` sums ``g`` itself, the bias's cotangent."""
    f32 = jnp.float32
    b, m = pl.program_id(1), pl.program_id(2)

    @pl.when((b == 0) & (m == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m == 0)
    def _():
        next_ref[...] = jnp.zeros_like(next_ref)

    front = front_ref[...]
    front = jnp.where(m == tiles - 1, jnp.zeros_like(front), front)

    def one(lanes, r0, n, after):
        taps = _taps(w_ref, lanes, width + 1)
        win = _window(x_ref, front, lanes, r0, n)
        xs = [_shifted(win, width - 1 - j) for j in range(width)]
        pre = taps[width] + sum(tap * x for tap, x in zip(taps, xs))
        sig = jax.nn.sigmoid(pre)
        g = (dy_ref[pl.ds(r0, n), lanes].astype(f32)
             * (sig * (1.0 + pre * (1.0 - sig))))
        for j, p in enumerate([g * x for x in xs] + [g]):
            acc_ref[j, :, lanes] += sum(p[i:i + SUBLANES]
                                        for i in range(0, n, SUBLANES))
        gwin = jnp.concatenate([g, after], axis=0)
        dx_ref[pl.ds(r0, n), lanes] = sum(
            taps[j] * _lifted(gwin, width - 1 - j, n)
            for j in range(width)).astype(dx_ref.dtype)
        if isinstance(r0, int):
            next_ref[:, lanes] = g[:SUBLANES]
        return g[:SUBLANES]

    _passes(x_ref, one, first=lambda lanes: next_ref[:, lanes], flip=True)

    @pl.when((b == pl.num_programs(1) - 1) & (m == tiles - 1))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        for j in range(width + 1):
            dw_ref[j:j + 1, :] = jnp.sum(acc_ref[j], axis=0, keepdims=True)


# lint: allow(raw-jit) — as _conv_fwd
@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _biased_fwd(x, w, bias, *, parts, interpret):
    """``causal_conv_bias_fwd``: ``_conv_fwd`` of ``conv(x) + bias``."""
    b, t, g, dw = x.shape
    c, width = w.shape
    sp = _specs(x, parts, False)
    # lint: allow(raw-pallas-call) — as _conv_fwd
    return pl.pallas_call(
        functools.partial(_biased_fwd_kernel, width=width),
        grid=sp["grid"], in_specs=[sp["data"], sp["taps"]],
        out_specs=sp["out"],
        out_shape=jax.ShapeDtypeStruct((b, t, c), x.dtype),
        scratch_shapes=[sp["scratch"]((HALO, sp["block"]), x.dtype)],
        compiler_params=sp["params"], interpret=interpret,
        name="causal_conv_bias_fwd",
    )(x.reshape(b, t, g * dw), _taps_and_bias(w, bias))


# lint: allow(raw-jit) — as _conv_fwd
@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _biased_bwd(x, w, bias, dy, *, parts, interpret):
    """``causal_conv_bias_bwd``: ``_conv_bwd``'s cotangents and the
    bias's."""
    b, t, g, dw = x.shape
    c, width = w.shape
    sp = _specs(x, parts, True)
    flat = x.reshape(b, t, g * dw)
    # lint: allow(raw-pallas-call) — as _conv_fwd
    dx, dtaps = pl.pallas_call(
        functools.partial(_biased_bwd_kernel, width=width,
                          tiles=sp["tiles"]),
        grid=sp["grid"],
        in_specs=[sp["data"], sp["front"], sp["taps"], sp["out"]],
        out_specs=[sp["data"], sp["taps"]],
        out_shape=[jax.ShapeDtypeStruct(flat.shape, x.dtype),
                   jax.ShapeDtypeStruct((SUBLANES, c), jnp.float32)],
        scratch_shapes=[
            sp["scratch"]((SUBLANES, sp["block"]), jnp.float32),
            sp["scratch"]((width + 1, SUBLANES, sp["block"]), jnp.float32)],
        compiler_params=sp["params"], interpret=interpret,
        name="causal_conv_bias_bwd",
    )(flat, flat, _taps_and_bias(w, bias), dy)
    return (dx, dtaps[:width].T.astype(w.dtype),
            dtaps[width].astype(bias.dtype))


def _plain_biased(x, w, bias, parts, act_type):
    """``biased_conv`` of ``(B, T, G, Dw)`` data by the plain form."""
    y = causal_conv1d(_taken(x, parts), w) + bias.astype(x.dtype)
    return (y if act_type is None else ACTIVATIONS[act_type](y),
            _rest(x, parts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _biased_lowerings(x, w, bias, parts, interpret: bool):
    """``_two_lowerings`` with the bias: ``silu(conv(.) + bias)`` of x's
    ``parts`` and the lanes behind them; the backward pass keeps x, w and
    the bias."""
    return _biased_lowerings_fwd(x, w, bias, parts, interpret)[0]


def _biased_lowerings_fwd(x, w, bias, parts, interpret):
    def kernels(x, w, bias):
        return (_biased_fwd(x, w, bias, parts=parts, interpret=interpret),
                _rest(x, parts))

    out = _kernel_on_tpu(
        kernels, lambda x, w, bias: _plain_biased(x, w, bias, parts, "silu"),
        interpret, x, w, bias)
    return out, (x, w, bias)


def _biased_lowerings_bwd(parts, interpret, res, cts):
    def kernels(x, w, bias, dy, drest):
        dx, dw, db = _biased_bwd(x, w, bias, dy, parts=parts,
                                 interpret=interpret)
        return _put_rest(dx, drest, x.shape[2]).reshape(x.shape), dw, db

    def plain(x, w, bias, dy, drest):
        return jax.vjp(lambda x, w, bias: _plain_biased(
            x, w, bias, parts, "silu"), x, w, bias)[1]((dy, drest))

    return _kernel_on_tpu(kernels, plain, interpret, *res, *cts)


_biased_lowerings.defvjp(_biased_lowerings_fwd, _biased_lowerings_bwd)


def biased_conv(x, w, bias, act_type=None, lanes=None,
                interpret: bool = False):
    """``causal_conv`` with ``(C,)`` bias added before the activation:
    the same inputs, outputs and choice of lowering (the kernel pair
    takes one tap fewer: the bias has the sublane behind the last), the
    same counter, its track ending ``+bias``."""
    grouped = x.ndim == 4
    if not grouped:
        x = x.reshape(x.shape[:2] + (1, -1))
    b, t, g, dw = x.shape
    parts = tuple(lanes) if grouped else (dw,)
    kernel = _kernel_takes(x, w, parts, act_type) and w.shape[1] < SUBLANES
    trace.counter("conv:lowering", cat="ops",
                  track="%s%s/%d+bias" % (x.dtype.name, [b, t, g * dw],
                                          g * sum(parts)),
                  kernel=int(kernel), plain=int(not kernel))
    y, rest = (_biased_lowerings(x, w, bias, parts, interpret) if kernel
               else _plain_biased(x, w, bias, parts, act_type))
    return [y, rest] if grouped else [y]
