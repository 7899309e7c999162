"""What stands between q's or k's projection and the attention kernel: a
head's RMSNorm and its rotation by the row's position,
``head_norm_rotary`` behind the op ``HeadNormRotary``.

Of ``(B * T, H * D)`` rows ``x`` (a projection's output, the heads side
by side, as the matmul writes them and the attention kernels read them)
rows in the same shape and dtype: for every head's ``D`` lanes, with a
``(D,)`` ``gamma``, ``rms_norm`` (the statistic in float32), then, with
``seq_len`` = ``T``, the half-split rotation at the row's position in its
sequence (``sectioned_rotary``: ``theta``, ``period``, ``sections``, a
``positions`` input).  Either half may be left out.

One algorithm, two lowerings.  The plain form is the statements a
builder wrote before there was an op, ``RMSNorm`` and ``RotaryEmbedding``
over the ``(B, T, H, D)`` view: every platform's, and the parity oracle.
Such an array lies head by sublane on a TPU while the matmul before it
and the kernel behind it keep the tokens there, and XLA ran the two as
nine passes a layer over q in that form, most of them float32 (``PERF.md``
§6, PR 70).  With ``D`` = 128, bfloat16 or float32 and rows in whole
sublane tiles the op therefore has a second lowering, chosen as
``GatedRMSNorm``'s is (``_kernel_on_tpu``: where the program is LOWERED
for a TPU): two Pallas kernels, ``head_rotary_fwd`` and
``head_rotary_bwd``, over the flat rows as they lie, one pass over the
data each, ``ops/gated_norm.py``'s tiling.  A head is one whole 128-lane
block of a row, its statistic a sum over the lanes inside it and the
half-split partner of a lane the lane 64 away in the same block: a lane
rotate.  The positions enter as a table, ``[cos | sin]`` of ``(B * T,
D)`` float32 built by XLA once a step (the same for q, k and every
layer); the grid is (row tile, lane block), so a step's rows of it are
fetched once for all of a row's lane blocks.  Everything is float32
inside and rounded once at the end.  Forward reads x and writes y.
Backward keeps x and gamma and nothing else (without the norm not even
x): it forms the statistic again, reads ``dy``, turns it back, writes
``dx`` in the row shape and sums ``dgamma`` in float32 scratch over
every step, written once.

The counter ``rotary:lowering`` (track ``<dtype><shape>/<D>``) records
the choice a traced op, ``kernel`` 1 or ``plain`` 1, as
``norm:lowering`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import trace
from ..base import MXNetError
from .gated_norm import (HEAD_LANES, _gamma_rows, _heads, _normed, _params,
                         _tiling)
from .pallas_kernels import _kernel_on_tpu, pl
from .registry import OpDef, Param, register_op
from .transformer import rms_norm, sectioned_rotary

__all__ = ["head_norm_rotary", "rotary_table"]

HALF = HEAD_LANES // 2


def _plain(x, gamma, positions, d, eps, seq_len, theta, period, sections):
    """The statements the builders wrote: ``RMSNorm`` (with a gamma) and
    ``RotaryEmbedding`` (with a ``seq_len``) over the ``(B, T, H, D)``
    view, laid back as rows."""
    y = x.reshape(-1, seq_len or 1, x.shape[-1] // d, d)
    if gamma is not None:
        y = rms_norm(y, gamma, eps)
    if seq_len:
        y = sectioned_rotary(y, positions, theta=theta, period=period,
                             sections=sections)
    return y.reshape(x.shape)


def rotary_table(n, d, seq_len, theta, period=0, sections=None,
                 positions=None):
    """``[cos | sin]`` ``(n, d)`` float32 of the angles
    ``sectioned_rotary`` turns the ``d / 2`` pairs of row ``0..n-1`` by,
    ``n`` whole sequences of ``seq_len`` rows."""
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / d))
    if positions is None:
        pos = jnp.arange(seq_len, dtype=jnp.float32)
        if period:
            pos = (jnp.arange(seq_len) % period).astype(jnp.float32)
        ang = jnp.tile(pos[:, None] * inv_freq[None, :], (n // seq_len, 1))
    else:
        axis_of = np.repeat(np.arange(len(sections)), sections)
        pos = positions.astype(jnp.float32)[:, axis_of, :]  # (B, half, T)
        ang = (pos.transpose(0, 2, 1) * inv_freq[None, None, :]).reshape(
            n, half)
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _turns(table_ref):
    """A step's ``(rows, 128)`` ``[cos | cos]`` and ``[-sin | sin]`` of
    its rows of the table ``[cos | sin]``: ``y = x c + partner(x) s``."""
    from jax.experimental.pallas import tpu as pltpu
    t = table_ref[...]
    other = pltpu.roll(t, HALF, 1)
    low = lax.broadcasted_iota(jnp.int32, t.shape, 1) < HALF
    return jnp.where(low, t, other), jnp.where(low, -other, t)


def _partner(x):
    """Every lane's half-split partner, the lane 64 away in its head."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, HALF, 1)


def _fwd_kernel(*refs, eps, norm, turn):
    """One step of the stage, a head at a time, in float32 and rounded
    once: refs x, [gamma], [table], y."""
    x_ref, y_ref = refs[0], refs[-1]
    if turn:
        c, s = _turns(refs[-2])
    for lanes in _heads(x_ref):
        if norm:
            y = _normed(x_ref, lanes, eps)[0] * refs[1][:1]
        else:
            y = x_ref[:, lanes].astype(jnp.float32)
        if turn:
            y = y * c + _partner(y) * s
        y_ref[:, lanes] = y.astype(y_ref.dtype)


def _bwd_kernel(*refs, eps, norm, turn):
    """One step of the cotangents, a head at a time: ``dy`` turned back
    (the transpose of a rotation is the rotation by the opposite angle),
    then, of the normed rows formed again from x, ``dgamma = sum dn
    xhat`` summed tile over tile (eight rows) into the scratch over every step
    and ``dx = r (dn gamma - xhat mean(dn gamma xhat))``: refs [x, gamma],
    [table], dy, dx, [dgamma, scratch]."""
    f32 = jnp.float32
    dy_ref = refs[2 * norm + turn]
    dx_ref = refs[2 * norm + turn + 1]
    if turn:
        c, s = _turns(refs[2 * norm])
    if norm:
        x_ref, gamma_ref, dgamma_ref, acc_ref = refs[0], refs[1], *refs[-2:]
        at = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

        @pl.when(at == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

    for lanes in _heads(dy_ref):
        dn = dy_ref[:, lanes].astype(f32)
        if turn:
            dn = dn * c - _partner(dn) * s
        if norm:
            xhat, r = _normed(x_ref, lanes, eps)
            p = dn * xhat
            acc_ref[...] += p.reshape(-1, 8, HEAD_LANES).sum(axis=0)
            dn = dn * gamma_ref[:1]
            dn = r * (dn - xhat * jnp.mean(dn * xhat, axis=-1,
                                           keepdims=True))
        dx_ref[:, lanes] = dn.astype(dx_ref.dtype)

    if norm:
        @pl.when(at == pl.num_programs(0) * pl.num_programs(1) - 1)
        def _():
            dgamma_ref[...] = acc_ref[...]


def _blocks(x):
    """The grid (row tile, lane block) over x's rows, a step's block of
    them, gamma's (eight sublanes of one tile, whole) and the table's (a
    step's rows, the same for every lane block of them)."""
    n, width = x.shape
    rows, block = _tiling(x, HEAD_LANES)
    return ((n // rows, width // block),
            pl.BlockSpec((rows, block), lambda m, j: (m, j)),
            pl.BlockSpec((8, HEAD_LANES), lambda m, j: (0, 0)),
            pl.BlockSpec((rows, HEAD_LANES), lambda m, j: (m, 0)))


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, so that every layer's call shares one traced jaxpr and one
# lowered function; the step that holds it goes through the cache
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rotary_fwd(x, gamma, table, *, eps, interpret):
    """``head_rotary_fwd``: the stage of ``(N, H * 128)`` x, in x's shape
    and dtype; ``gamma`` None, no norm; ``table`` None, no rotation."""
    norm, turn = gamma is not None, table is not None
    grid, data, whole, angles = _blocks(x)
    # lint: allow(raw-pallas-call) — one lowering of this op, a pair with
    # its own vjp, chosen by platform and held to the plain form by
    # tolerance (tests/test_head_rotary.py, tests/tpu): not a forward
    # kernel behind the kernel search's bitwise gate
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, norm=norm, turn=turn),
        grid=grid, in_specs=[data] + [whole] * norm + [angles] * turn,
        out_specs=data, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params("parallel"),
        interpret=interpret, name="head_rotary_fwd",
    )(x, *[_gamma_rows(gamma, x.dtype)] if norm else [], *[table] * turn)


# lint: allow(raw-jit) — as _rotary_fwd
@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rotary_bwd(x, gamma, table, dy, *, eps, interpret):
    """``head_rotary_bwd``: the cotangents of ``_rotary_fwd``'s x and
    gamma from them and the output's cotangent (both None: the rotation
    alone needs neither).  With a gamma the steps run in order:
    its sums ride a scratch tile from the first to the last."""
    from jax.experimental.pallas import tpu as pltpu
    norm, turn = gamma is not None, table is not None
    grid, data, whole, angles = _blocks(dy)
    flat = jax.ShapeDtypeStruct(dy.shape, dy.dtype)
    sums = jax.ShapeDtypeStruct((8, HEAD_LANES), jnp.float32)
    # lint: allow(raw-pallas-call) — as _rotary_fwd
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, norm=norm, turn=turn),
        grid=grid,
        in_specs=[data, whole] * norm + [angles] * turn + [data],
        out_specs=[data] + [whole] * norm, out_shape=[flat] + [sums] * norm,
        scratch_shapes=[pltpu.VMEM(sums.shape, sums.dtype)] * norm,
        compiler_params=_params("arbitrary" if norm else "parallel"),
        interpret=interpret, name="head_rotary_bwd",
    )(*[x, _gamma_rows(gamma, dy.dtype)] if norm else [], *[table] * turn,
      dy)
    return out[0], out[1].sum(axis=0).astype(gamma.dtype) if norm else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _two_lowerings(x, gamma, positions, table, d, how, interpret: bool):
    """The stage for rows the kernels take: the kernels (which read the
    table) where the program is lowered for a TPU, the plain form (which
    reads the positions) elsewhere, in both passes; ``how`` is
    ``_plain``'s ``eps, seq_len, theta, period, sections``.  The backward
    pass keeps x and gamma, beside the table and the positions."""
    return _two_lowerings_fwd(x, gamma, positions, table, d, how,
                              interpret)[0]


def _two_lowerings_fwd(x, gamma, positions, table, d, how, interpret):
    out = _kernel_on_tpu(
        lambda x, gamma, positions, table: _rotary_fwd(
            x, gamma, table, eps=how[0], interpret=interpret),
        lambda x, gamma, positions, table: _plain(
            x, gamma, positions, d, *how),
        interpret, x, gamma, positions, table)
    return out, (x if gamma is not None else None, gamma, positions, table)


def _two_lowerings_bwd(d, how, interpret, res, dy):
    x, gamma, positions, table = res

    def kernel(x, gamma, positions, table, dy):
        return _rotary_bwd(x, gamma, table, dy, eps=how[0],
                           interpret=interpret)

    def plain(x, gamma, positions, table, dy):
        if gamma is None:
            # linear in x: any x gives the rotation's transpose
            return jax.vjp(lambda x: _plain(x, None, positions, d, *how),
                           dy)[1](dy) + (None,)
        return jax.vjp(lambda x, gamma: _plain(x, gamma, positions, d, *how),
                       x, gamma)[1](dy)

    dx, dgamma = _kernel_on_tpu(kernel, plain, interpret, *res, dy)
    zeros = functools.partial(jax.tree.map, jnp.zeros_like)
    return dx, dgamma, zeros(positions), zeros(table)


_two_lowerings.defvjp(_two_lowerings_fwd, _two_lowerings_bwd)


def head_norm_rotary(x, gamma=None, positions=None, *, head_dim: int,
                     eps: float = 1e-6, seq_len: int = 0,
                     theta: float = 10000.0, period: int = 0, sections=None,
                     interpret: bool = False):
    """Of every head's ``head_dim`` lanes of ``(B * T, H * head_dim)`` x:
    ``rms_norm(., gamma, eps)`` where there is a ``(head_dim,)`` gamma,
    then, where ``seq_len`` = ``T`` is given, ``sectioned_rotary`` at the
    row's position in its sequence (``theta``, ``period``, ``sections``
    and ``(B, len(sections), T)`` ``positions`` are its).  One algorithm,
    two lowerings (see the module docstring); each trace records which as
    ``rotary:lowering``: ``kernel`` 1 means the op's TPU lowering is the
    kernel pair (a CPU program holds the plain form all the same),
    ``plain`` 1 the plain form on every platform."""
    d, seq_len, sections = int(head_dim), int(seq_len), tuple(sections or ())
    how = (float(eps), seq_len, float(theta), int(period), sections)
    kernel = (x.ndim == 2 and x.dtype in (jnp.bfloat16, jnp.float32)
              and _tiling(x, d) is not None)
    trace.counter("rotary:lowering", cat="ops",
                  track="%s%s/%d" % (x.dtype.name, list(x.shape), d),
                  kernel=int(kernel), plain=int(not kernel))
    if not kernel:
        return _plain(x, gamma, positions, d, *how)
    table = None
    if seq_len:
        table = lax.stop_gradient(rotary_table(
            x.shape[0], d, seq_len, how[2], how[3], sections, positions))
    if positions is not None:
        positions = lax.stop_gradient(positions.astype(jnp.float32))
    return _two_lowerings(x, gamma, positions, table, d, how, interpret)


@register_op("HeadNormRotary", hint="headnormrotary")
class HeadNormRotaryOp(OpDef):
    """Between a q or k projection and attention, on the ``(B * T, heads
    * head_dim)`` rows as the projection writes them: ``RMSNorm`` over
    every head's ``head_dim`` lanes (``norm``; ``gamma`` ``(head_dim,)``),
    then ``RotaryEmbedding`` of the heads at the row's position in its
    sequence of ``seq_len`` rows (0: no rotation; ``theta``, ``period``,
    ``sections`` and ``with_positions`` are ``RotaryEmbedding``'s, the
    positions ``(B, len(sections), seq_len)``).  Rows of the same shape
    (``ops/head_rotary.py``)."""
    params = [Param("head_dim", int, required=True),
              Param("norm", bool, default=True),
              Param("eps", float, default=1e-6),
              Param("seq_len", int, default=0),
              Param("theta", float, default=10000.0),
              Param("period", int, default=0),
              Param("sections", "shape"),
              Param("with_positions", bool)]

    def list_arguments(self, p):
        return ["data"] + ["gamma"] * bool(p.norm) \
            + ["positions"] * bool(p.with_positions)

    def infer_shape(self, p, in_shapes):
        d, sections = in_shapes[0], tuple(p.sections or ())
        if not p.norm and not p.seq_len:
            raise MXNetError("HeadNormRotary: neither a norm nor a seq_len "
                             "to rotate by")
        if p.head_dim < 2 or p.head_dim % 2 or p.seq_len < 0 or (
                d is not None and (len(d) != 2 or d[1] % p.head_dim
                                   or d[0] % (p.seq_len or 1))):
            raise MXNetError(
                "HeadNormRotary: data (sequences * %d, heads * %d) with an "
                "even head_dim; got %r" % (p.seq_len, p.head_dim, d))
        if (sections or p.period or p.with_positions) and not p.seq_len:
            raise MXNetError("HeadNormRotary: sections, a period and "
                             "positions are the rotation's: seq_len is 0")
        if sections and (min(sections) < 1 or p.period
                         or sum(sections) * 2 != p.head_dim):
            raise MXNetError("HeadNormRotary: sections %r are the pairs of "
                             "a head of %d lanes, axis by axis, without a "
                             "period" % (sections, p.head_dim))
        if p.with_positions and not sections:
            raise MXNetError("HeadNormRotary: a positions input needs "
                             "sections, the pairs each of its axes turns")
        shapes = [d] + [(p.head_dim,)] * bool(p.norm)
        if p.with_positions:
            shapes.append(None if d is None else
                          (d[0] // p.seq_len, len(sections), p.seq_len))
        return shapes, [d], []

    def forward(self, p, inputs, aux, ctx):
        x, rest = inputs[0], list(inputs[1:])
        return [head_norm_rotary(
            x, rest.pop(0) if p.norm else None,
            rest.pop(0) if p.with_positions else None, head_dim=p.head_dim,
            eps=p.eps, seq_len=p.seq_len, theta=p.theta, period=p.period,
            sections=p.sections)]
