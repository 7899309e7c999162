"""The delta-rule mixers' output stage: a head's RMSNorm times its gate,
``gated_rms_norm`` behind the op ``GatedRMSNorm``.

Of ``(..., H * D)`` rows ``x`` (the heads' outputs side by side, as the
delta-rule kernels write them and ``o_proj`` reads them), ``gate`` in
the same shape and ``(D,)`` ``gamma``, for every head's ``D`` lanes
``(x32 * rsqrt(mean(x32^2) + eps)).astype(dtype) * gamma * act(gate)``:
the statistic in float32, ``act`` ``silu`` (Gated DeltaNet) or
``sigmoid`` (Kimi Delta Attention).

One algorithm, two lowerings.  The plain form is the statements a
builder wrote before there was an op, ``RMSNorm`` over ``(rows * H, D)``
times ``Activation`` of the gate in the same shape: every platform's, and
the parity oracle.  Such an array can only lie head by sublane, while its
neighbours keep the tokens there, so on a TPU x, the gate and both
cotangents each crossed between the two layouts (``PERF.md`` §6, PR 68).
With ``D`` = 128, bfloat16 or float32 and rows in whole sublane tiles
the op therefore has a second lowering, chosen as the convolution's and
the delta rule's are (``_kernel_on_tpu``: where the program is LOWERED
for a TPU): two Pallas kernels, ``gated_norm_fwd`` and
``gated_norm_bwd``, over the flat rows as they lie, one pass over the
data each.  A head is one whole 128-lane block of a row and its
statistic a sum over the lanes inside it; the grid is (lane block, row
tile), a step ``STEP_NUMBERS`` numbers walked a head at a time, in
float32 and rounded once at the end (XLA:TPU keeps the plain form's
fused bfloat16 products in float32 too; on a CPU the statements round
after the norm, after gamma and after the activation).  Forward reads x
and the gate and writes y.  Backward keeps x, the gate and gamma and
nothing else of the stage: it forms the statistic again, reads ``dy``,
writes ``dx`` and ``dgate`` in their own row shapes and sums ``dgamma``
in float32 scratch over every step, written once.

The counter ``norm:lowering`` (track ``<dtype><shape>/<D>``) records the
choice a traced op, ``kernel`` 1 or ``plain`` 1, as ``conv:lowering``
does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..base import MXNetError
from .nn import ACTIVATIONS
from .pallas_kernels import _kernel_on_tpu, pl
from .registry import OpDef, Param, register_op
from .transformer import rms_norm

__all__ = ["gated_rms_norm"]

GATES = ("silu", "sigmoid")
# a head's lanes: one whole lane tile
HEAD_LANES = 128
# lanes a grid step takes: the widest that divides the row
LANE_BLOCKS = (1024, 512, 256, 128)
# numbers a grid step takes, rows times lanes (a step costs a third of a
# microsecond before it moves a byte: ``ops/causal_conv.py``).  The pair
# alone at (4096, 4096) bfloat16 on a v5e, forward / backward ms (PR 68;
# the bytes take 0.12 / 0.20): a step's 512 rows of 1024 lanes walked a
# head at a time 0.134 / 0.239, in passes of 128 rows under a
# ``fori_loop`` 0.186 / 0.320, of 256 0.156 / 0.251; 128 rows of all
# 4096 lanes 0.133 / 0.243 at four times the code
STEP_NUMBERS = 512 * 1024
# rows of a whole bfloat16 tile: what a step's rows come in
ROW_TILE = 16


def _plain(x, gate, gamma, eps, act):
    """The statements the builders wrote: ``RMSNorm`` and ``Activation``
    over ``(rows * H, D)``, their product in x's shape."""
    d = gamma.shape[0]
    y = rms_norm(x.reshape(-1, d), gamma, eps) \
        * ACTIVATIONS[act](gate.reshape(-1, d))
    return y.reshape(x.shape)


def _heads(ref):
    """The lanes of each head of a step's block."""
    return [slice(l0, l0 + HEAD_LANES)
            for l0 in range(0, ref.shape[1], HEAD_LANES)]


def _normed(x_ref, lanes, eps):
    """A head's float32 ``x * rsqrt(mean(x^2) + eps)`` and the factor."""
    x = x_ref[:, lanes].astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _fwd_kernel(x_ref, gate_ref, gamma_ref, y_ref, *, eps, act):
    """One step of the stage, a head at a time, in float32 and rounded
    once (as XLA:TPU's fusion of the plain form keeps its products)."""
    for lanes in _heads(x_ref):
        xhat, _ = _normed(x_ref, lanes, eps)
        g = gate_ref[:, lanes].astype(jnp.float32)
        a = jax.nn.sigmoid(g)
        if act == "silu":
            a = g * a
        y_ref[:, lanes] = (xhat * gamma_ref[:1] * a).astype(y_ref.dtype)


def _bwd_kernel(x_ref, gate_ref, gamma_ref, dy_ref, dx_ref, dgate_ref,
                dgamma_ref, acc_ref, *, eps, act):
    """One step of the cotangents, a head at a time: the normed rows
    again from x, ``dgate = dy xhat gamma act'(gate)``, ``dn = dy
    act(gate) gamma``, ``dx = r (dn - xhat mean(dn xhat))``, and ``dgamma
    = sum dy act(gate) xhat`` summed eight rows at a time into
    ``acc_ref`` over every step."""
    f32 = jnp.float32
    at = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(at == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for lanes in _heads(x_ref):
        xhat, r = _normed(x_ref, lanes, eps)
        g = gate_ref[:, lanes].astype(f32)
        dy = dy_ref[:, lanes].astype(f32)
        sig = jax.nn.sigmoid(g)
        if act == "silu":
            a, slope = g * sig, sig * (1.0 + g * (1.0 - sig))
        else:
            a, slope = sig, sig * (1.0 - sig)
        dgate_ref[:, lanes] = (dy * xhat * gamma_ref[:1] * slope).astype(
            dgate_ref.dtype)
        dyn = dy * a
        p = dyn * xhat
        acc_ref[...] += sum(p[i:i + 8] for i in range(0, p.shape[0], 8))
        dn = dyn * gamma_ref[:1]
        m = jnp.mean(dn * xhat, axis=-1, keepdims=True)
        dx_ref[:, lanes] = (r * (dn - xhat * m)).astype(dx_ref.dtype)

    @pl.when(at == pl.num_programs(0) * pl.num_programs(1) - 1)
    def _():
        dgamma_ref[...] = acc_ref[...]


def _tiling(x, d):
    """(row tile, lane block) of the flat rows of ``(..., H * d)`` x, or
    None where the kernels' tiling does not take them: a head one lane
    tile, the rows whole sublane tiles.  A step takes ``STEP_NUMBERS``
    numbers, the widest lane block of them."""
    width = x.shape[-1]
    n = x.size // width if width else 0
    if d != HEAD_LANES or width % d or not n or n % ROW_TILE:
        return None
    block = next(c for c in LANE_BLOCKS if width % c == 0)
    rows = min(n, max(ROW_TILE, STEP_NUMBERS // block // ROW_TILE * ROW_TILE))
    while n % rows:
        rows -= ROW_TILE
    return rows, block


def _blocks(x):
    """The grid (lane block, row tile) over x's flat rows, a step's
    block of them, and gamma's: eight sublanes of one tile, whole."""
    rows, block = _tiling(x, HEAD_LANES)
    width = x.shape[-1]
    return ((width // block, x.size // width // rows),
            pl.BlockSpec((rows, block), lambda j, m: (m, j)),
            pl.BlockSpec((8, HEAD_LANES), lambda j, m: (0, 0)))


def _gamma_rows(gamma, dtype):
    """``(D,)`` gamma as the statements round it, float32 on the eight
    sublanes of one tile."""
    g = gamma.astype(dtype).astype(jnp.float32)
    return jnp.broadcast_to(g[None, :], (8, g.shape[0]))


def _params(order):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(order, order),
                                vmem_limit_bytes=64 * 1024 * 1024)


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, so that every layer's call shares one traced jaxpr and one
# lowered function; the step that holds it goes through the cache
@functools.partial(jax.jit, static_argnames=("eps", "act", "interpret"))
def _norm_fwd(x, gate, gamma, *, eps, act, interpret):
    """``gated_norm_fwd``: the stage of ``(..., H * D)`` x and gate under
    ``(D,)`` gamma, in x's shape and dtype."""
    width = x.shape[-1]
    grid, data, whole = _blocks(x)
    # lint: allow(raw-pallas-call) — one lowering of this op, a pair with
    # its own vjp, chosen by platform and held to the plain form by
    # tolerance (tests/test_gated_norm.py, tests/tpu): not a forward
    # kernel behind the kernel search's bitwise gate
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, act=act),
        grid=grid, in_specs=[data, data, whole], out_specs=data,
        out_shape=jax.ShapeDtypeStruct((x.size // width, width), x.dtype),
        compiler_params=_params("parallel"),
        interpret=interpret, name="gated_norm_fwd",
    )(x.reshape(-1, width), gate.reshape(-1, width),
      _gamma_rows(gamma, x.dtype)).reshape(x.shape)


# lint: allow(raw-jit) — as _norm_fwd
@functools.partial(jax.jit, static_argnames=("eps", "act", "interpret"))
def _norm_bwd(x, gate, gamma, dy, *, eps, act, interpret):
    """``gated_norm_bwd``: the cotangents of ``_norm_fwd``'s x, gate and
    gamma from them and the output's cotangent.  The steps run in order:
    gamma's sums ride a scratch tile from the first to the last."""
    from jax.experimental.pallas import tpu as pltpu
    width = x.shape[-1]
    grid, data, whole = _blocks(x)
    flat = jax.ShapeDtypeStruct((x.size // width, width), x.dtype)
    sums = jax.ShapeDtypeStruct((8, HEAD_LANES), jnp.float32)
    # lint: allow(raw-pallas-call) — as _norm_fwd
    dx, dgate, dgamma = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, act=act),
        grid=grid, in_specs=[data, data, whole, data],
        out_specs=[data, data, whole], out_shape=[flat, flat, sums],
        scratch_shapes=[pltpu.VMEM(sums.shape, sums.dtype)],
        compiler_params=_params("arbitrary"),
        interpret=interpret, name="gated_norm_bwd",
    )(x.reshape(-1, width), gate.reshape(-1, width),
      _gamma_rows(gamma, x.dtype), dy.reshape(-1, width))
    return (dx.reshape(x.shape), dgate.reshape(gate.shape),
            dgamma.sum(axis=0).astype(gamma.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _two_lowerings(x, gate, gamma, eps, act, interpret: bool):
    """The stage for inputs the kernels take: the kernels where the
    program is lowered for a TPU, the plain form elsewhere, in both
    passes.  The backward pass keeps x, the gate and gamma."""
    return _two_lowerings_fwd(x, gate, gamma, eps, act, interpret)[0]


def _two_lowerings_fwd(x, gate, gamma, eps, act, interpret):
    out = _kernel_on_tpu(
        functools.partial(_norm_fwd, eps=eps, act=act, interpret=interpret),
        lambda x, gate, gamma: _plain(x, gate, gamma, eps, act),
        interpret, x, gate, gamma)
    return out, (x, gate, gamma)


def _two_lowerings_bwd(eps, act, interpret, res, dy):
    def plain(x, gate, gamma, dy):
        return jax.vjp(lambda x, gate, gamma: _plain(
            x, gate, gamma, eps, act), x, gate, gamma)[1](dy)

    return _kernel_on_tpu(
        functools.partial(_norm_bwd, eps=eps, act=act, interpret=interpret),
        plain, interpret, *res, dy)


_two_lowerings.defvjp(_two_lowerings_fwd, _two_lowerings_bwd)


def gated_rms_norm(x, gamma, gate, eps: float, act: str = "silu",
                   interpret: bool = False):
    """``rms_norm(x, gamma, eps)`` of every head's ``D`` = ``gamma.shape[0]``
    lanes of ``(..., H * D)`` x, times ``act(gate)`` (``silu`` |
    ``sigmoid``), gate in x's shape.  One algorithm, two lowerings (see the module
    docstring); each trace records which as ``norm:lowering``:
    ``kernel`` 1 means the op's TPU lowering is the kernel pair (a CPU
    program holds the plain form all the same), ``plain`` 1 the plain
    form on every platform."""
    if act not in GATES:
        raise ValueError("the gate's activation is one of %r; got %r"
                         % (GATES, act))
    d = gamma.shape[0]
    kernel = (x.dtype in (jnp.bfloat16, jnp.float32)
              and gate.dtype == x.dtype and _tiling(x, d) is not None)
    trace.counter("norm:lowering", cat="ops",
                  track="%s%s/%d" % (x.dtype.name, list(x.shape), d),
                  kernel=int(kernel), plain=int(not kernel))
    return _two_lowerings(x, gate, gamma, float(eps), act, interpret) \
        if kernel else _plain(x, gate, gamma, eps, act)


@register_op("GatedRMSNorm", hint="gatedrmsnorm")
class GatedRMSNormOp(OpDef):
    """``RMSNorm`` over every ``head_dim`` lanes of ``(..., H * head_dim)``
    data times ``act_type`` (``silu`` | ``sigmoid``) of ``gate`` in the
    same shape, ``gamma`` ``(head_dim,)``: a delta-rule mixer's output
    stage on the rows as the rule writes them (``ops/gated_norm.py``)."""
    params = [Param("head_dim", int, required=True),
              Param("eps", float, default=1e-5),
              Param("act_type", str, default="silu", enum=list(GATES))]

    def list_arguments(self, p):
        return ["data", "gamma", "gate"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0] if in_shapes[0] is not None else in_shapes[2]
        if d is None:
            return in_shapes, [None], []
        if p.head_dim < 1 or d[-1] % p.head_dim \
                or in_shapes[2] is not None and tuple(in_shapes[2]) != tuple(d):
            raise MXNetError("GatedRMSNorm: data and gate (..., heads * %d) "
                             "in one shape; got %r and %r"
                             % (p.head_dim, in_shapes[0], in_shapes[2]))
        return [d, (p.head_dim,), d], [d], []

    def forward(self, p, inputs, aux, ctx):
        return [gated_rms_norm(*inputs, p.eps, p.act_type)]
