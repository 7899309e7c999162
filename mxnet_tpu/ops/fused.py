"""Fused inference operators (the op-level half of ``passes.fuse``).

TVM/Relay demonstrated that the epilogue family — matmul/conv + bias +
activation (+ re-quantize) — is the single highest-value fusion in an
inference graph: the elementwise tail is free on the MXU/VPU when it
rides the matmul's output registers, and the graph the compiler sees
shrinks by 2-4 nodes per layer.  ``FuseEpiloguePass`` rewrites those
subgraphs into the ops below; each op's ``forward`` is ONE jnp/lax body,
so the executor's trace presents the whole epilogue to XLA as a single
producer (and the symbol json carries 1 node where it carried 3-4).

Two families, mirroring the unfused ops they replace:

* ``_fused_FullyConnected`` / ``_fused_Convolution`` — f32 compute,
  optional activation epilogue (``act_type``), optional int8 re-quantize
  epilogue (``out_scale``: set when the pass absorbed a downstream
  ``_contrib_quantize``, output dtype becomes int8).
* ``_fused_quantized_FullyConnected`` / ``_fused_quantized_Convolution``
  — the int8/int32-accumulate bodies of ``ops.quantized`` with the same
  two epilogues fused in (dequant + bias + act + requant in one body).

Plus ``_fused_elemwise``: an arbitrary chain of single-input elementwise
ops (activations, scalar arithmetic, unary math) collapsed into one node
carrying the serialized step list — ``ElementwiseFusePass``'s target.

Escape hatch: a FullyConnected epilogue lowered for a TPU dispatches to
a Pallas kernel (``pallas_kernels.fused_fc_epilogue``) for shapes XLA
schedules poorly; lowered for any other platform the jnp body runs, so
CPU tier-1 numerics are exactly the unfused graph's.  Knob:
``MXNET_FUSE_PALLAS`` (default on).

Inference-only, like ``ops.quantized``: the fusion passes run on the
serving pipeline and these ops define no bespoke gradient story.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, get_env
from .nn import _conv_out
from .quantized import INT8_QMAX
from .registry import OpDef, Param, register_op

__all__ = ["ACT_FNS", "ELEMWISE_STEP_OPS", "apply_act", "apply_steps",
           "parse_steps", "format_steps"]

# the activation epilogues the fused ops carry — exactly Activation's
# act_type enum plus "none" (epilogue absent)
ACT_FNS = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
}


def apply_act(x, act_type: str):
    fn = ACT_FNS.get(act_type or "none")
    if fn is None:
        raise MXNetError("fused op: unknown act_type %r (have %s)"
                         % (act_type, sorted(ACT_FNS)))
    return fn(x)


def _requantize(x, out_scale: Optional[float]):
    """The absorbed ``_contrib_quantize`` epilogue: f32 -> int8 by the
    calibrated scale (same math as ops.quantized.QuantizeOp)."""
    if out_scale is None:
        return x
    if out_scale <= 0:
        raise MXNetError("fused op: out_scale must be > 0, got %r"
                         % (out_scale,))
    q = jnp.clip(jnp.round(x / np.float32(out_scale)),
                 -INT8_QMAX, INT8_QMAX)
    return q.astype(jnp.int8)


def _pallas_wanted() -> bool:
    return get_env("MXNET_FUSE_PALLAS", True, bool)


# -- fused f32 family --------------------------------------------------------

_EPILOGUE_PARAMS = [
    Param("act_type", str, default="none",
          enum=sorted(ACT_FNS),
          doc="activation epilogue fused into the op"),
    Param("out_scale", float, default=None,
          doc="absorbed _contrib_quantize epilogue: when set, the op "
              "emits int8 at this scale"),
]


@register_op("_fused_FullyConnected", hint="fused_fullyconnected")
class FusedFullyConnectedOp(OpDef):
    """FullyConnected + bias + Activation (+ requantize) in one body:
    ``y = act(x·Wᵀ + b)`` [→ int8 by ``out_scale``]."""
    params = [Param("num_hidden", int, required=True),
              Param("no_bias", bool, default=False)] + _EPILOGUE_PARAMS

    def list_arguments(self, p):
        return ["data", "weight"] if p.no_bias else ["data", "weight", "bias"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        num_input = int(np.prod(d[1:]))
        shapes = [d, (p.num_hidden, num_input)]
        if not p.no_bias:
            shapes.append((p.num_hidden,))
        return shapes, [(d[0], p.num_hidden)], []

    def infer_type(self, p, in_types):
        t = next((x for x in in_types if x is not None),
                 np.dtype(np.float32))
        out = np.dtype(np.int8) if p.out_scale is not None else t
        return [t] * len(self.list_arguments(p)), [out], []

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0].reshape(inputs[0].shape[0], -1)
        w = inputs[1]
        b = None if p.no_bias else inputs[2]

        def body(x, w, b):
            out = jnp.dot(x, w.T)
            if b is not None:
                out = out + b
            return _requantize(apply_act(out, p.act_type), p.out_scale)

        if _pallas_wanted():
            from .pallas_kernels import fused_fc_epilogue
            out = fused_fc_epilogue(x, w, b, p.act_type, p.out_scale,
                                    dense=body)
            if out is not None:
                return [out]
        return [body(x, w, b)]


@register_op("_fused_Convolution", hint="fused_convolution")
class FusedConvolutionOp(OpDef):
    """Convolution + bias + Activation (+ requantize) in one body."""
    params = [Param("kernel", "shape", required=True),
              Param("stride", "shape", default=(1, 1)),
              Param("dilate", "shape", default=(1, 1)),
              Param("pad", "shape", default=(0, 0)),
              Param("num_filter", int, required=True),
              Param("num_group", int, default=1),
              Param("workspace", int, default=512),
              Param("no_bias", bool, default=False),
              Param("cudnn_tune", str, default=None),
              Param("cudnn_off", bool, default=False)] + _EPILOGUE_PARAMS

    def list_arguments(self, p):
        return ["data", "weight"] if p.no_bias else ["data", "weight", "bias"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        kh, kw = p.kernel
        wshape = (p.num_filter, d[1] // p.num_group, kh, kw)
        oshape = (d[0], p.num_filter,
                  _conv_out(d[2], kh, p.stride[0], p.pad[0], p.dilate[0]),
                  _conv_out(d[3], kw, p.stride[1], p.pad[1], p.dilate[1]))
        shapes = [d, wshape] + ([] if p.no_bias else [(p.num_filter,)])
        return shapes, [oshape], []

    def infer_type(self, p, in_types):
        t = next((x for x in in_types if x is not None),
                 np.dtype(np.float32))
        out = np.dtype(np.int8) if p.out_scale is not None else t
        return [t] * len(self.list_arguments(p)), [out], []

    def forward(self, p, inputs, aux, ctx):
        out = lax.conv_general_dilated(
            inputs[0], inputs[1], window_strides=tuple(p.stride),
            padding=[(p.pad[0], p.pad[0]), (p.pad[1], p.pad[1])],
            rhs_dilation=tuple(p.dilate),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=p.num_group)
        if not p.no_bias:
            out = out + inputs[2][None, :, None, None]
        return [_requantize(apply_act(out, p.act_type), p.out_scale)]


# -- fused int8 family -------------------------------------------------------

class _FusedQuantizedBase(OpDef):
    """int8 data+weight, f32 wscale (+f32 bias) — ops.quantized's
    convention with the activation/requantize epilogues fused in."""

    def list_arguments(self, p):
        args = ["data", "weight", "wscale"]
        if not p.no_bias:
            args.append("bias")
        return args

    def infer_type(self, p, in_types):
        i8, f32 = np.dtype(np.int8), np.dtype(np.float32)
        ins = [i8, i8, f32] + ([] if p.no_bias else [f32])
        out = i8 if p.out_scale is not None else f32
        return ins, [out], []


@register_op("_fused_quantized_FullyConnected",
             hint="fused_quantized_fullyconnected")
class FusedQuantizedFullyConnectedOp(_FusedQuantizedBase):
    """int8 GEMM (int32 accumulate) + dequant + bias + act (+ requant)
    in one body — the int8 serving layer as a single graph node."""
    params = [Param("num_hidden", int, required=True),
              Param("no_bias", bool, default=False),
              Param("scale_data", float, required=True)] + _EPILOGUE_PARAMS

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        num_input = int(np.prod(d[1:]))
        shapes = [d, (p.num_hidden, num_input), (p.num_hidden,)]
        if not p.no_bias:
            shapes.append((p.num_hidden,))
        return shapes, [(d[0], p.num_hidden)], []

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0].reshape(inputs[0].shape[0], -1)
        acc = lax.dot_general(x, inputs[1], (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * (np.float32(p.scale_data) * inputs[2])
        if not p.no_bias:
            out = out + inputs[3]
        return [_requantize(apply_act(out, p.act_type), p.out_scale)]


@register_op("_fused_quantized_Convolution",
             hint="fused_quantized_convolution")
class FusedQuantizedConvolutionOp(_FusedQuantizedBase):
    """int8 NCHW conv (int32 accumulate) + dequant + bias + act
    (+ requant) in one body."""
    params = [Param("kernel", "shape", required=True),
              Param("stride", "shape", default=(1, 1)),
              Param("dilate", "shape", default=(1, 1)),
              Param("pad", "shape", default=(0, 0)),
              Param("num_filter", int, required=True),
              Param("num_group", int, default=1),
              Param("no_bias", bool, default=False),
              Param("scale_data", float, required=True)] + _EPILOGUE_PARAMS

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        kh, kw = p.kernel
        wshape = (p.num_filter, d[1] // p.num_group, kh, kw)
        oshape = (d[0], p.num_filter,
                  _conv_out(d[2], kh, p.stride[0], p.pad[0], p.dilate[0]),
                  _conv_out(d[3], kw, p.stride[1], p.pad[1], p.dilate[1]))
        shapes = [d, wshape, (p.num_filter,)]
        if not p.no_bias:
            shapes.append((p.num_filter,))
        return shapes, [oshape], []

    def forward(self, p, inputs, aux, ctx):
        acc = lax.conv_general_dilated(
            inputs[0], inputs[1], window_strides=tuple(p.stride),
            padding=[(p.pad[0], p.pad[0]), (p.pad[1], p.pad[1])],
            rhs_dilation=tuple(p.dilate),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=p.num_group,
            preferred_element_type=jnp.int32)
        scale = (np.float32(p.scale_data) * inputs[2])[None, :, None, None]
        out = acc.astype(jnp.float32) * scale
        if not p.no_bias:
            out = out + inputs[3][None, :, None, None]
        return [_requantize(apply_act(out, p.act_type), p.out_scale)]


# -- fused elementwise chain -------------------------------------------------

# step name -> (needs_scalar, fn(x, scalar?)).  Exactly the single-input,
# shape- and dtype-preserving ops ElementwiseFusePass may chain.
ELEMWISE_STEP_OPS = {
    # activations (the Activation op's enum, by act_type)
    "relu": (False, jax.nn.relu),
    "sigmoid": (False, jax.nn.sigmoid),
    "tanh": (False, jnp.tanh),
    "softrelu": (False, jax.nn.softplus),
    # scalar arithmetic (the _*_scalar family)
    "_plus_scalar": (True, lambda x, s: jnp.add(x, s)),
    "_minus_scalar": (True, lambda x, s: jnp.subtract(x, s)),
    "_rminus_scalar": (True, lambda x, s: jnp.subtract(s, x)),
    "_mul_scalar": (True, lambda x, s: jnp.multiply(x, s)),
    "_div_scalar": (True, lambda x, s: jnp.divide(x, s)),
    "_rdiv_scalar": (True, lambda x, s: jnp.divide(s, x)),
    "_maximum_scalar": (True, jnp.maximum),
    "_minimum_scalar": (True, jnp.minimum),
    # unary math (tensor.py's simple-op family)
    "abs": (False, jnp.abs),
    "ceil": (False, jnp.ceil),
    "cos": (False, jnp.cos),
    "exp": (False, jnp.exp),
    "floor": (False, jnp.floor),
    "log": (False, jnp.log),
    "round": (False, jnp.round),
    "rsqrt": (False, lambda x: lax.rsqrt(x)),
    "sign": (False, jnp.sign),
    "sin": (False, jnp.sin),
    "sqrt": (False, jnp.sqrt),
    "square": (False, jnp.square),
}


def format_steps(steps) -> str:
    """[("relu", None), ("_mul_scalar", 2.0)] -> "relu;_mul_scalar:2.0"
    — the serialized form the ``steps`` param carries (json-stable)."""
    parts = []
    for name, scalar in steps:
        if name not in ELEMWISE_STEP_OPS:
            raise MXNetError("_fused_elemwise: unknown step %r (have %s)"
                             % (name, sorted(ELEMWISE_STEP_OPS)))
        parts.append(name if scalar is None
                     else "%s:%r" % (name, float(scalar)))
    return ";".join(parts)


def parse_steps(spec: str):
    """Inverse of :func:`format_steps`."""
    steps = []
    for part in (spec or "").split(";"):
        if not part:
            continue
        name, _, scalar = part.partition(":")
        if name not in ELEMWISE_STEP_OPS:
            raise MXNetError("_fused_elemwise: unknown step %r in %r"
                             % (name, spec))
        needs_scalar = ELEMWISE_STEP_OPS[name][0]
        if needs_scalar != bool(scalar):
            raise MXNetError("_fused_elemwise: step %r %s a scalar (%r)"
                             % (name, "needs" if needs_scalar
                                else "takes no", part))
        steps.append((name, float(scalar) if scalar else None))
    return steps


def apply_steps(x, spec: str):
    for name, scalar in parse_steps(spec):
        needs_scalar, fn = ELEMWISE_STEP_OPS[name]
        x = fn(x, np.float32(scalar)) if needs_scalar else fn(x)
    return x


@register_op("_fused_elemwise", hint="fused_elemwise")
class FusedElemwiseOp(OpDef):
    """A chain of single-input elementwise ops as one node: ``steps`` is
    the ';'-separated op list (``"relu;_mul_scalar:0.5;exp"``), applied
    in order in one traced body.  Shape- and dtype-preserving by
    construction (every eligible step is)."""
    params = [Param("steps", str, required=True,
                    doc="';'-joined step list, each 'op' or 'op:scalar' "
                        "(see ops.fused.ELEMWISE_STEP_OPS)")]

    def forward(self, p, inputs, aux, ctx):
        return [apply_steps(inputs[0], p.steps)]
