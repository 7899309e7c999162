"""Neural-network layer ops.

Reference: src/operator/{activation,fully_connected,convolution,deconvolution,
pooling,batch_norm,dropout,lrn,l2_normalization,leaky_relu,softmax_output,
softmax_activation,regression_output,make_loss,svm_output,upsampling,
identity_attach_KL_sparse_reg}-inl.h.

TPU-native: convs/matmuls go through lax.conv_general_dilated / jnp.dot so the
MXU sees large fused GEMMs; elementwise tails fuse in XLA.  NCHW semantics are
preserved at the API level (reference layout); XLA:TPU relayouts internally.
Loss layers reproduce reference *gradient* semantics via jax.custom_vjp
(their backward is defined, not derived — SoftmaxOutput injects
(softmax - onehot)·scale regardless of head gradient).
"""
from __future__ import annotations

from typing import List

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpDef, Param, register_op


def _conv_out(x, k, s, p, d=1):
    eff = d * (k - 1) + 1
    return (x + 2 * p - eff) // s + 1


def _relu2(x):
    """The squared ReLU, ``relu(x) ** 2``: an exact zero, with a zero
    gradient, wherever the ReLU has one."""
    return jnp.square(jax.nn.relu(x))


# act_type -> function, for Activation and the routed experts (ops/moe.py)
ACTIVATIONS = {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
               "tanh": jnp.tanh, "softrelu": jax.nn.softplus,
               "silu": jax.nn.silu, "relu2": _relu2}


@register_op("Activation", hint="activation")
class ActivationOp(OpDef):
    """reference activation-inl.h:182."""
    params = [Param("act_type", str, required=True, enum=list(ACTIVATIONS))]

    def forward(self, p, inputs, aux, ctx):
        return [ACTIVATIONS[p.act_type](inputs[0])]


@register_op("FullyConnected", hint="fullyconnected")
class FullyConnectedOp(OpDef):
    """reference fully_connected-inl.h:242.  y = x·Wᵀ + b, x flattened to 2D."""
    params = [Param("num_hidden", int, required=True),
              Param("no_bias", bool, default=False)]

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

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0].reshape(inputs[0].shape[0], -1)
        out = jnp.dot(x, inputs[1].T)
        if not p.no_bias:
            out = out + inputs[2]
        return [out]


@register_op("Convolution", hint="convolution")
class ConvolutionOp(OpDef):
    """reference convolution-inl.h:483 (im2col+gemm -> MXU conv)."""
    params = [Param("kernel", "shape", required=True),
              Param("stride", "shape", default=(1, 1)),
              Param("dilate", "shape", default=(1, 1)),
              Param("pad", "shape", default=(0, 0)),
              Param("num_filter", int, required=True),
              Param("num_group", int, default=1),
              Param("workspace", int, default=512),
              Param("no_bias", bool, default=False),
              Param("cudnn_tune", str, default=None),
              Param("cudnn_off", bool, default=False)]

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

    def forward(self, p, inputs, aux, ctx):
        x, w = inputs[0], inputs[1]
        out = lax.conv_general_dilated(
            x, w, window_strides=tuple(p.stride),
            padding=[(p.pad[0], p.pad[0]), (p.pad[1], p.pad[1])],
            rhs_dilation=tuple(p.dilate),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=p.num_group)
        if not p.no_bias:
            out = out + inputs[2][None, :, None, None]
        return [out]


@register_op("Deconvolution", hint="deconvolution")
class DeconvolutionOp(OpDef):
    """reference deconvolution-inl.h: out = s·(x-1) + k - 2p + adj."""
    params = [Param("kernel", "shape", required=True),
              Param("stride", "shape", default=(1, 1)),
              Param("pad", "shape", default=(0, 0)),
              Param("adj", "shape", default=(0, 0)),
              Param("target_shape", "shape", default=(0, 0)),
              Param("num_filter", int, required=True),
              Param("num_group", int, default=1),
              Param("workspace", int, default=512),
              Param("no_bias", bool, default=True)]

    def list_arguments(self, p):
        return ["data", "weight"] if p.no_bias else ["data", "weight", "bias"]

    def _out_hw(self, p, d):
        if p.target_shape and (p.target_shape[0] != 0 or p.target_shape[1] != 0):
            return tuple(p.target_shape)
        kh, kw = p.kernel
        return (p.stride[0] * (d[2] - 1) + kh - 2 * p.pad[0] + p.adj[0],
                p.stride[1] * (d[3] - 1) + kw - 2 * p.pad[1] + p.adj[1])

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        kh, kw = p.kernel
        wshape = (d[1], p.num_filter // p.num_group, kh, kw)
        oh, ow = self._out_hw(p, d)
        shapes = [d, wshape] + ([] if p.no_bias else [(p.num_filter,)])
        return shapes, [(d[0], p.num_filter, oh, ow)], []

    def forward(self, p, inputs, aux, ctx):
        x, w = inputs[0], inputs[1]
        kh, kw = p.kernel
        oh, ow = self._out_hw(p, x.shape)
        # transposed conv = conv with lhs dilation; padding k-1-p (+adj on high side)
        pad_h = kh - 1 - p.pad[0]
        pad_w = kw - 1 - p.pad[1]
        # weight (in_c, out_c/g, kh, kw), spatially flipped for the
        # transposed conv.  With groups, lax wants rhs I = in_c/g and the
        # O dim holding all out channels group-major, so regroup the
        # reference layout accordingly.
        w = jnp.flip(w, axis=(2, 3))
        if p.num_group > 1:
            g = p.num_group
            in_c, out_pg = w.shape[0], w.shape[1]
            w = w.reshape(g, in_c // g, out_pg, kh, kw)
            w = jnp.transpose(w, (1, 0, 2, 3, 4))
            w = w.reshape(in_c // g, g * out_pg, kh, kw)
        out = lax.conv_general_dilated(
            x, w,
            window_strides=(1, 1),
            padding=[(pad_h, pad_h + p.adj[0]), (pad_w, pad_w + p.adj[1])],
            lhs_dilation=tuple(p.stride),
            dimension_numbers=("NCHW", "IOHW", "NCHW"),
            feature_group_count=p.num_group)
        if not p.no_bias:
            out = out + inputs[2][None, :, None, None]
        return [out]


@register_op("Pooling", hint="pooling")
class PoolingOp(OpDef):
    """reference pooling-inl.h (floor convention, line 197)."""
    params = [Param("kernel", "shape", required=True),
              Param("pool_type", str, default="max", enum=["max", "avg", "sum"]),
              Param("global_pool", bool, default=False),
              Param("stride", "shape", default=(1, 1)),
              Param("pad", "shape", default=(0, 0))]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        if p.global_pool:
            return [d], [(d[0], d[1], 1, 1)], []
        kh, kw = p.kernel
        oshape = (d[0], d[1],
                  1 + (d[2] + 2 * p.pad[0] - kh) // p.stride[0],
                  1 + (d[3] + 2 * p.pad[1] - kw) // p.stride[1])
        return [d], [oshape], []

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        if p.global_pool:
            kh, kw = x.shape[2], x.shape[3]
            stride = (1, 1)
            pad = (0, 0)
        else:
            kh, kw = p.kernel
            stride = tuple(p.stride)
            pad = tuple(p.pad)
        dims = (1, 1, kh, kw)
        strides = (1, 1) + stride
        padding = [(0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])]
        # floor convention: lax.reduce_window with explicit padding matches
        if p.pool_type == "max":
            init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
            out = lax.reduce_window(x, init, lax.max, dims, strides, padding)
        else:
            out = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
            if p.pool_type == "avg":
                out = out / (kh * kw)
        # clip to floor output size (reduce_window may differ with padding)
        if not p.global_pool:
            oh = 1 + (x.shape[2] + 2 * pad[0] - kh) // stride[0]
            ow = 1 + (x.shape[3] + 2 * pad[1] - kw) // stride[1]
            out = out[:, :, :oh, :ow]
        return [out]


@register_op("BatchNorm", hint="batchnorm")
class BatchNormOp(OpDef):
    """reference batch_norm-inl.h:305 (eps=1e-3, momentum=0.9, fix_gamma=True).

    Aux states (moving_mean, moving_var) are threaded functionally: forward in
    train mode returns updated aux (SURVEY §7 hard-part 6)."""
    params = [Param("eps", float, default=1e-3),
              Param("momentum", float, default=0.9),
              Param("fix_gamma", bool, default=True),
              Param("use_global_stats", bool, default=False)]

    def list_arguments(self, p):
        return ["data", "gamma", "beta"]

    def list_outputs(self, p):
        # reference outputs [output, mean, var] but only output is visible by default
        return ["output"]

    def list_auxiliary_states(self, p):
        return ["moving_mean", "moving_var"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        c = (d[1],) if len(d) > 1 else (d[0],)
        return [d, c, c], [d], [c, c]

    def forward(self, p, inputs, aux, ctx):
        x, gamma, beta = inputs
        moving_mean, moving_var = aux
        axes = (0,) + tuple(range(2, x.ndim))
        if p.fix_gamma:
            gamma = jnp.ones_like(gamma)
        bshape = [1, -1] + [1] * (x.ndim - 2)
        # statistics in f32 regardless of compute dtype (bf16-safe on TPU)
        xf = x.astype(jnp.float32)
        if ctx.is_train and not p.use_global_stats:
            mean = jnp.mean(xf, axis=axes)
            var = jnp.mean(jnp.square(xf - mean.reshape(bshape)), axis=axes)
            y = (xf - mean.reshape(bshape)) * lax.rsqrt(var.reshape(bshape) + p.eps)
            y = gamma.astype(jnp.float32).reshape(bshape) * y \
                + beta.astype(jnp.float32).reshape(bshape)
            m = p.momentum
            mm = moving_mean.astype(jnp.float32)
            mv = moving_var.astype(jnp.float32)
            new_mean = (m * mm + (1 - m) * lax.stop_gradient(mean)).astype(moving_mean.dtype)
            new_var = (m * mv + (1 - m) * lax.stop_gradient(var)).astype(moving_var.dtype)
            return [y.astype(x.dtype)], [new_mean, new_var]
        y = (xf - moving_mean.astype(jnp.float32).reshape(bshape)) \
            * lax.rsqrt(moving_var.astype(jnp.float32).reshape(bshape) + p.eps)
        y = gamma.astype(jnp.float32).reshape(bshape) * y \
            + beta.astype(jnp.float32).reshape(bshape)
        return [y.astype(x.dtype)], [moving_mean, moving_var]


@register_op("CuDNNBatchNorm", hint="cudnnbatchnorm")
class CuDNNBatchNormOp(BatchNormOp):
    """reference cudnn_batch_norm-inl.h — same semantics; on TPU the XLA
    fusion IS the fast path, so this is an alias of BatchNorm."""


@register_op("Dropout", hint="dropout")
class DropoutOp(OpDef):
    """reference dropout-inl.h (scale by 1/(1-p) at train time)."""
    params = [Param("p", float, default=0.5)]
    needs_rng = True

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        if not ctx.is_train or p.p <= 0.0:
            return [x]
        keep = 1.0 - p.p
        mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)]


@register_op("LRN", hint="lrn")
class LRNOp(OpDef):
    """reference lrn-inl.h: cross-channel, alpha/nsize scaling."""
    params = [Param("alpha", float, default=1e-4),
              Param("beta", float, default=0.75),
              Param("knorm", float, default=2.0),
              Param("nsize", int, required=True)]

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        sq = jnp.square(x)
        half = p.nsize // 2
        pad = [(0, 0), (half, p.nsize - 1 - half), (0, 0), (0, 0)]
        summed = lax.reduce_window(sq, 0.0, lax.add, (1, p.nsize, 1, 1),
                                   (1, 1, 1, 1), pad)
        norm = jnp.power(p.knorm + (p.alpha / p.nsize) * summed, -p.beta)
        return [x * norm]


@register_op("L2Normalization", hint="l2normalization")
class L2NormalizationOp(OpDef):
    """reference l2_normalization-inl.h: per-instance L2 normalize."""
    params = [Param("eps", float, default=1e-10)]

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        flat = x.reshape(x.shape[0], -1)
        norm = jnp.sqrt(jnp.sum(jnp.square(flat), axis=1, keepdims=True) + p.eps)
        return [(flat / norm).reshape(x.shape)]


@register_op("LeakyReLU", hint="leakyrelu")
class LeakyReLUOp(OpDef):
    """reference leaky_relu-inl.h:328 (leaky/prelu/rrelu/elu)."""
    params = [Param("act_type", str, default="leaky",
                    enum=["leaky", "prelu", "rrelu", "elu"]),
              Param("slope", float, default=0.25),
              Param("lower_bound", float, default=0.125),
              Param("upper_bound", float, default=0.334)]
    needs_rng = True

    def list_arguments(self, p):
        return ["data", "gamma"] if p.act_type == "prelu" else ["data"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        if p.act_type == "prelu":
            return [d, (d[1],)], [d], []
        return [d], [d], []

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        if p.act_type == "leaky":
            return [jnp.where(x > 0, x, p.slope * x)]
        if p.act_type == "elu":
            return [jnp.where(x > 0, x, p.slope * (jnp.exp(x) - 1))]
        if p.act_type == "prelu":
            gamma = inputs[1].reshape([1, -1] + [1] * (x.ndim - 2))
            return [jnp.where(x > 0, x, gamma * x)]
        if p.act_type == "rrelu":
            if ctx.is_train:
                slope = jax.random.uniform(ctx.rng, x.shape,
                                           minval=p.lower_bound,
                                           maxval=p.upper_bound)
                slope = lax.stop_gradient(slope)
            else:
                slope = (p.lower_bound + p.upper_bound) / 2.0
            return [jnp.where(x > 0, x, slope * x)]
        raise MXNetError("unknown act_type %s" % p.act_type)


@register_op("SoftmaxActivation", hint="softmaxactivation")
class SoftmaxActivationOp(OpDef):
    """reference softmax_activation-inl.h (mode instance/channel)."""
    params = [Param("mode", str, default="instance", enum=["instance", "channel"])]

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        if p.mode == "channel":
            return [jax.nn.softmax(x, axis=1)]
        flat = x.reshape(x.shape[0], -1)
        return [jax.nn.softmax(flat, axis=1).reshape(x.shape)]


def _softmax_output_forward(p, data, label):
    """Forward softmax + custom_vjp reproducing reference backward
    (softmax_output-inl.h:96-195): d_data = (out - onehot(label)) · scale."""

    def fwd_only(data, label):
        if p.multi_output:
            n, k = data.shape[0], data.shape[1]
            d3 = data.reshape(n, k, -1)
            return jax.nn.softmax(d3, axis=1).reshape(data.shape)
        n = data.shape[0]
        d2 = data.reshape(n, -1)
        return jax.nn.softmax(d2, axis=1).reshape(data.shape)

    @jax.custom_vjp
    def f(data, label):
        return fwd_only(data, label)

    def f_fwd(data, label):
        out = fwd_only(data, label)
        return out, (out, label)

    def f_bwd(res, g):
        out, label = res
        del g  # reference ignores head gradient on loss layers
        if out.shape == label.shape:
            grad = (out - label) * p.grad_scale
            return grad, jnp.zeros_like(label)
        if p.multi_output:
            n, k = out.shape[0], out.shape[1]
            o3 = out.reshape(n, k, -1)
            lab = label.reshape(n, -1).astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, k, dtype=out.dtype)  # (n, rest, k)
            onehot = jnp.transpose(onehot, (0, 2, 1))
            grad = o3 - onehot
            if p.use_ignore:
                mask = (label.reshape(n, 1, -1) != p.ignore_label)
                grad = grad * mask.astype(grad.dtype)
            rest = o3.shape[2]
            if p.normalization == "batch":
                valid = float(n) * rest
                grad = grad * (p.grad_scale / valid)
            elif p.normalization == "valid":
                valid = jnp.maximum(jnp.sum(label != p.ignore_label), 1)
                grad = grad * (p.grad_scale / valid.astype(grad.dtype))
            else:
                grad = grad * (p.grad_scale / rest)
            return grad.reshape(out.shape), jnp.zeros_like(label)
        n = out.shape[0]
        o2 = out.reshape(n, -1)
        lab = label.reshape(-1).astype(jnp.int32)
        onehot = jax.nn.one_hot(lab, o2.shape[1], dtype=out.dtype)
        grad = o2 - onehot
        if p.use_ignore:
            mask = (label.reshape(-1, 1) != p.ignore_label)
            grad = grad * mask.astype(grad.dtype)
        if p.normalization == "batch":
            grad = grad * (p.grad_scale / n)
        elif p.normalization == "valid":
            valid = jnp.maximum(jnp.sum(label != p.ignore_label), 1)
            grad = grad * (p.grad_scale / valid.astype(grad.dtype))
        else:
            grad = grad * p.grad_scale
        return grad.reshape(out.shape), jnp.zeros_like(label)

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


@register_op("SoftmaxOutput", hint="softmaxoutput")
class SoftmaxOutputOp(OpDef):
    """reference softmax_output-inl.h:342."""
    head_grad_optional = True
    params = [Param("grad_scale", float, default=1.0),
              Param("ignore_label", float, default=-1.0),
              Param("multi_output", bool, default=False),
              Param("use_ignore", bool, default=False),
              # prob_label: label is a dense distribution shaped like the
              # output (reference softmax.cc's deprecated Softmax form,
              # used by the autoencoder example's softmax decoder)
              Param("prob_label", bool, default=False),
              Param("normalization", str, default="null",
                    enum=["null", "batch", "valid"])]

    def list_arguments(self, p):
        return ["data", "label"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        if p.prob_label:
            lshape = d
        elif p.multi_output:
            lshape = (d[0],) + tuple(d[2:])
        else:
            lshape = (d[0],)
        return [d, lshape], [d], []

    def forward(self, p, inputs, aux, ctx):
        return [_softmax_output_forward(p, inputs[0], inputs[1])]


@register_op("Softmax", hint="softmax")
class SoftmaxOp(SoftmaxOutputOp):
    """Deprecated alias of SoftmaxOutput (reference softmax_output.cc)."""


def _regression_forward(p, kind, data, label):
    def fwd_only(data):
        flat = data.reshape(data.shape[0], -1)
        if kind == "logistic":
            return jax.nn.sigmoid(flat).reshape(data.shape)
        return data

    @jax.custom_vjp
    def f(data, label):
        return fwd_only(data)

    def f_fwd(data, label):
        out = fwd_only(data)
        return out, (out, label)

    def f_bwd(res, g):
        out, label = res
        del g
        num_output = int(np.prod(label.shape[1:])) if label.ndim > 1 else 1
        lab = label.reshape(out.shape).astype(out.dtype)
        if kind == "mae":
            grad = jnp.sign(out - lab)
        else:  # linear and logistic share (out - label)
            grad = out - lab
        grad = grad * (p.grad_scale / num_output)
        return grad, jnp.zeros_like(label)

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


class _RegressionBase(OpDef):
    head_grad_optional = True
    params = [Param("grad_scale", float, default=1.0)]
    kind = "linear"

    def list_arguments(self, p):
        return ["data", "label"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        l = in_shapes[1] if len(in_shapes) > 1 else None
        if l is not None and int(np.prod(l)) == int(np.prod(d)):
            # reference accepts any label layout with matching element
            # count ((N,1) vs (N,)); the backward reshapes to out.shape
            lshape = l
        elif len(d) == 2 and d[1] == 1:
            lshape = (d[0],)
        else:
            lshape = d
        return [d, lshape], [d], []

    def forward(self, p, inputs, aux, ctx):
        return [_regression_forward(p, self.kind, inputs[0], inputs[1])]


@register_op("LinearRegressionOutput", hint="linearregressionoutput")
class LinearRegressionOutputOp(_RegressionBase):
    """reference regression_output-inl.h (identity fwd, out-label bwd)."""
    kind = "linear"


@register_op("LogisticRegressionOutput", hint="logisticregressionoutput")
class LogisticRegressionOutputOp(_RegressionBase):
    """reference regression_output-inl.h (sigmoid fwd, out-label bwd)."""
    kind = "logistic"


@register_op("MAERegressionOutput", hint="maeregressionoutput")
class MAERegressionOutputOp(_RegressionBase):
    """reference regression_output-inl.h (identity fwd, sign(out-label) bwd)."""
    kind = "mae"


@register_op("MakeLoss", hint="makeloss")
class MakeLossOp(OpDef):
    """reference make_loss-inl.h: forward identity; backward injects
    grad_scale (optionally normalized) regardless of head gradient."""
    head_grad_optional = True
    params = [Param("grad_scale", float, default=1.0),
              Param("normalization", str, default="null",
                    enum=["null", "batch", "valid"]),
              Param("valid_thresh", float, default=0.0)]

    def forward(self, p, inputs, aux, ctx):
        @jax.custom_vjp
        def f(x):
            return x

        def f_fwd(x):
            return x, x

        def f_bwd(x, g):
            del g
            scale = p.grad_scale
            if p.normalization == "batch":
                scale = scale / x.shape[0]
            elif p.normalization == "valid":
                valid = jnp.maximum(jnp.sum(x > p.valid_thresh), 1)
                return (jnp.full_like(x, p.grad_scale) / valid.astype(x.dtype),)
            return (jnp.full_like(x, scale),)

        f.defvjp(f_fwd, f_bwd)
        return [f(inputs[0])]


@register_op("SVMOutput", hint="svmoutput")
class SVMOutputOp(OpDef):
    """reference svm_output-inl.h: hinge-loss gradient layer."""
    head_grad_optional = True
    params = [Param("margin", float, default=1.0),
              Param("regularization_coefficient", float, default=1.0),
              Param("use_linear", bool, default=False)]

    def list_arguments(self, p):
        return ["data", "label"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        return [d, (d[0],)], [d], []

    def forward(self, p, inputs, aux, ctx):
        @jax.custom_vjp
        def f(data, label):
            return data

        def f_fwd(data, label):
            return data, (data, label)

        def f_bwd(res, g):
            data, label = res
            del g
            n, k = data.shape[0], data.shape[1]
            lab = label.astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, k, dtype=data.dtype)
            score_true = jnp.take_along_axis(data, lab[:, None], axis=1)
            if p.use_linear:
                # L1-SVM: grad = coeff * indicator
                viol = (data - score_true + p.margin > 0).astype(data.dtype)
                grad = p.regularization_coefficient * (viol * (1 - onehot)
                                                       - onehot * (jnp.sum(viol * (1 - onehot),
                                                                            axis=1, keepdims=True)))
            else:
                # L2-SVM
                m = jnp.maximum(0.0, data - score_true + p.margin) * (1 - onehot)
                grad = 2 * p.regularization_coefficient * (
                    m - onehot * jnp.sum(m, axis=1, keepdims=True))
            return grad, jnp.zeros_like(label)

        f.defvjp(f_fwd, f_bwd)
        return [f(inputs[0], inputs[1])]


@register_op("UpSampling", hint="upsampling")
class UpSamplingOp(OpDef):
    """reference upsampling-inl.h (nearest + bilinear-as-deconv)."""
    params = [Param("scale", int, required=True),
              Param("num_filter", int, default=0),
              Param("sample_type", str, required=True, enum=["nearest", "bilinear"]),
              Param("multi_input_mode", str, default="concat", enum=["concat", "sum"]),
              Param("num_args", int, default=1),
              Param("workspace", int, default=512)]
    variable_args = "num_args"

    def list_arguments(self, p):
        if p.sample_type == "bilinear":
            return ["data", "weight"]
        if p.num_args == 1:
            return ["data"]
        return ["arg%d" % i for i in range(p.num_args)]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        oh, ow = d[2] * p.scale, d[3] * p.scale
        if p.sample_type == "bilinear":
            k = 2 * p.scale - p.scale % 2
            wshape = (d[1], 1, k, k)
            return [d, wshape], [(d[0], d[1], oh, ow)], []
        if p.num_args == 1:
            return [d], [(d[0], d[1], oh, ow)], []
        c = int(np.sum([s[1] for s in in_shapes])) if p.multi_input_mode == "concat" else d[1]
        return in_shapes, [(d[0], c, oh, ow)], []

    def forward(self, p, inputs, aux, ctx):
        def up_nearest(x):
            x = jnp.repeat(x, p.scale, axis=2)
            return jnp.repeat(x, p.scale, axis=3)

        if p.sample_type == "bilinear":
            x, w = inputs
            k = 2 * p.scale - p.scale % 2
            pad = int(np.ceil((p.scale - 1) / 2.0))
            # depthwise transposed conv: weight (C, 1, k, k) is OIHW —
            # with feature_group_count=C the rhs in-feature dim must be
            # C/groups = 1
            out = lax.conv_general_dilated(
                x, jnp.flip(w, axis=(2, 3)),
                window_strides=(1, 1),
                padding=[(k - 1 - pad, k - 1 - pad)] * 2,
                lhs_dilation=(p.scale, p.scale),
                dimension_numbers=("NCHW", "OIHW", "NCHW"),
                feature_group_count=x.shape[1])
            return [out]
        ups = [up_nearest(x) for x in inputs]
        if len(ups) == 1:
            return [ups[0]]
        if p.multi_input_mode == "sum":
            out = ups[0]
            for u in ups[1:]:
                out = out + u
            return [out]
        return [jnp.concatenate(ups, axis=1)]


@register_op("IdentityAttachKLSparseReg", hint="identityattachklsparsereg")
class IdentityAttachKLSparseRegOp(OpDef):
    """reference identity_attach_KL_sparse_reg-inl.h: identity forward with a
    KL sparsity penalty gradient added in backward."""
    params = [Param("sparseness_target", float, default=0.1),
              Param("penalty", float, default=0.001),
              Param("momentum", float, default=0.9)]

    def list_auxiliary_states(self, p):
        return ["moving_avg"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        return [d], [d], [(1,)]

    def forward(self, p, inputs, aux, ctx):
        x = inputs[0]
        rho_hat = jnp.mean(x)
        new_avg = p.momentum * aux[0] + (1 - p.momentum) * lax.stop_gradient(rho_hat)

        @jax.custom_vjp
        def f(x):
            return x

        def f_fwd(x):
            return x, jnp.mean(x)

        def f_bwd(rho, g):
            rho = jnp.clip(rho, 1e-6, 1 - 1e-6)
            t = p.sparseness_target
            kl_grad = p.penalty * (-t / rho + (1 - t) / (1 - rho))
            return (g + kl_grad,)

        f.defvjp(f_fwd, f_bwd)
        return [f(x)], [new_avg]
