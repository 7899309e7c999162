"""Vision ops that are hand-written CUDA kernels in the reference.

Reference: src/operator/roi_pooling.cc:235, spatial_transformer-inl.h:264,
correlation.cu:609.

TPU-native: expressed as vectorized lax/jnp programs (gather/scatter/
reduce_window) so XLA tiles them; gradients come free from autodiff (the
reference hand-writes backward kernels for all three).  A Pallas rewrite is
the planned fast path once profiles justify it.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpDef, Param, register_op


@register_op("ROIPooling", hint="roipooling")
class ROIPoolingOp(OpDef):
    """reference roi_pooling.cc: max-pool each ROI into a fixed grid."""
    params = [Param("pooled_size", "shape", required=True),
              Param("spatial_scale", float, required=True)]

    def list_arguments(self, p):
        return ["data", "rois"]

    def infer_shape(self, p, in_shapes):
        d, r = in_shapes
        if d is None or r is None:
            return in_shapes, [None], []
        ph, pw = p.pooled_size
        return [d, r], [(r[0], d[1], ph, pw)], []

    def forward(self, p, inputs, aux, ctx):
        data, rois = inputs
        n, c, h, w = data.shape
        ph, pw = p.pooled_size

        def one_roi(roi):
            batch = roi[0].astype(jnp.int32)
            x1 = jnp.round(roi[1] * p.spatial_scale)
            y1 = jnp.round(roi[2] * p.spatial_scale)
            x2 = jnp.round(roi[3] * p.spatial_scale)
            y2 = jnp.round(roi[4] * p.spatial_scale)
            roi_h = jnp.maximum(y2 - y1 + 1.0, 1.0)
            roi_w = jnp.maximum(x2 - x1 + 1.0, 1.0)
            bin_h = roi_h / ph
            bin_w = roi_w / pw
            img = data[batch]                      # (C, H, W)
            ys = jnp.arange(h, dtype=jnp.float32)
            xs = jnp.arange(w, dtype=jnp.float32)
            # membership of each pixel in each bin (P_h, H) and (P_w, W)
            bh = jnp.arange(ph, dtype=jnp.float32)
            bw = jnp.arange(pw, dtype=jnp.float32)
            hstart = jnp.clip(jnp.floor(bh * bin_h) + y1, 0, h)
            hend = jnp.clip(jnp.ceil((bh + 1) * bin_h) + y1, 0, h)
            wstart = jnp.clip(jnp.floor(bw * bin_w) + x1, 0, w)
            wend = jnp.clip(jnp.ceil((bw + 1) * bin_w) + x1, 0, w)
            hmask = (ys[None, :] >= hstart[:, None]) & (ys[None, :] < hend[:, None])
            wmask = (xs[None, :] >= wstart[:, None]) & (xs[None, :] < wend[:, None])
            mask = hmask[:, None, :, None] & wmask[None, :, None, :]  # (Ph,Pw,H,W)
            neg = jnp.finfo(img.dtype).min
            masked = jnp.where(mask[None], img[:, None, None, :, :], neg)
            out = jnp.max(masked, axis=(3, 4))          # (C, Ph, Pw)
            any_px = jnp.any(mask, axis=(2, 3))
            return jnp.where(any_px[None], out, 0.0)

        return [jax.vmap(one_roi)(rois)]


@register_op("SpatialTransformer", hint="spatialtransformer")
class SpatialTransformerOp(OpDef):
    """reference spatial_transformer-inl.h: affine grid + bilinear sampler."""
    params = [Param("target_shape", "shape", required=True),
              Param("transform_type", str, default="affine", enum=["affine"]),
              Param("sampler_type", str, default="bilinear", enum=["bilinear"])]

    def list_arguments(self, p):
        return ["data", "loc"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        th, tw = p.target_shape
        return [d, (d[0], 6)], [(d[0], d[1], th, tw)], []

    def forward(self, p, inputs, aux, ctx):
        data, loc = inputs
        n, c, h, w = data.shape
        th, tw = p.target_shape
        # normalized target grid in [-1, 1]
        ys = jnp.linspace(-1.0, 1.0, th)
        xs = jnp.linspace(-1.0, 1.0, tw)
        gx, gy = jnp.meshgrid(xs, ys)           # (th, tw)
        grid = jnp.stack([gx.ravel(), gy.ravel(), jnp.ones(th * tw)])  # (3, P)

        theta = loc.reshape(n, 2, 3)
        src = jnp.einsum("nij,jp->nip", theta, grid)  # (n, 2, P) -> x,y in [-1,1]
        sx = (src[:, 0] + 1.0) * (w - 1) / 2.0
        sy = (src[:, 1] + 1.0) * (h - 1) / 2.0

        x0 = jnp.floor(sx)
        y0 = jnp.floor(sy)
        wx = sx - x0
        wy = sy - y0

        def sample(img, xi, yi):
            xi_c = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
            yi_c = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
            valid = ((xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1))
            vals = img[:, yi_c, xi_c]           # (c, P)
            return vals * valid.astype(img.dtype)[None]

        def one(img, x0i, y0i, wxi, wyi):
            v00 = sample(img, x0i, y0i)
            v01 = sample(img, x0i + 1, y0i)
            v10 = sample(img, x0i, y0i + 1)
            v11 = sample(img, x0i + 1, y0i + 1)
            out = (v00 * (1 - wxi) * (1 - wyi) + v01 * wxi * (1 - wyi)
                   + v10 * (1 - wxi) * wyi + v11 * wxi * wyi)
            return out.reshape(c, th, tw)

        return [jax.vmap(one)(data, x0, y0, wx, wy)]


@register_op("Correlation", hint="correlation")
class CorrelationOp(OpDef):
    """reference correlation.cu (FlowNet correlation layer)."""
    params = [Param("kernel_size", int, default=1),
              Param("max_displacement", int, default=1),
              Param("stride1", int, default=1),
              Param("stride2", int, default=1),
              Param("pad_size", int, default=0),
              Param("is_multiply", bool, default=True)]

    def list_arguments(self, p):
        return ["data1", "data2"]

    def _geom(self, p, d):
        n, c, h, w = d
        ph, pw = h + 2 * p.pad_size, w + 2 * p.pad_size
        kr = p.kernel_size // 2
        br = p.max_displacement + kr
        oh = int(np.ceil((ph - br * 2) / float(p.stride1)))
        ow = int(np.ceil((pw - br * 2) / float(p.stride1)))
        ng = p.max_displacement // p.stride2
        d2 = 2 * ng + 1
        return ph, pw, kr, br, oh, ow, ng, d2

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        _, _, _, _, oh, ow, _, d2 = self._geom(p, d)
        return [d, d], [(d[0], d2 * d2, oh, ow)], []

    def forward(self, p, inputs, aux, ctx):
        a, b = inputs
        n, c, h, w = a.shape
        ph, pw, kr, br, oh, ow, ng, d2 = self._geom(p, a.shape)
        def lax_path(a, b):
            pad = [(0, 0), (0, 0), (p.pad_size, p.pad_size),
                   (p.pad_size, p.pad_size)]
            ap = jnp.pad(a, pad)
            bp = jnp.pad(b, pad)
            outs = []
            ksz = p.kernel_size
            norm = float(c * ksz * ksz)
            for dy in range(-ng, ng + 1):
                for dx in range(-ng, ng + 1):
                    sy, sx = dy * p.stride2, dx * p.stride2
                    shifted = jnp.roll(bp, shift=(-sy, -sx), axis=(2, 3))
                    if p.is_multiply:
                        prod = ap * shifted
                    else:
                        prod = jnp.abs(ap - shifted)
                    # sum over channel and kernel window
                    summed = jnp.sum(prod, axis=1, keepdims=True)
                    if ksz > 1:
                        summed = lax.reduce_window(
                            summed, 0.0, lax.add, (1, 1, ksz, ksz),
                            (1, 1, 1, 1),
                            [(0, 0), (0, 0), (kr, kr), (kr, kr)])
                    # sample output grid starting at border br with stride1
                    sl = summed[:, :, br:br + oh * p.stride1:p.stride1,
                                br:br + ow * p.stride1:p.stride1]
                    outs.append(sl / norm)
            return jnp.concatenate(outs, axis=1)

        # Pallas fast path (the reference's hand-written correlation.cu
        # equivalent) where the program is lowered for a TPU: one
        # VMEM-resident displacement loop instead of d2*d2 HBM passes.
        # Covers the FlowNet configuration.
        if (p.kernel_size == 1 and p.stride1 == 1
                and p.pad_size == p.max_displacement
                and not getattr(ctx, "is_train", False)):
            # inference only: pallas_call has no reverse-mode rule, so
            # training must take the differentiable lax lowering
            from .pallas_kernels import correlation as _pallas_corr
            out = _pallas_corr(a, b, p.max_displacement, p.stride2,
                               p.is_multiply, dense=lax_path)
            if out is not None:
                return [out]
        return [lax_path(a, b)]
