"""Transformer block ops: ``RMSNorm``, ``RotaryEmbedding``,
``CausalSelfAttention`` and the per-token loss head ``SoftmaxCELoss``
(SiLU and the gated product live inside ``_moe_expert_ffn``).

What a pre-norm decoder block needs beyond FullyConnected / Embedding /
Reshape, as plain jnp bodies (autodiff gives the gradients).  Statistics
run in float32 whatever the compute dtype: the RMS, the attention
softmax and the loss's log-sum-exp.

Attention never materializes the ``(B, H, T, T)`` scores: the op calls
ONE inner function, ``causal_attention``, which walks the queries in
blocks under ``lax.map`` and recomputes each block's scores in the
backward pass (``jax.checkpoint``), so its memory is one block's scores
and a kernel can replace the function later.

The bodies of ``CausalSelfAttention`` and ``SoftmaxCELoss`` run under a
``jax.named_scope`` (``attn.l<layer>``, ``lm_loss``) so a device trace
can tell the block's parts apart.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpDef, Param, register_op

__all__ = ["causal_attention", "rms_norm", "rotary_embedding"]

# queries per block of causal_attention: one block's float32 scores are
# B*H*ATTN_BLOCK_Q*T*4 bytes (0.5 GB at B=4, H=16, T=4096)
ATTN_BLOCK_Q = 512


def layer_scope(kind: str, layer):
    """``jax.named_scope`` of one block part: ``attn.l3``, ``moe_experts.l0``
    (no suffix where the builder gave no layer index)."""
    return jax.named_scope(kind if layer is None or layer < 0
                           else "%s.l%d" % (kind, layer))


def rms_norm(x, gamma, eps: float):
    """``x / sqrt(mean(x**2) + eps) * gamma`` over the last axis, the
    mean and the division in float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * gamma.astype(x.dtype)


def rotary_embedding(x, theta: float):
    """Rotary position embedding of ``(B, T, H, Dh)`` at positions
    ``0..T-1``, half-split pairing (dimension ``i`` rotates with
    ``i + Dh/2``, as the ``olmoe``/``llama`` modelling code), angles in
    float32."""
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / dh))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def causal_attention(q, k, v, scale: float):
    """Causal multi-head self-attention of ``(B, T, H, Dh)`` q, k, v ->
    ``(B, T, H, Dh)``; softmax in float32.  Queries go in blocks of
    ATTN_BLOCK_Q; a block's scores live only inside its (checkpointed)
    body, in the forward and again in the backward pass."""
    b, t, h, dh = q.shape
    bq = min(ATTN_BLOCK_Q, t)
    nb = -(-t // bq)
    pad = nb * bq - t
    if pad:
        # padded query rows see every key: finite, and cut off below
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = q.reshape(b, nb, bq, h, dh).transpose(1, 0, 2, 3, 4)
    k_pos = jnp.arange(t)

    @jax.checkpoint
    def one_block(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       preferred_element_type=jnp.float32) * scale
        q_pos = i * bq + jnp.arange(bq)
        s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None, None],
                      s, jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    if nb == 1:
        out = one_block((jnp.int32(0), blocks[0]))[None]
    else:
        out = lax.map(one_block, (jnp.arange(nb, dtype=jnp.int32), blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nb * bq, h, dh)
    return out[:, :t] if pad else out


@register_op("RMSNorm", hint="rmsnorm")
class RMSNormOp(OpDef):
    """Root-mean-square LayerNorm over the last axis (Zhang & Sennrich
    2019): no mean subtraction, no bias; float32 statistics."""
    params = [Param("eps", float, default=1e-5)]

    def list_arguments(self, p):
        return ["data", "gamma"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        return [d, (d[-1],)], [d], []

    def forward(self, p, inputs, aux, ctx):
        return [rms_norm(inputs[0], inputs[1], p.eps)]


@register_op("RotaryEmbedding", hint="rotary")
class RotaryEmbeddingOp(OpDef):
    """Rotary position embedding of ``(B, T, H, Dh)`` (Su et al. 2021),
    half-split pairing, positions ``0..T-1``."""
    params = [Param("theta", float, default=10000.0)]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is not None and (len(d) != 4 or d[3] % 2):
            raise MXNetError("RotaryEmbedding: data must be (batch, seq, "
                             "heads, even head_dim), got %r" % (d,))
        return in_shapes, [d], []

    def forward(self, p, inputs, aux, ctx):
        return [rotary_embedding(inputs[0], p.theta)]


@register_op("CausalSelfAttention", hint="attention")
class CausalSelfAttentionOp(OpDef):
    """Causal multi-head self-attention over ``(B, T, H, Dh)`` query,
    key and value: ``softmax(q k^T * scale + causal mask) v`` per head,
    softmax in float32, scores never materialized whole.  ``scale`` 0
    means ``Dh**-0.5``; ``layer`` names the trace scope."""
    params = [Param("scale", float, default=0.0),
              Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["query", "key", "value"]

    def infer_shape(self, p, in_shapes):
        d = next((s for s in in_shapes if s is not None), None)
        if d is None:
            return in_shapes, [None], []
        if len(d) != 4:
            raise MXNetError("CausalSelfAttention: inputs must be (batch, "
                             "seq, heads, head_dim), got %r" % (d,))
        for s in in_shapes:
            if s is not None and tuple(s) != tuple(d):
                raise MXNetError("CausalSelfAttention: query, key and value "
                                 "shapes differ: %r" % (in_shapes,))
        return [d, d, d], [d], []

    def forward(self, p, inputs, aux, ctx):
        q, k, v = inputs
        scale = p.scale or float(q.shape[-1]) ** -0.5
        with layer_scope("attn", p.layer):
            return [causal_attention(q, k, v, scale)]


def _softmax_ce(logits, label):
    """Per-row ``logsumexp(logits) - logits[label]`` in float32."""
    x = logits.astype(jnp.float32)
    idx = lax.stop_gradient(label).astype(jnp.int32)
    picked = jnp.take_along_axis(x, idx[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(x, axis=-1) - picked


@register_op("SoftmaxCELoss", hint="softmaxceloss")
class SoftmaxCELossOp(OpDef):
    """Per-token softmax cross-entropy that emits the LOSS: logits
    ``(N, V)`` + integer labels ``(N,)`` -> float32 ``(N,)``, the
    log-sum-exp in float32.  Differentiable (its gradient is
    ``(softmax - onehot) * head``); wrap it in ``MakeLoss`` to train on
    it.  Where ``SoftmaxOutput`` hands the metric ``(N, V)``
    probabilities, this hands it N numbers."""

    def list_arguments(self, p):
        return ["data", "label"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        if len(d) != 2:
            raise MXNetError("SoftmaxCELoss: data must be (rows, classes), "
                             "got %r" % (d,))
        return [d, (d[0],)], [(d[0],)], []

    def infer_type(self, p, in_types):
        t = in_types[0] if in_types[0] is not None else np.dtype(np.float32)
        lt = in_types[1] if in_types[1] is not None else np.dtype(np.float32)
        return [t, lt], [np.dtype(np.float32)], []

    def forward(self, p, inputs, aux, ctx):
        with jax.named_scope("lm_loss"):
            return [_softmax_ce(inputs[0], inputs[1])]
