"""Linear-attention block ops: ``KimiDeltaAttention`` (the gated delta
rule with a per-channel decay, "KDA": Kimi Linear, arXiv:2510.26692) and
the depthwise ``CausalConv1D`` in front of it.

Per head the layer keeps a ``(Dk, Dv)`` state and, token by token,

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

with ``g_t <= 0`` a log-decay per key channel and ``beta_t`` in (0, 1).
``gated_delta_rule`` computes exactly that in chunks of ``KDA_CHUNK``
tokens (the WY form): inside a chunk the corrections ``u_i = beta_i (v_i
- S_{i-1}^T diag(exp g_i) k_i)`` solve one unit lower-triangular system
in float32, whose matrix holds ``k_i . k_j exp(G_i - G_j)`` (``G`` the
running sum of ``g`` inside the chunk); between chunks a ``lax.scan``
carries the state.  ``exp(G_i - G_j)`` is never split into ``exp(G_i)
exp(-G_j)`` across more than the chunk's decay allows: blocks of
``KDA_SUB`` tokens take a reference point between them (both factors
<= 1), and a block against itself is computed channel by channel.  So a
decay of ``exp(-40)`` a token is as safe as one of 1.

One lowering, plain ``lax`` / ``jnp``, differentiable by autodiff; the
counter ``kda:lowering`` records it per traced op as ``attn:lowering``
does for attention, and the body runs under ``kda.l<layer>``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..base import MXNetError
from .registry import OpDef, Param, register_op
from .transformer import layer_scope

__all__ = ["causal_conv1d", "gated_delta_rule", "kda_gates"]

# tokens a chunk: one triangular system and one step of the state's scan
KDA_CHUNK = 64
# tokens a block inside a chunk: its own (KDA_SUB, KDA_SUB, Dk) products
# are formed channel by channel, every other block by a matmul
KDA_SUB = 16


def _l2norm(x, eps=1e-6):
    """``x / sqrt(sum(x**2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def kda_gates(decay, beta, a_log, dt_bias):
    """The layer's two gates from their projections, in float32:
    ``g = -exp(a_log[head]) * softplus(decay + dt_bias)`` per channel
    (``decay`` ``(B, T, H, Dk)``, ``dt_bias`` ``(H * Dk,)``) and ``beta =
    sigmoid(beta)`` per head."""
    f32 = jnp.float32
    h, dk = decay.shape[2], decay.shape[3]
    g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
        decay.astype(f32) + dt_bias.astype(f32).reshape(h, dk))
    return g, jax.nn.sigmoid(beta.astype(f32))


@jax.checkpoint
def _chunk_scores(q, k, G):
    """Inside every chunk: ``A[i, j] = k_i . k_j exp(G_i - G_j)`` for
    ``j < i`` and ``B[i, j] = q_i . k_j exp(G_i - G_j)`` for ``j <= i``,
    zero elsewhere; all of ``(..., C, Dk)`` -> two ``(..., C, C)``.
    Checkpointed: the ``(KDA_SUB, KDA_SUB, Dk)`` products of the diagonal
    blocks are formed again in the backward pass, not kept."""
    c = q.shape[-2]
    s = min(KDA_SUB, c)
    low = jnp.tril(jnp.ones((s, s), bool))
    rows_a, rows_b = [], []
    for lo in range(0, c, s):
        Gi, ki, qi = (x[..., lo:lo + s, :] for x in (G, k, q))
        n = Gi.shape[-2]
        e = jnp.exp(jnp.where(low[:n, :n, None],
                              Gi[..., :, None, :] - Gi[..., None, :, :],
                              -jnp.inf))                     # (.., s, s, Dk)
        kj = ki[..., None, :, :] * e
        a = [jnp.tril((ki[..., :, None, :] * kj).sum(-1), -1)]
        b = [(qi[..., :, None, :] * kj).sum(-1)]
        if lo:
            # against the blocks before it: the reference point is the
            # running sum just before this block, so both factors decay
            ref = G[..., lo - 1:lo, :]
            right = k[..., :lo, :] * jnp.exp(ref - G[..., :lo, :])
            left = jnp.exp(Gi - ref)
            a.insert(0, jnp.einsum("...ik,...jk->...ij", ki * left, right))
            b.insert(0, jnp.einsum("...ik,...jk->...ij", qi * left, right))
        tail = ((0, 0),) * (q.ndim - 1) + ((0, c - lo - n),)
        rows_a.append(jnp.pad(jnp.concatenate(a, -1), tail))
        rows_b.append(jnp.pad(jnp.concatenate(b, -1), tail))
    return jnp.concatenate(rows_a, -2), jnp.concatenate(rows_b, -2)


def gated_delta_rule(q, k, v, g, beta, scale: float, chunk: int = KDA_CHUNK):
    """The recurrence of the module docstring over ``(B, T, H, Dk)`` q, k
    and g (float32 log-decay), ``(B, T, H, Dv)`` v and ``(B, T, H)``
    beta, from a zero state -> ``(B, T, H, Dv)`` in v's dtype.  Chunked;
    T need not be a multiple of the chunk (the tail is padded with
    tokens that write nothing)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    n = -(-t // chunk)
    pad = n * chunk - t
    f32 = jnp.float32
    trace.counter("kda:lowering", cat="ops",
                  track="%s%s" % (v.dtype.name, list(q.shape)),
                  chunked=1, chunk=chunk)

    def blocks(x):                      # (B, T, H, ..) -> (N, B, H, C, ..)
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    out_dtype = v.dtype
    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)                      # (N, B, H, C, Dk)
    A, Bq = _chunk_scores(q, k, G)
    decayed = jnp.exp(G)
    bcol = beta[..., None]
    # u = Wv - Wk S0 for the chunk's entry state S0: both from one solve
    W = jax.scipy.linalg.solve_triangular(
        jnp.eye(chunk, dtype=f32) + bcol * A,
        jnp.concatenate([bcol * v, bcol * k * decayed], -1),
        lower=True, unit_diagonal=True)
    Gc = G[..., -1:, :]
    xs = (W[..., :dv], W[..., dv:], q * decayed * scale, Bq * scale,
          k * jnp.exp(Gc - G), jnp.exp(Gc[..., 0, :]))

    def one_chunk(S, x):
        wv, wk, qd, bq, kc, dec = x
        u = wv - jnp.einsum("bhck,bhkv->bhcv", wk, S)
        o = jnp.einsum("bhck,bhkv->bhcv", qd, S) \
            + jnp.einsum("bhcj,bhjv->bhcv", bq, u)
        S = dec[..., None] * S + jnp.einsum("bhck,bhcv->bhkv", kc, u)
        return S, o

    _, o = lax.scan(one_chunk, jnp.zeros((b, h, dk, dv), f32), xs)
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)    # (B, N, C, H, Dv)
    return o.reshape(b, n * chunk, h, dv)[:, :t].astype(out_dtype)


def causal_conv1d(x, w):
    """Depthwise causal convolution over time: ``(B, T, C)`` data, one
    ``W``-tap filter a channel ``(C, W)``, no bias: ``y_t = sum_j w[:, j]
    x_{t - (W - 1) + j}``, positions before 0 read as zero."""
    width = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    w = w.astype(x.dtype)
    return sum(xp[:, j:j + t, :] * w[:, j] for j in range(width))


@register_op("CausalConv1D", hint="causalconv1d")
class CausalConv1DOp(OpDef):
    """Depthwise causal convolution over time of ``(B, T, C)``: one
    ``kernel``-tap filter a channel (``weight`` ``(C, kernel)``), no bias;
    output ``t`` reads inputs ``t - kernel + 1 .. t``."""
    params = [Param("kernel", int, default=4)]

    def list_arguments(self, p):
        return ["data", "weight"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        if len(d) != 3:
            raise MXNetError("CausalConv1D: data must be (batch, seq, "
                             "channels), got %r" % (d,))
        return [d, (d[2], p.kernel)], [d], []

    def forward(self, p, inputs, aux, ctx):
        return [causal_conv1d(inputs[0], inputs[1])]


@register_op("KimiDeltaAttention", hint="kda")
class KimiDeltaAttentionOp(OpDef):
    """Kimi Delta Attention over ``(B, T, H, Dk)`` query and key, ``(B,
    T, H, Dv)`` value: q and k are L2-normalized per head, the decay gate
    is ``g = -exp(a_log[head]) * softplus(decay + dt_bias)`` per channel
    from its projection ``decay`` ``(B, T, H, Dk)``, the write gate is
    ``sigmoid(beta)`` from ``(B, T, H)``, and the output is the gated
    delta rule's ``S_t^T q_t * Dk**-0.5`` (see ``gated_delta_rule``),
    ``(B, T, H, Dv)``.  The state starts at zero and runs through the
    whole sequence.  ``layer`` names the trace scope."""
    params = [Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["query", "key", "value", "decay", "beta", "a_log_bias",
                "dt_bias"]

    def infer_shape(self, p, in_shapes):
        q, v = in_shapes[0], in_shapes[2]
        if q is None or v is None:
            return in_shapes, [None], []
        if len(q) != 4 or len(v) != 4 or tuple(q[:3]) != tuple(v[:3]):
            raise MXNetError("KimiDeltaAttention: query (batch, seq, heads, "
                             "key_dim) and value (batch, seq, heads, "
                             "value_dim), got %r and %r" % (q, v))
        b, t, h, dk = q
        return [q, q, v, q, (b, t, h), (h,), (h * dk,)], [v], []

    def forward(self, p, inputs, aux, ctx):
        q, k, v, decay, beta, a_log, dt_bias = inputs
        with layer_scope("kda", p.layer):
            g, beta = kda_gates(decay, beta, a_log, dt_bias)
            return [gated_delta_rule(_l2norm(q), _l2norm(k), v, g, beta,
                                     float(q.shape[-1]) ** -0.5)]
