"""Transformer block ops: ``RMSNorm``, ``RotaryEmbedding``,
``CausalSelfAttention`` and the per-token loss head ``SoftmaxCELoss``
(SiLU and the gated product live inside ``_moe_expert_ffn``).

What a pre-norm decoder block needs beyond FullyConnected / Embedding /
Reshape, as plain jnp bodies (autodiff gives the gradients).  Statistics
run in float32 whatever the compute dtype: the RMS, the attention
softmax and the loss's log-sum-exp.

Attention never materializes the ``(B, H, T, T)`` scores: the op calls
ONE inner function, ``causal_attention``, blockwise softmax attention
that recomputes the scores in the backward pass.  It has two lowerings.
The plain blocks walk the queries under ``lax.map`` of a
``jax.checkpoint``ed body and run on every platform.  Where the program
is lowered for a TPU and the inputs are ones the kernels take (bfloat16,
heads of 64 or of 128 and more lanes, ``T`` whole tiles), a Pallas pair
runs instead (online softmax in VMEM, empty tiles skipped): this repo's
own over ``(T, H * Dh)`` rows or the library's splash attention
(``kernel_pair``).  The code chooses from what it sees, no option does;
``attn:lowering`` records it.  Key and value may have fewer heads (grouped
queries: query head ``j`` reads key/value head ``j // (H / Hkv)``), and
the mask is one of ``MASKS``: ``causal``, ``block_diffusion`` over a
doubled sequence ``[noised ; clean]`` (``block_diffusion_allowed``), or
``sliding_window``: the causal mask cut to a query's own position and
the ``window - 1`` before it (``sliding_window_allowed``).  Both
lowerings take all three, from the one definition of each mask
(``_mask_function``).

The bodies of ``CausalSelfAttention`` and ``SoftmaxCELoss`` run under a
declared device scope (``attn.l<layer>``, ``lm_loss``; ``trace/scopes.py``)
so a device trace can tell the block's parts apart.  A builder names the
parts that are made of plain ops through the symbol attribute
``__scope__`` (``node_scope``): ``mla_q.l3`` around a projection,
``mtp.`` before the scopes of a prediction module's nodes.  Every other
node gets the generic scope of its op type and name.
"""
from __future__ import annotations

import contextlib
import functools
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..trace import scopes as _scopes
from ..base import MXNetError
from .pallas_kernels import _kernel_on_tpu
from .registry import OpDef, Param, register_op

__all__ = ["causal_attention", "rms_norm", "rotary_embedding"]

# queries per block of causal_attention: one block's float32 scores are
# B*H*ATTN_BLOCK_Q*T*4 bytes (0.5 GB at B=4, H=16, T=4096)
ATTN_BLOCK_Q = 512
# the TPU kernel's tiling, forward and backward: queries and keys per
# tile, and keys per matmul inside a tile (the fastest of 38 settings of
# two library kernels on a v5e at (4, 4096, 16, 128): PERF.md, PR 27)
ATTN_KERNEL_BLOCK = 1024
ATTN_KERNEL_SLICE = 512


_scope = threading.local()         # .prefix: what node_scope("x.") set


def layer_scope(kind: str, layer):
    """Declared device scope of one block part: ``attn.l3``,
    ``moe_experts.l0`` (no suffix where the builder gave no layer index),
    behind the prefix of the ``node_scope`` it runs in, if any:
    ``mtp.attn``."""
    name = kind if layer is None or layer < 0 else "%s.l%d" % (kind, layer)
    return _scopes.declared(scope_prefix() + name)


def scope_prefix() -> str:
    """The prefix of the ``node_scope`` this thread is tracing under
    (``mtp.``), or nothing."""
    return getattr(_scope, "prefix", "")


@contextlib.contextmanager
def node_scope(name, op_type=None, node_name=None):
    """What the executor enters around every op node, so that a device
    trace can tell the step's operations apart (``trace/scopes.py``).  A
    node whose symbol carries the attribute ``__scope__``
    (``mx.AttrScope(__scope__=...)`` in a model builder) is a block part
    made of plain ops and keeps that name, a declared scope: ``mla_q.l3``.
    Any other node gets the generic ``<op type, lower case>.<node
    name>`` (``convolution.stage1_unit1_conv1``), which a scope the op
    declares itself (``layer_scope``) wins over.  A ``__scope__`` that
    ends in ``.`` (``mtp.``) is a prefix: it goes before the generic name
    and before the scopes the node's op names itself.  Nothing where
    there is neither attribute nor node."""
    prefix = name if name and name.endswith(".") else ""
    if name and not prefix:
        with _scopes.declared(name):
            yield
        return
    was = getattr(_scope, "prefix", "")
    _scope.prefix = prefix
    try:
        if op_type is None:
            yield
        else:
            with _scopes.generic("%s%s.%s" % (prefix, op_type.lower(),
                                              node_name)):
                yield
    finally:
        _scope.prefix = was


def rms_norm(x, gamma, eps: float):
    """``x / sqrt(mean(x**2) + eps) * gamma`` over the last axis, the
    mean and the division in float32."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * gamma.astype(x.dtype)


def grouped_rms_norm(x, gamma, eps: float, groups=None):
    """``rms_norm`` with the statistic over each of ``groups`` equal parts
    of the last axis (None or 0: over all of it) and ONE gain over the
    whole axis: Mamba-2's gated norm over ``n_groups`` > 1."""
    if not groups:
        return rms_norm(x, gamma, eps)
    if x.shape[-1] % groups:
        raise MXNetError("RMSNorm: %d lanes in %d groups"
                         % (x.shape[-1], groups))
    parts = x.shape[:-1] + (groups, x.shape[-1] // groups)
    return rms_norm(x.reshape(parts), gamma.reshape(parts[-2:]),
                    eps).reshape(x.shape)


def rotary_embedding(x, theta: float, period: int = 0):
    """Rotary position embedding of ``(B, T, H, Dh)`` at positions
    ``0..T-1``, or ``n mod period`` for row ``n`` where ``period`` is
    given (a sequence laid out as copies of the same positions),
    half-split pairing (dimension ``i`` rotates with ``i + Dh/2``, as the
    ``olmoe``/``llama`` modelling code), angles in float32."""
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / dh))
    pos = jnp.arange(t, dtype=jnp.float32)
    if period:
        pos = (jnp.arange(t) % period).astype(jnp.float32)
    ang = pos[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def sectioned_rotary(x, positions=None, theta: float = 10000.0,
                     period: int = 0, sections=None):
    """``rotary_embedding`` whose pairs are shared out among several
    position axes (M-RoPE, Qwen2-VL's: temporal, height, width):
    ``sections`` ``(n_0, n_1, ..)`` sum to ``Dh / 2``, and of a head's
    ``Dh / 2`` frequencies the first ``n_0`` turn by ``positions[:, 0]``,
    the next ``n_1`` by ``positions[:, 1]``, and so on; ``positions``
    ``(B, len(sections), T)``.  Without ``positions`` every axis is the
    row's index in its sequence, which is ``rotary_embedding`` itself."""
    if positions is None:
        return rotary_embedding(x, theta, period)
    dh = x.shape[3]
    half = dh // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / dh))
    axis_of = np.repeat(np.arange(len(sections)), sections)
    pos = positions.astype(jnp.float32)[:, axis_of, :]      # (B, half, T)
    ang = pos.transpose(0, 2, 1) * inv_freq[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


MASKS = ("causal", "block_diffusion", "sliding_window")


def block_diffusion_allowed(q_ids, k_ids, half: int, block: int):
    """Whether query row ``q_ids`` reads key row ``k_ids`` of a doubled
    sequence of ``2 * half`` rows: the noised copy, then the clean one,
    row ``n`` at position ``n mod half`` in block ``(n mod half) //
    block`` (the vectorised form of the block-diffusion objective,
    arXiv:2503.09573).  A noised query sees the noised keys of its own
    block, both directions, and the clean keys of earlier blocks; a clean
    query sees the clean keys of its own and earlier blocks; nothing
    else.  Operators only: the ids are numpy arrays where the kernel's
    tiles are classified and traced arrays inside both lowerings."""
    if half & (half - 1) or block & (block - 1):
        q_blk, k_blk = (q_ids % half) // block, (k_ids % half) // block
    else:
        # powers of two: a mask and a shift where the vector unit would
        # emulate two integer divisions an element of every partial tile
        low, shift = half - 1, block.bit_length() - 1
        q_blk, k_blk = (q_ids & low) >> shift, (k_ids & low) >> shift
    q_clean, k_clean = q_ids >= half, k_ids >= half
    return (k_clean & (k_blk < q_blk)) \
        | ((q_clean == k_clean) & (k_blk == q_blk))


def sliding_window_allowed(q_ids, k_ids, window: int):
    """Whether query row ``q_ids`` reads key row ``k_ids`` under a causal
    window of ``window`` positions: the query's own and the ``window -
    1`` before it, ``0 <= q - k < window``.  Operators only, as
    ``block_diffusion_allowed``."""
    return (q_ids >= k_ids) & (q_ids - k_ids < window)


def _mask_function(kind, t: int):
    """The mask ``kind`` = (name, size) over ``t`` rows as a function of
    broadcastable ``(q_ids, k_ids)``: what the plain blocks evaluate on
    traced ids and what the kernel's computable mask is made of."""
    name, size = kind
    if name == "causal":
        return lambda q_ids, k_ids: q_ids >= k_ids
    if name == "sliding_window":
        return lambda q_ids, k_ids: sliding_window_allowed(q_ids, k_ids,
                                                           size)
    return lambda q_ids, k_ids: block_diffusion_allowed(q_ids, k_ids,
                                                        t // 2, size)


def causal_attention(q, k, v, scale: float, mask: str = "causal",
                     block: int = 0, window: int = 0):
    """Masked self-attention of ``(B, T, H, Dh)`` q, ``(B, T, Hkv, Dh)``
    k and ``(B, T, Hkv, Dv)`` v -> ``(B, T, H, Dv)``; scores, softmax and
    accumulation in float32.  ``Dv`` may differ from ``Dh`` (latent
    attention); ``Hkv`` is ``H`` or a divisor of it, and query head ``j``
    then reads key/value head ``j // (H / Hkv)``.  ``mask`` is ``causal``
    (the name the function keeps), ``block_diffusion`` with its ``block``
    length, over ``T = 2 x`` the clean length, or ``sliding_window`` with
    its ``window`` >= 1 (``block_diffusion_allowed``, ``sliding_window_
    allowed``); a window of ``T`` or more is, and is counted as, causal.

    One algorithm, two lowerings.  Inputs the flash-attention kernels
    take (``_kernel_takes``) run one where the program is LOWERED for a
    TPU and the plain blocks on any other platform; every other input
    runs the plain blocks everywhere.  Each trace records which, as the
    counter ``attn:lowering``: ``kernel`` 1 means this op's TPU lowering
    is a kernel pair (a CPU program's text holds the plain blocks all the
    same) and ``pair`` which (``kernel_pair``: ``rows``, the repo's own,
    or ``library``); ``plain`` 1 the plain blocks everywhere (``pair``
    ``none``).  The track names dtype, shape, ``/kv<Hkv>`` under fewer key
    heads and any other ``/<mask><size>``; ``mask_form``: ``kernel_mask``."""
    t, h, hkv = q.shape[1], q.shape[2], k.shape[2]
    if mask not in MASKS:
        raise MXNetError("attention mask %r is none of %s" % (mask, MASKS))
    if h % hkv or v.shape[2] != hkv:
        raise MXNetError("attention: %d query heads over %d key and %d "
                         "value heads" % (h, hkv, v.shape[2]))
    if mask == "causal":
        kind = ("causal", 0)
    elif mask == "sliding_window":
        if window < 1:
            raise MXNetError("sliding_window attention over a window of %d: "
                             "a query reads at least itself" % window)
        kind = (mask, int(window)) if window < t else ("causal", 0)
    elif block > 0 and t % 2 == 0 and (t // 2) % block == 0:
        kind = (mask, int(block))
    else:
        raise MXNetError("block_diffusion attention over %d rows in blocks "
                         "of %d: the rows are two copies of whole blocks"
                         % (t, block))
    kernel = _kernel_takes(q, k, v)
    pair = kernel_pair(q, k, v) if kernel else "none"
    trace.counter("attn:lowering", cat="ops", track="%s%s%s%s%s" % (
        q.dtype.name, list(q.shape),
        "" if v.shape[3] == q.shape[3] else "x%d" % v.shape[3],
        "" if hkv == h else "/kv%d" % hkv,
        "" if kind[0] == "causal" else "/%s%d" % kind),
        kernel=int(kernel), plain=int(not kernel), pair=pair,
        mask_form=kernel_mask(kind, t)[0] if kernel else "none")
    if pair != "library":
        return _plain_or_rows(q, k, v, scale, kind, pair)
    return _kernel_on_tpu(
        lambda q, k, v: _flash_attention(q, k, v, scale, kind),
        lambda q, k, v: _plain_attention(q, k, v, scale, kind), False,
        q, k, v)


def _kernel_tiles(t: int):
    """(keys and queries a tile, keys a matmul) for sequences of ``t``,
    whatever the head sizes: at 256-lane q, k AND v (20 heads, the widest
    this repo runs) both kernels still compile for a v5e inside Mosaic's
    default scoped VMEM (16 MiB of the chip's 128), so no rule by head
    size is needed (PERF.md, PR 35)."""
    tile = min(ATTN_KERNEL_BLOCK, t)
    return tile, min(ATTN_KERNEL_SLICE, tile)


def _kernel_takes(q, k, v) -> bool:
    """What the TPU kernel's tiling accepts: the configuration's compute
    dtype (float32 keeps the plain blocks its chip parity was measured
    on), heads of 64 lanes or of 128 and more, the value's a multiple of
    64 (``_flash_fwd`` pads q, k and v with zeros to whole 128-lane rows,
    which no score sees, and cuts the output's lanes: 64 to 128, 192 to
    256, nothing at 128 or 256), sequences of whole tiles of slices."""
    t, dh, dv = q.shape[1], q.shape[3], v.shape[3]
    tile, piece = _kernel_tiles(t)
    return (all(x.dtype == jnp.bfloat16 for x in (q, k, v))
            and (dh >= 128 or dh == 64) and dv % 64 == 0 and t % 128 == 0
            and t % tile == 0 and tile % piece == 0)


@functools.lru_cache(maxsize=None)
def _splash_mask():
    """The library's computable-mask class over ``kernel_mask``: the
    kernel evaluates its function in EVERY tile it visits and never loads
    a ``(T, T)`` array.  Made once; two masks of one ``(t, kind)`` are
    equal: one kernel pair a kind a process, however many layers."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as sm)

    class KindMask(sm._ComputableMask):
        def __init__(self, t: int, kind):
            self.kind = kind
            _, rows, allowed = kernel_mask(kind, t)
            super().__init__((t, t), allowed)
            self.q_sequence = rows

        def __eq__(self, other):
            return isinstance(other, type(self)) \
                and (self.shape, self.kind) == (other.shape, other.kind)

        def __hash__(self):
            return hash((type(self), self.shape, self.kind))

    return KindMask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, scale: float, kind=("causal", 0)):
    """The TPU lowering: ``jax.experimental.pallas.ops.tpu.
    splash_attention`` under the mask ``kind`` (online softmax in VMEM,
    key tiles the mask empties never visited: those above the diagonal
    for the causal mask, 40 of 64 at 8192 rows of ``block_diffusion``, 7
    of 16 at 4096 rows under a window of 2048, where the causal mask
    empties 6; a fused backward kernel that recomputes the scores tile by
    tile; the mask formed in every visited tile, whole or partial),
    tiled by ``_kernel_tiles``.  The kernel takes one sequence as
    ``(H, T, Dh)`` against ``(Hkv, T, Dh)`` (a key/value head serves its
    group of query heads in place: nothing is repeated) and has no scale
    of its own: the queries are scaled first (one more bfloat16 rounding
    of q), the batch is a ``vmap``, and three transposes go in and one
    out, with theirs in the backward pass."""
    return _flash_fwd(q, k, v, scale, kind)[0]


def _flash_fwd(q, k, v, scale, kind):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    t, h = q.shape[1], q.shape[2]
    tile, piece = _kernel_tiles(t)
    sizes = sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=piece,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=piece,
        use_fused_bwd_kernel=True)
    one_head = sm.CausalMask((t, t)) if kind[0] == "causal" \
        else _splash_mask()(t, kind)
    attend = sk.make_splash_mha_single_device(
        sm.MultiHeadMask([one_head] * h), block_sizes=sizes)

    lanes = [-x.shape[3] % 128 for x in (q, k, v)]  # heads to whole 128 lanes

    def kernel(q, k, v):
        q, k, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, n),)) if n else x
                   for x, n in zip((q * scale, k, v), lanes))
        out = jax.vmap(attend)(*(x.transpose(0, 2, 1, 3)
                                 for x in (q, k, v)))
        return out.transpose(0, 2, 1, 3)[..., :v.shape[3] - lanes[2]]

    # bfloat16 products are exact at any precision, and Mosaic refuses
    # bfloat16 operands under a "highest" default ("Bad lhs type"), so
    # both passes are traced at the default one, whatever the caller's
    with jax.default_matmul_precision("default"):
        return jax.vjp(kernel, q, k, v)


def _flash_bwd(scale, kind, kernel_vjp, g):
    with jax.default_matmul_precision("default"):
        return kernel_vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _plain_attention(q, k, v, scale: float, kind=("causal", 0)):
    """The lowering of every platform, and the kernel's parity twin:
    queries in blocks of ATTN_BLOCK_Q; a block's scores live only inside
    its (checkpointed) body, in the forward and again in the backward
    pass.  Grouped queries are a reshape of the query heads to ``(Hkv,
    H / Hkv)`` against the keys as they are."""
    b, t, h, dh = q.shape
    hkv = k.shape[2]
    bq = min(ATTN_BLOCK_Q, t)
    nb = -(-t // bq)
    pad = nb * bq - t
    if pad:
        # padded query rows see every key: finite, and cut off below
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = q.reshape(b, nb, bq, h, dh).transpose(1, 0, 2, 3, 4)
    k_pos = jnp.arange(t)

    def allowed(i):
        """(bq, T): which keys the queries of block ``i`` read."""
        q_pos = i * bq + jnp.arange(bq)
        return _mask_function(kind, t)(q_pos[:, None], k_pos[None, :])

    @jax.checkpoint
    def one_block(args):
        i, qi = args
        if hkv == h:
            # the statements, and their order, of the op before it took
            # groups: an older symbol's step lowers to the text it had
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(allowed(i)[None, None], s, jnp.float32(-1e30))
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)
        s = jnp.einsum("bqngd,bknd->bngqk",
                       qi.reshape(b, bq, hkv, h // hkv, dh), k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(allowed(i)[None, None, None], s, jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bngqk,bknd->bqngd", p, v).reshape(
            b, bq, h, v.shape[3])

    if nb == 1:
        out = one_block((jnp.int32(0), blocks[0]))[None]
    else:
        out = lax.map(one_block, (jnp.arange(nb, dtype=jnp.int32), blocks))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nb * bq, h, v.shape[3])
    return out[:, :t] if pad else out


@register_op("RMSNorm", hint="rmsnorm")
class RMSNormOp(OpDef):
    """Root-mean-square LayerNorm over the last axis (Zhang & Sennrich
    2019): no mean subtraction, no bias; float32 statistics.  With
    ``groups`` the statistic is over each of that many equal parts of the
    axis under ONE gain (``grouped_rms_norm``)."""
    params = [Param("eps", float, default=1e-5), Param("groups", int)]

    def list_arguments(self, p):
        return ["data", "gamma"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        return [d, (d[-1],)], [d], []

    def forward(self, p, inputs, aux, ctx):
        return [grouped_rms_norm(inputs[0], inputs[1], p.eps, p.groups)]


@register_op("LayerNorm", hint="layernorm")
class LayerNormOp(OpDef):
    """Layer normalization over the last axis (Ba et al. 2016): ``(x -
    mean) / sqrt(var + eps) * gamma + beta``, the statistics in float32."""
    params = [Param("eps", float, default=1e-5)]

    def list_arguments(self, p):
        return ["data", "gamma", "beta"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is None:
            return in_shapes, [None], []
        return [d, (d[-1],), (d[-1],)], [d], []

    def forward(self, p, inputs, aux, ctx):
        x, gamma, beta = inputs
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
        return [(centred * lax.rsqrt(var + p.eps)).astype(x.dtype)
                * gamma.astype(x.dtype) + beta.astype(x.dtype)]


@register_op("RotaryEmbedding", hint="rotary")
class RotaryEmbeddingOp(OpDef):
    """Rotary position embedding of ``(B, T, H, Dh)`` (Su et al. 2021),
    half-split pairing, positions ``0..T-1``; with ``period`` > 0 row
    ``n`` is at position ``n mod period`` (a sequence made of copies of
    the same positions, as the block-diffusion objective's ``[noised ;
    clean]``).

    ``sections`` ``(n_0, n_1, ..)`` share a head's ``Dh / 2`` pairs out
    among several position axes (M-RoPE: temporal, height, width): the
    first ``n_0`` frequencies turn by axis 0's position, the next ``n_1``
    by axis 1's, and so on (``sectioned_rotary``).  The positions are a
    second input ``(B, len(sections), T)`` with ``with_positions``;
    without it every axis is the row's index in its sequence, which is
    plain rotary."""
    params = [Param("theta", float, default=10000.0),
              Param("period", int, default=0),
              Param("sections", "shape"),
              Param("with_positions", bool)]

    def list_arguments(self, p):
        return ["data", "positions"] if p.with_positions else ["data"]

    def infer_shape(self, p, in_shapes):
        d = in_shapes[0]
        if d is not None and (len(d) != 4 or d[3] % 2):
            raise MXNetError("RotaryEmbedding: data must be (batch, seq, "
                             "heads, even head_dim), got %r" % (d,))
        sections = tuple(p.sections or ())
        if sections and (min(sections) < 1 or p.period
                         or (d is not None and sum(sections) * 2 != d[3])):
            raise MXNetError("RotaryEmbedding: sections %r are the pairs "
                             "of a head of %s lanes, axis by axis, without "
                             "a period" % (sections, d and d[3]))
        if not p.with_positions:
            return in_shapes, [d], []
        if not sections:
            raise MXNetError("RotaryEmbedding: a positions input needs "
                             "sections, the pairs each of its axes turns")
        return ([d, None if d is None else (d[0], len(sections), d[1])],
                [d], [])

    def forward(self, p, inputs, aux, ctx):
        return [sectioned_rotary(*inputs, theta=p.theta, period=p.period,
                                 sections=p.sections)]


@register_op("CausalSelfAttention", hint="attention")
class CausalSelfAttentionOp(OpDef):
    """Masked multi-head self-attention over ``(B, T, H, Dh)`` query,
    ``(B, T, Hkv, Dh)`` key and ``(B, T, Hkv, Dv)`` value (``Dv`` may
    differ from ``Dh``: latent attention's 192 against 128, or equal it
    at 256 against 256 where the value heads are as wide as the nope and
    rope parts together): ``softmax(q k^T * scale + mask) v`` per head
    -> ``(B, T, H, Dv)``, softmax in float32, scores never materialized
    whole.  ``scale`` 0 means ``Dh**-0.5``; ``layer`` names the trace
    scope.

    ``Hkv`` is ``H`` or a whole divisor of it (grouped queries: query
    head ``j`` reads key/value head ``j // (H / Hkv)``).  ``mask`` is
    ``causal`` (the default, and the op's name), ``block_diffusion``
    with ``block`` > 0 (``T`` is then two copies of one sequence, the
    noised one then the clean one, and a row reads what
    ``block_diffusion_allowed`` says), or ``sliding_window`` with
    ``window`` >= 1: a row reads itself and the ``window - 1`` rows
    before it (``sliding_window_allowed``; a window of ``T`` or more is
    the causal mask).  One op and one inner function for all three,
    because the two lowerings, their choice and their counter are the
    same code with another mask object handed to each.

    Which lowering runs is ``causal_attention``'s choice, from the
    platform the program is lowered for and the inputs: bfloat16 with
    ``Dv % 64 == 0``, ``Dh`` 64 or >= 128 and ``T`` a multiple of 128 and
    of ``min(1024, T)``, lowered for a TPU, is a Pallas kernel pair: the
    repo's own in row form where grouped query heads of 128 lanes share
    key/value heads (``kernel_pair``), else JAX's splash attention (heads
    padded to whole 128 lanes in its wrapper; 256 / 256 fits its VMEM at
    a tile of 1024); float32, any other shape and every other platform
    are the plain query blocks.  ``attn:lowering`` records it per bind."""
    params = [Param("scale", float, default=0.0),
              Param("layer", int, default=-1),
              Param("mask", str, default="causal", enum=MASKS),
              Param("block", int, default=0),
              Param("window", int, default=0)]

    def list_arguments(self, p):
        return ["query", "key", "value"]

    def infer_shape(self, p, in_shapes):
        q, k, v = in_shapes
        if p.mask == "sliding_window" and p.window < 1:
            raise MXNetError("CausalSelfAttention: mask sliding_window "
                             "needs window >= 1, got %d" % p.window)
        if q is None and k is None and v is None:
            return in_shapes, [None], []
        # a shape that is not given is query's (or key's, or value's)
        like = q if q is not None else k if k is not None else v
        q, k, v = (like if s is None else s for s in (q, k, v))
        for name, s in (("query", q), ("key", k), ("value", v)):
            if len(s) != 4:
                raise MXNetError("CausalSelfAttention: %s must be (batch, "
                                 "seq, heads, head_dim), got %r" % (name, s))
        if (tuple(k[:2]), k[3]) != (tuple(q[:2]), q[3]) \
                or k[2] < 1 or q[2] % k[2]:
            raise MXNetError("CausalSelfAttention: key %r differs from "
                             "query %r by more than a whole divisor of "
                             "its heads" % (tuple(k), tuple(q)))
        if (tuple(v[:2]), v[2]) != (tuple(q[:2]), k[2]):
            raise MXNetError("CausalSelfAttention: value %r differs from "
                             "query %r before the heads, or from key %r in "
                             "its heads" % (tuple(v), tuple(q), tuple(k)))
        return [q, k, v], [tuple(q[:3]) + (v[3],)], []

    def forward(self, p, inputs, aux, ctx):
        q, k, v = inputs
        scale = p.scale or float(q.shape[-1]) ** -0.5
        with layer_scope("attn", p.layer):
            return [causal_attention(q, k, v, scale, p.mask, p.block,
                                     p.window)]


# Below the op, so that no line above its ``forward`` moves: a Mosaic
# kernel's payload names its call sites by file and line and is part of
# the compile cache's key, so the other kinds' kernels stay cache hits.
def block_diffusion_codes(half: int, block: int):
    """``block_diffusion_allowed`` with the query's side made on the
    host, for ``half`` and ``block`` powers of two: ``(codes, allowed)``
    with ``allowed(codes[q_ids], k_ids) == block_diffusion_allowed(q_ids,
    k_ids, half, block)``, pair for pair.  A row's code is its block's
    index over the doubled sequence with the two copies swapped
    (``(n >> shift) ^ nb`` for ``nb = half / block`` blocks a copy: a
    clean row's is ``0 .. nb - 1``, a noised row's ``nb .. 2 nb - 1``).
    The keys' side is the same two operations on ``k_ids``; then a key
    is read where the codes are equal (the same block of the same copy)
    or the key's is under the row's block (an earlier clean block: a
    noised key's code is ``nb`` or more, never under it).  Six passes
    over a tile of scores where the function as it is written takes
    fourteen."""
    shift, nb = block.bit_length() - 1, half // block
    codes = (np.arange(2 * half, dtype=np.int32) >> shift) ^ nb

    def allowed(q_codes, k_ids):
        k_codes = (k_ids >> shift) ^ nb
        return (k_codes == q_codes) | (k_codes < (q_codes & (nb - 1)))

    return codes, allowed


def kernel_mask(kind, t: int):
    """The form in which the TPU kernel gets the mask ``kind`` over ``t``
    rows: ``(form, rows, allowed)``, the counter's ``mask_form``, one
    int32 entry a row (the library's ``q_sequence``) and the function of
    ``(rows' entries, key ids)`` that the kernel evaluates in every tile
    it visits, whole or partial, forward and backward, on two int32
    arrays the size of the tile's scores, and the host once a tile to
    tell the empty, partial and whole tiles apart.  ``function``: the
    row ids and ``_mask_function`` itself (the window's four passes).
    ``codes``: ``block_diffusion_codes``, for a block mask whose sizes
    are powers of two (as the function's own shift branch asks): the
    same allowed pairs from six passes for fourteen, which is 3.7 ms a
    layer at 8192 rows over 32 heads on a v5e (PERF.md, PR 42).
    ``library``: the causal mask is the library's own object (one
    compare); there are no rows and no function to hand over."""
    name, size = kind
    if name == "causal":
        return "library", None, None
    half = t // 2
    if name == "block_diffusion" \
            and not (half & (half - 1) or size & (size - 1)):
        return ("codes",) + block_diffusion_codes(half, size)
    return "function", np.arange(t, dtype=np.int32), _mask_function(kind, t)


def kernel_pair(q, k, v) -> str:
    """Which kernel pair the TPU lowering of inputs that ``_kernel_takes``
    is, from what the inputs show: ``rows``, this repo's own
    (``ops/selected_attention.py`` ``computed_attention_fwd`` / ``_bwd``:
    the operands as the projections leave them, a key/value head's group
    of query heads a grid step under ONE evaluation of the mask), where
    the query heads share key/value heads (a group of 2 or more: equal
    heads give a step's contraction over the group nothing), q, k and v
    are all of 128 lanes (a 64-lane head is no whole lane block of a row,
    a 256-lane one doubles a step's VMEM) and the backward kernel keeps a
    key/value head's ``dk`` and ``dv`` in VMEM (``backward_tiles``);
    ``library``, JAX's splash attention, everywhere else."""
    from . import selected_attention
    t, h, dh = q.shape[1:]
    group = h // k.shape[2]
    rows = (group >= 2 and dh == v.shape[3] == selected_attention.LANES
            and selected_attention.backward_tiles(t, group, dh) is not None)
    return "rows" if rows else "library"


def _rows_mask(kind, t: int):
    """``kernel_mask``'s ``(rows, allowed)`` for the repo's own pair: the
    causal mask, the library's own object there, is the row ids under
    its one compare here."""
    _, rows, allowed = kernel_mask(kind, t)
    if rows is None:
        return np.arange(t, dtype=np.int32), _mask_function(kind, t)
    return rows, allowed


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rows_attention(q, k, v, scale: float, kind=("causal", 0),
                    interpret: bool = False):
    """The TPU lowering of ``kernel_pair`` ``rows``: this repo's kernel
    pair under the mask ``kind`` evaluated in VMEM (``ops/
    selected_attention.py``), forward and backward on one plan.  q, the
    output, its cotangent and ``dq`` are ``(B, T, H * Dh)`` rows and k, v,
    ``dk``, ``dv`` ``(B, T, Hkv * D)`` rows, what the projections give and
    take but for a reshape; the batch is a grid axis; only key tiles that
    hold an allowed pair are visited (a host-made table a ``(kind, T)``);
    the forward's float32 log-sum-exp ``(B, H, T)`` is kept for the
    backward pass, which forms ``di`` itself and sums ``dq`` in VMEM.  The
    queries are scaled first, as for the library's pair."""
    return _rows_fwd(q, k, v, scale, kind, interpret)[0]


def _rows_fwd(q, k, v, scale, kind, interpret):
    q = q * scale
    out, lse = _rows_forward(q, k, v, kind=kind, interpret=interpret)
    return out, (q, k, v, out, lse)


def _rows_bwd(scale, kind, interpret, kept, g):
    dq, dk, dv = _rows_backward(*kept, g, kind=kind, interpret=interpret)
    return dq * scale, dk, dv


_rows_attention.defvjp(_rows_fwd, _rows_bwd)


def _plain_or_rows(q, k, v, scale, kind, pair):
    """``causal_attention`` where ``pair`` is ``none`` (the plain blocks
    on every platform) or ``rows`` (this repo's kernels where the program
    is lowered for a TPU, the plain blocks elsewhere)."""
    if pair == "none":
        return _plain_attention(q, k, v, scale, kind)
    return _kernel_on_tpu(
        lambda q, k, v: _rows_attention(q, k, v, scale, kind),
        lambda q, k, v: _plain_attention(q, k, v, scale, kind), False,
        q, k, v)


# lint: allow(raw-jit) — never dispatched on their own: jits inside the
# step program, so that every layer and module of a process shares one
# traced kernel and one visit plan a (shape, kind), as
# ``selected_attention._selected_attention_bwd``
@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def _rows_forward(q, k, v, *, kind, interpret):
    from .selected_attention import computed_attention_fwd
    # bfloat16 products are exact at any precision, and Mosaic refuses
    # bfloat16 operands under a "highest" default, as ``_flash_fwd`` says
    with jax.default_matmul_precision("default"):
        return computed_attention_fwd(
            q, k, v, *_rows_mask(kind, q.shape[1]), interpret=interpret)


# lint: allow(raw-jit) — as ``_rows_forward``
@functools.partial(jax.jit, static_argnames=("kind", "interpret"))
def _rows_backward(q, k, v, out, lse, d_out, *, kind, interpret):
    from .selected_attention import computed_attention_bwd
    with jax.default_matmul_precision("default"):
        return computed_attention_bwd(
            q, k, v, *_rows_mask(kind, q.shape[1]), out, lse, d_out,
            interpret=interpret)


def _softmax_ce(logits, label, ignore=None):
    """Per-row ``logsumexp(logits) - logits[label]`` in float32; a row
    whose label is ``ignore`` gives exactly 0 and takes no gradient."""
    x = logits.astype(jnp.float32)
    idx = lax.stop_gradient(label).astype(jnp.int32)
    left_out = None if ignore is None else idx == ignore
    if left_out is not None:
        idx = jnp.where(left_out, 0, idx)
    picked = jnp.take_along_axis(x, idx[:, None], axis=-1)[:, 0]
    loss = jax.nn.logsumexp(x, axis=-1) - picked
    return loss if left_out is None else jnp.where(left_out, 0.0, loss)


@register_op("SoftmaxCELoss", hint="softmaxceloss")
class SoftmaxCELossOp(OpDef):
    """Per-token softmax cross-entropy that emits the LOSS: logits
    ``(N, V)`` + integer labels ``(N,)`` -> float32 ``(N,)``, the
    log-sum-exp in float32.  Differentiable (its gradient is
    ``(softmax - onehot) * head``); wrap it in ``MakeLoss`` to train on
    it.  Where ``SoftmaxOutput`` hands the metric ``(N, V)``
    probabilities, this hands it N numbers.  With ``use_ignore`` a row
    whose label is ``ignore_label`` (a position that has no target) reads
    exactly 0 and sends no gradient to its logits (``SoftmaxOutput``'s
    pair of parameters); ``MakeLoss(normalization="valid")`` then
    normalizes over the rows that have one."""
    params = [Param("use_ignore", bool, default=False),
              Param("ignore_label", int, default=-1)]

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
        with layer_scope("lm_loss", None):
            return [_softmax_ce(inputs[0], inputs[1],
                                p.ignore_label if p.use_ignore else None)]
