"""Attention that selects its keys: ``IndexedSelfAttention``, the
DeepSeek-Sparse-Attention mixer's core (DeepSeek-V3.2-Exp's "lightning
indexer") as one op of the Symbol graph.

A learned indexer scores every causal (query, key) pair,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]) * Di**-0.5 * Hi**-0.5

over ``Hi`` indexer heads of ``Di`` lanes against ONE indexer key head;
query row ``t`` then reads the ``min(t + 1, topk)`` keys ``s <= t`` with
the largest score (a tie goes to the earlier key), and softmax attention
runs over that selection alone: grouped queries, ``H`` heads over ``Hkv``.
The indexer is trained by a loss of its own (the sparse training stage):
the target ``p[t, .]`` is the main attention's probabilities averaged
over the heads, and a row's loss ``KL(p[t, .] || softmax over the
selection of I[t, .])``.  The op gives the heads' outputs, a sequence's
mean row loss and what the selection did; its backward is written out
(``jax.custom_vjp``): the first output's cotangent reaches q, k and v
and nothing else, the second's the indexer's three inputs and nothing
else, the selection and the target take no gradient.

Three passes over the ``T x T`` pairs, none of which holds ``(H, T, T)``
or gathers ``(T, topk, ..)``:

* ``select``: the scores a block of query rows at a time, the row's
  exact k-th value (32 count passes over the scores' ordered-integer
  image, no sort), the tie rule -> the selection as a boolean mask
  ``(T, T)``, the one thing kept of it.
* ``attend``: softmax attention under that mask.  Two lowerings, chosen
  as ``causal_attention`` chooses (``_kernel_takes``): the plain query
  blocks on every platform; two kernels of this repo's own where the
  program is lowered for a TPU (``ops/selected_attention.py``), none of
  the library's.  Forward: a grid step holds the query heads of one
  key/value head and reads ONE tile of the selection for all of them,
  as int8, a byte a pair.  Backward, on the same plan and under the same
  int8 tile: the scores again from the forward's log-sum-exp, ``dq`` of
  a step's rows summed over its causal key tiles in VMEM, ``dk`` and
  ``dv`` of a key/value head over all its query tiles and its group's
  heads in VMEM; q, the output, its cotangent and ``dq`` as ``(T, H *
  Dh)`` rows.  ``dsa:lowering`` records which lowering, the heads a
  loaded mask tile serves and whether the backward pass is the kernel
  too (``backward_kernel``: a ``T`` whose ``dk`` and ``dv`` fit).
* ``target``: the heads' probabilities formed again from the saved
  log-sum-exp, summed over the heads, against the scores formed again:
  the row losses and, while the op is being differentiated, the
  indexer's gradient for a unit cotangent (it is linear in the
  sequence's one cotangent, so the backward pass scales it and runs no
  pass over the pairs for the indexer).  Two lowerings again: the plain
  blocks of rows (float32 ``(Hkv, H / Hkv, rows, keys)`` and ``(rows,
  Hi, keys)`` products through memory); beside the attend kernels ONE
  kernel a layer of this repo's (``selected_target``), which sums the
  heads' probabilities of a tile in VMEM under the same int8 tile of the
  selection and sweeps a row block's key tiles twice for the gradient.
  ``dsa:lowering``'s ``target_kernel`` records which.

Device scopes: ``dsa_score.l<i>``, ``dsa_select.l<i>``, ``dsa_attn.l<i>``,
``dsa_kl.l<i>``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import trace
from ..base import MXNetError
from .pallas_kernels import _kernel_on_tpu
from .registry import OpDef, Param, register_op
from .selected_attention import (backward_tiles, forward_tiles,
                                 selected_attention_bwd,
                                 selected_attention_fwd, selected_target,
                                 target_tiles)
from .transformer import _kernel_takes, layer_scope

__all__ = ["indexed_attention", "indexer_scores", "select_keys"]

# query rows a block of the select pass and of the target pass's plain
# blocks: a block's float32 indexer products are bq * Hi * T * 4 bytes
# (128 MiB at 16 heads and 8192 keys), the plain target's head products
# bq * H * T * 4 (256 MiB at 32 heads; its kernel holds a tile's in VMEM)
DSA_BLOCK_Q = 256
# the select and target passes walk the rows in this many groups, each
# over its own causal keys only (a static slice a group: four programs of
# a pass's body for 62.5 % of its work)
DSA_ROW_GROUPS = 4
# the counter's tiles: ``tiles_hit`` counts the causal DSA_TILE x DSA_TILE
# tiles that hold a selected pair (sa_config's q_chunk_size/kv_chunk_size)
DSA_TILE = 512
STATS = ("rows", "selected_pairs", "causal_pairs", "tiles_hit",
         "tiles_causal")


def _block_rows(t: int) -> int:
    """Rows a block: the largest divisor of ``t`` within DSA_BLOCK_Q."""
    return next(n for n in range(min(DSA_BLOCK_Q, t), 0, -1) if t % n == 0)


def _row_groups(t: int):
    """``[(first row, rows, keys)]``: the sequence's rows as up to
    DSA_ROW_GROUPS equal groups of whole blocks.  A group reads the keys
    up to its own last row and no further (the pairs beyond are not
    causal): 62.5 % of the ``T x T`` rectangle over four groups."""
    blocks = t // _block_rows(t)
    groups = next(n for n in range(min(DSA_ROW_GROUPS, blocks), 0, -1)
                  if blocks % n == 0)
    rows = t // groups
    return [(i * rows, rows, (i + 1) * rows) for i in range(groups)]


def indexer_scores(qi, ki, w):
    """``I`` of ``(rows, Hi, Di)`` indexer queries, ``(T, Di)`` indexer
    keys and ``(rows, Hi)`` head weights -> float32 ``(rows, T)``: the
    products accumulate in float32, the ReLU, the weights and the sum
    over the heads are float32."""
    hi, di = qi.shape[1], qi.shape[2]
    z = jnp.einsum("qjd,kd->qjk", qi, ki,
                   preferred_element_type=jnp.float32)
    weighted = jax.nn.relu(z) * w.astype(jnp.float32)[:, :, None]
    return weighted.sum(axis=1) * np.float32(di ** -0.5 * hi ** -0.5)


def _ordered(x):
    """float32 -> uint32 with the same order (0.0 and -0.0 one value);
    every image is above 0, which stands for a pair that is not causal."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    image = jnp.where(bits < 0, ~bits, bits | np.int32(-2 ** 31))
    return lax.bitcast_convert_type(image, jnp.uint32)


def _kth_largest(keys, want):
    """The ``want``-th largest of each row of uint32 ``keys``, bit by
    bit from the top: the largest value that ``want`` keys reach."""
    def step(i, best):
        cand = best | (np.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(reach >= want, cand, best)

    return lax.fori_loop(0, 32, step, jnp.zeros(keys.shape[:1], jnp.uint32))


def select_keys(scores, q_pos, topk: int):
    """The selection of a block of rows: ``scores`` float32 ``(rows,
    T)``, ``q_pos`` their positions -> bool ``(rows, T)``, True at the
    ``min(q_pos + 1, topk)`` causal keys with the largest score, a tie
    going to the earlier key.  Exact: the k-th largest score is found
    bit by bit over the scores' ordered-integer image."""
    t = scores.shape[1]
    causal = jnp.arange(t)[None, :] <= q_pos[:, None]
    want = jnp.minimum(q_pos + 1, topk).astype(jnp.int32)
    keys = jnp.where(causal, _ordered(scores), np.uint32(0))
    kth = _kth_largest(keys, want)[:, None]
    above, level = (keys > kth) & causal, (keys == kth) & causal
    # the keys level with the k-th: as many of them as the row still
    # wants, the earliest first; a prefix count only where some row has
    # more of them than it wants
    spare = want - jnp.sum(above, axis=1, dtype=jnp.int32)
    tied = jnp.sum(level, axis=1, dtype=jnp.int32) > spare
    return lax.cond(
        jnp.any(tied),
        lambda: above | (level & (jnp.cumsum(level, axis=1, dtype=jnp.int32)
                                  <= spare[:, None])),
        lambda: above | level)


def _select(qi, ki, w, topk: int, layer):
    """Pass one over one sequence -> the selection, bool ``(T, T)``.  A
    group of rows that ends within the first ``topk`` keeps every causal
    key: no score is formed for it."""
    t = qi.shape[0]
    bq = _block_rows(t)
    parts = []
    for lo, rows, keys in _row_groups(t):
        if lo + rows <= topk:
            with layer_scope("dsa_select", layer):
                part = jnp.arange(keys)[None, :] \
                    <= (lo + jnp.arange(rows))[:, None]
        else:
            def one_block(args, keys=keys):
                i, qi_b, w_b = args
                with layer_scope("dsa_score", layer):
                    scores = indexer_scores(qi_b, ki[:keys], w_b)
                with layer_scope("dsa_select", layer):
                    return select_keys(scores, i * bq + jnp.arange(bq), topk)

            part = lax.map(one_block, (
                lo // bq + jnp.arange(rows // bq, dtype=jnp.int32),
                qi[lo:lo + rows].reshape((rows // bq, bq) + qi.shape[1:]),
                w[lo:lo + rows].reshape(rows // bq, bq, -1)))
        with layer_scope("dsa_select", layer):
            parts.append(jnp.pad(part.reshape(rows, keys),
                                 ((0, 0), (0, t - keys))))
    with layer_scope("dsa_select", layer):
        return jnp.concatenate(parts)


def _selection_stats(mask):
    """STATS of one sequence's selection, float32 ``(5,)``."""
    t = mask.shape[0]
    tile = min(DSA_TILE, t)
    nb = -(-t // tile)
    pad = nb * tile - t
    tiles = jnp.pad(mask, ((0, pad), (0, pad))).reshape(nb, tile, nb, tile)
    return jnp.stack([
        jnp.float32(t), jnp.sum(mask, dtype=jnp.float32),
        jnp.float32(t * (t + 1) // 2),
        jnp.sum(tiles.any(axis=(1, 3)), dtype=jnp.float32),
        jnp.float32(nb * (nb + 1) // 2)])


def _attend_plain(q, k, v, mask):
    """Softmax attention of one sequence under ``mask``: ``(T, H, Dh)``
    scaled queries against ``(T, Hkv, Dh)`` keys and ``(T, Hkv, Dv)``
    values, query blocks under ``lax.map`` of a checkpointed body (a
    block's scores live inside it, forward and backward) -> ``(T, H,
    Dv)`` and the float32 log-sum-exp ``(H, T)``.  Every row reads at
    least itself."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    bq = _block_rows(t)

    @jax.checkpoint
    def one_block(args):
        q_b, m_b = args
        s = jnp.einsum("qngd,knd->ngqk", q_b.reshape(bq, hkv, h // hkv, dh),
                       k, preferred_element_type=jnp.float32)
        s = jnp.where(m_b[None, None], s, jnp.float32(-1e30))
        top = s.max(axis=-1, keepdims=True)
        e = jnp.exp(s - top)
        total = e.sum(axis=-1, keepdims=True)
        out = jnp.einsum("ngqk,knd->qngd", (e / total).astype(v.dtype), v)
        return (out.reshape(bq, h, v.shape[2]),
                (top + jnp.log(total))[..., 0].reshape(h, bq))

    out, lse = lax.map(one_block, (q.reshape(t // bq, bq, h, dh),
                                   mask.reshape(t // bq, bq, t)))
    return out.reshape(t, h, v.shape[2]), \
        lse.transpose(1, 0, 2).reshape(h, t)


def _attend_kernel(q, k, v, mask, interpret: bool = False):
    """``_attend_plain``'s TPU lowering: this repo's forward kernel
    (``ops/selected_attention.py``), which reads a tile of the selection
    once for a group of query heads, a byte a pair."""
    with jax.default_matmul_precision("default"):
        return selected_attention_fwd(q, k, v, mask, interpret)


def _attend_kernel_bwd(q, k, v, mask, out, lse, g, interpret: bool = False):
    """``_attend_plain_bwd``'s TPU lowering: this repo's backward kernel
    (``ops/selected_attention.py``) under the same selection, fed the
    forward's output and log-sum-exp -> the cotangents of the scaled q,
    of k and of v."""
    with jax.default_matmul_precision("default"):
        return selected_attention_bwd(q, k, v, mask, out, lse, g, interpret)


def _attend_plain_bwd(q, k, v, mask, out, lse, g):
    del out, lse
    return jax.vjp(lambda q, k, v: _attend_plain(q, k, v, mask)[0],
                   q, k, v)[1](g)


def _target(qi, ki, w, q, k, lse, mask, layer, with_grads: bool):
    """Pass three over one sequence -> ``(mean row loss, unit gradients
    of it by (qi, ki, w) or None)``."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    bq = _block_rows(t)

    def rows_loss(qi_b, ki, w_b, p, m_b):
        scores = indexer_scores(qi_b, ki, w_b)
        chosen = jnp.where(m_b, scores, -jnp.inf)
        lse_i = jax.nn.logsumexp(chosen, axis=1)
        return jnp.sum(jnp.sum(jax.scipy.special.xlogy(p, p)
                               - p * jnp.where(m_b, scores, 0.0), axis=1)
                       + jnp.sum(p, axis=1) * lse_i)

    def one_block(d_ki, args, k, ki):
        q_b, lse_b, m_b, qi_b, w_b = args
        with layer_scope("dsa_kl", layer):
            s = jnp.einsum("qngd,knd->ngqk",
                           q_b.reshape(bq, hkv, h // hkv, dh), k,
                           preferred_element_type=jnp.float32)
            a = jnp.exp(s - lse_b.reshape(hkv, h // hkv, bq)[..., None])
            p = jnp.where(m_b, a.sum(axis=(0, 1)), 0.0) / h
            if not with_grads:
                return d_ki, (rows_loss(qi_b, ki, w_b, p, m_b),)
            loss, (d_qi, d_k, d_w) = jax.value_and_grad(
                rows_loss, argnums=(0, 1, 2))(qi_b, ki, w_b, p, m_b)
            return d_ki + d_k.astype(jnp.float32), (loss, d_qi, d_w)

    # the loops themselves stay outside the passes' scopes: in a device
    # trace a ``while`` is an event that SPANS its body's operations, and
    # under the node's generic scope it is not summed with them twice
    d_ki, outs = jnp.zeros(ki.shape, jnp.float32), []
    for lo, rows, keys in _row_groups(t):
        nb, at = rows // bq, slice(lo, lo + rows)
        blocks = (q[at].reshape(nb, bq, h, dh),
                  lse[:, at].reshape(h, nb, bq).transpose(1, 0, 2),
                  mask[at, :keys].reshape(nb, bq, keys),
                  qi[at].reshape((nb, bq) + qi.shape[1:]),
                  w[at].reshape(nb, bq, -1))
        d_part, part = lax.scan(
            functools.partial(one_block, k=k[:keys], ki=ki[:keys]),
            jnp.zeros((keys,) + ki.shape[1:], jnp.float32), blocks)
        outs.append(part)
        with layer_scope("dsa_kl", layer):
            d_ki = d_ki + jnp.pad(d_part, ((0, t - keys), (0, 0)))
    with layer_scope("dsa_kl", layer):
        outs = [jnp.concatenate(x) for x in zip(*outs)]
        loss = jnp.sum(outs[0]) / t
        if not with_grads:
            return loss, None
        return loss, ((outs[1].reshape(qi.shape) / t).astype(qi.dtype),
                      (d_ki / t).astype(ki.dtype),
                      (outs[2].reshape(w.shape) / t).astype(w.dtype))


def _target_kernel(qi, ki, w, q, k, lse, mask, layer, with_grads: bool,
                   interpret: bool = False):
    """``_target``'s TPU lowering: this repo's kernel
    (``ops/selected_attention.py`` ``selected_target``), which holds a
    tile's products in VMEM."""
    with layer_scope("dsa_kl", layer), \
            jax.default_matmul_precision("default"):
        return selected_target(qi, ki, w, q, k, lse, mask, with_grads,
                               interpret)


def _one_sequence(args, topk, scale, layer, kernel, target, with_grads):
    """All three passes over one sequence."""
    q, k, v, qi, ki, w = args
    mask = _select(qi, ki[:, 0], w, topk, layer)
    with layer_scope("dsa_select", layer):
        stats = _selection_stats(mask)
    with layer_scope("dsa_attn", layer):
        qs = q * jnp.asarray(scale, q.dtype)
        if kernel:
            out, lse = _kernel_on_tpu(_attend_kernel, _attend_plain, False,
                                      qs, k, v, mask)
        else:
            out, lse = _attend_plain(qs, k, v, mask)
    by_kernel, plain = (
        functools.partial(fn, layer=layer, with_grads=with_grads)
        for fn in (_target_kernel, _target))
    operands = (qi, ki[:, 0], w, qs, k, lse, mask)
    if target:
        loss, grads = _kernel_on_tpu(by_kernel, plain, False, *operands)
    else:
        loss, grads = plain(*operands)
    return (out, loss, stats), (mask, lse, grads)


def _over_batch(fn, args):
    """``fn`` of one sequence over the batch, one sequence at a time
    (the selection is data: a batched mask is no scalar-prefetch
    operand)."""
    if args[0].shape[0] == 1:
        return jax.tree_util.tree_map(
            lambda x: x[None], fn(tuple(x[0] for x in args)))
    return lax.map(fn, args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _indexed_attention(q, k, v, qi, ki, w, topk, scale, layer, kernel,
                       target):
    return _over_batch(
        lambda args: _one_sequence(args, topk, scale, layer, kernel, target,
                                   False)[0], (q, k, v, qi, ki, w))


def _indexed_fwd(q, k, v, qi, ki, w, topk, scale, layer, kernel, target):
    outs, (mask, lse, grads) = _over_batch(
        lambda args: _one_sequence(args, topk, scale, layer, kernel, target,
                                   True), (q, k, v, qi, ki, w))
    return outs, (q, k, v, mask, outs[0], lse, grads)


def _indexed_bwd(topk, scale, layer, kernel, target, res, cotangents):
    q, k, v, mask, out, lse, unit = res
    g_out, g_loss, _ = cotangents
    by_kernel = kernel and _backward_takes(q, v)

    def one_sequence(args):
        q, k, v, mask, out, lse, g = args
        with layer_scope("dsa_attn", layer):
            qs = q * jnp.asarray(scale, q.dtype)
            if by_kernel:
                d_qs, d_k, d_v = _kernel_on_tpu(
                    _attend_kernel_bwd, _attend_plain_bwd, False,
                    qs, k, v, mask, out, lse, g)
            else:
                d_qs, d_k, d_v = _attend_plain_bwd(qs, k, v, mask, out, lse,
                                                   g)
            return d_qs * jnp.asarray(scale, q.dtype), d_k, d_v

    d_q, d_k, d_v = _over_batch(one_sequence,
                                (q, k, v, mask, out, lse, g_out))
    # the sequence's mean row loss has ONE cotangent: the indexer's
    # gradient is the unit gradient the forward pass formed, scaled
    with layer_scope("dsa_kl", layer):
        d_qi, d_ki, d_w = (
            (u.astype(jnp.float32)
             * g_loss.reshape((-1,) + (1,) * (u.ndim - 1))).astype(u.dtype)
            for u in unit)
    return d_q, d_k, d_v, d_qi, d_ki[:, :, None, :], d_w


_indexed_attention.defvjp(_indexed_fwd, _indexed_bwd)


def _backward_takes(q, v) -> bool:
    """Whether the backward kernel takes what the forward kernel took:
    ``(B, T, H, Dh)`` q and ``(B, T, Hkv, Dv)`` v whose ``T`` leaves one
    key/value head's float32 ``dk`` and ``dv`` room in VMEM."""
    return backward_tiles(q.shape[1], q.shape[2] // v.shape[2],
                          max(q.shape[3], v.shape[3])) is not None


def indexed_attention(q, k, v, qi, ki, w, topk: int, scale: float,
                      layer=None):
    """``IndexedSelfAttention``'s body: ``(B, T, H, Dh)`` q, ``(B, T, Hkv,
    Dh)`` k, ``(B, T, Hkv, Dv)`` v, ``(B, T, Hi, Di)`` indexer queries,
    ``(B, T, 1, Di)`` indexer keys, ``(B, T, Hi)`` head weights -> the
    heads' outputs ``(B, T, H, Dv)``, each sequence's mean row loss
    ``(B,)`` float32 and the selections' STATS ``(B, 5)`` float32.
    Each trace records the attend pass's lowering as ``dsa:lowering``
    (``kernel`` 1: the kernels where the program is lowered for a TPU,
    with ``heads_a_mask_tile`` the query heads that the forward kernel
    serves from one loaded tile of the selection; ``plain`` 1: the plain
    blocks everywhere, ``heads_a_mask_tile`` 0); the track names dtype,
    shape, ``/kv<Hkv>`` and ``/top<topk>``.  ``target_kernel`` 1: the
    target pass runs its kernel beside them (the indexer in the same
    dtype, heads of whole 64 lanes, ``target_tiles`` takes ``T``);
    ``backward_kernel`` 1: the backward attend pass runs this repo's
    kernel too (``backward_tiles`` takes ``T``), 0: the plain blocks."""
    h, hkv = q.shape[2], k.shape[2]
    if h % hkv or v.shape[2] != hkv or ki.shape[2] != 1:
        raise MXNetError("indexed attention: %d query heads over %d key and "
                         "%d value heads, %d indexer key heads (one)"
                         % (h, hkv, v.shape[2], ki.shape[2]))
    # the kernels read a head's lanes out of ``(T, H * Dh)`` rows
    kernel = _kernel_takes(q, k, v) and q.shape[3] % 128 == 0 \
        and v.shape[3] % 128 == 0
    heads = forward_tiles(q.shape[1], h // hkv,
                          max(q.shape[3], v.shape[3]))[0] if kernel else 0
    # the target kernel beside them: the indexer in the same dtype, heads
    # of whole half lane blocks, a length whose kept rows fit its VMEM
    target = bool(kernel and qi.shape[3] % 64 == 0
                  and all(x.dtype == q.dtype for x in (qi, ki, w))
                  and target_tiles(q.shape[1], qi.shape[2]))
    trace.counter("dsa:lowering", cat="ops", track="%s%s%s/top%d" % (
        q.dtype.name, list(q.shape), "" if hkv == h else "/kv%d" % hkv,
        topk), kernel=int(kernel), plain=int(not kernel),
        heads_a_mask_tile=heads, target_kernel=int(target),
        backward_kernel=int(kernel and _backward_takes(q, v)))
    return _indexed_attention(q, k, v, qi, ki, w, int(topk), float(scale),
                              layer, kernel, target)


@register_op("IndexedSelfAttention", hint="indexedattention")
class IndexedSelfAttentionOp(OpDef):
    """Causal self-attention over the keys a learned indexer selects
    (DeepSeek Sparse Attention): ``(B, T, H, Dh)`` query, ``(B, T, Hkv,
    Dh)`` key, ``(B, T, Hkv, Dv)`` value (``Hkv`` a whole divisor of
    ``H``), the indexer's ``(B, T, Hi, Di)`` queries, ``(B, T, 1, Di)``
    keys and ``(B, T, Hi)`` head weights.  Row ``t`` reads the ``min(t +
    1, topk)`` keys ``s <= t`` with the largest ``I[t, s] = sum_j w[t, j]
    relu(qI[t, j] . kI[s]) Di**-0.5 Hi**-0.5``, a tie going to the
    earlier key; ``topk`` of ``T`` or more is ``CausalSelfAttention``.

    Outputs: ``output`` ``(B, T, H, Dv)``; ``index_loss`` ``(B,)``
    float32, a sequence's mean over its rows of ``KL(p[t, .] || softmax
    over the selection of I[t, .])`` with ``p`` the heads' mean
    probabilities (wrap it in ``MakeLoss`` to train the indexer);
    ``selection`` ``(B, 5)`` float32, no gradient: rows, selected pairs,
    causal pairs, DSA_TILE-square causal tiles holding a selected pair,
    causal tiles.  ``output``'s gradient reaches query, key and value
    only, ``index_loss``'s the indexer's three inputs only; the
    selection and the target take none.  ``scale`` 0 means ``Dh**-0.5``;
    ``layer`` names the trace scopes ``dsa_*.l<layer>``.  The attend pass
    of a program lowered for a TPU (bfloat16, heads of whole 128 lanes,
    ``T`` in whole tiles) runs two kernels of this repo's
    (``ops/selected_attention.py``): forward ``splash_mha_fwd_selected``,
    backward ``splash_mha_dkv_selected``, each reading one int8 tile of
    the selection for a group of query heads; the target pass beside
    them one kernel a layer,
    ``dsa_target_grads`` (``dsa_target_loss`` where nothing is
    differentiated); everything else runs the plain blocks."""
    params = [Param("topk", int, required=True),
              Param("scale", float, default=0.0),
              Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["query", "key", "value", "index_query", "index_key",
                "index_weight"]

    def list_outputs(self, p):
        return ["output", "index_loss", "selection"]

    def infer_shape(self, p, in_shapes):
        q, k, v, qi, ki, w = in_shapes
        if p.topk < 1:
            raise MXNetError("IndexedSelfAttention: topk %d: a query reads "
                             "at least itself" % p.topk)
        if any(s is None for s in (q, k, v, qi)):
            return in_shapes, [None] * 3, []
        for name, s in (("query", q), ("key", k), ("value", v),
                        ("index_query", qi)):
            if len(s) != 4 or tuple(s[:2]) != tuple(q[:2]):
                raise MXNetError("IndexedSelfAttention: %s must be (batch, "
                                 "seq, heads, head_dim) over query's rows "
                                 "%r, got %r" % (name, tuple(q[:2]), s))
        if k[3] != q[3] or k[2] < 1 or q[2] % k[2] or v[2] != k[2]:
            raise MXNetError("IndexedSelfAttention: key %r and value %r "
                             "against query %r" % (tuple(k), tuple(v),
                                                   tuple(q)))
        b, t, hi, di = qi
        return ([q, k, v, qi, (b, t, 1, di), (b, t, hi)],
                [tuple(q[:3]) + (v[3],), (b,), (b, len(STATS))], [])

    def infer_type(self, p, in_types):
        known = next((t for t in in_types if t is not None),
                     np.dtype(np.float32))
        f32 = np.dtype(np.float32)
        return ([known if t is None else t for t in in_types],
                [known, f32, f32], [])

    def forward(self, p, inputs, aux, ctx):
        q = inputs[0]
        scale = p.scale or float(q.shape[-1]) ** -0.5
        return list(indexed_attention(*inputs, topk=p.topk, scale=scale,
                                      layer=p.layer))
