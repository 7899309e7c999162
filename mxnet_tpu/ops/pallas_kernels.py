"""Pallas TPU kernels for hot ops.

The RTC subsystem's successor (SURVEY §2.1 RTC row): where the reference let
users JIT raw CUDA (mxrtc.cc), the TPU build ships Pallas kernels and lets
users write their own through mxnet_tpu.rtc.

flash_attention: blockwise attention with online softmax, MXU-shaped tiles
(q blocks x k blocks of 128, fp32 accumulators in VMEM), causal masking via
block skipping; ragged lengths are padded up to the tile grid and masked.

paged_attention: attention through a paged KV cache (serve.paged) — the
per-slot page table rides scalar prefetch and indexes the block pool
directly from the BlockSpec index map, so each grid step streams one
physical KV block; online softmax accumulates across the page walk in
VMEM scratch.

Every kernel ships beside a dense jnp twin.  Which of the two runs is
decided when the program is LOWERED, from the platform it is compiled
for (``_kernel_on_tpu``): Mosaic on a TPU, the twin anywhere else.
Tier-1 runs the kernels with ``interpret=True``; tests/tpu/
test_pallas_tpu.py holds the compiled-vs-twin checks from the chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..base import get_env
from .quantized import INT8_QMAX

try:
    from jax.experimental import pallas as pl
    HAS_PALLAS = True
except Exception:  # pragma: no cover
    pl = None
    HAS_PALLAS = False

__all__ = ["flash_attention", "paged_attention", "correlation",
           "fused_fc_epilogue", "HAS_PALLAS"]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel_on_tpu(kernel_fn, dense_fn, interpret: bool, *args):
    """``kernel_fn(*args)`` where this computation is lowered for a TPU
    (or anywhere under ``interpret=True``), ``dense_fn(*args)`` on every
    other platform.  The platform is the one the program is compiled
    for, not the process's default backend: a ``mx.cpu()`` program in a
    TPU process never reaches Mosaic, and a TPU program never silently
    takes the dense path.  ``dense_fn=None`` means the kernel
    unconditionally (the parity checks)."""
    if interpret or dense_fn is None:
        return kernel_fn(*args)
    return lax.platform_dependent(*args, tpu=kernel_fn, default=dense_fn)


def _searched(family: str, *args):
    """The kernel search's persisted winner for this call's shape class,
    or None.  Tiling resolves explicit argument > searched winner >
    hand-tuned default; the winner layer only engages under
    ``MXNET_KERNEL_SEARCH=1`` (call-time behavior must not silently
    depend on store state), is LOAD-ONLY (never searches on the hot
    path), and is process-cached per class — negative lookups included
    (autotune.kernelsearch.best_config)."""
    if not get_env("MXNET_KERNEL_SEARCH", False, bool):
        return None
    from ..autotune import kernelsearch as ks
    cls = {"flash": ks.flash_class, "fc": ks.fc_class,
           "paged": ks.paged_class}[family](*args)
    return ks.best_config(cls)


def _attention_dense(q, k, v, causal):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, causal,
                  scale, seq_len, true_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)            # (block_q, D)
    d = q.shape[-1]
    nk = seq_len // block_k

    m0 = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, d), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        kblk = k_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, kblk.T, preferred_element_type=jnp.float32) * scale
        k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
        if true_len < seq_len:
            # ragged tail: the sequence was padded up to the tile grid —
            # padded KEYS are masked here, padded QUERY rows compute
            # garbage the caller slices off
            s = jnp.where(k_pos < true_len, s, -jnp.inf)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        blk_max = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        safe_m = jnp.where(jnp.isinf(new_m), 0.0, new_m)
        p = jnp.where(jnp.isinf(s), 0.0, jnp.exp(s - safe_m[:, None]))
        corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - safe_m))
        l2 = l * corr + jnp.sum(p, axis=-1)
        acc2 = acc * corr[:, None] + jnp.dot(p, vblk,
                                             preferred_element_type=jnp.float32)
        return new_m, l2, acc2

    if causal:
        # only blocks with k_start <= q_end contribute
        nk_run = (qi * block_q + block_q + block_k - 1) // block_k
        nk_run = jnp.minimum(nk_run, nk)
    else:
        nk_run = nk
    m, l, acc = lax.fori_loop(0, nk_run, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = False, block_q=None,
                    block_k=None, interpret: bool = False):
    """Blockwise attention.  q, k, v: (B, T, H, D) -> (B, T, H, D).

    The Pallas kernel where the program is lowered for a TPU (or with
    interpret=True anywhere); dense attention elsewhere.
    ``block_q``/``block_k`` default to the kernel search's persisted
    winner for this shape class when ``MXNET_KERNEL_SEARCH=1`` (every
    winner was bitwise-parity-gated before persistence), else 128; an
    explicit argument always wins.
    """
    from ..parallel.ring import attention_reference
    dense = functools.partial(attention_reference, causal=causal)
    if not HAS_PALLAS:
        return dense(q, k, v)
    b, t, h, d = q.shape
    if block_q is None or block_k is None:
        win = _searched("flash", t, d, causal, q.dtype) or {}
        block_q = int(win.get("block_q", 128)) if block_q is None \
            else block_q
        block_k = int(win.get("block_k", 128)) if block_k is None \
            else block_k

    # ragged sequence lengths: clamp the tiles near T (8-aligned for the
    # f32 sublane), pad T up to the tile grid, mask the padded keys in
    # the kernel, slice the padded queries off the output — odd lengths
    # stay on the kernel instead of silently falling back to dense
    block_q = min(block_q, _round_up(t, 8))
    block_k = min(block_k, _round_up(t, 8))
    tp = _round_up(t, block_q * block_k // math.gcd(block_q, block_k))
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal,
                               scale=1.0 / math.sqrt(d), seq_len=tp,
                               true_len=t)

    def run(q, k, v):
        if tp != t:
            pad = [(0, 0), (0, tp - t), (0, 0), (0, 0)]
            q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        # (B, T, H, D) -> (B*H, T, D)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, tp, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * h, tp, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * h, tp, d)
        out = pl.pallas_call(
            kernel,
            grid=(b * h, tp // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, tp, d), lambda bh, i: (bh, 0, 0)),
                pl.BlockSpec((1, tp, d), lambda bh, i: (bh, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda bh, i: (bh, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b * h, tp, d), q.dtype),
            interpret=interpret,
        )(qf, kf, vf)
        out = out.reshape(b, h, tp, d).transpose(0, 2, 1, 3)
        return out[:, :t] if tp != t else out

    return _kernel_on_tpu(run, dense, interpret, q, k, v)


def _paged_attention_dense(q, k_pool, v_pool, pages, lengths, q_pos,
                           causal: bool = True):
    """Dense reference for paged attention — and the off-TPU execution
    path of the paged engine (it is jit-traceable and bitwise-stable
    across physical block layouts: the gather reorders pool rows into
    logical order before one fixed-shape reduction, so dense-stripe and
    scattered page tables produce identical floats).

    q:               (S, C, H, D)  per-slot query window
    k_pool / v_pool: (N, bt, H, D) block pools (N may include a
                     sentinel scratch block at index >= the page-table
                     domain; any out-of-range entry is clamped and its
                     keys masked by ``lengths``)
    pages:           (S, B)  int32 physical block id per logical block
    lengths:         (S,)    int32 valid context tokens per slot
    q_pos:           (S, C)  int32 absolute position of each query
    -> (S, C, H, D)
    """
    n = k_pool.shape[0]
    s_, c, h, d = q.shape
    b = pages.shape[1]
    bt = k_pool.shape[1]
    scale = 1.0 / math.sqrt(d)
    safe = jnp.minimum(pages, n - 1)
    kg = k_pool[safe].reshape(s_, b * bt, h, d).astype(jnp.float32)
    vg = v_pool[safe].reshape(s_, b * bt, h, d).astype(jnp.float32)
    s = jnp.einsum("schd,skhd->shck", q.astype(jnp.float32), kg) * scale
    k_idx = jnp.arange(b * bt, dtype=jnp.int32)
    mask = (k_idx[None, :] < lengths[:, None])[:, None, None, :]
    if causal:
        mask = mask & (k_idx[None, None, :]
                       <= q_pos[:, :, None])[:, None, :, :]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isinf(m), 0.0, m)
    p = jnp.where(jnp.isinf(s), 0.0, jnp.exp(s - m_safe))
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-20)
    out = jnp.einsum("shck,skhd->schd", p / l, vg)
    return out.astype(q.dtype)


def _paged_kernel(pages_ref, len_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_s, l_s, acc_s, *, block_tokens, causal, scale):
    """Online-softmax attention over one slot's page-table walk: grid
    (S, B), one physical KV block per step (fetched straight from the
    pool via the scalar-prefetched page table — no gather materializes
    the context), f32 m/l/acc carries in VMEM scratch across the B
    axis, output written on the last block."""
    s_i, b_i = pl.program_id(0), pl.program_id(1)

    @pl.when(b_i == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    qh = q_ref[0].astype(jnp.float32).transpose(1, 0, 2)   # (H, C, D)
    kh = k_ref[0].astype(jnp.float32).transpose(1, 0, 2)   # (H, bt, D)
    vh = v_ref[0].astype(jnp.float32).transpose(1, 0, 2)
    s = jnp.einsum("hcd,hkd->hck", qh, kh,
                   preferred_element_type=jnp.float32) * scale
    k_pos = b_i * block_tokens + lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = k_pos < len_ref[s_i]
    if causal:
        mask = mask & (k_pos <= pos_ref[0][None])
    s = jnp.where(mask, s, -jnp.inf)
    m_prev = m_s[...]
    new_m = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    safe_m = jnp.where(jnp.isinf(new_m), 0.0, new_m)
    p = jnp.where(jnp.isinf(s), 0.0, jnp.exp(s - safe_m[..., None]))
    corr = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev - safe_m))
    m_s[...] = new_m
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1)
    acc_s[...] = acc_s[...] * corr[..., None] + jnp.einsum(
        "hck,hkd->hcd", p, vh, preferred_element_type=jnp.float32)

    @pl.when(b_i == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-20)
        o_ref[0] = (acc_s[...] / l[..., None]).transpose(1, 0, 2).astype(
            o_ref.dtype)


def paged_attention(q, k_pool, v_pool, pages, lengths, q_pos=None,
                    causal: bool = True, interpret: bool = False):
    """Attention through a paged KV cache (see _paged_attention_dense
    for the argument contract).  Q is a (S, C) token window per slot —
    C = 1 for plain decode, the prefill chunk / speculative verify
    width otherwise.

    The Pallas page-walk kernel where the program is lowered for a TPU
    (or with ``interpret=True`` anywhere): the page table rides scalar
    prefetch, so each grid step DMAs exactly one physical block from the
    pool — context length costs bandwidth, not a materialized gather.
    The dense gather reference on every other platform, keeping CPU
    tier-1 numerics identical to the engine's reference path.
    """
    s_, c, h, d = q.shape
    if q_pos is None:
        q_pos = lengths[:, None] - c + jnp.arange(c, dtype=jnp.int32)[None]
    dense = functools.partial(_paged_attention_dense, causal=causal)
    if not HAS_PALLAS:
        return dense(q, k_pool, v_pool, pages, lengths, q_pos)
    # the kernel's blocking is fixed by the pool's page size, so the
    # searched axis is WHICH program: a persisted "dense" winner means
    # the gather reference beat the page walk on this backend/class
    win = _searched("paged", k_pool.shape[1], d, causal, q.dtype)
    if win is not None and win.get("impl") == "dense":
        return dense(q, k_pool, v_pool, pages, lengths, q_pos)
    from jax.experimental.pallas import tpu as pltpu
    n, bt = k_pool.shape[0], k_pool.shape[1]
    b = pages.shape[1]
    kernel = functools.partial(_paged_kernel, block_tokens=bt,
                               causal=causal, scale=1.0 / math.sqrt(d))

    def _page(sl, bl, pages_ref, _len):
        # sentinel / unassigned entries clamp to a real block — their
        # keys sit past `lengths` and are masked in the kernel
        return (jnp.minimum(pages_ref[sl, bl], n - 1), 0, 0, 0)

    # pages and lengths ride scalar prefetch (SMEM: the index map and
    # the kernel read them one scalar at a time); q_pos is read as a
    # vector, so it is a VMEM input laid out (C, 1) — C on the sublanes,
    # like the score tile it masks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, b),
        in_specs=[
            pl.BlockSpec((1, c, 1), lambda sl, bl, *_: (sl, 0, 0)),
            pl.BlockSpec((1, c, h, d), lambda sl, bl, *_: (sl, 0, 0, 0)),
            pl.BlockSpec((1, bt, h, d), _page),
            pl.BlockSpec((1, bt, h, d), _page),
        ],
        out_specs=pl.BlockSpec((1, c, h, d), lambda sl, bl, *_:
                               (sl, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, c), jnp.float32),
            pltpu.VMEM((h, c), jnp.float32),
            pltpu.VMEM((h, c, d), jnp.float32),
        ],
    )

    def run(q, k_pool, v_pool, pages, lengths, q_pos):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_, c, h, d), q.dtype),
            interpret=interpret,
        )(pages.astype(jnp.int32), lengths.astype(jnp.int32),
          q_pos.astype(jnp.int32)[..., None], q, k_pool, v_pool)

    return _kernel_on_tpu(run, dense, interpret, q, k_pool, v_pool, pages,
                          lengths, q_pos)


def _fc_epilogue_kernel(x_ref, w_ref, b_ref, o_ref, *, act_type, out_scale):
    """One N-block of act(x·Wᵀ + b) [+ int8 requantize]: the epilogue
    rides the MXU tile's output registers — one VMEM round trip for the
    whole matmul+bias+act(+quantize) chain instead of one per op."""
    x = x_ref[...].astype(jnp.float32)                 # (M, K)
    w = w_ref[...].astype(jnp.float32)                 # (block_n, K)
    acc = jnp.dot(x, w.T, preferred_element_type=jnp.float32)
    acc = acc + b_ref[...]                             # (1, block_n)
    if act_type == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif act_type == "sigmoid":
        acc = jax.nn.sigmoid(acc)
    elif act_type == "tanh":
        acc = jnp.tanh(acc)
    elif act_type == "softrelu":
        acc = jax.nn.softplus(acc)
    if out_scale is not None:
        acc = jnp.clip(jnp.round(acc / out_scale), -INT8_QMAX, INT8_QMAX)
    o_ref[...] = acc.astype(o_ref.dtype)


def fused_fc_epilogue(x, w, b, act_type: str, out_scale=None,
                      block_n=None, interpret: bool = False, dense=None):
    """FullyConnected epilogue kernel: x (M, K) · w (N, K)ᵀ + b, fused
    activation, optional int8 requantize (``out_scale``).  Returns the
    (M, N) result — f32, or int8 when ``out_scale`` is set — or None
    when the shape or activation is ineligible, and the caller runs its
    own jnp body.  ``dense(x, w, b)`` is that body: it runs instead of
    the kernel wherever the program is not lowered for a TPU, so CPU
    tier-1 numerics stay identical to the unfused graph; without it the
    kernel runs unconditionally.  ``block_n`` defaults to the kernel
    search's persisted winner under ``MXNET_KERNEL_SEARCH=1``, else
    128."""
    if not HAS_PALLAS:
        return None
    if act_type not in ("none", "relu", "sigmoid", "tanh", "softrelu"):
        return None
    m, k = x.shape
    n = w.shape[0]
    if block_n is None:
        win = _searched("fc", n, k, act_type, out_scale is not None,
                        x.dtype) or {}
        block_n = int(win.get("block_n", 128))
    # MXU lane/sublane alignment: K and N on the 128 lanes; M must fill
    # the output tile's sublanes (8 for f32, 32 for an int8 result) —
    # the interpreter alone takes any M
    min_m = 32 if out_scale is not None else 8
    if n % block_n or k % 128 or (not interpret and m % min_m):
        return None
    out_dtype = jnp.int8 if out_scale is not None else x.dtype
    kernel = functools.partial(
        _fc_epilogue_kernel, act_type=act_type,
        out_scale=None if out_scale is None else float(out_scale))

    def run(x, w, b):
        if b is None:
            b = jnp.zeros((n,), jnp.float32)
        # the bias rides as (1, N): a 1-D operand's XLA layout does not
        # tile the way a (block_n,) Mosaic block would
        return pl.pallas_call(
            kernel,
            grid=(n // block_n,),
            in_specs=[
                pl.BlockSpec((m, k), lambda i: (0, 0)),
                pl.BlockSpec((block_n, k), lambda i: (i, 0)),
                pl.BlockSpec((1, block_n), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((m, block_n), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            interpret=interpret,
        )(x, w, b.reshape(1, n))

    return _kernel_on_tpu(run, dense, interpret, x, w, b)


def _correlation_kernel(a_ref, b_ref, o_ref, *, d2, stride2, base, hh, ww,
                        is_multiply, norm):
    """One batch sample per grid step: a (C,H,W) against the padded
    b (C,H+2m,W+2m); the d2*d2 displacement loop reuses both VMEM tiles —
    one HBM read per input instead of one per displacement (what the
    unrolled jnp.roll lowering pays).  Displacement offsets are STATIC
    python-unrolled slices: Mosaic cannot prove alignment for dynamic
    lane-dimension offsets."""
    a = a_ref[0].astype(jnp.float32)                      # (C, H, W)
    b = b_ref[0].astype(jnp.float32)                      # (C, H+2m, W+2m)
    for idx in range(d2 * d2):
        # centered displacement (i-ng)*stride2 relative to the m-padded
        # image: offset = m + (i-ng)*stride2 = base + i*stride2, which
        # differs from i*stride2 whenever stride2 does not divide m
        dy = base + (idx // d2) * stride2
        dx = base + (idx % d2) * stride2
        b_tile = b[:, dy:dy + hh, dx:dx + ww]
        if is_multiply:
            corr = jnp.sum(a * b_tile, axis=0) / norm
        else:
            corr = jnp.sum(jnp.abs(a - b_tile), axis=0) / norm
        o_ref[0, idx] = corr.astype(o_ref.dtype)


def correlation(a, b, max_displacement: int, stride2: int = 1,
                is_multiply: bool = True, interpret: bool = False,
                dense=None):
    """FlowNet correlation (reference correlation.cu) for the
    kernel_size=1 / stride1=1 / pad=max_displacement configuration.
    a, b: (N, C, H, W) -> (N, D2*D2, H, W) with D2 = 2*(m//stride2)+1.
    Returns None when the window is ineligible (caller falls back to
    its lax lowering).  ``dense(a, b)`` is that lowering: it runs
    instead of the kernel wherever the program is not lowered for a
    TPU; without it the kernel runs unconditionally."""
    if not HAS_PALLAS:
        return None
    n, c, h, w = a.shape
    m = max_displacement
    ng = m // stride2
    d2 = 2 * ng + 1
    if d2 * d2 > 169:   # static unroll bound: fall back for huge windows
        return None
    kernel = functools.partial(
        _correlation_kernel, d2=d2, stride2=stride2, base=m - ng * stride2,
        hh=h, ww=w, is_multiply=is_multiply, norm=float(c))

    def run(a, b):
        bp = jnp.pad(b, [(0, 0), (0, 0), (m, m), (m, m)])
        return pl.pallas_call(
            kernel,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, c, h, w), lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((1, c, h + 2 * m, w + 2 * m),
                             lambda i: (i, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, d2 * d2, h, w),
                                   lambda i: (i, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n, d2 * d2, h, w), a.dtype),
            interpret=interpret,
        )(a, bp)

    return _kernel_on_tpu(run, dense, interpret, a, b)
