"""PagedDecodeEngine: LLM-class continuous batching over a paged KV cache.

The dense :class:`~mxnet_tpu.serve.decode.DecodeEngine` carries
fixed-shape per-slot state rows — right for RNN cells, wrong for
transformer decode, where per-slot state is a KV cache that grows with
context and padding every slot to max context makes long and short
streams uneconomical to co-host.  This engine keeps the slot/queue/
drain discipline of decode.py and swaps the state story:

* **paged KV cache** (pool.py) — K/V live in a shared device pool of
  fixed-size blocks; each slot maps logical context onto physical
  blocks through a page table.  Admission reserves a stream's exact
  worst-case block count (prompt + max_new are known at submit), so an
  admitted stream can never be dropped or deadlocked mid-generation:
  ``dropped_streams`` is 0 **by design**, and the bench gate holds it
  there;
* **one step program, two widths** — the compiled step consumes a
  ``(num_slots, C)`` token window with a per-slot valid count; C = 1 is
  the pure-decode program, C = ``chunk_tokens`` serves prefill chunks
  and speculative verification.  Both are warmed at construction, so
  the steady loop never compiles;
* **chunked prefill** — a long prompt enters the batch ``chunk_tokens``
  tokens at a time *alongside* in-flight decode slots (which keep
  emitting one token per step), bounding p99 inter-token latency under
  mixed prompt lengths instead of stalling the world on admission;
* **speculative decode** (spec.py) — a draft model sharing the pool's
  page table proposes K tokens per round; the target verifies K+1
  positions in ONE chunk-width step.  Greedy argmax acceptance makes
  the emitted stream token-identical to pure target decode — rejected
  tokens roll back by moving length counters, their stale KV rows are
  simply overwritten later;
* **attention** — the Pallas page-walk kernel
  (:func:`mxnet_tpu.ops.pallas_kernels.paged_attention`) where the step
  is lowered for a TPU, the dense gather reference on any other
  platform.  The reference reorders pool rows
  into logical order before one fixed-shape reduction, so dense-stripe
  (``paged=False``) and scattered page tables produce bitwise-identical
  logits — the parity baseline the tests pin.

Knobs: ``MXNET_KVPOOL_BLOCKS``, ``MXNET_KVPOOL_BLOCK_TOKENS``,
``MXNET_PAGED_CHUNK``, ``MXNET_SPEC_DECODE_K``, ``MXNET_PAGED_PALLAS``
(plus the decode-engine family: ``MXNET_SERVE_SLOTS``,
``MXNET_SERVE_DECODE_QUEUE``, ``MXNET_SERVE_MAX_TOKENS``) — see
docs/env_var.md and docs/llm_serve.md.
"""
from __future__ import annotations

import collections
import functools
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from ... import trace as _trace
from ...base import get_env, make_condition
from ...faults import point as _fault_point
from ..batcher import _IDLE_POLL_S, _set_exception, _set_result
from ..decode import _DecodeRequest, _trace_end
from ..errors import (ServeClosedError, ServeDeadlineError, ServeError,
                      ServeOverloadError, ServeRequestError)
from ..stats import PagedStats
from .model import LMConfig, lm_forward, param_bytes
from .pool import KVBlockPool

__all__ = ["PagedDecodeEngine"]


def _paged_step(params, kv_k, kv_v, tokens, pages, positions, n_valid,
                lengths, *, cfg, use_kernel):
    """One compiled decode step over a (S, C) token window.

    tokens/positions (S, C) int32; pages (S, B) int32; n_valid (S,)
    int32 tokens valid per slot; lengths (S,) int32 context size AFTER
    this step's appends.  Appends each valid token's K/V through the
    page table, then attends causally over the paged context.  Returns
    (argmax tokens (S, C) int32, kv_k, kv_v).

    Invalid window positions scatter into the pool's sentinel scratch
    row — a *positive* index with ``mode='drop'`` as the backstop, so
    nothing can wrap to block -1 (negative indices wrap in ``.at[]``;
    the PR 12 embedding-engine bug class).
    """
    import jax.numpy as jnp

    from ...ops.pallas_kernels import (_paged_attention_dense,
                                       paged_attention)
    s, c = tokens.shape
    bt = kv_k.shape[2]
    sentinel_row = kv_k.shape[1] - 1
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < n_valid[:, None]
    logical = jnp.clip(positions // bt, 0, pages.shape[1] - 1)
    phys = jnp.take_along_axis(pages, logical, axis=1)
    dest_blk = jnp.where(valid, phys, sentinel_row)
    off = positions % bt
    state = {"k": kv_k, "v": kv_v}

    def attend(layer, q, k_new, v_new):
        state["k"] = state["k"].at[layer, dest_blk, off].set(
            k_new, mode="drop")
        state["v"] = state["v"].at[layer, dest_blk, off].set(
            v_new, mode="drop")
        kp, vp = state["k"][layer], state["v"][layer]
        if use_kernel:
            return paged_attention(q, kp, vp, pages, lengths,
                                   q_pos=positions, causal=True)
        return _paged_attention_dense(q, kp, vp, pages, lengths,
                                      positions, causal=True)

    logits = lm_forward(params, tokens, positions, attend, cfg)
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return toks, state["k"], state["v"]


class _PagedSlot:
    __slots__ = ("req", "pos", "cache_len", "emitted", "next_tok",
                 "draft_len", "last_emit_t")

    def __init__(self, req: _DecodeRequest):
        self.req = req
        self.pos = 0                    # prompt tokens consumed
        self.cache_len = 0              # target KV length (tokens)
        self.emitted: List[int] = []
        self.next_tok: Optional[int] = None
        self.draft_len = 0              # draft KV length (tokens)
        self.last_emit_t = time.perf_counter()

    def prefilling(self) -> bool:
        return self.pos < self.req.prompt.size

    def committed(self, idx: int) -> int:
        """Token at committed-sequence index (prompt then emitted)."""
        p = self.req.prompt.size
        return int(self.req.prompt[idx]) if idx < p \
            else int(self.emitted[idx - p])


class PagedDecodeEngine:
    """Continuous batching for a paged-KV transformer LM (see module
    docstring).

    Parameters
    ----------
    params : dict name -> array
        :func:`~mxnet_tpu.serve.paged.model.init_lm_params` blob for
        ``cfg``.
    cfg : LMConfig
        Model geometry; ``cfg.max_context`` bounds
        ``prompt + max_new_tokens`` per stream.
    num_slots / max_new_tokens / queue_depth / deadline_ms / eos_id :
        As in DecodeEngine (same env defaults).
    num_blocks / block_tokens : int, optional
        KV pool geometry (``MXNET_KVPOOL_BLOCKS`` — default
        dense-equivalent — / ``MXNET_KVPOOL_BLOCK_TOKENS``).
    paged : bool
        False = dense baseline: every slot statically owns a full
        max-context block stripe (the DecodeEngine memory discipline),
        same step program — the bitwise token-parity reference.
    chunk_tokens : int, optional
        Prefill chunk / verify width (``MXNET_PAGED_CHUNK``, 32).
        Raised to ``spec_k + 1`` when speculative decode is on.
    draft_params / draft_cfg / spec_k :
        Speculative decode: draft model blob + geometry and the
        proposal depth K (``MXNET_SPEC_DECODE_K``, 0 = off).  The draft
        shares the pool's allocator and page table with its own K/V
        view.
    use_pallas : bool, optional
        False pins the dense gather reference everywhere; True (the
        ``MXNET_PAGED_PALLAS`` default) runs the Pallas kernel where
        the step is lowered for a TPU and the reference elsewhere.
    """

    def __init__(self, params: Dict, cfg: LMConfig, *,
                 num_slots: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 paged: bool = True,
                 chunk_tokens: Optional[int] = None,
                 draft_params: Optional[Dict] = None,
                 draft_cfg: Optional[LMConfig] = None,
                 spec_k: Optional[int] = None,
                 max_new_tokens: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 eos_id: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 name: str = "paged", warmup: bool = True):
        import jax.numpy as jnp

        from ...compile_cache import cached_jit

        if num_slots is None:
            num_slots = get_env("MXNET_SERVE_SLOTS", 8, int)
        self.num_slots = int(num_slots)
        if self.num_slots < 1:
            raise ServeError("num_slots must be >= 1, got %d"
                             % self.num_slots)
        if max_new_tokens is None:
            max_new_tokens = get_env("MXNET_SERVE_MAX_TOKENS", 128, int)
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ServeError("max_new_tokens must be >= 1, got %d"
                             % self.max_new_tokens)
        if queue_depth is None:
            queue_depth = get_env("MXNET_SERVE_DECODE_QUEUE",
                                  4 * self.num_slots, int)
        self.queue_depth = int(queue_depth)
        if self.queue_depth < 1:
            raise ServeError("queue_depth must be >= 1, got %d"
                             % self.queue_depth)
        self.deadline_ms = float(deadline_ms) if deadline_ms else None
        self.eos_id = eos_id
        self.name = name
        self.cfg = cfg
        self.max_context = int(cfg.max_context)
        self.paged = bool(paged)

        if spec_k is None:
            spec_k = get_env("MXNET_SPEC_DECODE_K", 0, int)
        self.spec_k = int(spec_k) if draft_params is not None else 0
        if self.spec_k and draft_cfg is None:
            raise ServeError("spec_k > 0 needs draft_cfg with "
                             "draft_params")
        if chunk_tokens is None:
            chunk_tokens = get_env("MXNET_PAGED_CHUNK", 32, int)
        self.chunk = max(2, min(int(chunk_tokens), self.max_context))
        if self.spec_k:
            if self.spec_k + 1 > self.chunk:
                # the verify window must fit the chunk program
                self.chunk = self.spec_k + 1
            if draft_cfg.max_context < cfg.max_context:
                raise ServeError(
                    "draft max_context %d < target max_context %d"
                    % (draft_cfg.max_context, cfg.max_context))

        if block_tokens is None:
            block_tokens = get_env("MXNET_KVPOOL_BLOCK_TOKENS", 16, int)
        bt = int(block_tokens)
        max_blocks = -(-self.max_context // bt)
        if not self.paged:
            num_blocks = self.num_slots * max_blocks
        self._pool = KVBlockPool(self.num_slots, max_blocks,
                                 num_blocks=num_blocks, block_tokens=bt,
                                 dense=not self.paged)
        self._pool.add_view("target", cfg.layers, cfg.heads, cfg.head_dim)
        self._params = {k: jnp.asarray(v) for k, v in params.items()}

        if use_pallas is None:
            use_pallas = bool(get_env("MXNET_PAGED_PALLAS", 1, int))
        self._use_kernel = bool(use_pallas)
        self._step_jit = cached_jit(
            functools.partial(_paged_step, cfg=cfg,
                              use_kernel=self._use_kernel),
            name="serve:paged_step")

        self.stats = PagedStats(name, self.num_slots,
                                self._pool.num_blocks)
        from ... import profiler
        profiler.register_serve_stats(self.stats)

        self._spec = None
        if self.spec_k:
            from .spec import SpecDecoder
            self._spec = SpecDecoder(self, draft_params, draft_cfg,
                                     use_kernel=self._use_kernel)

        self._cv = make_condition("serve.paged")
        self._q: collections.deque = collections.deque()
        self._slots: List[Optional[_PagedSlot]] = [None] * self.num_slots
        self._active = 0
        self._closed = False
        self._drain = True

        if warmup:
            self._warmup()
        self._thread = threading.Thread(
            target=self._loop, name="%s-paged" % name, daemon=True)
        self._thread.start()

    # -- compiled-step plumbing --------------------------------------------
    def _run_target(self, tokens, positions, n_valid, lengths) -> np.ndarray:
        kv_k, kv_v = self._pool.view("target")
        toks, kk, vv = self._step_jit(
            self._params, kv_k, kv_v, tokens, self._pool.page_table(),
            positions, n_valid, lengths)
        self._pool.set_view("target", kk, vv)
        return np.asarray(toks)         # the step's ONE host sync

    def _staging(self, c: int):
        s = self.num_slots
        return (np.zeros((s, c), np.int32), np.zeros((s, c), np.int32),
                np.zeros((s,), np.int32), np.zeros((s,), np.int32))

    def _warmup(self) -> None:
        """Trace + compile every steady-loop program (C = 1 and
        C = chunk, target and draft) through the persistent compile
        cache: the decode loop itself never sees the XLA compiler.
        Zero-valid windows scatter only into the sentinel scratch row,
        so warmup leaves the logical cache untouched."""
        try:
            for c in (1, self.chunk):
                self._run_target(*self._staging(c))
            if self._spec is not None:
                for c in (1, self.chunk):
                    self._spec.run(*self._staging(c))
        except Exception as e:
            raise ServeError(
                "paged step compilation failed (slots=%d, chunk=%d, "
                "cfg=%s): %s: %s" % (self.num_slots, self.chunk,
                                     (self.cfg,), type(e).__name__, e)) \
                from e

    # -- client API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> Future:
        """Enqueue one decode stream; Future resolves to the np.int32
        array of newly generated tokens (prompt not echoed).  Raises
        ServeRequestError / ServeOverloadError / ServeClosedError
        immediately, in this thread."""
        arr = np.asarray(prompt)
        if arr.ndim == 0:
            arr = arr.reshape(1)
        if arr.ndim != 1 or arr.size < 1:
            raise ServeRequestError(
                "prompt must be a non-empty 1-D token-id sequence, got "
                "shape %s" % (tuple(arr.shape),))
        if arr.dtype.kind not in "iu":
            if arr.dtype.kind == "f" and np.all(arr == np.floor(arr)):
                arr = arr.astype(np.int64)
            else:
                raise ServeRequestError(
                    "prompt dtype %s is not integral token ids"
                    % arr.dtype)
        if int(arr.min()) < 0 or int(arr.max()) >= self.cfg.vocab:
            raise ServeRequestError(
                "prompt token ids must be in [0, %d)" % self.cfg.vocab)
        mn = self.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if mn < 1:
            raise ServeRequestError(
                "max_new_tokens must be >= 1, got %d" % mn)
        if arr.size + mn > self.max_context:
            raise ServeRequestError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_context "
                "%d" % (arr.size, mn, self.max_context))
        eos = self.eos_id if eos_id is None else eos_id
        dl = self.deadline_ms if deadline_ms is None else \
            (float(deadline_ms) or None)
        now = time.perf_counter()
        traced = _trace.enabled()
        req = _DecodeRequest(
            arr.astype(np.int64), mn, eos, Future(), now,
            now + dl / 1000.0 if dl else None,
            trace_id=_trace.next_async_id() if traced else None)
        if traced:
            _trace.async_begin("serve:decode_request", req.trace_id,
                               cat="serve", prompt_len=int(arr.size))
        with self._cv:
            if self._closed:
                _trace_end(req, "closed")
                raise ServeClosedError(
                    "paged engine %r is closed" % self.name)
            if len(self._q) >= self.queue_depth:
                self.stats.on_overload()
                _trace_end(req, "overloaded")
                raise ServeOverloadError(
                    "paged decode queue full (%d queued, depth %d): "
                    "shed load or retry with backoff"
                    % (len(self._q), self.queue_depth))
            self._q.append(req)
            self.stats.on_submit(len(self._q))
            self._cv.notify_all()
        return req.future

    def generate(self, prompt, timeout: Optional[float] = None,
                 **kwargs) -> np.ndarray:
        """Blocking one-shot: submit + result."""
        return self.submit(prompt, **kwargs).result(timeout=timeout)

    # -- decode loop (one owner thread) ------------------------------------
    def _blocks_for(self, req: _DecodeRequest) -> int:
        return self._pool.blocks_for(req.prompt.size + req.max_new)

    def _claim_locked(self) -> Optional[List[_DecodeRequest]]:
        """Pop admissible requests for the free slots (cv held).
        Admission is FIFO with **exact block reservation**: when the
        head stream's worst-case blocks do not fit the pool, nothing
        behind it is admitted either (no head-of-line skipping — large
        streams cannot be starved by a trickle of small ones)."""
        free = self.num_slots - self._active
        if free <= 0 or not self._q:
            return None
        out: List[_DecodeRequest] = []
        budget = self._pool.available_blocks()
        now = time.perf_counter()
        while self._q and len(out) < free:
            head = self._q[0]
            need = self._blocks_for(head)
            if need > budget and not head.future.cancelled() and not (
                    head.deadline_t is not None and now > head.deadline_t):
                break                   # pool full: head waits, FIFO
            req = self._q.popleft()
            if not req.future.set_running_or_notify_cancel():
                self.stats.on_cancelled(1)
                _trace_end(req, "cancelled")
            elif req.deadline_t is not None and now > req.deadline_t:
                self.stats.on_expired(1)
                _trace_end(req, "expired")
                _set_exception(req.future, ServeDeadlineError(
                    "admission deadline exceeded: %.1f ms queued against "
                    "a %.1f ms deadline"
                    % ((now - req.enqueue_t) * 1e3,
                       (req.deadline_t - req.enqueue_t) * 1e3)))
            else:
                out.append(req)
                budget -= need
        self.stats.set_queue_depth(len(self._q))
        return out or None

    def _join(self, reqs: List[_DecodeRequest]) -> None:
        for req in reqs:
            slot_idx = self._slots.index(None)
            if not self._pool.reserve(slot_idx, self._blocks_for(req)):
                # _claim_locked checked the budget and only this thread
                # touches the pool — reaching here is an accounting bug
                raise ServeError(
                    "pool reservation failed after admission check "
                    "(slot %d)" % slot_idx)
            self._slots[slot_idx] = _PagedSlot(req)
            self._active += 1
            if req.trace_id is not None and _trace.enabled():
                _trace.async_instant("serve:decode_request", req.trace_id,
                                     cat="serve", at="admit",
                                     slot=slot_idx)
        self.stats.on_admitted(len(reqs))

    def _k_eff(self, sl: _PagedSlot) -> int:
        """Speculation depth for this slot this round: never propose
        past max_new (the bonus token always lands) or the verify
        window."""
        return max(0, min(self.spec_k,
                          sl.req.max_new - len(sl.emitted) - 1,
                          self.chunk - 1))

    def _emit(self, i: int, sl: _PagedSlot, toks: List[int]) -> int:
        """Append generated tokens to slot ``i``'s stream, stopping at
        eos / max_new; resolves + frees the slot when the stream
        finishes.  Returns the number of tokens emitted."""
        req = sl.req
        now = time.perf_counter()
        gaps: List[float] = []
        count = 0
        finished = False
        for t in toks:
            sl.emitted.append(t)
            sl.next_tok = t
            count += 1
            gaps.append((now - sl.last_emit_t) * 1e3 if count == 1
                        else 0.0)
            if len(sl.emitted) >= req.max_new or \
                    (req.eos_id is not None and t == req.eos_id):
                finished = True
                break
        sl.last_emit_t = now
        self.stats.on_inter_token(gaps)
        if finished:
            if _set_result(req.future, np.asarray(sl.emitted, np.int32)):
                self.stats.on_complete([(now - req.enqueue_t) * 1e3])
            _trace_end(req, "resolved")
            self._pool.release(i)
            self._slots[i] = None
            self._active -= 1
        return count

    def _mixed_step(self, active) -> int:
        """One chunk-width step: prefilling slots consume up to
        ``chunk`` prompt tokens, decoding slots one token — a long
        prompt shares the batch with in-flight decode instead of
        stalling it."""
        tokens, positions, n_valid, lengths = self._staging(self.chunk)
        plan: Dict[int, int] = {}
        for i, sl in active:
            if sl.prefilling():
                c = min(self.chunk, sl.req.prompt.size - sl.pos)
                tokens[i, :c] = sl.req.prompt[sl.pos:sl.pos + c]
                plan[i] = c
            else:
                c = 1
                tokens[i, 0] = sl.next_tok
                plan[i] = 0
            n_valid[i] = c
            positions[i, :c] = sl.cache_len + np.arange(c)
            lengths[i] = sl.cache_len + c
            self._pool.ensure(i, sl.cache_len + c)
        toks = self._run_target(tokens, positions, n_valid, lengths)
        emitted = 0
        prefill_tokens = 0
        for i, sl in active:
            c = plan[i]
            if c:                       # prefill slot
                sl.pos += c
                sl.cache_len += c
                prefill_tokens += c
                if not sl.prefilling():
                    # final chunk: its last logit is the first token
                    emitted += self._emit(i, sl, [int(toks[i, c - 1])])
            else:
                sl.cache_len += 1
                emitted += self._emit(i, sl, [int(toks[i, 0])])
        if prefill_tokens:
            self.stats.on_prefill(prefill_tokens)
        return emitted

    def _plain_step(self, active) -> int:
        """One pure-decode step: every slot consumes its last token."""
        tokens, positions, n_valid, lengths = self._staging(1)
        for i, sl in active:
            tokens[i, 0] = sl.next_tok
            n_valid[i] = 1
            positions[i, 0] = sl.cache_len
            lengths[i] = sl.cache_len + 1
            self._pool.ensure(i, sl.cache_len + 1)
        toks = self._run_target(tokens, positions, n_valid, lengths)
        emitted = 0
        for i, sl in active:
            sl.cache_len += 1
            emitted += self._emit(i, sl, [int(toks[i, 0])])
        return emitted

    def _spec_round(self, active) -> int:
        """One speculative round: the draft proposes up to K tokens per
        slot, the target verifies every slot's window in ONE chunk-width
        step, greedy acceptance commits the longest agreeing prefix
        plus the target's own next token.  Rejected positions roll back
        by *not advancing* the length counters — their stale KV rows
        are overwritten when those positions refill."""
        k_eff = {i: self._k_eff(sl) for i, sl in active}
        props = self._spec.propose(active, k_eff)
        tokens, positions, n_valid, lengths = self._staging(self.chunk)
        for i, sl in active:
            window = [sl.next_tok] + props.get(i, [])
            nv = len(window)
            tokens[i, :nv] = window
            n_valid[i] = nv
            positions[i, :nv] = sl.cache_len + np.arange(nv)
            lengths[i] = sl.cache_len + nv
            self._pool.ensure(i, sl.cache_len + nv)
        toks = self._run_target(tokens, positions, n_valid, lengths)
        emitted = 0
        for i, sl in active:
            prop = props.get(i, [])
            a = [int(x) for x in toks[i, :len(prop) + 1]]
            j = 0
            while j < len(prop) and prop[j] == a[j]:
                j += 1
            base = sl.cache_len
            sl.cache_len = base + j + 1
            sl.draft_len = base + min(j + 1, len(prop))
            self.stats.on_spec_round(len(prop), j)
            emitted += self._emit(i, sl, a[:j + 1])
        return emitted

    def _step(self) -> None:
        active = [(i, sl) for i, sl in enumerate(self._slots)
                  if sl is not None]
        n_active = len(active)
        # same seam as decode.step: `delay` stretches a step, `error`
        # kills the loop (replica-crash shape)
        _fault_point("paged.step", active=n_active)
        with _trace.span("serve:paged_step", cat="serve",
                         active=n_active, slots=self.num_slots):
            if any(sl.prefilling() for _, sl in active):
                emitted = self._mixed_step(active)
            elif self._spec is not None and \
                    any(self._k_eff(sl) > 0 for _, sl in active):
                emitted = self._spec_round(active)
            else:
                emitted = self._plain_step(active)
        self.stats.on_step(n_active, emitted)
        self.stats.set_pool(self._pool.used_blocks(),
                            self._pool.reserved_blocks())
        _trace.counter("serve:paged_kv_blocks", cat="serve",
                       used=self._pool.used_blocks(),
                       reserved=self._pool.reserved_blocks())

    def _loop(self) -> None:
        try:
            while True:
                admitted = None
                with self._cv:
                    while (not self._closed and self._active == 0
                           and not self._q):
                        self._cv.wait(_IDLE_POLL_S)
                    if self._closed and not self._drain:
                        break
                    admitted = self._claim_locked()
                    if (self._closed and self._active == 0
                            and admitted is None and not self._q):
                        break
                if admitted:
                    self._join(admitted)
                if self._active:
                    self._step()
        finally:
            self._shutdown_tail()

    def _shutdown_tail(self) -> None:
        """Loop epilogue: fail whatever remains (drain=False, or a step
        error) and flip _closed so no new submit can enqueue onto a
        dead loop."""
        with self._cv:
            self._closed = True
            leftovers = list(self._q)
            self._q.clear()
            self.stats.set_queue_depth(0)
        exc = ServeClosedError(
            "paged engine %r closed before this stream finished"
            % self.name)
        failed = cancelled = 0
        for i, sl in enumerate(self._slots):
            if sl is None:
                continue
            self._slots[i] = None
            self._active -= 1
            self._pool.release(i)
            _trace_end(sl.req, "closed")
            if _set_exception(sl.req.future, exc):
                failed += 1
        for req in leftovers:
            _trace_end(req, "closed")
            if _set_exception(req.future, exc):
                failed += 1
            else:
                cancelled += 1
        if failed:
            self.stats.on_failed(failed)
        if cancelled:
            self.stats.on_cancelled(cancelled)

    # -- introspection / lifecycle -----------------------------------------
    def pending_requests(self) -> int:
        with self._cv:
            return len(self._q)

    def outstanding(self) -> int:
        return self.stats.outstanding()

    @property
    def pool(self) -> KVBlockPool:
        return self._pool

    def device_bytes(self) -> int:
        """Device footprint: target params + draft params + the FULL
        KV block pool (every view) — the multiplexer admission
        currency.  The pool is the dominant term for long contexts;
        counting it here is what keeps co-hosting a draft model from
        silently blowing MXNET_SERVE_MUX_BYTES."""
        total = param_bytes(self._params) + self._pool.device_bytes()
        if self._spec is not None:
            total += param_bytes(self._spec.params)
        return total

    def close(self, drain: bool = True) -> None:
        """Stop admissions; drain=True finishes queued + in-flight
        streams first, drain=False fails them with ServeClosedError.
        Thread-safe, idempotent; from the decode thread itself this
        degrades to a non-joining shutdown request."""
        with self._cv:
            self._closed = True
            if not drain:
                self._drain = False
            self._cv.notify_all()
        if threading.current_thread() is self._thread:
            return
        self._thread.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
