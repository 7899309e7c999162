"""Speculative decode: a draft model proposes, the target verifies.

Per-token decode is latency-bound: every emitted token costs one full
target forward.  Speculative decode spends cheap draft forwards to
batch the expensive target forwards — the draft proposes K tokens one
at a time, then the target scores all K+1 positions in ONE chunk-width
step (the same compiled program chunked prefill uses).  With greedy
argmax on both sides, the emitted stream is **token-identical to pure
target decode**: an accepted token is by construction exactly what the
target would have produced, and the first disagreement is replaced by
the target's own argmax (the "bonus" token), so every round emits at
least one token and never a wrong one.

Cache discipline (the part the paged pool makes cheap):

* the draft holds its OWN K/V view over the SAME allocator and page
  table as the target — block i of a stream is one physical id for
  both, so no second allocator, no second fragmentation story, and
  speculation can never out-allocate the admission reservation;
* rejected positions roll back by **moving the length counters only**
  — stale K/V rows beyond the committed length are invisible to the
  causally-masked attention and are overwritten in place when those
  positions refill on a later round;
* after a fully-accepted round the draft lags the target by exactly
  the bonus token; ``catch_up`` feeds committed-but-unseen tokens back
  through the draft (chunk-width on first contact with a stream —
  draft prefill — then C=1) before the next proposal round.

Acceptance-rate counters land in :class:`~..stats.PagedStats`
(``spec_proposed`` / ``spec_accepted``) and the profiler serve report —
an acceptance rate too low to cover the draft's cost is a draft-model
quality regression, not a serving bug.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np

from .model import LMConfig

__all__ = ["SpecDecoder"]


class SpecDecoder:
    """Draft-model side of speculative decode; owned and driven by one
    PagedDecodeEngine (all calls happen on the engine's decode thread).
    """

    def __init__(self, engine, draft_params: Dict, draft_cfg: LMConfig,
                 use_kernel: bool = False):
        import jax.numpy as jnp

        from ...compile_cache import cached_jit
        from .engine import _paged_step
        self._engine = engine
        self.cfg = draft_cfg
        self.params = {k: jnp.asarray(v) for k, v in draft_params.items()}
        engine.pool.add_view("draft", draft_cfg.layers, draft_cfg.heads,
                             draft_cfg.head_dim)
        self._jit = cached_jit(
            functools.partial(_paged_step, cfg=draft_cfg,
                              use_kernel=use_kernel),
            name="serve:paged_draft_step")

    def run(self, tokens, positions, n_valid, lengths) -> np.ndarray:
        """One draft step over a (S, C) window against the draft KV
        view (same page table as the target)."""
        pool = self._engine.pool
        kv_k, kv_v = pool.view("draft")
        toks, kk, vv = self._jit(self.params, kv_k, kv_v, tokens,
                                 pool.page_table(), positions, n_valid,
                                 lengths)
        pool.set_view("draft", kk, vv)
        return np.asarray(toks)

    def catch_up(self, active) -> None:
        """Feed each slot's committed-but-draft-unseen tokens through
        the draft: the whole prompt on first contact (draft prefill,
        chunk-width), the single bonus token after a fully-accepted
        round (C=1)."""
        engine = self._engine
        while True:
            lagging = [(i, sl) for i, sl in active
                       if sl.draft_len < sl.cache_len]
            if not lagging:
                return
            width = engine.chunk if any(
                sl.cache_len - sl.draft_len > 1 for _, sl in lagging) \
                else 1
            tokens, positions, n_valid, lengths = engine._staging(width)
            for i, sl in lagging:
                c = min(width, sl.cache_len - sl.draft_len)
                for t in range(c):
                    tokens[i, t] = sl.committed(sl.draft_len + t)
                n_valid[i] = c
                positions[i, :c] = sl.draft_len + np.arange(c)
                lengths[i] = sl.draft_len + c
            self.run(tokens, positions, n_valid, lengths)
            for i, sl in lagging:
                sl.draft_len += int(n_valid[i])

    def propose(self, active, k_eff: Dict[int, int]) -> Dict[int, List[int]]:
        """Up to ``k_eff[i]`` draft proposals per slot, built over
        ``max(k_eff)`` batched C=1 draft steps (slots with a smaller
        depth sit out the later steps with an empty window).  Draft
        K/V for the proposals lands at the slot's speculative positions
        — inside the admission reservation, rolled back by the engine
        after verification.  Returns {slot: [tokens...]}."""
        engine = self._engine
        self.catch_up(active)
        k_round = max(k_eff.values()) if k_eff else 0
        props: Dict[int, List[int]] = {i: [] for i, _ in active
                                       if k_eff[i] > 0}
        if k_round == 0:
            return props
        tip = {i: sl.next_tok for i, sl in active}
        for r in range(k_round):
            # one host sync per proposal step is the speculative
            # contract: K tiny draft syncs buy one batched target step
            tokens, positions, n_valid, lengths = engine._staging(1)
            for i, sl in active:
                if k_eff[i] > r:
                    tokens[i, 0] = tip[i]
                    n_valid[i] = 1
                    positions[i, 0] = sl.draft_len + r
                    lengths[i] = sl.draft_len + r + 1
                    engine.pool.ensure(i, sl.draft_len + r + 1)
            toks = self.run(tokens, positions, n_valid, lengths)
            for i, sl in active:
                if k_eff[i] > r:
                    t = int(toks[i, 0])
                    props[i].append(t)
                    tip[i] = t
        return props
