"""Top-k softmax routing with capacity-factor dropping (sentinel-fold).

Pure jnp, shape-static, trace-safe: every array in the routing plan has
a shape fixed by (tokens, experts, k, capacity), so the fused train step
and the decode engine compile it once per geometry.  Overflow handling
follows the embed engine's sentinel discipline (embed/sparse.py):
instead of clamping an over-capacity token onto some expert row (the
PR 12 pad-bug class), its dispatch slot folds to the single out-of-range
sentinel ``num_experts * capacity`` — the scatter drops it, the combine
masks it, and its gate weight is zeroed, so dropped traffic is exactly
absent rather than approximately present.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["drop_free", "moved_select_bias", "resolve_capacity", "route",
           "route_sorted", "RoutingPlan", "SortedPlan"]


def drop_free(capacity_factor) -> bool:
    """``capacity_factor <= 0`` (or None): no token-choice is dropped."""
    return capacity_factor is None or capacity_factor <= 0


def resolve_capacity(capacity_factor: float, n_tokens: int,
                     num_experts: int, k: int) -> int:
    """Static per-expert bucket size for a routing geometry:
    ``C = ceil(cf * n_tokens * k / num_experts)`` — the perfectly-
    balanced load times the slack factor — clamped to ``[1, n_tokens]``.
    Mirrors ``embed.sparse.resolve_cap``.

    ``capacity_factor <= 0`` means no dropping, and that has no bucket:
    the ops take the sorted layout (``route_sorted``, exactly
    ``n_tokens * k`` rows).  Asking for its capacity is an error.
    """
    if drop_free(capacity_factor):
        raise ValueError("capacity_factor <= 0 drops nothing and has no "
                         "bucket; route it with route_sorted")
    n_tokens = int(n_tokens)
    worst = max(1, n_tokens)
    cap = int(math.ceil(float(capacity_factor) * n_tokens * int(k)
                        / float(max(1, int(num_experts)))))
    return max(1, min(worst, cap))


class RoutingPlan(NamedTuple):
    """Everything downstream of the gate, shapes static per geometry.

    ``slot``    (T, k) int32 in ``[0, E*C]``; ``E*C`` IS the sentinel —
                out of range for the ``(E*C, D)`` dispatch buffer, so
                the scatter's ``mode="drop"`` discards it
    ``weight``  (T, k) f32 combine weights; exactly 0.0 on folded slots
    ``counts``  (E,) f32 tokens accepted per expert (post-capacity)
    ``assigned``(E,) f32 tokens routed per expert (pre-capacity)
    ``hits``    (T, E) f32 per-token accepted-assignment one-hots
                (sums to ``counts`` over tokens) — the per-slot routing
                state a decode graph accumulates
    ``aux``     () f32 load-balance loss (GShard/Switch form:
                ``E * sum(mean_gate_prob * dispatch_frac)``)
    ``dropped`` () f32 token-choice pairs folded to the sentinel
    """
    slot: jax.Array
    weight: jax.Array
    counts: jax.Array
    assigned: jax.Array
    hits: jax.Array
    aux: jax.Array
    dropped: jax.Array


def route(logits, k: int, capacity: int,
          renormalize: bool = False) -> RoutingPlan:
    """Route ``(T, E)`` gate logits into capacity buckets.

    Priority is GShard's: all first choices (across tokens, in batch
    order) claim capacity before any second choice — position-in-expert
    is a cumulative sum over the ``(k, T)``-flattened one-hot assignment
    matrix.  Deterministic, shape-static, and independent of data
    values except through the top-k itself.
    """
    T, E = logits.shape
    k = int(k)
    capacity = int(capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_k, expert_k = jax.lax.top_k(gates, k)            # (T, k)
    if renormalize:
        gate_k = gate_k / jnp.maximum(
            gate_k.sum(axis=-1, keepdims=True), jnp.float32(1e-9))
    # one-hot assignments ordered (choice-rank, token): cumsum gives each
    # (token, choice) its position within the chosen expert's bucket
    onehot = jax.nn.one_hot(expert_k, E, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)     # (k*T, E)
    running = jnp.cumsum(flat, axis=0) - flat
    pos = (running * flat).sum(axis=-1).reshape(k, T).transpose(1, 0)
    over = pos >= capacity                                 # (T, k)
    sentinel = jnp.int32(E * capacity)
    slot = jnp.where(over, sentinel,
                     (expert_k * capacity + pos).astype(jnp.int32))
    weight = jnp.where(over, jnp.float32(0.0), gate_k)
    assigned = flat.sum(axis=0).astype(jnp.float32)        # (E,)
    counts = jnp.minimum(assigned, jnp.float32(capacity))
    hits = (onehot.astype(jnp.float32)
            * (~over)[..., None].astype(jnp.float32)).sum(axis=1)
    dropped = over.sum().astype(jnp.float32)
    # load balance: mean gate mass per expert x fraction of routed
    # choices per expert, scaled by E so a uniform router scores 1.0
    me = gates.mean(axis=0)
    ce = assigned / jnp.float32(max(1, T * k))
    aux = (me * ce).sum() * jnp.float32(E)
    return RoutingPlan(slot=slot, weight=weight,
                       counts=jax.lax.stop_gradient(counts),
                       assigned=jax.lax.stop_gradient(assigned),
                       hits=jax.lax.stop_gradient(hits),
                       aux=aux,
                       dropped=jax.lax.stop_gradient(dropped))


class SortedPlan(NamedTuple):
    """The drop-free routing plan: the ``T*k`` (token, choice) pairs
    sorted by expert, nothing bucketed and nothing dropped.

    ``order``   (T*k,) int32: sorted row ``r`` holds pair ``order[r]``
                (token ``order[r] // k``), experts ascending, pairs of
                one expert in token order (with ``held``: the held
                experts' pairs first, the absent experts' behind them)
    ``slot``    (T, k) int32: the sorted row of each pair (``order``'s
                inverse)
    ``weight``  (T, k) f32 combine weights (the top-k gate values)
    ``counts``  (E,) f32 pairs per expert = the sorted layout's group
                sizes; sums to ``T*k``
    ``hits``    (T, E) f32 per-token assignment one-hots
    ``aux``     () f32 load-balance loss, as ``RoutingPlan.aux``
    ``dropped`` () f32, always 0
    """
    order: jax.Array
    slot: jax.Array
    weight: jax.Array
    counts: jax.Array
    hits: jax.Array
    aux: jax.Array
    dropped: jax.Array


def route_sorted(logits, k: int, renormalize: bool = False,
                 score: str = "softmax", scale: float = 1.0,
                 select_bias=None, held=None) -> SortedPlan:
    """Route ``(T, E)`` gate logits without a capacity: scores over all
    experts (``score``: ``softmax``, or ``sigmoid`` of each logit), top-k,
    and a stable sort of the ``T*k`` pairs by expert.

    ``select_bias`` ``(E,)`` is added to the scores for the CHOICE only:
    the weights are the chosen experts' own scores, and the bias gets no
    gradient.  ``renormalize`` divides a token's k weights by their sum
    and ``scale`` multiplies them, over all k chosen whether held here or
    not.  ``held = (first, n)`` says this rank holds experts ``first ..
    first + n - 1``: pairs that chose one of them are sorted first, by
    expert, and the others behind them with weight 0, so the held rows
    are ``0 .. counts[first:first + n].sum() - 1`` and what the absent
    experts would have added is left out.  ``counts`` and ``hits`` stay
    ``E`` wide: a choice of an absent expert is routed, not dropped."""
    T, E = logits.shape
    k = int(k)
    x = logits.astype(jnp.float32)
    gates = jax.nn.sigmoid(x) if score == "sigmoid" \
        else jax.nn.softmax(x, axis=-1)
    if select_bias is None:
        gate_k, expert_k = jax.lax.top_k(gates, k)        # (T, k)
    else:
        _, expert_k = jax.lax.top_k(
            gates + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
            k)
        gate_k = jnp.take_along_axis(gates, expert_k, axis=-1)
    if renormalize:
        gate_k = gate_k / jnp.maximum(
            gate_k.sum(axis=-1, keepdims=True), jnp.float32(1e-9))
    if scale != 1.0:
        gate_k = gate_k * jnp.float32(scale)
    sort_key = expert_k
    if held is not None:
        first, n = held
        here = (expert_k >= first) & (expert_k < first + n)
        sort_key = jnp.where(here, expert_k - first, n)
        gate_k = jnp.where(here, gate_k, jnp.float32(0.0))
    order = jnp.argsort(sort_key.reshape(T * k), stable=True)
    slot = jnp.argsort(order).reshape(T, k)
    hits = (expert_k[..., None] == jnp.arange(E)).sum(
        axis=1).astype(jnp.float32)                        # (T, E)
    counts = hits.sum(axis=0)
    me = gates.mean(axis=0)
    ce = counts / jnp.float32(max(1, T * k))
    aux = (me * ce).sum() * jnp.float32(E)
    return SortedPlan(order=order.astype(jnp.int32),
                      slot=slot.astype(jnp.int32), weight=gate_k,
                      counts=jax.lax.stop_gradient(counts),
                      hits=jax.lax.stop_gradient(hits), aux=aux,
                      dropped=jnp.zeros((), jnp.float32))


def moved_select_bias(bias, counts, rate: float):
    """One step of the selection bias (DeepSeek-V3's auxiliary-loss-free
    balancing): ``b_e += rate * sign(mean load - load_e)``."""
    counts = counts.astype(jnp.float32)
    return bias + jnp.float32(rate) * jnp.sign(counts.mean() - counts)
