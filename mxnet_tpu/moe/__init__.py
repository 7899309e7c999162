"""``mxnet_tpu.moe`` — top-k routed Mixture-of-Experts (ISSUE 19).

MoE is the embed engine wearing a different hat: a batch of tokens is a
batch of ids into an expert table, the capacity buckets are the capped
unique buffer, and overflow handling is the same sentinel-fold
discipline that fixed the PR 12 pad bug — out-of-capacity tokens fold
to ONE out-of-range sentinel slot, read zero on combine, and drop on
the dispatch scatter, so an expert's rows are never corrupted by
traffic it did not accept.

Layers of the subsystem:

* ``router``    top-k softmax gating, static capacity resolution,
                position-in-expert bucketing, load-balance aux loss
* ``dispatch``  capacity-bucketed dispatch/combine as pure-jnp
                primitives (THE scatter choke point — see the
                ``moe-raw-scatter`` lint rule), and the drop-free
                layout: rows sorted by expert, grouped matmuls, and
                the static row bound of one rank's share
                (``held_rows_bound``)
* ``layer``     ``MoEFeedForward`` symbol block over the
                ``_moe_dispatch`` / ``_moe_expert_ffn`` /
                ``_moe_combine`` ops (``_moe_share_ffn`` for a rank's
                share), ``with_aux_loss`` head attach
* ``detect``    graph-side discovery (``find_moe_blocks``) feeding the
                fused step's program descriptor + stats registration
* ``stats``     ``MoeStats`` behind ``mx.profiler.moe_report()``

Training rides the fused train step unchanged (aux loss is just another
output head accumulated in the superstep scan); serving rides
``DecodeEngine`` (per-slot routing state is just more slot state, with
per-expert hit counters sampled into ``moe_report()``).  Sharding the
stacked expert tensors over an ``ep``/``tp`` mesh axis (``__sharding__``
attrs, ``MoEFeedForward(expert_axis="ep")``) makes GSPMD materialize the
dispatch/combine resharding as collectives — visible in
``multichip_report()``'s census.  See docs/moe.md.
"""
from .router import resolve_capacity, route
from .dispatch import dispatch, combine
from .layer import (MoEFeedForward, aux_loss_symbols, count_symbols,
                    dropped_symbols, hit_symbols, with_act_zeros_head,
                    with_aux_loss, with_load_heads)
from .detect import MoEBlockSpec, find_load_heads, find_moe_blocks
from .stats import MoeStats

__all__ = [
    "resolve_capacity", "route", "dispatch", "combine",
    "MoEFeedForward", "aux_loss_symbols", "count_symbols",
    "dropped_symbols", "hit_symbols", "with_act_zeros_head", "with_aux_loss",
    "with_load_heads", "MoEBlockSpec", "find_load_heads", "find_moe_blocks",
    "MoeStats",
]
