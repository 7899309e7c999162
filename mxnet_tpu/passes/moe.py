"""MoEServeParityPass: no-drop routing on the serving graph.

Capacity-factor token dropping is a TRAINING throughput trade: a
dropped token rides the residual path and the optimizer sees it again
next epoch.  At serve time there is no next epoch — a dropped token is
a corrupted response, and which tokens drop depends on what else is in
the batch (slot composition under continuous batching), so the same
request can answer differently run to run.  This pass rewrites every
``_moe_dispatch`` node to ``capacity_factor=0``: no bucket and no
sentinel, the ``T*k`` token-choices sorted by expert and run as grouped
matmuls (``moe.router.route_sorted``).  The expert and combine nodes
follow the rank of the dispatch node's output and every expert node
already takes the dispatch node's ``counts``, so only the dispatch node
is rewritten.  Routed serving then answers as the dense-gather reference
does — the same terms, summed in the grouped matmul's order, so equal to
rounding and not bit for bit.

On by default for serving pipelines; ``MXNET_MOE_SERVE_EXACT=0`` keeps
the training capacity (a latency experiment, not a serving
configuration).  Attrs are preserved node-for-node — the pipeline's
round-trip verifier checks this like every other pass.
"""
from __future__ import annotations

from ..base import get_env
from .graph_passes import _make_node, rebuild
from .pipeline import Pass

__all__ = ["MoEServeParityPass", "default_moe_exact"]


def default_moe_exact() -> bool:
    """The ``MXNET_MOE_SERVE_EXACT`` default for serving pipelines."""
    return get_env("MXNET_MOE_SERVE_EXACT", True, bool)


class MoEServeParityPass(Pass):
    """``_moe_dispatch(capacity_factor=cf)`` -> ``capacity_factor=0``
    on every node still carrying a dropping capacity (see module
    docstring)."""

    name = "moe_serve_parity"
    # after quantize/fusion-feeding passes for the usual reason: earlier
    # passes match on the ORIGINAL op names and params
    order_after = ("quantize",)

    def apply(self, sym, params):
        rewritten = []

        def transform(node, new_inputs):
            if node.is_variable or \
                    getattr(node.op, "name", "") != "_moe_dispatch":
                return None
            p = node.params
            if not p.capacity_factor or p.capacity_factor <= 0:
                return None    # already no-drop
            new = _make_node(
                "_moe_dispatch", node.name,
                {"num_experts": p.num_experts, "k": p.k,
                 "capacity_factor": 0.0, "renormalize": p.renormalize,
                 "layer": p.layer},
                new_inputs, attrs=node.attrs)
            rewritten.append(node.name)
            return [(new, i) for i in range(node.num_outputs())]

        out = rebuild(sym, transform)
        self.summary = {"rewritten": len(rewritten), "nodes": rewritten}
        return (out if rewritten else sym), params
