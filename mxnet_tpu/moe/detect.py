"""Graph-side detection of MoE blocks.

The fused train step asks: does this symbol route tokens through
``_moe_dispatch``?  If so it registers a ``MoeStats`` with the profiler
and folds each block's routing geometry into the compile-cache program
descriptor — two graphs that differ only in an expert count or capacity
factor can never alias a compiled program (the geometry is also in the
serialized symbol json, so this is belt-and-braces the same way the
embed specs are).  Serving uses the same walk to find the blocks whose
capacity the ``MoEServeParityPass`` pins to the no-drop setting.
"""
from __future__ import annotations

from typing import Dict

__all__ = ["MoEBlockSpec", "find_load_heads", "find_moe_blocks"]


class MoEBlockSpec:
    """One routed block: its name and static routing geometry."""

    __slots__ = ("name", "num_experts", "k", "capacity_factor",
                 "renormalize", "held")

    def __init__(self, name: str, num_experts: int, k: int,
                 capacity_factor: float, renormalize: bool,
                 experts_held: int = 0, first_expert: int = 0):
        self.name = name
        self.num_experts = int(num_experts)
        self.k = int(k)
        self.capacity_factor = float(capacity_factor)
        self.renormalize = bool(renormalize)
        # the experts this rank holds, as a slice of the E routed over
        self.held = slice(int(first_expert), int(first_expert)
                          + int(experts_held or num_experts))

    def __repr__(self):
        return ("MoEBlockSpec(name=%r, E=%d, k=%d, cf=%g, renorm=%r)"
                % (self.name, self.num_experts, self.k,
                   self.capacity_factor, self.renormalize))


def find_moe_blocks(symbol) -> Dict[str, MoEBlockSpec]:
    """``{dispatch_node_name: MoEBlockSpec}`` for every ``_moe_dispatch``
    node reachable from ``symbol``'s heads."""
    from ..symbol import _topo
    out: Dict[str, MoEBlockSpec] = {}
    for node in _topo(symbol._heads):
        if node.is_variable or \
                getattr(node.op, "name", "") != "_moe_dispatch":
            continue
        p = node.params
        out[node.name] = MoEBlockSpec(
            node.name, p.num_experts, p.k, p.capacity_factor,
            p.renormalize, p.experts_held, p.first_expert)
    return out


def find_load_heads(symbol):
    """``(i, [dispatch node names])``: output ``i`` of ``symbol`` is the
    ``(blocks, E + 1)`` load head that ``moe.layer.with_load_heads``
    groups on, and the names are its rows' blocks.  None where the
    symbol has no such head."""
    from .layer import _COUNTS_IDX, _DROPPED_IDX

    def op_name(node):
        return None if node.is_variable else getattr(node.op, "name", "")

    def block_of(node):
        """The dispatch node of one row, Reshape(Concat(counts,
        dropped)), else None."""
        if op_name(node) != "Reshape":
            return None
        cat = node.inputs[0][0]
        if op_name(cat) != "Concat" or len(cat.inputs) != 2:
            return None
        (a, i), (b, j) = cat.inputs
        if a is b and op_name(a) == "_moe_dispatch" \
                and (i, j) == (_COUNTS_IDX, _DROPPED_IDX):
            return a.name
        return None

    for i, (node, _) in enumerate(symbol._heads):
        if op_name(node) != "BlockGrad":
            continue
        node = node.inputs[0][0]
        rows = [node] if op_name(node) == "Reshape" else \
            [n for n, _ in node.inputs] if op_name(node) == "Concat" else []
        blocks = [block_of(r) for r in rows]
        if blocks and all(blocks):
            return i, blocks
    return None
