"""``MoEFeedForward``: the routed-expert block at the symbol level.

One call builds gate -> ``_moe_dispatch`` -> ``_moe_expert_ffn`` ->
``_moe_combine`` (for one expert-parallel rank's share: gate ->
``_moe_dispatch`` -> ``_moe_share_ffn``) and returns the combined
``(T, D)`` output symbol.
The load-balance aux loss stays an un-consumed extra output of the
dispatch node until ``with_aux_loss(net)`` groups ``MakeLoss`` heads
onto the final symbol — at which point the fused train step's vjp
trains the router and the superstep scan accumulates the loss value
on-device like any metric (no fused-step special cases).

Sharding: ``expert_axis="ep"`` stamps ``__sharding__`` attrs on the
stacked expert tensors (row-sharded over the named mesh axis, the same
layout a row-sharded embedding table uses), which
``parallel.sharding_attrs`` feeds into the fused step's GSPMD
constraints — dispatch/combine reshard as collectives in
``multichip_report()``'s census.  The gate stays replicated.
"""
from __future__ import annotations

from typing import List, Optional

from ..attribute import AttrScope
from ..base import get_env
from .. import symbol as _sym
from ..trace.heads import MOE_ACT_ZEROS, MOE_LOAD

__all__ = ["MoEFeedForward", "aux_loss_symbols", "count_symbols",
           "hit_symbols", "dropped_symbols", "with_act_zeros_head",
           "with_aux_loss", "with_load_heads"]

# _moe_dispatch output indices (ops/moe.py list_outputs)
_AUX_IDX = 3
_COUNTS_IDX = 4
_HITS_IDX = 5
_DROPPED_IDX = 6
_ORDER_IDX = 7


def MoEFeedForward(data, num_hidden: int, num_experts: int, k: int = 2,
                   capacity_factor: Optional[float] = None,
                   name: str = "moe", act_type: str = "relu",
                   renormalize: bool = False, output_dim: int = 0,
                   no_bias: bool = False,
                   expert_axis: Optional[str] = None,
                   gated: bool = False, layer: Optional[int] = None,
                   score: str = "softmax", scale: float = 1.0,
                   bias_rate: float = 0.0, shared_hidden: int = 0,
                   experts_held: int = 0, first_expert: int = 0,
                   router_data=None, act_zeros: bool = False,
                   shared_gate: bool = False):
    """Build one routed MoE feed-forward block over ``data`` (T, D).

    ``capacity_factor`` None is 0 (no token-choice dropped: the ``T*k``
    rows are sorted by expert and the experts are grouped matmuls over
    exactly those rows; ``> 0`` buckets to a capacity and drops the
    overflow);
    ``expert_axis`` names the mesh axis the stacked expert weights
    shard over (None = replicated).  ``gated`` adds the stacked
    ``i2h_gate`` projection: ``(act(x Wg) * (x W1)) W2``, SwiGLU with
    ``act_type="silu"``.  ``layer`` is the block's index in its model,
    for the trace scopes (``moe_experts.l<layer>``).

    The drop-free layout also takes a DeepSeek-V3 style router: ``score``
    ``"sigmoid"`` scores each logit by itself, ``scale`` multiplies the
    (``renormalize``d) weights, ``bias_rate`` > 0 adds the selection
    bias, an aux state of the dispatch node that enters the choice only
    and moves by ``bias_rate * sign(mean load - load)`` a training step;
    ``shared_hidden`` > 0 adds one shared expert of that width (three
    ``FullyConnected``, in the experts' gated or plain form) to every
    token's output, with ``shared_gate`` times ``sigmoid(x w_sg)``, one
    number a token from a fourth, bias-free ``FullyConnected`` of width 1
    (``<name>_shared_gate``); its nodes and the sum that adds it carry the
    device scope ``mlp.l<layer>`` (``_shared_scope``).  ``experts_held`` > 0 makes this one
    expert-parallel rank's share: the router, its weights'
    renormalization and the load
    head stay ``num_experts`` wide, the stacked weights hold experts
    ``first_expert .. first_expert + experts_held - 1`` only, and rows
    that chose another expert are left out of the grouped matmuls and
    of the output (no code stands in for the other ranks or their
    exchange; the shares of all ranks, with the shared expert once, sum
    to the whole layer's output).  A share's gather, experts and combine
    are one node, ``_moe_share_ffn``, whose sorted-row passes are sized
    by a static bound on the rows the rank holds, with the rows behind
    it as the exact fallback (``moe.dispatch.held_rows_bound``).  Returns the
    combined output symbol; recover the aux-loss / counts heads with
    ``aux_loss_symbols`` / ``count_symbols`` or attach them in one move
    with ``with_aux_loss``.
    """
    if capacity_factor is None:
        capacity_factor = 0.0
    scope = {} if layer is None else {"layer": int(layer)}
    if act_zeros and not experts_held:
        raise ValueError("MoEFeedForward: act_zeros counts the rows a rank "
                         "holds (experts_held > 0)")
    logits = _sym.FullyConnected(data if router_data is None else router_data,
                                 num_hidden=num_experts,
                                 no_bias=True, name=name + "_gate")
    share = {"experts_held": int(experts_held),
             "first_expert": int(first_expert)}
    # said only where it is so: an unset parameter is not in a node's JSON
    read = {} if router_data is None else {"router_rows": "mixer"}
    disp = _sym._moe_dispatch(data, logits, num_experts=num_experts,
                              k=k, capacity_factor=capacity_factor,
                              renormalize=renormalize, score=score,
                              scale=float(scale),
                              bias_rate=float(bias_rate),
                              name=name + "_dispatch", **scope, **share,
                              **read)

    def expert_var(suffix, spec):
        attr = {"__sharding__": spec} if expert_axis else None
        return _sym.Variable("%s_experts_%s" % (name, suffix), attr=attr)

    row3 = "%s,None,None" % expert_axis
    row2 = "%s,None" % expert_axis
    weights = []
    for stem in (["i2h_gate"] if gated else []) + ["i2h", "h2o"]:
        weights.append(expert_var(stem + "_weight", row3))
        if not no_bias:
            weights.append(expert_var(stem + "_bias", row2))
    ffn = dict(num_hidden=num_hidden, output_dim=output_dim,
               act_type=act_type, no_bias=no_bias, gated=gated, **scope,
               **share)
    if experts_held:
        # a rank's share: gather, experts and combine are one node, sized
        # by a static bound on the rows it holds (ops/moe.py); the
        # dispatch node's sorted rows are not read
        out = _sym._moe_share_ffn(
            data, disp[1], disp[2], disp[_ORDER_IDX], disp[_COUNTS_IDX],
            *weights, name=name + "_share", **ffn,
            **({"act_zeros": True} if act_zeros else {}))
        if act_zeros:
            out = out[0]
    else:
        rows = _sym._moe_expert_ffn(disp[0], *weights, disp[_COUNTS_IDX],
                                    name=name + "_experts", **ffn)
        out = _sym._moe_combine(rows, disp[1], disp[2], disp[_ORDER_IDX],
                                name=name + "_combine", **scope)
    if not shared_hidden:
        return out
    if not output_dim:
        raise ValueError("MoEFeedForward: a shared expert needs output_dim "
                         "(the model width its last projection returns to)")

    def fc(x, stem, width):
        return _sym.FullyConnected(x, num_hidden=width, no_bias=no_bias,
                                   name="%s_shared_%s" % (name, stem))

    def act(x):
        return x if act_type == "identity" else \
            _sym.Activation(x, act_type=act_type)

    with _shared_scope(layer):
        hidden = fc(data, "i2h", shared_hidden)
        hidden = act(fc(data, "i2h_gate", shared_hidden)) * hidden if gated \
            else act(hidden)
        shared = fc(hidden, "h2o", output_dim)
        if shared_gate:
            shared = _sym.broadcast_mul(shared, _sym.Activation(
                _sym.FullyConnected(data, num_hidden=1, no_bias=True,
                                    name=name + "_shared_gate"),
                act_type="sigmoid"))
        return out + shared


def _shared_scope(layer: Optional[int]) -> AttrScope:
    """The ``__scope__`` of a shared expert's nodes (``ops.transformer.
    node_scope``): a dense MLP, so ``mlp``, ``.l<layer>`` behind it,
    behind the prefix the caller's attribute scope holds (``mtp.``).  A
    caller that named the whole layer itself keeps its name."""
    outer = AttrScope.current().get(None).get("__scope__", "")
    if outer and not outer.endswith("."):
        return AttrScope()
    return AttrScope(__scope__="%smlp%s" % (
        outer, "" if layer is None else ".l%d" % layer))


def _dispatch_heads(symbol, out_idx: int) -> List:
    from ..symbol import Symbol, _topo
    heads = []
    for node in _topo(symbol._heads):
        if not node.is_variable and \
                getattr(node.op, "name", "") == "_moe_dispatch":
            heads.append(Symbol([(node, out_idx)]))
    return heads


def aux_loss_symbols(symbol) -> List:
    """The ``(1,)`` load-balance aux-loss head of every MoE block
    reachable from ``symbol``, in topological order."""
    return _dispatch_heads(symbol, _AUX_IDX)


def count_symbols(symbol) -> List:
    """The ``(E,)`` per-expert accepted-count head of every MoE block
    (stop-gradient — a stats/metric output, never a loss)."""
    return _dispatch_heads(symbol, _COUNTS_IDX)


def hit_symbols(symbol) -> List:
    """The ``(T, E)`` per-token accepted-assignment head of every MoE
    block (stop-gradient).  A decode graph adds this onto its per-slot
    ``moe_hits`` state variable — ``DecodeEngine(moe_hits_state=...)``
    then samples the running histogram into ``moe_report()``."""
    return _dispatch_heads(symbol, _HITS_IDX)


def dropped_symbols(symbol) -> List:
    """The ``(1,)`` dropped-token-choices head of every MoE block
    (stop-gradient; 0 for a block that does not drop)."""
    return _dispatch_heads(symbol, _DROPPED_IDX)


def with_load_heads(net):
    """Group ONE head, ``moe_load``, onto ``net`` behind ``BlockGrad``:
    the ``(blocks, E + 1)`` stack of every MoE block's ``counts`` and
    its ``dropped`` count, in ``find_moe_blocks`` order.  It travels with
    the step's outputs, and ``Module.fit`` feeds ``MoeStats`` and the
    ``moe:load`` trace counter from it with one host read a step
    whatever the depth, and no device sync of its own
    (``trace.heads.MOE_LOAD``).  The blocks must agree on the
    number of experts.  Returns ``net`` unchanged when the graph has no
    MoE blocks."""
    rows = [_sym.Reshape(_sym.Concat(counts, dropped, dim=0), shape=(1, -1))
            for counts, dropped in zip(count_symbols(net),
                                       dropped_symbols(net))]
    if not rows:
        return net
    load = rows[0] if len(rows) == 1 else _sym.Concat(*rows, dim=0)
    return _sym.Group([net, _sym.BlockGrad(load, name=MOE_LOAD.name)])


def with_act_zeros_head(net):
    """Group ONE head, ``moe_act_zeros``, onto ``net`` behind
    ``BlockGrad``: the ``(blocks, 2)`` stack of ``(zeros, lanes)`` of
    every share node built with ``act_zeros`` (``MoEFeedForward``), in
    topological order.  It travels with the step's outputs beside
    ``moe_load``, and ``Module.fit`` feeds the ``moe:act_zeros`` trace
    counter from it while tracing is on
    (``trace.heads.MOE_ACT_ZEROS``).  Returns ``net`` unchanged when
    no node carries the output."""
    from ..symbol import Symbol, _topo
    rows = [_sym.Reshape(Symbol([(node, 1)]), shape=(1, 2))
            for node in _topo(net._heads)
            if not node.is_variable
            and getattr(node.op, "name", "") == "_moe_share_ffn"
            and node.params.get("act_zeros")]
    if not rows:
        return net
    stack = rows[0] if len(rows) == 1 else _sym.Concat(*rows, dim=0)
    return _sym.Group([net, _sym.BlockGrad(stack, name=MOE_ACT_ZEROS.name)])


def with_aux_loss(net, grad_scale: Optional[float] = None):
    """Group ``MakeLoss`` heads for every MoE block's aux loss onto
    ``net``.  ``grad_scale`` None reads ``MXNET_MOE_AUX_COEF`` (default
    0.01).  The forward value stays the raw balance score (a uniform
    router reads 1.0) so metrics see it unscaled; only the injected
    gradient is scaled.  Returns ``net`` unchanged when the graph has
    no MoE blocks."""
    if grad_scale is None:
        grad_scale = get_env("MXNET_MOE_AUX_COEF", 0.01, float)
    auxes = aux_loss_symbols(net)
    if not auxes:
        return net
    heads = [net]
    for i, aux in enumerate(auxes):
        heads.append(_sym.MakeLoss(aux, grad_scale=float(grad_scale),
                                   name="%s_aux" % aux._heads[0][0].name))
    return _sym.Group(heads)
