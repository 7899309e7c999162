"""MoE ops: ``_moe_dispatch`` / ``_moe_expert_ffn`` / ``_moe_combine``,
and ``_moe_share_ffn``, the last two in one node for one expert-parallel
rank's share.

The symbol-level surface of ``mxnet_tpu.moe`` (ISSUE 19).  The routing
math and the expert-buffer scatter/gather live in ``moe.router`` /
``moe.dispatch`` — these ops only bind them into the graph, the same
split ``_sparse_embedding`` keeps with ``embed.sparse``.  All shapes
are static per routing geometry (tokens, experts, k, capacity), so the
fused train step and the decode engine compile each geometry once.

``_moe_dispatch`` is multi-output: the ``(E, C, D)`` buffer plus the
combine weights/slots, the load-balance aux loss (wrap it in
``MakeLoss`` to train the router — ``moe.layer.with_aux_loss``), and
the per-expert accepted counts and the number of dropped token-choices
(stop-gradient; metric/stats heads).

Two layouts, chosen by the dispatch node's ``capacity_factor`` alone.
``> 0``: capacity buckets, ``dispatched`` is ``(E, C, D)`` and
over-capacity choices drop.  ``<= 0``: nothing drops and nothing is
bucketed; ``dispatched`` is the ``(T*k, D)`` rows sorted by expert,
``slot`` the sorted row of each (token, choice), ``order`` the
(token, choice) of each sorted row, ``counts`` the group sizes, and
``_moe_expert_ffn`` is three grouped matmuls over exactly ``T*k``
rows.  ``_moe_expert_ffn`` and ``_moe_combine`` tell the two by
the rank of their data, so a pass that re-pins a dispatch node's
capacity (``MoEServeParityPass``) need touch nothing else.

Each body runs under a ``jax.named_scope`` (``moe_route``,
``moe_experts``, ``moe_combine``, with ``.l<layer>``);
``_moe_share_ffn`` enters the three around its gather, its experts and
its combine, and ``moe_share`` around what a row bound adds beside them
(``_share_bounded``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .. import trace as _trace
from ..base import MXNetError, _AttrDict
from ..moe.router import drop_free
from .registry import OpDef, Param, register_op

_ACTS = ["relu", "tanh", "sigmoid", "softrelu", "identity", "silu", "relu2"]


def _act(name):
    from .nn import ACTIVATIONS
    return (lambda x: x) if name == "identity" else ACTIVATIONS[name]


# _moe_dispatch's outputs "slot", "counts" and "order" (list_outputs)
_SLOT_OUT = 2
_COUNTS_OUT = 4
_ORDER_OUT = 7


def _from_dispatch(op, given, via, via_out, want, want_out, why):
    """``{want: output want_out}`` of the ``_moe_dispatch`` node whose
    output ``via_out`` is the input ``via`` (``implied_inputs``: a caller
    that does not give ``want``, and a graph saved before it was an
    input)."""
    if want in given or via not in given:
        return {}
    src, out = given[via]
    if src.is_variable or src.op.name != "_moe_dispatch" or out != via_out:
        raise MXNetError(
            "%s: %s is not a _moe_dispatch node's output, so give %s (%s)"
            % (op, via, want, why))
    return {want: (src, want_out)}


def _scope(kind, p):
    from .transformer import layer_scope
    return layer_scope(kind, p.layer)


@register_op("_moe_dispatch", hint="moe_dispatch")
class MoEDispatchOp(OpDef):
    """Route ``data`` (T, D) by ``logits`` (T, E).  ``capacity_factor
    > 0``: into the capacity-bucketed expert buffer (E, C, D), C static
    (``moe.router.resolve_capacity(capacity_factor, T, E, k)``);
    overflowed token-choices fold to the out-of-range sentinel slot
    ``E*C`` and drop on the scatter — an expert's rows are never
    corrupted (``moe.dispatch``, the scatter choke point).
    ``capacity_factor <= 0``: no token-choice is dropped; ``dispatched``
    is the (T*k, D) rows sorted by expert (``moe.router.route_sorted``)
    and ``counts`` their group sizes.  In that layout the scores may be
    each logit's ``sigmoid`` (``score``), the weights multiplied by
    ``scale``, and ``bias_rate > 0`` adds the aux state ``select_bias``
    (E,): it joins the scores for the choice only and moves by
    ``bias_rate * sign(mean load - load)`` a training step.
    ``experts_held`` > 0 says this rank holds experts ``first_expert ..
    first_expert + experts_held - 1`` of the E routed over: their rows
    come first, the absent experts' behind them with weight 0, and
    ``counts`` stays E wide.  ``router_rows`` says which rows ``logits``
    were read from, ``ffn`` (``data``; unset says the same) or ``mixer``
    (the rows the block's mixer read, ``MoEFeedForward``'s
    ``router_data``): each trace of the node records it, as the counter
    ``moe:router_rows``."""
    params = [Param("num_experts", int, required=True),
              Param("k", int, default=2),
              Param("capacity_factor", float, default=0.0),
              Param("renormalize", bool, default=False),
              Param("layer", int, default=-1),
              Param("score", str, default="softmax",
                    enum=["softmax", "sigmoid"]),
              Param("scale", float, default=1.0),
              Param("bias_rate", float, default=0.0),
              Param("experts_held", int, default=0),
              Param("first_expert", int, default=0),
              # unset (the rows the experts read) is not in a node's JSON
              Param("router_rows", str, enum=["mixer", "ffn"])]

    def list_arguments(self, p):
        return ["data", "logits"]

    def list_auxiliary_states(self, p):
        return ["select_bias"] if p.bias_rate > 0 else []

    def list_outputs(self, p):
        return ["dispatched", "weight", "slot", "aux", "counts", "hits",
                "dropped", "order"]

    def _cap(self, p, T):
        from ..moe.router import resolve_capacity
        return resolve_capacity(p.capacity_factor, T, p.num_experts, p.k)

    def infer_shape(self, p, in_shapes):
        d, lg = in_shapes
        if d is None:
            return in_shapes, [None] * 8, []
        if len(d) != 2:
            raise MXNetError("_moe_dispatch: data must be (tokens, dim), "
                             "got %r" % (d,))
        T, D = d
        E, k = p.num_experts, p.k
        if k < 1 or k > E:
            raise MXNetError("_moe_dispatch: k=%d outside [1, %d]" % (k, E))
        if lg is not None and tuple(lg) != (T, E):
            raise MXNetError("_moe_dispatch: logits must be (%d, %d), "
                             "got %r" % (T, E, lg))
        if drop_free(p.capacity_factor):
            buf = (T * k, D)
        else:
            buf = (E, self._cap(p, T), D)
            if p.score != "softmax" or p.scale != 1.0 or p.bias_rate > 0 \
                    or p.experts_held:
                raise MXNetError(
                    "_moe_dispatch: score, scale, bias_rate and "
                    "experts_held belong to the drop-free layout "
                    "(capacity_factor <= 0)")
        if p.experts_held and not \
                0 <= p.first_expert <= E - p.experts_held:
            raise MXNetError("_moe_dispatch: experts %d..%d are not among "
                             "the %d routed" % (
                                 p.first_expert,
                                 p.first_expert + p.experts_held - 1, E))
        return [d, (T, E)], \
            [buf, (T, k), (T, k), (1,), (E,), (T, E), (1,), (T * k,)], \
            [(E,)] * len(self.list_auxiliary_states(p))

    def infer_type(self, p, in_types):
        t = in_types[0] if in_types[0] is not None else np.dtype(np.float32)
        f32 = np.dtype(np.float32)
        return [t, f32], \
            [t, f32, np.dtype(np.int32), f32, f32, f32, f32,
             np.dtype(np.int32)], \
            [f32] * len(self.list_auxiliary_states(p))

    def forward(self, p, inputs, aux, ctx):
        from ..moe.dispatch import dispatch as _dispatch, sort_rows
        from ..moe.router import (moved_select_bias, route as _route,
                                  route_sorted)
        x, logits = inputs
        T = x.shape[0]
        mixer = p.router_rows == "mixer"
        _trace.counter("moe:router_rows", cat="moe",
                       track="l%d" % p.layer if p.layer >= 0 else "moe",
                       mixer=int(mixer), ffn=int(not mixer))
        with _scope("moe_route", p):
            if drop_free(p.capacity_factor):
                plan = route_sorted(
                    logits, p.k, renormalize=p.renormalize, score=p.score,
                    scale=p.scale, select_bias=aux[0] if aux else None,
                    held=(p.first_expert, p.experts_held)
                    if p.experts_held else None)
                buf, order = sort_rows(x, plan.order, plan.slot), plan.order
            else:
                C = self._cap(p, T)
                plan = _route(logits, p.k, C, renormalize=p.renormalize)
                buf = _dispatch(x, plan.slot, p.num_experts, C)
                # buckets have no order; the combine node reads none
                order = jnp.zeros((T * p.k,), jnp.int32)
            outs = [buf, plan.weight, plan.slot, plan.aux.reshape(1),
                    plan.counts, plan.hits, plan.dropped.reshape(1), order]
            if not aux:
                return outs
            # the selection bias is a state of the op, as BatchNorm's
            # moving mean is: it moves in training steps only
            return outs, [moved_select_bias(aux[0], plan.counts, p.bias_rate)
                          if ctx.is_train else aux[0]]


def _layers(p, tensors):
    """``[(weight, bias or None)]`` of an expert FFN's stacked inputs."""
    tensors = list(tensors)
    if p.no_bias:
        return [(w, None) for w in tensors]
    return list(zip(tensors[0::2], tensors[1::2]))


def _ffn(p, x, layers, linear, note=None):
    """``note``, where given, is called with the activated lanes: ``act(x
    Wg)`` of a gated layer, ``act(x W1)`` of a plain one."""
    act = _act(p.act_type)
    if p.gated:
        (wg, bg), (w1, b1), (w2, b2) = layers
        h = act(linear(x, wg, bg))
        if note:
            note(h)
        h = h * linear(x, w1, b1)
    else:
        (w1, b1), (w2, b2) = layers
        h = act(linear(x, w1, b1))
        if note:
            note(h)
    return linear(h, w2, b2)


def _held_sizes(p, counts):
    """The group sizes of the experts this rank holds (all, where
    ``experts_held`` is 0), of the dispatch node's ``counts``."""
    sizes = counts.astype(jnp.int32)
    if p.experts_held:
        sizes = sizes[p.first_expert:p.first_expert + p.experts_held]
    return sizes


def _sorted_ffn(p, x, layers, counts, window=None):
    """The expert FFN over sorted rows ``x`` ``(rows, D)``: grouped
    matmuls whose group sizes are the held experts' ``counts``.  The
    rows are all ``T*k`` of the plan, or a rank's sorted rows ``lo .. lo
    + n - 1`` (``window = (lo, n)``, ``lo`` no further than its held
    rows reach): then each group's part inside the window.  With the
    node's ``act_zeros``: -> ``(rows, (zeros, lanes))``, float32 counts
    of the activated lanes of the rows that belong to a group (never of
    the rows a static bound pads): how many there are, and how many of
    them are exactly 0."""
    from ..moe.dispatch import group_tiles, grouped_matmul
    sizes = _held_sizes(p, counts)
    if window is not None:
        lo, n = window
        ends = jnp.cumsum(sizes)
        sizes = jnp.maximum(jnp.minimum(ends, lo + n)
                            - jnp.maximum(ends - sizes, lo), 0)
    mine = None
    if p.experts_held:
        # the dispatch node sorted this rank's rows first: the
        # groups are its experts', the rows behind them no one's
        mine = (jnp.arange(x.shape[0]) < sizes.sum())[:, None]
    E = sizes.shape[0]

    def own(rows):
        """Rows that belong to no group read exactly zero, in
        this pass and (the select's transpose) in the backward
        one: a grouped matmul leaves them unwritten, which on a
        TPU is whatever the buffer held."""
        return rows if mine is None else jnp.where(
            mine, rows, jnp.zeros((), rows.dtype))

    x = own(x)
    expert_of_row = None if p.no_bias else jnp.repeat(
        jnp.arange(E), sizes, total_repeat_length=x.shape[0])
    # one tile -> group map for the layer's nine products
    groups = group_tiles(sizes, x.shape[0])

    def linear(h, w, b):
        out = grouped_matmul(h, w, groups)
        return own(out if b is None else out + jnp.take(
            b, expert_of_row, axis=0))
    if not p.get("act_zeros"):
        return _ffn(p, x, layers, linear)
    seen = []
    out = _ffn(p, x, layers, linear, seen.append)
    lanes = jax.lax.stop_gradient(seen[0])
    zeros = jnp.sum((lanes == 0) & mine, dtype=jnp.int32)
    return out, jnp.stack([zeros, sizes.sum() * lanes.shape[1]]) \
        .astype(jnp.float32)


def _ffn_arguments(p):
    # *_weight / *_bias suffixes keep auto-created variables on the
    # initializer's name-pattern dispatch (the RNN op's convention)
    names = []
    for stem in (["i2h_gate"] if p.gated else []) + ["i2h", "h2o"]:
        names.append(stem + "_weight")
        if not p.no_bias:
            names.append(stem + "_bias")
    return names


def _ffn_shapes(p, E, D):
    """The stacked tensors' shapes, in ``_ffn_arguments``' order."""
    H, O = p.num_hidden, p.output_dim or D
    shapes = []
    for w, b in ([((E, D, H), (E, H))] if p.gated else []) \
            + [((E, D, H), (E, H)), ((E, H, O), (E, O))]:
        shapes.append(w)
        if not p.no_bias:
            shapes.append(b)
    return shapes


_FFN_PARAMS = [Param("num_hidden", int, required=True),
               Param("output_dim", int, default=0),
               Param("act_type", str, default="relu", enum=_ACTS),
               Param("no_bias", bool, default=False),
               Param("gated", bool, default=False),
               Param("layer", int, default=-1),
               Param("experts_held", int, default=0),
               Param("first_expert", int, default=0)]


@register_op("_moe_expert_ffn", hint="moe_experts")
class MoEExpertFFNOp(OpDef):
    """Per-expert 2-layer FFN.  Plain: ``act(x @ w1[e] + b1[e]) @ w2[e]
    + b2[e]``; ``gated``: ``(act(x @ wg[e] + bg[e]) * (x @ w1[e] +
    b1[e])) @ w2[e] + b2[e]`` (SwiGLU with ``act_type="silu"``).  Over
    the (E, C, D) buffer it is batched einsums; over the (T*k, D) sorted
    rows it is grouped matmuls (``moe.dispatch.grouped_matmul``) whose
    group sizes are ``counts``.  The stacked weights (E, D, H)/(E, H, O)
    are what an ``ep``-axis ``__sharding__`` attr shards row-wise,
    exactly like a row-sharded embedding table.  ``experts_held`` > 0:
    the stacked weights hold that many experts, ``first_expert`` on, of
    the ``counts`` routed over (sorted rows only), and the rows behind
    their groups come out zero."""
    params = _FFN_PARAMS

    def list_arguments(self, p):
        return ["data"] + _ffn_arguments(p) + ["counts"]

    def implied_inputs(self, p, given):
        # counts are the dispatch node's: a caller that gives data alone,
        # and a graph saved before counts was an input, get them from
        # the node data comes from
        return _from_dispatch(
            "_moe_expert_ffn", given, "data", 0, "counts", _COUNTS_OUT,
            "the group sizes of sorted rows; unused over (experts, "
            "capacity, dim) buckets")

    def infer_shape(self, p, in_shapes):
        d, cnt = in_shapes[0], in_shapes[-1]
        if d is None:
            return in_shapes, [None], []
        if len(d) == 3:
            E, D = d[0], d[2]
        elif len(d) == 2 and cnt is not None:
            E, D = p.experts_held or cnt[0], d[1]
        elif len(d) == 2:
            return in_shapes, [None], []
        else:
            raise MXNetError("_moe_expert_ffn: data must be (experts, "
                             "capacity, dim) or sorted (rows, dim), got %r"
                             % (d,))
        return [d] + _ffn_shapes(p, E, D) \
            + [cnt if cnt is not None else (E,)], \
            [tuple(d[:-1]) + (p.output_dim or D,)], []

    def infer_type(self, p, in_types):
        t = in_types[0] if in_types[0] is not None else np.dtype(np.float32)
        n = len(self.list_arguments(p))
        return [t] * (n - 1) + [np.dtype(np.float32)], [t], []

    def forward(self, p, inputs, aux, ctx):
        x, counts = inputs[0], inputs[-1]
        layers = _layers(p, inputs[1:-1])
        # the whole body: the rank's row mask and its first select are
        # the experts' work too
        with _scope("moe_experts", p):
            if x.ndim == 2:
                return [_sorted_ffn(p, x, layers, counts)]

            def linear(h, w, b):
                out = jnp.einsum("ecd,edh->ech", h, w)
                return out if b is None else out + b[:, None, :]
            return [_ffn(p, x, layers, linear)]


@register_op("_moe_share_ffn", hint="moe_share")
class MoEShareFFNOp(OpDef):
    """One expert-parallel rank's share of a drop-free routed layer in
    ONE node: ``data`` (T, D) tokens, the dispatch node's ``weight``,
    ``slot``, ``order`` and ``counts``, and the stacked weights of the
    ``experts_held`` experts from ``first_expert`` on -> (T, O).  It
    is ``sort_rows``, ``_moe_expert_ffn``'s sorted body and
    ``combine_sorted`` (the same functions, under the scopes
    ``moe_route``, ``moe_experts``, ``moe_combine``) over the first ``R``
    sorted rows, ``R = moe.dispatch.held_rows_bound(T*k, E,
    experts_held)``: the dispatch node sorted the held rows first, so
    they are rows ``0 .. held - 1``, and where ``held <= R`` nothing
    behind row ``R`` is anyone's.  Where ``held > R`` the same body runs
    once more, over rows ``R .. T*k - 1``, and its output is added: one
    ``lax.cond`` on the device whose other branch is zeros, so no
    choice is dropped and the mathematics on the held rows is the same
    (a token's rows on both sides of ``R`` are summed in two parts).
    That branch is checkpointed: it saves the node's inputs, so a common
    step writes no ``T*k``-sized zeros for it.  Both passes of the first
    ``R`` rows run outside any conditional (XLA:TPU stops its fusions at
    one, and its code for a conditional with kernels in both branches is
    ten times either branch's: PERF.md, PR 40).  Where ``R`` is ``T*k``
    (a bound that would save too few rows to be worth a conditional) the
    node is the one window ``(0, T*k)`` with no ``cond``; a program over
    more than one device runs the three nodes' statements.  A window
    leaves nothing ``T*k``-sized where ``moe.dispatch.held_sum``'s kernel
    runs (the combine's forward, the row gradient's backward); its parts
    and the bounded node are jits of this module, traced once a process.

    ``act_zeros`` adds a second output ``(2,)`` float32, no gradient:
    ``(zeros, lanes)`` of the activated lanes (``act(x Wg)``) of the rows
    the rank really holds, both sides of the bound summed
    (``_sorted_ffn``).  Unset, the node is the one it was."""
    # unset is not in a node's JSON: the symbols that are there keep theirs
    params = _FFN_PARAMS + [Param("act_zeros", bool)]

    def list_outputs(self, p):
        return ["output", "act_zeros"] if p.act_zeros else ["output"]

    def list_arguments(self, p):
        # the dispatch node's four before the stacked tensors: a graph's
        # arguments are listed in the order its nodes' inputs reach them,
        # and the router's weight came before the experts' in every
        # saved graph
        return ["data", "weight", "slot", "order", "counts"] \
            + _ffn_arguments(p)

    def infer_shape(self, p, in_shapes):
        d, w, s, _, cnt = in_shapes[:5]
        if not p.experts_held:
            raise MXNetError("_moe_share_ffn is a rank's share: "
                             "experts_held > 0 (the whole layer is "
                             "_moe_expert_ffn between _moe_dispatch and "
                             "_moe_combine)")
        tk = w if w is not None else s
        if d is None or tk is None:
            return in_shapes, [None], []
        if len(d) != 2 or len(tk) != 2 or tk[0] != d[0]:
            raise MXNetError("_moe_share_ffn: data must be (tokens, dim) "
                             "and weight, slot (tokens, k), got %r and %r"
                             % (d, tk))
        tk = tuple(tk)
        return [d, tk, tk, (tk[0] * tk[1],), cnt] \
            + _ffn_shapes(p, p.experts_held, d[1]), \
            [(d[0], p.output_dim or d[1])] + ([(2,)] if p.act_zeros else []), []

    def infer_type(self, p, in_types):
        t = in_types[0] if in_types[0] is not None else np.dtype(np.float32)
        f32, i32 = np.dtype(np.float32), np.dtype(np.int32)
        return [t, f32, i32, i32, f32] + [t] * len(_ffn_arguments(p)), \
            [t] + ([f32] if p.act_zeros else []), []

    def forward(self, p, inputs, aux, ctx):
        from ..moe.dispatch import held_rows_bound
        from ..parallel.mesh import traced_devices
        from .transformer import scope_prefix
        every = inputs[2].size                  # slot's (T, k) choices
        bound = held_rows_bound(every, inputs[4].shape[0], p.experts_held)
        if traced_devices() > 1:
            out = _share_window(p)(*inputs)
        elif bound == every:
            out = _share_window(p, (0, every))(*inputs)
        else:
            out = _share_bounded(_static(p.items()), scope_prefix(), bound,
                                 *inputs)
        return list(out) if p.act_zeros else [out]


def _static(items):
    """A node's parameters as a jit's static argument; one that is unset
    is left out, so that a parameter a later PR adds keys nothing."""
    return tuple(sorted((k, v) for k, v in items if v is not None))


def _window_rows(params, window, x, order, slot, counts):
    from ..moe.dispatch import sort_rows
    held = _held_sizes(_AttrDict(params), counts) if window else None
    return sort_rows(x, order, slot, held, window)


def _window_ffn(params, window, rows, counts, *stacked):
    p = _AttrDict(params)
    return _sorted_ffn(p, rows, _layers(p, stacked), counts, window)


def _window_sum(params, window, rows, order, slot, weight, counts):
    from ..moe.dispatch import combine_sorted as combine
    held = _held_sizes(_AttrDict(params), counts) if window else None
    return combine(rows, order, slot, weight, window and window[0], held)


# lint: allow(raw-jit) — never dispatched on their own: jits inside the step
# program (as moe/gmm.py's), so that a process traces, differentiates and
# lowers a window's three parts once a geometry and not once a layer, a
# pass and a module: the layers' nodes differ by their scopes' names alone,
# and those are entered around the calls
_WINDOW_PARTS = tuple(jax.jit(part, static_argnums=(0, 1)) for part in
                      (_window_rows, _window_ffn, _window_sum))


def _share_window(p, window=None):
    """``_moe_share_ffn``'s body over sorted rows ``lo .. lo + n - 1``
    (``window = (lo, n)``), or, given none, over all of them: the three
    nodes' statements.  With the node's ``act_zeros``: -> ``(output,
    (zeros, lanes))`` of the window's rows."""
    params = _static((k, v) for k, v in p.items() if k != "layer")
    gather, experts, combine = _WINDOW_PARTS if window else (
        _window_rows, _window_ffn, _window_sum)

    def body(x, weight, slot, order, counts, *stacked):
        with _scope("moe_route", p):
            rows = gather(params, window, x, order, slot, counts)
        with _scope("moe_experts", p):
            rows = experts(params, window, rows, counts, *stacked)
        if p.get("act_zeros"):
            rows, seen = rows
        with _scope("moe_combine", p):
            out = combine(params, window, rows, order, slot, weight, counts)
        return (out, seen) if p.get("act_zeros") else out
    return body


# lint: allow(raw-jit) — never dispatched on its own: a jit inside the step
# program, so that a process traces a node's body once (as moe/gmm.py's)
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _share_bounded(params, prefix, bound, *inputs):
    """``_moe_share_ffn`` under a row bound below ``T*k``.  A jit of the
    module, so that a process traces and differentiates a layer's two
    passes once: a second module over the same symbol (a run's checking
    module and its training one) finds them made.  ``params`` are the
    node's, ``prefix`` the scope prefix it is traced under (the named
    scopes inside are read when it is traced: both are in the key)."""
    p = _AttrDict(params)
    slot, counts = inputs[2], inputs[4]
    every = slot.shape[0] * slot.shape[1]
    out = _share_window(p, (0, bound))(*inputs)
    # the rows behind the bound, where any is held: the same body over
    # them, added.  The branch that runs nothing is all a common step
    # pays for it (zeros out and, backward, zero gradients).  The scope
    # ``moe_share`` is around the bound's test and the sum, never around
    # the ``cond``: the outermost declared scope wins, and the second
    # pass's parts keep their own.  The ``conditional``, its branch of
    # zeros and the backward pass's sums of the two passes' gradients
    # (JAX's transpose makes those, in no scope of this function) stay
    # under the node's generic scope
    with _scope("moe_share", p):
        overflows = _held_sizes(p, counts).sum() > bound
    behind = jax.lax.cond(
        overflows, jax.checkpoint(_share_window(p, (bound, every - bound))),
        lambda *_: jax.tree.map(jnp.zeros_like, out), *inputs)
    with _scope("moe_share", p):
        return jax.tree.map(jnp.add, out, behind)


@register_op("_moe_combine", hint="moe_combine")
class MoECombineOp(OpDef):
    """Gather expert outputs back to token order (T, O), weighted by
    the routing plan's combine weights: from the (E, C, O) buffer, where
    the sentinel slot reads zero (clip-gather + explicit mask in
    ``moe.dispatch.combine``) so dropped tokens contribute exactly
    nothing, or from the (T*k, O) sorted rows, where nothing was
    dropped.  ``order`` is the dispatch node's, the inverse of ``slot``
    over sorted rows: their backward pass gathers through it (unused
    over buckets)."""
    params = [Param("layer", int, default=-1)]

    def list_arguments(self, p):
        return ["data", "weight", "slot", "order"]

    def implied_inputs(self, p, given):
        # as the expert node's counts: the dispatch node's, for a caller
        # that gives none and a graph saved before order was an input
        return _from_dispatch(
            "_moe_combine", given, "slot", _SLOT_OUT, "order", _ORDER_OUT,
            "the (token, choice) of each sorted row; unused over "
            "(experts, capacity, dim) buckets")

    def infer_shape(self, p, in_shapes):
        d, w, s, _ = in_shapes
        if d is None or (w is None and s is None):
            return in_shapes, [None], []
        if len(d) not in (2, 3):
            raise MXNetError("_moe_combine: data must be (experts, "
                             "capacity, dim) or sorted (rows, dim), got %r"
                             % (d,))
        tk = tuple(w if w is not None else s)
        return [d, tk, tk, (tk[0] * tk[1],)], [(tk[0], d[-1])], []

    def infer_type(self, p, in_types):
        t = in_types[0] if in_types[0] is not None else np.dtype(np.float32)
        i32 = np.dtype(np.int32)
        return [t, np.dtype(np.float32), i32, i32], [t], []

    def forward(self, p, inputs, aux, ctx):
        from ..moe.dispatch import combine as _combine, combine_sorted
        x, weight, slot, order = inputs
        with _scope("moe_combine", p):
            if x.ndim == 2:
                return [combine_sorted(x, order, slot, weight)]
            E, C = x.shape[0], x.shape[1]
            return [_combine(x, slot, weight, E, C)]
