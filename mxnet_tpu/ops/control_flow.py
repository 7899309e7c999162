"""Control flow: ``Repeat``, a node that holds a body ``Symbol`` and applies
it ``num_steps`` times with one set of weights.

What the later MXNet called ``sym.contrib.foreach`` / ``while_loop``, cut
to what a looped model needs: a stack of layers run several times over
the same rows.  The body is a ``Symbol`` of its own.  Its free variables
are the node's inputs, in ``body.list_arguments()`` order, each bound
ONCE whatever ``num_steps`` is: those named in ``carry`` take the first
pass's value and, in every later pass, what the pass before gave back
(the body's first ``len(carry)`` outputs, same shapes and dtypes in and
out); every other one is read unchanged by every pass (a weight, or a
value the outer graph computed: the flattened labels).  The body's
further outputs are per-pass outputs.  The node gives the last carry and
each per-pass output stacked ``(num_steps, ...)``.

The forward is ``jax.lax.scan`` over ``num_steps`` of the body's
``_GraphProgram.eval``: the lowered program holds ONE copy of the body.
The gradient of an input every pass reads is the sum over the passes,
which ``scan``'s transpose gives; no code here adds them.  With
``recompute`` (the default) a pass is a ``jax.checkpoint``: the backward
pass keeps each pass's carry and forms one pass's activations again at
a time, not all passes' at once.  ``recompute`` is a parameter of the
node, set by whoever builds the graph; nothing else switches it.

A node of the body keeps its ``__scope__`` attribute and its op's own
device scope and runs under them in every pass, so a device trace's
``attn.l3`` is the sum of the passes.  The executor enters no scope
around the loop node (``own_scope``): what its body's nodes name
themselves is what a reader of the trace sees, as in a graph without a
loop.  The node's own scope ``loop`` is an enclosing one
(``trace/scopes.py``): it names what is left, the ``while`` itself, its
counters and the sums of the passes' gradients.

Refused, with an error that says so: a body with auxiliary states (a
BatchNorm's moving statistics, a router's selection bias: which pass's
would the state keep?) and a body whose ops draw random numbers (one
key a node, not one a pass).  Each trace records ``loop:body``:
``num_steps``, the body's op nodes, the carry's bytes and ``recompute``.
"""
from __future__ import annotations

import jax
import numpy as np
from jax import lax

from .. import trace
from ..base import MXNetError
from ..trace import scopes as _scopes
from .registry import OpDef, Param, register_op
from .transformer import scope_prefix

__all__ = ["carry_names", "body_op_nodes"]


def carry_names(p):
    """The body's carried variables, in the order of its first outputs."""
    return [n for n in (p.carry or "").split(",") if n]


def body_op_nodes(body):
    """The body's op nodes (every node that is not a variable)."""
    from ..symbol import _topo
    return [n for n in _topo(body._heads) if not n.is_variable]


@register_op("Repeat", hint="repeat")
class RepeatOp(OpDef):
    """Apply a body Symbol ``num_steps`` times over one set of inputs;
    see the module docstring.  ``mx.sym.Repeat`` is the constructor."""
    own_scope = False
    params = [Param("body", "symbol", required=True,
                    doc="the body: a Symbol, or its JSON"),
              Param("num_steps", int, required=True),
              Param("carry", str, required=True,
                    doc="the body's carried variables, comma-separated, in "
                        "the order of the body's first outputs"),
              Param("recompute", bool, default=True,
                    doc="form a pass again in the backward pass, keeping "
                        "its carry only")]

    def parse_params(self, kwargs):
        p = super().parse_params(kwargs)
        body, carry = p.body, carry_names(p)
        free = body.list_arguments()
        if p.num_steps < 1:
            raise MXNetError("Repeat: num_steps %d: a loop runs at least "
                             "once" % p.num_steps)
        if not carry or len(set(carry)) != len(carry) \
                or any(n not in free for n in carry):
            raise MXNetError("Repeat: carry %r is not a list of distinct "
                             "free variables of the body, which has %s"
                             % (p.carry, free))
        if len(body._heads) < len(carry):
            raise MXNetError("Repeat: the body gives %d outputs for %d "
                             "carried variables" % (len(body._heads),
                                                    len(carry)))
        if body.list_auxiliary_states():
            raise MXNetError(
                "Repeat: the body holds auxiliary states %s; a state that "
                "every pass would update is not supported inside a loop"
                % body.list_auxiliary_states())
        random = [n.name for n in body_op_nodes(body) if n.op.needs_rng]
        if random:
            raise MXNetError(
                "Repeat: the body's nodes %s draw random numbers; an op "
                "that needs a key is not supported inside a loop" % random)
        return p

    def list_arguments(self, p):
        return p.body.list_arguments()

    def list_outputs(self, p):
        return p.body.list_outputs()

    def infer_shape(self, p, in_shapes):
        body, carry = p.body, carry_names(p)
        names = body.list_arguments()
        known = {n: tuple(s) for n, s in zip(names, in_shapes)
                 if s is not None}
        arg_s, out_s, _ = body._infer_shape_impl(False, **known)
        if arg_s is None:
            arg_s, out_s, _ = body._infer_shape_impl(True, **known)
        for i, c in enumerate(carry):
            a, b = arg_s[names.index(c)], out_s[i]
            if a is not None and b is not None and tuple(a) != tuple(b):
                raise MXNetError("Repeat: carry %r enters a pass as %s and "
                                 "leaves it as %s" % (c, tuple(a), tuple(b)))
        outs = list(out_s[:len(carry)]) + [
            None if s is None else (p.num_steps,) + tuple(s)
            for s in out_s[len(carry):]]
        return list(arg_s), outs, []

    def infer_type(self, p, in_types):
        names = p.body.list_arguments()
        known = {n: t for n, t in zip(names, in_types) if t is not None}
        arg_t, out_t, _ = p.body.infer_type(**known)
        return arg_t, out_t, []

    def forward(self, p, inputs, aux, ctx):
        from ..executor import _GraphProgram
        body, carry = p.body, carry_names(p)
        prog = _GraphProgram(body, {}, None, do_mirror=False)
        args = dict(zip(body.list_arguments(), inputs))
        first = tuple(args.pop(c) for c in carry)
        nodes = len(body_op_nodes(body))
        trace.counter(
            "loop:body", cat="ops", track="%dx%d" % (p.num_steps, nodes),
            num_steps=p.num_steps, nodes=nodes,
            carry_bytes=int(sum(x.size * np.dtype(x.dtype).itemsize
                                for x in first)),
            recompute=int(bool(p.recompute)))

        def one_pass(state, read):
            outs, _ = prog.eval(dict(read, **dict(zip(carry, state))), {},
                                None, ctx.is_train)
            # a pass gives back what it was given: same dtypes
            state = tuple(o.astype(s.dtype)
                          for o, s in zip(outs[:len(carry)], state))
            return state, tuple(outs[len(carry):])

        if p.recompute:
            one_pass = jax.checkpoint(one_pass)
        with _scopes.enclosing(scope_prefix() + "loop"):
            last, stacked = lax.scan(lambda state, _: one_pass(state, args),
                                     first, None, length=p.num_steps)
        return list(last) + list(stacked)
