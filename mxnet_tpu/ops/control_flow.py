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

The forward is ``jax.lax.scan`` over the body's ``_GraphProgram.eval``:
the lowered program does not grow with ``num_steps``.  The gradient of an
input every pass reads is the sum over the passes, last pass first, in
the input's dtype.

What the backward pass keeps and what it forms again.  Without
``recompute`` every pass's activations are kept (``scan``'s own
backward pass).  With ``recompute`` (the default) the backward pass does
not hold all passes' activations:

* of every EARLIER pass the carry it started from is kept (stacked
  ``(num_steps - 1, ...)``) and nothing else; the pass is formed again
  from it, one pass at a time, under ``jax.checkpoint``, behind a
  barrier on what it is formed from (what ``jax.checkpoint`` itself
  sets, ``prevent_cse``), so that no compiler pass can take "formed
  again" for "computed before" and keep the pass instead;
* the LAST pass is not formed again.  It has just run when the backward
  pass starts with it, so it stands in the program OUTSIDE the two
  ``while``s, its forward half (``run``) and its backward half
  (``undo``) in one computation with NO barrier between them: what
  ``undo`` would form again is, operation for operation, what ``run``
  has just computed from the same values, and XLA merges the two
  (common subexpressions).  Nothing is held for the last pass that
  forming it again would not hold at that moment, and ``num_steps`` 1 is
  the body itself.

The second point is the compiler's doing, not this file's: the program
as written forms the last pass again, and says so under
``rematted_computation`` in the lowered text; that it runs once is read
off the COMPILED program (``tests/test_loop_node.py`` on the CPU's,
``tests/tpu/test_ouro_tpu.py`` on the chip's: the forward kernels of one
pass in ENTRY, not of two).  A compiler that stopped merging would cost
the time back (the pass formed again, as before ISSUE 56) and no memory.
The first point's barrier is the chip's contract: XLA's CPU pipeline
expands barriers away before its last merge, so at ``num_steps`` 2, where
a ``while`` of one trip is unrolled beside the last pass, the CPU's
program keeps both passes (``jax.checkpoint`` outside a ``scan`` reads
the same there).

ONE derivative serves every pass: ``jax.vjp`` of the checkpointed pass,
taken once and cut in two where the ``while``s cut it (``_split_pass``),
so the body's Python and JAX's differentiation rules are met once a
trace, as under ``scan``.  The program holds two copies of a pass's
forward half (the earlier passes' ``while`` and the last pass) and ONE
of its backward half, a function both call, whatever ``num_steps`` is.
The loop is a ``jax.custom_vjp``, because the sums of the passes'
gradients have to START from the last pass's: ``scan``'s transpose
starts its own from zeros, and adding the last pass's to them
afterwards holds two sets of gradient-sized buffers through the backward
``while``.

``recompute`` is a parameter of the node, set by whoever builds the
graph; nothing else switches it.

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
``num_steps``, the body's op nodes, the carry's bytes, ``recompute`` and
``kept_passes`` (the passes whose activations the backward pass reads as
the forward left them: 1 with ``recompute``, else all).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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


def _traced_once(fn, *example):
    """``fn`` as the evaluation of the jaxpr it traces to at ``example``'s
    types: the body's Python runs once however many copies of it the
    program holds, and every operation keeps the scopes it was made in."""
    fn, hoisted = jax.closure_convert(fn, *example)
    if hoisted:
        raise MXNetError("Repeat: the body reads %d traced values that "
                         "are not inputs of the node" % len(hoisted))
    return fn


def _split_pass(one_pass, first, read):
    """A pass and its derivative, cut where the two ``while``s cut them:
    ``(run, undo)``, each traced once.

    ``run(state, read)`` -> ``(next state, outputs)`` is the pass going
    forward.  ``undo(state, read, cotangents)`` -> the cotangents of
    ``(state, read)`` forms the pass again from ``state`` and goes back
    through it; it is an inner ``jax.jit``: one function of the program,
    called by the backward ``while`` and by the last pass.  Both are the
    two halves of ONE ``jax.vjp`` of the checkpointed pass.  What its
    pullback holds is the pass's own inputs (the carry and everything
    every pass reads: a checkpoint with no policy saves nothing else),
    which are handed to ``undo`` anew, found by identity, and constants
    of the program.  The checkpoint sets no barrier
    (``prevent_cse=False``): the loop sets its own where a pass must be
    formed again, and none where ``run`` and ``undo`` meet in one
    computation, so that the compiler may merge what they share."""
    again = jax.checkpoint(one_pass, prevent_cse=False)
    pullback = {}

    def run(state, read):
        out, pull = jax.vjp(again, state, read)
        held, pullback["tree"] = jax.tree_util.tree_flatten(pull)
        given = {id(x): i for i, x in enumerate(
            jax.tree_util.tree_leaves((state, read)))}
        pullback["held"] = [(True, given[id(x)]) if id(x) in given
                            else (False, x) for x in held]
        return out

    run = _traced_once(run, first, read)

    # lint: allow(raw-jit) — an inner jit: a function of the step's own
    # program, traced inside its trace; nothing to cache on its own
    @jax.jit
    def undo(state, read, cotangents):
        given = jax.tree_util.tree_leaves((state, read))
        held = [given[x] if is_input else x
                for is_input, x in pullback["held"]]
        return jax.tree_util.tree_unflatten(pullback["tree"], held)(
            cotangents)

    return run, undo


def _add(total, one):
    """A pass's cotangent onto the running sum; an integer input's
    (``float0``) has nothing to add."""
    return total if total.dtype == jax.dtypes.float0 else total + one


def _forming_passes_again(every_pass, steps, split):
    """``every_pass``, ``(first, read) -> (last state, stacked outputs)``
    over ``steps`` > 1 passes, with a backward pass of its own: each pass
    ``split()``'s ``run`` forward and ``undo`` backward, ``steps - 1`` of
    them inside a ``while`` each way, keeping their carries, the last one
    between the two ``while``s; see the module docstring.  ``split`` is
    asked only when the loop is differentiated."""
    front = steps - 1
    tree_map = jax.tree_util.tree_map
    loop = jax.custom_vjp(every_pass)

    def forward(first, read):
        run = split()[0]

        def step(state, _):
            state_out, outs = run(state, read)
            return state_out, (state, outs)

        mid, (carries, outs) = lax.scan(step, first, None, length=front)
        last, tail = run(mid, read)
        stacked = tree_map(lambda a, b: jnp.concatenate([a, b[None]]),
                           outs, tail)
        return (last, stacked), (carries, mid, read)

    def backward(residuals, cotangents):
        carries, mid, read = residuals
        d_last, d_outs = cotangents
        undo = split()[1]
        # the sums START from the last pass's cotangents: one set of
        # gradient-sized buffers through the ``while``
        sums = undo(mid, read,
                    (d_last, tree_map(lambda x: x[front], d_outs)))

        def step(sums, at):
            # an earlier pass is FORMED again, not found again: a ``while``
            # of one trip is unrolled, and without the barrier its pass
            # would be merged with the forward's as the last pass's is.
            # In a longer ``while`` it holds nothing back (the Ouro step
            # reads 0.4 ms LESS with it: PERF.md §6, PR 56 (2))
            state, d_out = lax.optimization_barrier(at)
            d_state, d_read = undo(state, read, (sums[0], d_out))
            return (d_state, tree_map(_add, sums[1], d_read)), None

        sums, _ = lax.scan(
            step, sums, (carries, tree_map(lambda x: x[:front], d_outs)),
            reverse=True)
        return sums

    loop.defvjp(forward, backward)
    return loop


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
                    doc="the backward pass does not hold all passes' "
                        "activations: it keeps the last pass and forms "
                        "each earlier one again from its carry")]

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
        read = dict(zip(body.list_arguments(), inputs))
        first = tuple(read.pop(c) for c in carry)
        steps, nodes = p.num_steps, len(body_op_nodes(body))
        trace.counter(
            "loop:body", cat="ops", track="%dx%d" % (steps, nodes),
            num_steps=steps, nodes=nodes,
            carry_bytes=int(sum(x.size * np.dtype(x.dtype).itemsize
                                for x in first)),
            recompute=int(bool(p.recompute)),
            kept_passes=1 if p.recompute else steps)

        def one_pass(state, read):
            outs, _ = prog.eval(dict(read, **dict(zip(carry, state))), {},
                                None, ctx.is_train)
            # a pass gives back what it was given: same dtypes
            state = tuple(o.astype(s.dtype)
                          for o, s in zip(outs[:len(carry)], state))
            return state, tuple(outs[len(carry):])

        again = p.recompute and steps > 1
        if again:
            one_pass = _traced_once(one_pass, first, read)
            split = functools.cache(
                lambda: _split_pass(one_pass, first, read))

        def every_pass(first, read):
            return lax.scan(lambda state, _: one_pass(state, read), first,
                            None, length=steps)

        with _scopes.enclosing(scope_prefix() + "loop"):
            if again:
                last, stacked = _forming_passes_again(
                    every_pass, steps, split)(first, read)
            elif p.recompute:              # one pass: the body itself
                last, stacked = one_pass(first, read)
                stacked = tuple(x[None] for x in stacked)
            else:
                last, stacked = every_pass(first, read)
        return list(last) + list(stacked)
