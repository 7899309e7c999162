"""The loop node ``Repeat`` (ISSUE 54, tier-1): against the same body
unrolled by hand over shared ``Variable``s (outputs and every gradient),
what the graph lists, infers and saves, what is refused, that the
recomputed and the kept body give the same gradients and that only the
recomputed one is formed again, the lowered program's size beside the
unrolled build's, and the device scopes of a body's nodes.  Since ISSUE
56 also what the recomputed loop's backward pass keeps: the earlier
passes' carries, and the last pass as the forward left it, which is read
off the compiled program."""
import json
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.executor import _GraphProgram
from mxnet_tpu.trace import scopes

STEPS = 3
ROWS, WIDTH, CLASSES = 6, 8, 5


def _block(x, label, w1, w2, head):
    """One pass: a residual MLP over shared weights, then a per-row loss
    through a shared head.  -> (the next rows, the rows' losses)."""
    h = sym.Activation(sym.FullyConnected(x, weight=w1, num_hidden=2 * WIDTH,
                                          no_bias=True), act_type="tanh")
    x = x + sym.FullyConnected(h, weight=w2, num_hidden=WIDTH, no_bias=True)
    x = sym.RMSNorm(x, eps=1e-6, name="close")
    logits = sym.FullyConnected(x, weight=head, num_hidden=CLASSES,
                                no_bias=True)
    return x, sym.SoftmaxCELoss(logits, label)


def _weights():
    return [sym.Variable(n) for n in ("w1", "w2", "head")]


def _looped(recompute=True, steps=STEPS):
    with mx.name.NameManager():
        nxt, loss = _block(sym.Variable("rows"), sym.Variable("lab"),
                           *_weights())
        body = sym.Group([nxt, loss])
        label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
        loop = sym.Repeat(body, {"rows": sym.Variable("data")}, steps,
                          name="loop", recompute=recompute, lab=label)
        total = sym.sum_axis(loop[1], axis=0)
        return sym.Group([sym.MakeLoss(total, normalization="batch"),
                          sym.BlockGrad(loop[0])])


def _unrolled(steps=STEPS):
    """The same passes written out, every pass over the SAME variables."""
    with mx.name.NameManager():
        w1, w2, head = _weights()
        gamma = sym.Variable("close_gamma")
        label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
        x, losses = sym.Variable("data"), []
        for _ in range(steps):
            h = sym.Activation(sym.FullyConnected(
                x, weight=w1, num_hidden=2 * WIDTH, no_bias=True),
                act_type="tanh")
            x = x + sym.FullyConnected(h, weight=w2, num_hidden=WIDTH,
                                       no_bias=True)
            x = sym.RMSNorm(x, gamma=gamma, eps=1e-6)
            logits = sym.FullyConnected(x, weight=head, num_hidden=CLASSES,
                                        no_bias=True)
            losses.append(sym.SoftmaxCELoss(logits, label))
        total = losses[0]
        for one in losses[1:]:
            total = total + one
        return sym.Group([sym.MakeLoss(total, normalization="batch"),
                          sym.BlockGrad(x)])


def _values(seed=0):
    rng = np.random.RandomState(seed)
    return {"data": rng.randn(ROWS, WIDTH).astype(np.float32),
            "softmax_label": rng.randint(0, CLASSES, (ROWS,))
            .astype(np.float32),
            "w1": (0.4 * rng.randn(2 * WIDTH, WIDTH)).astype(np.float32),
            "w2": (0.4 * rng.randn(WIDTH, 2 * WIDTH)).astype(np.float32),
            "head": (0.4 * rng.randn(CLASSES, WIDTH)).astype(np.float32),
            "close_gamma": (1 + 0.1 * rng.randn(WIDTH)).astype(np.float32)}


def _run(net, values):
    args = {k: mx.nd.array(values[k]) for k in net.list_arguments()}
    grads = {k: mx.nd.zeros(v.shape) for k, v in args.items()
             if k not in ("softmax_label",)}
    ex = net.bind(mx.cpu(0), args, args_grad=grads)
    outs = [o.asnumpy() for o in ex.forward(is_train=True)]
    ex.backward()
    return outs, {k: v.asnumpy() for k, v in grads.items()}


# -- against the hand-unrolled graph ---------------------------------------------
@pytest.mark.parametrize("steps", [1, 2, STEPS, 4])
@pytest.mark.parametrize("recompute", [True, False])
def test_the_loop_equals_the_passes_written_out(recompute, steps):
    values = _values()
    outs, grads = _run(_looped(recompute, steps), values)
    want_outs, want = _run(_unrolled(steps), values)
    for got, ref in zip(outs, want_outs):
        np.testing.assert_allclose(got, ref, rtol=2e-6, atol=1e-6)
    assert set(grads) == set(want)
    for k in want:
        # the passes' gradients are summed by scan's transpose, in
        # another order than the written-out graph's adds: float32 rounding
        np.testing.assert_allclose(grads[k], want[k], rtol=2e-5, atol=2e-6,
                                   err_msg=k)
    # the gradient reaches the first rows through every pass
    assert np.abs(grads["data"]).min() > 0


def test_one_pass_is_the_body_itself():
    values = _values(1)
    outs, grads = _run(_looped(steps=1), values)
    want_outs, want = _run(_unrolled(steps=1), values)
    np.testing.assert_allclose(outs[0], want_outs[0], rtol=1e-6)
    for k in want:
        np.testing.assert_allclose(grads[k], want[k], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("steps", [1, 2, STEPS, 4])
def test_recomputed_and_kept_bodies_give_the_same_gradients(steps):
    values = _values(2)
    _, kept = _run(_looped(recompute=False, steps=steps), values)
    _, again = _run(_looped(recompute=True, steps=steps), values)
    for k in kept:
        np.testing.assert_allclose(again[k], kept[k], rtol=1e-6, atol=1e-7)


# -- what the graph says -----------------------------------------------------------
def test_arguments_are_listed_once_whatever_the_number_of_passes():
    net = _looped()
    names = net.list_arguments()
    assert names == ["data", "w1", "w2", "close_gamma", "head",
                     "softmax_label"]
    assert _looped(steps=7).list_arguments() == names
    assert net.list_auxiliary_states() == []
    assert _unrolled().list_arguments().count("w1") == 1
    # the outputs carry the body's names behind the node's
    loop = net.get_internals()
    assert "loop_close_output" in loop.list_outputs()


def test_shapes_and_types_are_inferred_through_the_body():
    net = _looped()
    arg_shapes, out_shapes, aux = net.infer_shape(
        data=(ROWS, WIDTH), softmax_label=(ROWS,))
    assert dict(zip(net.list_arguments(), arg_shapes)) == {
        "data": (ROWS, WIDTH), "w1": (2 * WIDTH, WIDTH),
        "w2": (WIDTH, 2 * WIDTH), "close_gamma": (WIDTH,),
        "head": (CLASSES, WIDTH), "softmax_label": (ROWS,)}
    assert out_shapes == [(ROWS,), (ROWS, WIDTH)] and aux == []
    # the per-pass output is stacked under the number of passes
    inner = net.get_internals()
    shapes = dict(zip(inner.list_outputs(), inner.infer_shape(
        data=(ROWS, WIDTH), softmax_label=(ROWS,))[1]))
    assert shapes["loop_softmaxceloss0_output"] == (STEPS, ROWS)
    assert shapes["loop_close_output"] == (ROWS, WIDTH)
    arg_types, out_types, _ = net.infer_type(data=np.float32)
    assert all(t == np.float32 for t in arg_types + out_types)
    # nothing known: nothing inferred, and no error
    assert net.infer_shape() == (None, None, None)


def test_a_carry_that_changes_shape_is_refused_at_inference():
    x = sym.Variable("x")
    body = sym.FullyConnected(x, num_hidden=WIDTH + 1, name="fc")
    loop = sym.Repeat(body, {"x": sym.Variable("data")}, 2)
    with pytest.raises(MXNetError, match="enters a pass"):
        loop.infer_shape(data=(ROWS, WIDTH))


def test_json_round_trip_keeps_the_body_and_the_result():
    net = _looped()
    text = net.tojson()
    again = sym.load_json(text)
    assert again.tojson() == text
    assert again.list_arguments() == net.list_arguments()
    doc = json.loads(text)
    node = next(n for n in doc["nodes"] if n["op"] == "Repeat")
    assert node["param"]["num_steps"] == str(STEPS)
    assert node["param"]["carry"] == "rows"
    assert node["param"]["recompute"] == "True"
    body = json.loads(node["param"]["body"])
    assert {n["name"] for n in body["nodes"] if n["op"] == "null"} \
        == {"rows", "lab", "w1", "w2", "head", "close_gamma"}
    values = _values(3)
    outs, grads = _run(net, values)
    outs2, grads2 = _run(again, values)
    np.testing.assert_array_equal(outs[0], outs2[0])
    for k in grads:
        np.testing.assert_array_equal(grads[k], grads2[k])
    # a copy shares nothing the original can change under it
    copy = net.__copy__()
    assert copy.tojson() == text


def test_a_weights_attributes_reach_the_outer_variable():
    x = sym.Variable("x")
    w = sym.Variable("w", lr_mult=0.5, shape=(WIDTH, WIDTH))
    body = sym.FullyConnected(x, weight=w, num_hidden=WIDTH, no_bias=True)
    loop = sym.Repeat(body, {"x": sym.Variable("data")}, 2, name="loop")
    assert loop.attr_dict()["w"] == {"lr_mult": "0.5",
                                     "__shape__": str((WIDTH, WIDTH))}
    # a weight another node shares is bound by name
    shared = sym.Variable("w")
    loop = sym.Repeat(body, {"x": sym.Variable("data")}, 2, name="loop",
                      w=shared)
    net = sym.Group([loop, sym.FullyConnected(
        sym.Variable("data"), weight=shared, num_hidden=WIDTH,
        no_bias=True)])
    assert net.list_arguments().count("w") == 1


# -- what is refused ------------------------------------------------------------------
def test_what_a_body_may_not_hold():
    x = sym.Variable("x")
    data = {"x": sym.Variable("data")}
    with pytest.raises(MXNetError, match="auxiliary states"):
        sym.Repeat(sym.BatchNorm(x, name="bn"), data, 2)
    with pytest.raises(MXNetError, match="random"):
        sym.Repeat(sym.Dropout(x, p=0.5), data, 2)
    with pytest.raises(MXNetError, match="at least once"):
        sym.Repeat(x * 2.0, data, 0)
    with pytest.raises(MXNetError, match="not free variables"):
        sym.Repeat(x * 2.0, {"y": sym.Variable("data")}, 2)
    with pytest.raises(MXNetError, match="not free variables"):
        sym.Repeat(x * 2.0, data, 2, z=sym.Variable("z"))
    with pytest.raises(MXNetError, match="carry"):
        sym.Repeat(x * 2.0, {}, 2)
    with pytest.raises(TypeError):
        sym.Repeat(x * 2.0, {"x": 3}, 2)
    with pytest.raises(TypeError):
        sym.Repeat("not a symbol", data, 2)


# -- the lowered program ------------------------------------------------------------------
def _lowered(net, values):
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(values[k]) for k in net.list_arguments()}

    def loss(train):
        outs, _ = prog.eval(dict(args, **train), {}, None, True)
        return outs

    train = {k: args[k] for k in ("w1", "w2", "head", "close_gamma")}

    def step(train):
        outs, vjp = jax.vjp(loss, train)
        return vjp([jnp.ones_like(o) for o in outs])[0]

    return jax.jit(step).lower(train)


def _count(pattern, text):
    return len(re.findall(pattern, text))


def test_the_program_does_not_grow_with_the_passes():
    """The loop's program does not grow with the passes; the written-out
    graph's does.  Counted in the lowered text's ``dot_general``s: one
    pass has three matmuls forward and six backward.  The recomputed loop
    is lowered as a forward half (the earlier passes' ``while`` and the
    last pass call it) and a backward half that forms the forward again
    (the backward ``while`` and the last pass call it): under two copies
    and a forward, at 3 passes and at 12."""
    values = _values()

    def dots(net):
        return _lowered(net, values).as_text().count("dot_general")

    one_copy = dots(_looped(recompute=False))
    assert one_copy == 3 + 6 == dots(_looped(recompute=False, steps=12))
    again = dots(_looped(steps=3))
    assert again == dots(_looped(steps=12)) == dots(_looped(steps=2))
    assert one_copy < again <= 2 * one_copy + 3
    assert dots(_unrolled(steps=6)) > 1.8 * dots(_unrolled(steps=3))
    assert dots(_looped(steps=12)) < dots(_unrolled(steps=6))
    # recomputation forms the forward again: more products in the text,
    # under JAX's own name for it; the kept body has none, and one pass
    # alone is the body itself: nothing is formed again, no ``while``
    text = _lowered(_looped(recompute=True), values).as_text(debug_info=True)
    assert "rematted_computation" in text
    for net in (_looped(recompute=False), _looped(steps=1)):
        kept = _lowered(net, values).as_text(debug_info=True)
        assert "rematted_computation" not in kept
        assert "checkpoint" not in kept
    # (the one pass's rows are the graph's input: no product for theirs)
    assert dots(_looped(steps=1)) == one_copy - 1
    assert "stablehlo.while" not in _lowered(_looped(steps=1),
                                             values).as_text()


@pytest.mark.parametrize("steps", [3, 4, 12])
def test_the_compiled_program_forms_the_last_pass_once(steps):
    """The last pass stands between the two ``while``s, its forward and
    its backward half in one computation with no barrier between them,
    and the compiler merges what the backward half would form again with
    what the forward half has just computed.  That is the compiler's
    doing and not the lowered text's (which says ``rematted_computation``
    for the last pass too), so it is read off the COMPILED program (the
    CPU's here; the chip's in ``tests/tpu/test_ouro_tpu.py``), by the one
    ``tanh`` of a pass: once in the forward ``while``, ONCE for the last
    pass, once more in the backward ``while``, which forms a pass again.
    A compiler that stopped merging reads 4 here: the time of ISSUE 56
    given back, no memory."""
    values = _values()

    def compiled(net):
        text = _lowered(net, values).compile().as_text()
        return _count(r" tanh\(", text), _count(r" while\(", text)

    assert compiled(_looped(steps=steps)) == (3, 2)
    # everything kept: one copy, stacked by ``scan``
    assert compiled(_looped(recompute=False, steps=steps)) == (1, 2)


def test_at_two_passes_the_barrier_is_the_chips():
    """A ``while`` of one trip is unrolled, and the earlier pass then
    stands in one computation with the last: the loop's barrier is what
    keeps it formed again there.  It is in the lowered text; XLA's CPU
    pipeline expands barriers away before its last merge and keeps both
    passes (``jax.checkpoint`` outside a ``scan`` reads the same on the
    CPU), the chip's does not (``tests/tpu/test_ouro_tpu.py``)."""
    values = _values()
    lowered = _lowered(_looped(steps=2), values)
    assert "optimization_barrier" in lowered.as_text()
    text = lowered.compile().as_text()
    assert _count(r" while\(", text) == 0
    assert _count(r" tanh\(", text) in (2, 3)      # 3: the contract held


def _held(net, values):
    """{shape: count} of the floating-point arrays the backward pass
    holds: the leaves of the whole graph's ``jax.vjp``."""
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(values[k]) for k in net.list_arguments()}
    _, vjp = jax.vjp(lambda a: prog.eval(a, {}, None, True)[0], args)
    held = {}
    for x in jax.tree_util.tree_leaves(vjp):
        if hasattr(x, "shape") and jnp.issubdtype(x.dtype, jnp.floating):
            held[tuple(x.shape)] = held.get(tuple(x.shape), 0) + 1
    return held


@pytest.mark.parametrize("steps", [2, STEPS, 5])
def test_the_backward_pass_keeps_the_carries_only(steps):
    """What the backward pass is handed.  With ``recompute``: the carries
    the ``steps - 1`` earlier passes started from, stacked, and the last
    pass's, alone; no other activation of any pass (the last pass's are
    the compiler's to hold: the test above).  Without it every activation
    of every pass, stacked by ``scan``."""
    values = _values()
    front = steps - 1
    carry, hidden = (ROWS, WIDTH), (ROWS, 2 * WIDTH)
    logits = (ROWS, CLASSES)

    def stacked(held, n):
        return {s[1:]: c for s, c in held.items()
                if len(s) >= 2 and s[0] == n and s[1] == ROWS}

    again = _held(_looped(True, steps), values)
    assert stacked(again, front) == {carry: 1}
    assert again[carry] == 1 and hidden not in again and logits not in again
    kept = _held(_looped(False, steps), values)
    every = stacked(kept, steps)
    assert every[hidden] >= 1 and every[logits] >= 1 and every[carry] >= 3
    assert hidden not in kept and logits not in kept


# -- the trace ------------------------------------------------------------------------------
def test_a_bodys_nodes_keep_their_scopes_and_the_loop_names_the_rest():
    x = sym.Variable("x")
    with mx.AttrScope(__scope__="mixer.l0"):
        h = sym.FullyConnected(x, num_hidden=WIDTH, no_bias=True, name="fc")
    body = x + sym.Activation(h, act_type="tanh", name="act")
    loop = sym.Repeat(body, {"x": sym.Variable("data")}, STEPS, name="loop")
    net = sym.MakeLoss(sym.sum_axis(loop, axis=1))
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {"data": jnp.ones((ROWS, WIDTH)),
            "fc_weight": jnp.ones((WIDTH, WIDTH)) * 0.1}

    def step(w):
        outs, vjp = jax.vjp(lambda w: prog.eval(
            dict(args, fc_weight=w), {}, None, True)[0], w)
        return vjp([jnp.ones_like(o) for o in outs])[0]

    text = jax.jit(step).lower(args["fc_weight"]).compile().as_text()
    names = scopes.op_names_of(text)
    table = scopes.table_of(text)
    kinds = {scopes.kind_of(s) for s in table.values()}
    # the declared scope and the generic one of the body's nodes, as in a
    # graph without a loop; the loop's own only for what they leave
    assert {"mixer", "activation", "loop"} <= kinds
    assert "repeat" not in kinds
    inside = [i for i, n in names.items() if "/while/body/" in n]
    assert inside and any(table.get(i) == "mixer.l0" for i in inside)
    whiles = [i for i, n in names.items()
              if n.endswith("/while") and "loop" in n]
    assert whiles and all(table[i] == "loop" for i in whiles)
    assert scopes.resolve("jit(f)/jvp(loop)/while/body/mixer.l0/dot") \
        == "mixer.l0"
    assert scopes.resolve("jit(f)/transpose(jvp(loop))/while/body/add_any") \
        == "loop"


@pytest.mark.parametrize("recompute", [True, False])
def test_each_trace_of_the_node_records_loop_body(recompute):
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        _run(_looped(recompute), _values())
        events = mx.trace.counter_events(["loop:body"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert events
    for e in events:
        assert e["id"] == "%dx%d" % (STEPS, e["args"]["nodes"])
        assert e["args"] == {"num_steps": STEPS, "nodes": 7,
                             "carry_bytes": ROWS * WIDTH * 4,
                             "recompute": int(recompute),
                             "kept_passes": 1 if recompute else STEPS}
