"""``CausalConv1D`` with its activation: the kernel pair
``causal_conv_fwd`` / ``causal_conv_bwd`` under the Pallas interpreter
against the plain ``causal_conv1d`` + SiLU, the lanes taken out of a
fused projection, the choice of lowering and its counter, and the
Qwen3-Next mixer built on it against the chain it replaced."""
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import causal_conv as cc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (data, lanes, numbers a step): contiguous channels in one step; a key
# head's q, k, v out of two groups of 768 with the z lanes behind them;
# the same over three row tiles (the carried rows cross two borders,
# both ways); a batch of two over two tiles (the carries start anew a
# sequence); the deepest step, 4096 rows of 128 lanes in 32 passes
TAKEN = {
    "contiguous": ((2, 256, 512), None, None),
    "out-of-a-wider-axis": ((1, 256, 2, 768), (128, 128, 256), None),
    "three-row-tiles": ((1, 384, 2, 768), (128, 128, 256), 0),
    "batch-of-two-tiles": ((2, 512, 256), None, 256 * 256),
    "deepest-step": ((1, 4096, 1, 384), (128, 128), None),
}
TOLERANCE = {"float32": 4e-6, "bfloat16": 1.6e-2}


def _inputs(shape, lanes, dtype, taps=4):
    rng = np.random.RandomState(53)
    channels = shape[2] * sum(lanes) if lanes else shape[2]
    rest = shape[2] * (shape[3] - sum(lanes)) if lanes else 0
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(0.5 * rng.standard_normal((channels, taps)), dtype)
    cts = [jnp.asarray(rng.standard_normal(shape[:2] + (n,)), dtype)
           for n in (channels, rest) if n]
    return x, w, cts


def _oracle(x, w, lanes):
    """``causal_conv1d`` + SiLU in float32, written out: the lanes cut,
    side by side part by part, and the rest as it is."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if lanes is None:
        return [jax.nn.silu(cc.causal_conv1d(x, w))]
    b, t = x.shape[:2]
    ends = np.cumsum((0,) + tuple(lanes))
    taken = jnp.concatenate([x[..., lo:hi].reshape(b, t, -1)
                             for lo, hi in zip(ends, ends[1:])], axis=2)
    return [jax.nn.silu(cc.causal_conv1d(taken, w)),
            x[..., ends[-1]:].reshape(b, t, -1)]


def _with_cotangents(fn, x, w, cts):
    out, vjp = jax.vjp(fn, x, w)
    return list(out) + list(vjp([c.astype(o.dtype)
                                 for c, o in zip(cts, out)]))


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("case", sorted(TAKEN))
def test_kernels_match_the_plain_form(case, dtype, monkeypatch):
    """Forward, and the cotangents of x (through the lane selection and
    the rest's hand-through) and of w."""
    shape, lanes, numbers = TAKEN[case]
    if numbers is not None:
        # the module's jitted kernels are traced once a signature: a case
        # with its own step has a shape of its own
        monkeypatch.setattr(cc, "STEP_NUMBERS", numbers)
    x, w, cts = _inputs(shape, lanes, jnp.dtype(dtype))
    x4 = x.reshape(x.shape[:2] + (-1, x.shape[-1]))
    assert cc._kernel_takes(x4, w, lanes or (shape[2],), "silu")
    tiles = {"three-row-tiles": 3, "batch-of-two-tiles": 2}.get(case, 1)
    assert shape[1] // cc._tiling(x4, lanes or (shape[2],))[0] == tiles
    got = _with_cotangents(
        lambda x, w: cc.causal_conv(x, w, "silu", lanes, interpret=True),
        x, w, cts)
    want = _with_cotangents(lambda x, w: _oracle(x, w, lanes), x, w, cts)
    assert len(got) == len(want) == (4 if lanes else 3)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == x.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= TOLERANCE[dtype] * np.abs(b).max()


# what the kernels' tiling refuses, and what they do not compute
REFUSED = {
    "rows-of-no-tile": ((1, 70, 256), None, "silu"),
    "rows-of-no-pass": ((1, 192, 256), None, "silu"),
    "lanes-of-no-block": ((1, 128, 192), None, "silu"),
    "a-part-of-no-block": ((1, 128, 2, 384), (64, 192), "silu"),
    "no-activation": ((1, 128, 256), None, None),
    "another-activation": ((1, 128, 256), None, "relu"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_input_runs_the_plain_form(case):
    shape, lanes, act = REFUSED[case]
    x, w, cts = _inputs(shape, lanes, jnp.float32)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        got = _with_cotangents(
            lambda x, w: cc.causal_conv(x, w, act, lanes, interpret=True),
            x, w, cts)
        text = jax.export.export(jax.jit(
            lambda x, w: cc.causal_conv(x, w, act, lanes)),
            platforms=["tpu"])(x, w).mlir_module()
        events = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert "tpu_custom_call" not in text
    assert {(e["args"]["kernel"], e["args"]["plain"]) for e in events} \
        == {(0, 1)}
    groups = shape[2] if lanes else 1
    assert events[0]["id"] == "float32%s/%d" % (
        [shape[0], shape[1], int(np.prod(shape[2:]))],
        groups * sum(lanes or shape[2:]))
    acts = {None: lambda y: y, "silu": jax.nn.silu, "relu": jax.nn.relu}

    def oracle(x, w):
        out = _oracle(x, w, lanes)
        pre = cc.causal_conv1d(
            x if lanes is None else cc._taken(x, lanes), w)
        return [acts[act](pre)] + out[1:]

    for a, b in zip(got, _with_cotangents(oracle, x, w, cts)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_op_shapes_outputs_and_counter():
    data = mx.sym.Variable("data")
    plain = mx.sym.CausalConv1D(data, kernel=3, name="c")
    args, outs, _ = plain.infer_shape(data=(2, 64, 256))
    assert dict(zip(plain.list_arguments(), args)) == {
        "data": (2, 64, 256), "c_weight": (256, 3)}
    assert outs == [(2, 64, 256)]
    # an unset act_type and lanes are not serialized: a symbol written
    # before the op had them reads back the same
    assert "act_type" not in plain.tojson() and "lanes" not in plain.tojson()
    taken = mx.sym.CausalConv1D(data, act_type="silu", lanes=(128, 256),
                                name="c")
    assert taken.list_outputs() == ["c_output", "c_rest"]
    args, outs, _ = taken.infer_shape(data=(2, 64, 3, 512))
    assert args[1] == (3 * 384, 4)
    assert outs == [(2, 64, 3 * 384), (2, 64, 3 * 128)]
    with pytest.raises(mx.MXNetError):
        taken.infer_shape(data=(2, 64, 1536))
    with pytest.raises(mx.MXNetError):
        plain.infer_shape(data=(2, 64, 3, 512))
    with pytest.raises(mx.MXNetError):
        mx.sym.CausalConv1D(data, lanes=(512, 128)).infer_shape(
            data=(2, 64, 3, 512))


def test_three_layers_trace_each_kernel_once_and_count_three():
    """A TPU program of three convolutions at the kernels' sizes holds
    the pair, every op's ``conv:lowering`` reads ``kernel``, and x is
    all the backward pass is handed of the forward one."""
    x = jax.ShapeDtypeStruct((1, 128, 2, 384), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((512, 4), jnp.float32)

    def three(x, w):
        def loss(x, w):
            total = 0.0
            for _ in range(3):
                y, rest = cc.causal_conv(x, w.astype(x.dtype), "silu",
                                         (128, 128))
                total += jnp.square(y.astype(jnp.float32)).sum() \
                    + rest.astype(jnp.float32).sum()
            return total
        return jax.grad(loss, argnums=(0, 1))(x, w)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(jax.jit(three), platforms=["tpu"])(
            x, w).mlir_module()
        chosen = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert "causal_conv_fwd" in text and "causal_conv_bwd" in text
    assert [(e["id"], e["args"]) for e in chosen] == [
        ("bfloat16[1, 128, 768]/512", {"kernel": 1, "plain": 0})] * 3
    _, kept = jax.eval_shape(
        lambda x, w: cc._two_lowerings_fwd(x, w, (128, 128), False),
        x, jax.ShapeDtypeStruct(w.shape, x.dtype))
    assert [k.shape for k in kept] == [x.shape, w.shape]


def _replica():
    spec = importlib.util.spec_from_file_location(
        "gdn_block_copies", os.path.join(ROOT, "tools",
                                         "gdn_block_copies.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


PARAMS = ("qkvz", "conv", "ba", "a_log", "dt_bias", "norm", "o")


@pytest.mark.parametrize("param", PARAMS + ("rows", "output"))
def test_the_mixer_is_the_chain_it_replaced(param, monkeypatch):
    """A Gated DeltaNet mixer at small widths (``tools/
    gdn_block_copies.py``'s replica of the builder's block, whose
    ``chain`` form is the block as it was built before the op took the
    projection where it lies): the output and the gradient of every
    parameter and of the rows."""
    tool = _replica()
    for name, value in dict(HIDDEN=64, HK=2, HV=4, D=16, GROUP=2).items():
        monkeypatch.setattr(tool, name, value)
    rng = np.random.RandomState(7)
    shapes = tool.param_shapes()
    assert tuple(shapes) == PARAMS
    params = {n: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
              for n, s in shapes.items()}
    rows, dy = (jnp.asarray(rng.standard_normal((128, 64)), jnp.float32)
                for _ in range(2))
    sides = {}
    for form in ("chain", "op"):
        y, dparams, drows = tool.step(form, params, rows, dy,
                                      dtype=jnp.float32)
        sides[form] = dict(dparams, rows=drows, output=y)
    a, b = sides["op"][param], sides["chain"][param]
    assert np.abs(np.asarray(b)).max() > 0
    np.testing.assert_allclose(a, b, rtol=2e-4,
                               atol=2e-5 * np.abs(np.asarray(b)).max())


# The gated form (ISSUE 61): (data, numbers a step).  One tile; three
# row tiles (the carried rows cross two borders, both ways); a batch of
# two over two tiles; a step of two passes (the ``fori_loop``) over two
# tiles; a short sequence of one short pass
GATED = {
    "one-tile": ((1, 128, 3 * 256), None),
    "three-row-tiles": ((1, 384, 3 * 128), 128 * 128),
    "batch-of-two-tiles": ((2, 256, 3 * 256), 128 * 256),
    "two-passes-a-tile": ((1, 512, 3 * 128), 256 * 128),
    "a-short-pass": ((2, 48, 3 * 128), None),
}


def _gated_inputs(shape, dtype, taps=3):
    rng = np.random.RandomState(61)
    c = shape[2] // 3
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(0.5 * rng.standard_normal((c, taps)), dtype)
    return x, w, [jnp.asarray(rng.standard_normal(shape[:2] + (c,)), dtype)]


def _gated_statement(x, w):
    """``C * conv(B * u)`` as one grouped ``lax.conv_general_dilated``
    over a left-padded sequence, in float32."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    c, taps = w.shape
    gate_in, gate_out, u = x[..., :c], x[..., c:2 * c], x[..., 2 * c:]
    conv = jax.lax.conv_general_dilated(
        gate_in * u, w.T[:, None, :], window_strides=(1,),
        padding=[(taps - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c, precision="highest")
    return [gate_out * conv]


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("case", sorted(GATED))
def test_gated_kernels_and_plain_form_match_the_statement(case, dtype,
                                                          monkeypatch):
    """Forward and the cotangents of the data (both gates and the
    convolved third) and of the taps: the plain form against the direct
    statement, the kernels under the interpreter against both."""
    shape, numbers = GATED[case]
    if numbers is not None:
        monkeypatch.setattr(cc, "STEP_NUMBERS", numbers)
    x, w, cts = _gated_inputs(shape, jnp.dtype(dtype))
    c = shape[2] // 3
    tiles = {"three-row-tiles": 3, "batch-of-two-tiles": 2,
             "two-passes-a-tile": 2}.get(case, 1)
    assert shape[1] // cc._gated_rows(shape[1], c) == tiles
    want = _with_cotangents(_gated_statement, x, w, cts)
    plain = _with_cotangents(lambda x, w: [cc._plain_gated(x, w)], x, w, cts)
    kernels = _with_cotangents(
        lambda x, w: [cc.gated_conv(x, w, interpret=True)], x, w, cts)
    assert len(want) == len(plain) == len(kernels) == 3
    for got in (plain, kernels):
        for a, b in zip(got, want):
            assert a.shape == b.shape and a.dtype == x.dtype
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(a - b).max() <= 2 * TOLERANCE[dtype] * np.abs(b).max()


@pytest.mark.parametrize("extra, shape", [
    (dict(act_type="silu"), (2, 256, 3 * 128)),
    (dict(lanes=(128,)), (2, 256, 3, 128)),
    (dict(), (2, 256, 128))])
def test_the_gates_stand_alone(extra, shape):
    """No activation behind the gates and no lanes to take; data in
    whole thirds."""
    op = mx.sym.CausalConv1D(mx.sym.Variable("data"), kernel=3, gated=True,
                             **extra)
    with pytest.raises(mx.MXNetError):
        op.infer_shape(data=shape)
    with pytest.raises(ValueError):
        cc.gated_conv(jnp.zeros((1, 128, 128)), jnp.zeros((128, 3)))


def test_gated_op_shapes_counter_and_the_tpu_program():
    data = mx.sym.Variable("data")
    op = mx.sym.CausalConv1D(data, kernel=3, gated=True, name="c")
    args, outs, _ = op.infer_shape(data=(2, 256, 3 * 128))
    assert args[1] == (128, 3) and outs == [(2, 256, 128)]
    with pytest.raises(mx.MXNetError):
        op.infer_shape(data=(2, 256, 128))
    # an unset ``gated`` is not serialized: the SiLU form's symbols stand
    assert "gated" not in mx.sym.CausalConv1D(data, kernel=4).tojson()
    x = jax.ShapeDtypeStruct((1, 256, 3 * 128), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((128, 3), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((1, 256, 3 * 64), jnp.bfloat16)

    def grads(x, w):
        return jax.grad(lambda x, w: jnp.square(cc.gated_conv(
            x, w).astype(jnp.float32)).sum(), (0, 1))(x, w)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(jax.jit(grads), platforms=["tpu"])(
            x, w).mlir_module()
        refused = jax.export.export(jax.jit(grads), platforms=["tpu"])(
            narrow, jax.ShapeDtypeStruct((64, 3), jnp.bfloat16)).mlir_module()
        chosen = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    # the backward pass is the kernel, the forward XLA's own
    assert "gated_conv_bwd" in text and "gated_conv_fwd" not in text
    assert "causal_conv_fwd" not in text
    assert "tpu_custom_call" not in refused
    assert [(e["id"], e["args"]) for e in chosen] == [
        ("bfloat16[1, 256, 384]/gated128", {"kernel": 1, "plain": 0}),
        ("bfloat16[1, 256, 192]/gated64", {"kernel": 0, "plain": 1})]
    _, kept = jax.eval_shape(
        lambda x, w: cc._gated_lowerings_fwd(x, w, False), x, w)
    assert [k.shape for k in kept] == [x.shape, w.shape]
