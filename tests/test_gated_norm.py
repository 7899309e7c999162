"""``GatedRMSNorm``, a head's RMSNorm times its gate on the rows as the
delta-rule kernels write them: the kernel pair ``gated_norm_fwd`` /
``gated_norm_bwd`` under the Pallas interpreter against the plain form
(the statements the builders wrote before the op), the choice of
lowering and its counter, ``dgamma`` against a float64 sum, and the op's
shapes."""
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import gated_norm as gn
from mxnet_tpu.ops.transformer import rms_norm

# (data, numbers a step): four heads in one step; the cells' row width
# over a batch of two (four lane blocks of two row tiles); four row
# tiles of one lane block; two lane blocks of two row tiles each, the
# rows flat already
TAKEN = {
    "four-heads": ((1, 256, 4 * 128), None),
    "batch-of-two-cell-rows": ((2, 512, 32 * 128), None),
    "four-row-tiles": ((1, 256, 4 * 128), 64 * 512),
    "flat-rows-four-steps": ((128, 16 * 128), 64 * 1024),
}
TOLERANCE = {"float32": 4e-6, "bfloat16": 1.6e-2}
EPS = 1e-6


def _inputs(shape, dtype, d=128, seed=68):
    rng = np.random.RandomState(seed)
    x, gate, dy = (jnp.asarray(rng.standard_normal(shape), dtype)
                   for _ in range(3))
    gamma = jnp.asarray(1 + 0.1 * rng.standard_normal(d), dtype)
    return x, gamma, gate, dy


def _statements(x, gamma, gate, act):
    """What ``models/qwen3_next.py`` and ``models/kimi_linear.py`` built
    until ISSUE 68: ``RMSNorm`` and ``Activation`` over ``(rows * H, D)``,
    their product laid back as rows."""
    d = gamma.shape[0]
    acts = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}
    y = rms_norm(x.reshape(-1, d), gamma, EPS) * acts[act](
        gate.reshape(-1, d))
    return y.reshape(x.shape)


def _with_cotangents(fn, x, gamma, gate, dy):
    out, vjp = jax.vjp(fn, x, gamma, gate)
    return (out,) + vjp(dy.astype(out.dtype))


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("act", gn.GATES)
@pytest.mark.parametrize("case", sorted(TAKEN))
def test_kernels_match_the_plain_form(case, act, dtype, monkeypatch):
    """The output and the cotangents of x, gamma and the gate."""
    shape, numbers = TAKEN[case]
    if numbers is not None:
        # the module's jitted kernels are traced once a signature: a case
        # with its own step has a shape of its own
        monkeypatch.setattr(gn, "STEP_NUMBERS", numbers)
    x, gamma, gate, dy = _inputs(shape, jnp.dtype(dtype))
    rows, block = gn._tiling(x, 128)
    steps = {"batch-of-two-cell-rows": 8, "four-row-tiles": 4,
             "flat-rows-four-steps": 4}.get(case, 1)
    assert (x.size // rows // block) == steps
    got = _with_cotangents(
        lambda x, gamma, gate: gn.gated_rms_norm(x, gamma, gate, EPS, act,
                                                 interpret=True),
        x, gamma, gate, dy)
    f32 = jnp.float32
    want = _with_cotangents(
        lambda x, gamma, gate: _statements(x, gamma, gate, act),
        x.astype(f32), gamma.astype(f32), gate.astype(f32), dy)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == x.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= TOLERANCE[dtype] * np.abs(b).max()


@pytest.mark.parametrize("act", gn.GATES)
def test_the_plain_form_is_the_builders_statements_bit_for_bit(act):
    x, gamma, gate, dy = _inputs((2, 24, 4 * 8), jnp.bfloat16, d=8)
    got = _with_cotangents(
        lambda x, gamma, gate: gn.gated_rms_norm(x, gamma, gate, EPS, act),
        x, gamma, gate, dy)
    want = _with_cotangents(
        lambda x, gamma, gate: _statements(x, gamma, gate, act),
        x, gamma, gate, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# what the kernels' tiling refuses
REFUSED = {
    "heads-of-64": ((1, 256, 8 * 64), 64, jnp.float32),
    "rows-of-no-tile": ((1, 72, 4 * 128), 128, jnp.float32),
    "one-odd-row": ((3, 7, 2 * 128), 128, jnp.bfloat16),
    "half-precision": ((1, 256, 4 * 128), 128, jnp.float16),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_input_runs_the_plain_form(case):
    shape, d, dtype = REFUSED[case]
    x, gamma, gate, dy = _inputs(shape, dtype, d=d)

    def stage(x, gamma, gate):
        return gn.gated_rms_norm(x, gamma, gate, EPS, "silu", interpret=True)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        got = _with_cotangents(stage, x, gamma, gate, dy)
        text = jax.export.export(
            jax.jit(lambda x, gamma, gate: gn.gated_rms_norm(
                x, gamma, gate, EPS)), platforms=["tpu"])(
                    x, gamma, gate).mlir_module()
        events = mx.trace.counter_events(["norm:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert "tpu_custom_call" not in text
    assert {(e["args"]["kernel"], e["args"]["plain"]) for e in events} \
        == {(0, 1)}
    assert events[0]["id"] == "%s%s/%d" % (jnp.dtype(dtype).name,
                                           list(shape), d)
    want = _with_cotangents(
        lambda x, gamma, gate: _statements(x, gamma, gate, "silu"),
        x, gamma, gate, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("act", gn.GATES)
def test_dgamma_is_the_float64_sum(act):
    """Gamma's cotangent is one number a lane summed over every row and
    head: the kernel's float32 scratch against numpy's float64, over
    eight steps."""
    shape = (2, 1024, 16 * 128)
    x, gamma, gate, dy = _inputs(shape, jnp.float32, seed=7)
    rows, block = gn._tiling(x, 128)
    assert x.size // rows // block == 8
    got = jax.grad(
        lambda gamma: jnp.vdot(gn.gated_rms_norm(
            x, gamma, gate, EPS, act, interpret=True), dy))(gamma)
    x64, g64, dy64 = (np.asarray(a, np.float64).reshape(-1, 128)
                      for a in (x, gate, dy))
    xhat = x64 / np.sqrt(np.mean(x64 * x64, axis=1, keepdims=True) + EPS)
    sig = 1.0 / (1.0 + np.exp(-g64))
    want = (dy64 * (g64 * sig if act == "silu" else sig) * xhat).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=2e-5, atol=2e-5 * np.abs(want).max())


def test_op_shapes_arguments_and_what_it_refuses():
    data, gate = mx.sym.Variable("data"), mx.sym.Variable("gate")
    op = mx.sym.GatedRMSNorm(data, gate=gate, head_dim=128, eps=1e-6,
                             act_type="sigmoid", name="l1_o_norm")
    assert op.list_arguments() == ["data", "l1_o_norm_gamma", "gate"]
    for shape in ((2, 64, 512), (128, 512)):
        args, outs, _ = op.infer_shape(data=shape)
        assert args == [shape, (128,), shape] and outs == [shape]
    args, outs, _ = op.infer_shape(gate=(2, 64, 512))
    assert args[0] == (2, 64, 512) and outs == [(2, 64, 512)]
    with pytest.raises(mx.MXNetError):
        op.infer_shape(data=(2, 64, 192))
    with pytest.raises(mx.MXNetError):
        op.infer_shape(data=(2, 64, 512), gate=(2, 64, 256))
    with pytest.raises(Exception):
        mx.sym.GatedRMSNorm(data, gate=gate, head_dim=128, act_type="relu")
    with pytest.raises(ValueError):
        gn.gated_rms_norm(jnp.zeros((16, 128)), jnp.ones((128,)),
                          jnp.zeros((16, 128)), EPS, "tanh")
    # the default gate is SiLU
    silu = mx.sym.GatedRMSNorm(data, gate=gate, head_dim=8, name="n")
    x, gamma, z, _ = _inputs((2, 6, 16), jnp.float32, d=8)
    exe = silu.bind(mx.cpu(), {"data": mx.nd.array(np.asarray(x)),
                               "n_gamma": mx.nd.array(np.asarray(gamma)),
                               "gate": mx.nd.array(np.asarray(z))})
    np.testing.assert_allclose(
        exe.forward()[0].asnumpy(),
        np.asarray(gn._plain(x, z, gamma, 1e-5, "silu")), rtol=1e-6)


def test_three_layers_trace_each_kernel_once_and_count_three():
    """A TPU program of three stages at the kernels' sizes holds the
    pair, every op's ``norm:lowering`` reads ``kernel``, and x, the gate
    and gamma are all the backward pass is handed of the forward one."""
    x = jax.ShapeDtypeStruct((1, 64, 384), jnp.bfloat16)
    gamma = jax.ShapeDtypeStruct((128,), jnp.bfloat16)

    def three(x, gamma, gate):
        def loss(x, gamma, gate):
            total = 0.0
            for _ in range(3):
                x = gn.gated_rms_norm(x, gamma, gate, EPS, "sigmoid")
                total += jnp.square(x.astype(jnp.float32)).sum()
            return total
        return jax.grad(loss, argnums=(0, 1, 2))(x, gamma, gate)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(jax.jit(three), platforms=["tpu"])(
            x, gamma, x).mlir_module()
        chosen = mx.trace.counter_events(["norm:lowering"], since_ns=mark)
        cpu = jax.jit(three).lower(x, gamma, x).as_text()
    finally:
        mx.trace.set_enabled(was)
    assert "gated_norm_fwd" in text and "gated_norm_bwd" in text
    assert text.count("func.func private @_norm_fwd") == 1
    assert text.count("func.func private @_norm_bwd") == 1
    assert "tpu_custom_call" not in cpu
    assert [(e["id"], e["args"]) for e in chosen[:3]] == [
        ("bfloat16[1, 64, 384]/128", {"kernel": 1, "plain": 0})] * 3
    _, kept = jax.eval_shape(
        lambda x, gate, gamma: gn._two_lowerings_fwd(
            x, gate, gamma, EPS, "sigmoid", False), x, x, gamma)
    assert [k.shape for k in kept] == [x.shape, x.shape, gamma.shape]


PARAMS = ("qkvz", "conv", "ba", "a_log", "dt_bias", "norm", "o")


@pytest.mark.parametrize("param", PARAMS + ("rows", "output"))
def test_the_mixer_with_the_op_is_the_mixer_it_replaced(param, monkeypatch):
    """A Gated DeltaNet mixer at small widths (``tools/
    gdn_block_copies.py``'s replica of the builder's block): its ``norm``
    form, the stage as the op on the rows the rule writes and the z lanes
    as the convolution hands them on, against its ``op`` form, the three
    statements over ``(rows * heads, D)``: the output and the gradient
    of every parameter and of the rows."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "gdn_block_copies", os.path.join(root, "tools",
                                         "gdn_block_copies.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.FORMS == ("chain", "op", "norm")
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
    for form in ("op", "norm"):
        y, dparams, drows = tool.step(form, params, rows, dy,
                                      dtype=jnp.float32)
        sides[form] = dict(dparams, rows=drows, output=y)
    a, b = sides["norm"][param], sides["op"][param]
    assert np.abs(np.asarray(b)).max() > 0
    np.testing.assert_allclose(a, b, rtol=2e-4,
                               atol=2e-5 * np.abs(np.asarray(b)).max())
