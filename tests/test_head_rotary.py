"""``HeadNormRotary``, q's and k's head norm and rotation on the rows as
the projections write them: the kernel pair ``head_rotary_fwd`` /
``head_rotary_bwd`` under the Pallas interpreter against the plain form
(the statements the builders wrote before the op: ``RMSNorm`` and
``RotaryEmbedding`` over the ``(B, T, H, D)`` view), the choice of
lowering and its counter, ``dgamma`` against a float64 sum, the table
against the angles ``sectioned_rotary`` turns by, and the op's shapes."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import gated_norm as gn
from mxnet_tpu.ops import head_rotary as hr
from mxnet_tpu.ops.transformer import rms_norm, sectioned_rotary

SEQ = 64
EPS = 1e-6
TOLERANCE = {"float32": 4e-6, "bfloat16": 1.6e-2}
# heads of 128 lanes: q's and k's of the grouped-head cells
HEADS = {"q": 32, "k": 4}
# what is asked of the stage: (norm, the rotation's keywords or None,
# a positions input)
STAGES = {
    "norm-and-rotation": (True, dict(theta=1e6), False),
    "norm-alone": (True, None, False),
    "rotation-alone": (False, dict(theta=1e4), False),
    "period": (True, dict(theta=1e6, period=SEQ // 2), False),
    "sections": (True, dict(theta=1e7, sections=(16, 24, 24)), False),
    "sections-at-positions": (True, dict(theta=1e7, sections=(16, 24, 24)),
                              True),
    "rotation-at-positions": (False, dict(theta=1e7, sections=(16, 24, 24)),
                              True),
}


def _inputs(rows, heads, dtype, norm=True, positions=False, d=128, seed=70):
    rng = np.random.RandomState(seed)
    x, dy = (jnp.asarray(rng.standard_normal((rows, heads * d)), dtype)
             for _ in range(2))
    gamma = jnp.asarray(1 + 0.1 * rng.standard_normal(d), dtype) \
        if norm else None
    where = jnp.asarray(rng.randint(0, 4 * SEQ, (rows // SEQ, 3, SEQ)),
                        jnp.float32) if positions else None
    return x, gamma, where, dy


def _statements(x, gamma, where, d, seq_len, rotation):
    """What ``models/decoder.py`` ``gqa_attention`` built until ISSUE 70:
    ``Reshape`` to heads, ``RMSNorm`` over a head's lanes,
    ``RotaryEmbedding``; laid back as rows."""
    y = x.reshape(-1, seq_len, x.shape[1] // d, d)
    if gamma is not None:
        y = rms_norm(y, gamma, EPS)
    if rotation is not None:
        y = sectioned_rotary(y, where, **rotation)
    return y.reshape(x.shape)


def _with_cotangents(fn, x, gamma, dy):
    out, vjp = jax.vjp(fn, x, gamma)
    return (out,) + tuple(g for g in vjp(dy.astype(out.dtype))
                          if g is not None)


def _stage(rotation, where, d=128, interpret=True):
    how = dict(rotation, seq_len=SEQ) if rotation is not None else {}
    return lambda x, gamma: hr.head_norm_rotary(
        x, gamma, where, head_dim=d, eps=EPS, interpret=interpret, **how)


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("case", sorted(STAGES))
def test_kernels_match_the_plain_form(case, heads, dtype):
    """The output and the cotangents of x and gamma, two sequences."""
    norm, rotation, positions = STAGES[case]
    x, gamma, where, dy = _inputs(2 * SEQ, HEADS[heads], jnp.dtype(dtype),
                                  norm, positions)
    got = _with_cotangents(_stage(rotation, where), x, gamma, dy)
    f32 = jnp.float32
    want = _with_cotangents(
        lambda x, gamma: _statements(x, gamma, where, 128, SEQ, rotation),
        x.astype(f32), gamma.astype(f32) if norm else None, dy)
    assert len(got) == len(want) == 2 + norm
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == x.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= TOLERANCE[dtype] * np.abs(b).max()


def test_the_grid_is_row_tiles_by_lane_blocks(monkeypatch):
    """Four row tiles of two lane blocks: every step reads its rows of
    the table, gamma's sums ride the scratch through all eight.  The
    module's jits are traced once a signature: this step size has a shape
    of its own."""
    monkeypatch.setattr(gn, "STEP_NUMBERS", 64 * 1024)
    x, gamma, _, dy = _inputs(4 * SEQ, 16, jnp.float32, seed=3)
    grid = hr._blocks(x)[0]
    assert grid == (4, 2)
    rotation = dict(theta=1e4)
    got = _with_cotangents(_stage(rotation, None), x, gamma, dy)
    want = _with_cotangents(
        lambda x, gamma: _statements(x, gamma, None, 128, SEQ, rotation),
        x, gamma, dy)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= TOLERANCE["float32"] * np.abs(b).max()


# what the kernels' tiling refuses: (rows, heads, D, dtype)
REFUSED = {
    "heads-of-64": (2 * SEQ, 8, 64, jnp.bfloat16),
    "heads-of-256": (2 * SEQ, 2, 256, jnp.float32),
    "rows-of-no-tile": (24, 4, 128, jnp.float32),
    "half-precision": (2 * SEQ, 4, 128, jnp.float16),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_refused_input_runs_the_builders_statements_bit_for_bit(case):
    rows, heads, d, dtype = REFUSED[case]
    seq_len = SEQ if rows % SEQ == 0 else rows
    x, gamma, _, dy = _inputs(rows, heads, dtype, d=d)
    rotation = dict(theta=1e6)

    def stage(x, gamma, interpret=True):
        return hr.head_norm_rotary(x, gamma, head_dim=d, eps=EPS,
                                   seq_len=seq_len, interpret=interpret,
                                   **rotation)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        got = _with_cotangents(stage, x, gamma, dy)
        text = jax.export.export(
            jax.jit(lambda x, gamma: stage(x, gamma, False)),
            platforms=["tpu"])(x, gamma).mlir_module()
        events = mx.trace.counter_events(["rotary:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert "tpu_custom_call" not in text
    assert {(e["args"]["kernel"], e["args"]["plain"]) for e in events} \
        == {(0, 1)}
    assert events[0]["id"] == "%s%s/%d" % (jnp.dtype(dtype).name,
                                           [rows, heads * d], d)
    want = _with_cotangents(
        lambda x, gamma: _statements(x, gamma, None, d, seq_len, rotation),
        x, gamma, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_a_cpu_program_of_taken_rows_is_the_statements_bit_for_bit():
    """Rows the kernels take, lowered for a CPU: the plain form in both
    passes, behind the custom rule."""
    x, gamma, where, dy = _inputs(2 * SEQ, 4, jnp.bfloat16, positions=True)
    rotation = dict(theta=1e7, sections=(16, 24, 24))
    got = _with_cotangents(_stage(rotation, where, interpret=False),
                           x, gamma, dy)
    want = _with_cotangents(
        lambda x, gamma: _statements(x, gamma, where, 128, SEQ, rotation),
        x, gamma, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("case", ["order", "period", "sections",
                                  "positions"])
def test_the_table_holds_the_angles_the_statements_turn_by(case):
    """Rotating the unit pairs ``(1, 0)`` by the plain form gives ``(cos,
    sin)`` of every row's angles: the table, to the last bit."""
    rows, d = 2 * SEQ, 128
    how = {"order": dict(theta=1e4), "period": dict(theta=1e6, period=16),
           "sections": dict(theta=1e7, sections=(16, 24, 24)),
           "positions": dict(theta=1e7, sections=(16, 24, 24))}[case]
    where = _inputs(rows, 1, jnp.float32, positions=True)[2] \
        if case == "positions" else None
    ones = jnp.concatenate([jnp.ones((rows, d // 2)),
                            jnp.zeros((rows, d // 2))], axis=1)
    want = sectioned_rotary(ones.reshape(2, SEQ, 1, d), where, **how)
    got = hr.rotary_table(rows, d, SEQ, how["theta"], how.get("period", 0),
                          how.get("sections"), where)
    assert got.shape == (rows, d) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).reshape(rows, d))


def test_dgamma_is_the_float64_sum():
    """Gamma's cotangent is one number a lane summed over every row and
    head: the kernel's float32 scratch against numpy's float64, over
    eight steps, through the rotation's transpose."""
    rows, heads = 32 * SEQ, 16
    x, gamma, _, dy = _inputs(rows, heads, jnp.float32, seed=7)
    assert hr._blocks(x)[0] == (4, 2)
    theta = 1e4
    got = jax.grad(lambda gamma: jnp.vdot(hr.head_norm_rotary(
        x, gamma, head_dim=128, eps=EPS, seq_len=SEQ, theta=theta,
        interpret=True), dy))(gamma)
    x64, dy64 = (np.asarray(a, np.float64).reshape(rows, heads, 128)
                 for a in (x, dy))
    xhat = x64 / np.sqrt(np.mean(x64 * x64, axis=2, keepdims=True) + EPS)
    ang = (np.arange(rows) % SEQ)[:, None] \
        / theta ** (np.arange(64) * 2.0 / 128)
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    d1, d2 = dy64[..., :64], dy64[..., 64:]
    dn = np.concatenate([d1 * cos + d2 * sin, d2 * cos - d1 * sin], axis=2)
    want = (dn * xhat).sum(axis=(0, 1))
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=2e-5, atol=2e-5 * np.abs(want).max())


def test_op_shapes_arguments_and_what_it_refuses():
    data = mx.sym.Variable("data")
    op = mx.sym.HeadNormRotary(data, head_dim=128, seq_len=64, theta=1e6,
                               name="l1_q_norm")
    assert op.list_arguments() == ["data", "l1_q_norm_gamma"]
    args, outs, _ = op.infer_shape(data=(128, 512))
    assert args == [(128, 512), (128,)] and outs == [(128, 512)]
    turned = mx.sym.HeadNormRotary(data, head_dim=128, seq_len=64,
                                   norm=False, name="l1_q_rotary")
    assert turned.list_arguments() == ["data"]
    where = mx.sym.Variable("positions")
    placed = mx.sym.HeadNormRotary(
        data, positions=where, with_positions=True, head_dim=128,
        seq_len=64, sections=(16, 24, 24), name="l1_k_norm")
    assert placed.list_arguments() == ["data", "l1_k_norm_gamma",
                                       "positions"]
    args, outs, _ = placed.infer_shape(data=(128, 512))
    assert args == [(128, 512), (128,), (2, 3, 64)]

    def refused(shape=(128, 512), **params):
        params = dict(dict(head_dim=128, seq_len=64), **params)
        with pytest.raises(mx.MXNetError):
            mx.sym.HeadNormRotary(data, **params).infer_shape(data=shape)

    refused(shape=(2, 64, 512))               # rows, not sequences of them
    refused(shape=(128, 192))                 # no whole heads
    refused(shape=(100, 512))                 # no whole sequences
    refused(head_dim=127, shape=(128, 508))   # an odd head has no pairs
    refused(norm=False, seq_len=0)            # nothing to do
    refused(seq_len=0, period=8)              # a period of no rotation
    refused(sections=(16, 24, 24), period=8)
    refused(sections=(16, 24, 23))
    refused(with_positions=True)              # positions without sections
    # the op through an executor: the norm alone, at a head of 8 lanes
    x, gamma, _, _ = _inputs(12, 4, jnp.float32, d=8)
    exe = mx.sym.HeadNormRotary(data, head_dim=8, eps=1e-5, name="n").bind(
        mx.cpu(), {"data": mx.nd.array(np.asarray(x)),
                   "n_gamma": mx.nd.array(np.asarray(gamma))})
    np.testing.assert_allclose(
        exe.forward()[0].asnumpy(),
        np.asarray(rms_norm(x.reshape(12, 4, 8), gamma, 1e-5)).reshape(12, 32),
        rtol=1e-6)


def test_three_layers_trace_each_kernel_once_and_count_six():
    """A TPU program of three layers' q and k at the kernels' sizes holds
    the pair, one traced function a shape, every op's ``rotary:lowering``
    reads ``kernel``, and x, gamma and the table are all the backward
    pass is handed of the forward one (the rotation alone: the table)."""
    q = jax.ShapeDtypeStruct((2 * SEQ, 512), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2 * SEQ, 128), jnp.bfloat16)
    gamma = jax.ShapeDtypeStruct((128,), jnp.bfloat16)

    def three(q, k, gq, gk):
        def loss(q, k, gq, gk):
            total = 0.0
            for _ in range(3):
                q, k = (hr.head_norm_rotary(x, g, head_dim=128, eps=EPS,
                                            seq_len=SEQ, theta=1e6)
                        for x, g in ((q, gq), (k, gk)))
                total += jnp.square(q.astype(jnp.float32)).sum() \
                    + jnp.square(k.astype(jnp.float32)).sum()
            return total
        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, gq, gk)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(jax.jit(three), platforms=["tpu"])(
            q, k, gamma, gamma).mlir_module()
        chosen = mx.trace.counter_events(["rotary:lowering"], since_ns=mark)
        cpu = jax.jit(three).lower(q, k, gamma, gamma).as_text()
    finally:
        mx.trace.set_enabled(was)
    assert "head_rotary_fwd" in text and "head_rotary_bwd" in text
    # one traced function a shape (q's and k's), shared by the layers
    assert text.count("func.func private @_rotary_fwd") == 2
    assert text.count("func.func private @_rotary_bwd") == 2
    assert "tpu_custom_call" not in cpu
    assert [(e["id"], e["args"]) for e in chosen[:6]] == [
        ("bfloat16[128, 512]/128", {"kernel": 1, "plain": 0}),
        ("bfloat16[128, 128]/128", {"kernel": 1, "plain": 0})] * 3
    table = jax.ShapeDtypeStruct((2 * SEQ, 128), jnp.float32)
    how = (EPS, SEQ, 1e6, 0, ())
    for g, kept_shapes in ((gamma, [q.shape, gamma.shape, table.shape]),
                           (None, [table.shape])):
        _, kept = jax.eval_shape(
            lambda x, g, t: hr._two_lowerings_fwd(x, g, None, t, 128, how,
                                                  False), q, g, table)
        assert [a.shape for a in jax.tree.leaves(kept)] == kept_shapes
