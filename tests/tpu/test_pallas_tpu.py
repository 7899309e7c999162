"""Pallas kernels compiled by Mosaic (``interpret=False``) on the chip,
against the jnp twin that ships beside each.

Tier-1 (tests/test_pallas.py, tests/test_kernelsearch.py) runs the same
kernels in the Pallas interpreter on the CPU; whether Mosaic accepts
them, and whether what it builds computes the same thing, only a chip
can say.  Shapes are the ones the callers use: the paged engine's
default geometry (block 16; C=1 decode and C=32 prefill chunk) and one
real head shape, a serving FC width, a long causal attention, and a
cost-volume correlation.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu -m tpu_smoke -q

Each check runs at "highest" matmul precision on both sides, where
kernel and twin must agree closely, and at the default precision the
serving path runs at (one bf16 MXU pass on either side), where they agree
to ~1e-2 (chip_smoke.KERNEL_PRECISIONS).  The paged and FC checks are
chip_smoke.py's own kernel phase, one test per shape.
"""
import numpy as np
import pytest

from _mirror import tpu_gate
import chip_smoke
from chip_smoke import KERNEL_PRECISIONS, lowers_to_mosaic, run_at

pytestmark = [tpu_gate(), pytest.mark.tpu_smoke]


def _assert_parity(result):
    """chip_smoke's (lowered to Mosaic?, [(precision, error, bound)])."""
    mosaic, errors = result
    assert mosaic, "the dense twin was lowered, not the kernel"
    for precision, err, tol in errors:
        assert err < tol, (precision, err)


@pytest.mark.parametrize("s,c,h,d", chip_smoke.PAGED_SHAPES)
def test_paged_attention_compiled_matches_twin(s, c, h, d):
    _assert_parity(chip_smoke.paged_parity(s, c, h, d))


@pytest.mark.parametrize("out_scale", [None, 0.05])
def test_fused_fc_epilogue_compiled_matches_twin(out_scale):
    _assert_parity(chip_smoke.fc_parity(out_scale))


def test_flash_attention_compiled_matches_twin():
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention
    from mxnet_tpu.parallel.ring import attention_reference
    rng = np.random.RandomState(2)
    b, t, h, d = 1, 2048, 4, 128
    args = tuple(jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
                 for _ in range(3))

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True)

    assert lowers_to_mosaic(kernel, *args)
    want = run_at(lambda q, k, v: attention_reference(q, k, v, causal=True),
                  args, "highest")
    for precision, tol in KERNEL_PRECISIONS:
        got = run_at(kernel, args, precision)
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() < tol, (precision,
                                                np.abs(got - want).max())


def _correlation_numpy(a, b, m, is_multiply):
    """Independent numpy reference (correlation.cu semantics, stride2=1)."""
    n, c, h, w = a.shape
    d2 = 2 * m + 1
    bpad = np.pad(b, [(0, 0), (0, 0), (m, m), (m, m)])
    want = np.empty((n, d2 * d2, h, w), np.float32)
    for i in range(d2):
        for j in range(d2):
            tile = bpad[:, :, i:i + h, j:j + w]
            val = a * tile if is_multiply else np.abs(a - tile)
            want[:, i * d2 + j] = val.sum(axis=1) / c
    return want


def test_correlation_compiled_matches_twin():
    """The cost-volume shape of a flow network's correlation layer
    (PWC-Net's: max_displacement 4, stride2 1 -> 81 displacements) over
    a 1/8-resolution feature map of a 384x512 frame.  No matmul in this
    kernel, so one tolerance.  Mosaic takes most of a minute to compile
    the 81-way unrolled displacement loop at this size."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import correlation
    rng = np.random.RandomState(3)
    n, c, h, w, m = 2, 128, 48, 64, 4
    a = rng.randn(n, c, h, w).astype(np.float32)
    b = rng.randn(n, c, h, w).astype(np.float32)
    args = (jnp.asarray(a), jnp.asarray(b))

    def kernel(a, b):
        return correlation(a, b, m, 1, True)

    assert lowers_to_mosaic(kernel, *args)
    got = run_at(kernel, args, "highest")
    want = _correlation_numpy(a, b, m, True)
    assert np.abs(got - want).max() < 1e-4, np.abs(got - want).max()


@pytest.mark.parametrize("is_multiply", [True, False])
def test_correlation_op_reaches_the_kernel(is_multiply):
    """Through the op, which is how a model reaches the kernel (a small
    map: the compile is what costs)."""
    import mxnet_tpu as mx
    rng = np.random.RandomState(4)
    n, c, h, w, m = 2, 32, 24, 32, 4
    a = rng.randn(n, c, h, w).astype(np.float32)
    b = rng.randn(n, c, h, w).astype(np.float32)
    sym = mx.sym.Correlation(mx.sym.Variable("a"), mx.sym.Variable("b"),
                             kernel_size=1, max_displacement=m, stride1=1,
                             stride2=1, pad_size=m, is_multiply=is_multiply)
    exe = sym.bind(mx.tpu(0), {"a": mx.nd.array(a, ctx=mx.tpu(0)),
                               "b": mx.nd.array(b, ctx=mx.tpu(0))})
    out = exe.forward(is_train=False)[0]
    assert {d.platform for d in out._get().devices()} == {"tpu"}
    assert np.abs(out.asnumpy() - _correlation_numpy(a, b, m, is_multiply)
                  ).max() < 1e-4
