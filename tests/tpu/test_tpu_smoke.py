"""tpu_smoke tier: ONE representative test per mirror subsystem.

The full mirror suite is ~290 tests.  This file re-collects a single
fast, load-bearing test from each mirrored subsystem so a bounded
on-chip gate exists:

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu -m tpu_smoke -q

(tier policy in docs/build.md; the tier also holds the compiled Pallas
kernel checks of test_pallas_tpu.py.)
"""
import pytest

from _mirror import tpu_gate

pytestmark = [tpu_gate(), pytest.mark.tpu_smoke]

# one per subsystem: a single fast, load-bearing test per mirror file
# (parametrized originals are wrapped down to one case to stay bounded)
from test_ndarray import test_ndarray_elementwise            # noqa: F401,E402
from test_operator import test_elementwise_sum               # noqa: F401,E402
from test_executor import test_head_gradient                 # noqa: F401,E402
from test_io import test_NDArrayIter                         # noqa: F401,E402
from test_metric_init import test_accuracy_and_topk          # noqa: F401,E402
from test_models import test_mlp_shapes                      # noqa: F401,E402
from test_module import test_module_predict_and_params       # noqa: F401,E402
from test_optimizer import test_sgd_plain_and_momentum       # noqa: F401,E402
from test_random import test_seed_determinism                # noqa: F401,E402
from test_rnn_op import test_rnn_op_state_outputs            # noqa: F401,E402
from jax_cache import jax_cache_dir                          # noqa: F401,E402


def test_smoke_unary_grad():
    """One FD gradient check on-chip (the full 95-case suite is nightly)."""
    import test_operator_grad as g
    g.test_unary_grad("exp")


def test_smoke_fused_matches_classic():
    """One fused-vs-classic trajectory parity config on-chip."""
    import numpy as np
    from test_fused import _train
    _, pf = _train(True, num_epoch=1)
    _, pc = _train(False, num_epoch=1)
    for k in pf:
        assert np.abs(pf[k] - pc[k]).max() < 1e-4, k


def test_smoke_warmed_serve_grid_restarts_from_the_persistent_cache(
        jax_cache_dir, tmp_path):                            # noqa: F811
    """A ServeEngine bound to the chip (``dev_type="tpu"`` — the engine's
    default is the host): every bucket warmed and dispatched through the
    raw ``LoadedExecutable.execute`` path; then a second engine whose
    grid is read from JAX's persistent cache alone — compile requests,
    none compiled, same answers as the first and as a host-bound
    engine."""
    import numpy as np
    from compile_guard import count_backend_compiles
    import test_compile_cache as t
    prefix, X = t._save_pair(tmp_path)
    eng1 = t._engine(prefix, dev_type="tpu")
    try:
        want = eng1.predict(X[0], timeout=60)
    finally:
        eng1.close()
    with count_backend_compiles() as c:
        eng2 = t._engine(prefix, dev_type="tpu")
    try:
        assert c.count > 0 and c.compiled == 0, \
            "warm serve-grid construction compiled %d of %d programs" \
            % (c.compiled, c.count)
        got = eng2.predict(X[0], timeout=60)
    finally:
        eng2.close()
    assert np.allclose(got, want, atol=1e-6)
    host = t._engine(prefix)                     # dev_type="cpu"
    try:
        ref = host.predict(X[0], timeout=60)
    finally:
        host.close()
    assert np.allclose(got, ref, atol=1e-4)
