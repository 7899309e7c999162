"""Opt-in hardware gate for the TPU consistency suite.

tests/conftest.py (inherited here) pins jax_platforms=cpu so the main
suite never touches hardware.  This suite EXISTS to touch hardware
(reference tests/python/gpu ran on real GPUs) — but flipping the platform
mid-pytest-session would poison other tests' backends, so it only
activates when explicitly requested:

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/ -q

Without the env var every test here skips (also the behavior inside the
main `pytest tests/` run).  With it, a process that finds no TPU is an
ERROR (tpu_gate in _mirror.py), never a green run of skips.
"""
import os

ENABLED = os.environ.get("MXNET_TPU_TESTS") == "1"

if ENABLED:
    os.environ.pop("JAX_PLATFORMS", None)
    os.environ.pop("XLA_FLAGS", None)
    import jax

    jax.config.update("jax_platforms", "tpu,cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _run_on_tpu():
    """Route every test in tests/tpu/ to the chip.

    The mirror suites (test_suite_*_tpu.py) re-collect the CPU test
    functions, which resolve their device via mx.current_context(); pushing
    mx.tpu(0) on the context stack sends all of them to the TPU.  Matmul
    precision is pinned to "highest" so finite-difference gradient checks
    keep their CPU tolerances (the chip's default bf16 matmuls would not).
    """
    if not ENABLED:
        yield
        return
    import jax
    import mxnet_tpu as mx

    with jax.default_matmul_precision("highest"):
        with mx.tpu(0):
            yield
