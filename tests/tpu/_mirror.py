"""Shared plumbing for the TPU mirror suites.

Reference trick being reproduced: tests/python/gpu/test_operator_gpu.py
does ``from test_operator import *`` and swaps the default context so the
whole CPU unit suite re-executes on the accelerator.  Here the swap is the
``_run_on_tpu`` autouse fixture in conftest.py; this module just makes the
CPU test modules importable and centralizes the hardware gate.
"""
import os
import sys

import pytest

_TESTS_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_TESTS_DIR, os.path.join(_TESTS_DIR, "common")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
sys.path.insert(0, os.path.dirname(_TESTS_DIR))


def tpu_gate():
    """skipif marker: the suite is opt-in (``MXNET_TPU_TESTS=1``).  Opted
    in on a machine where JAX finds no TPU, collection fails — asking
    for the chip and not getting it is an error, not a skip."""
    enabled = os.environ.get("MXNET_TPU_TESTS") == "1"
    if enabled:
        import jax
        plat = jax.devices()[0].platform
        if plat != "tpu":
            raise RuntimeError(
                "MXNET_TPU_TESTS=1 but JAX found no TPU: jax.devices() = %s"
                % (jax.devices(),))
    return pytest.mark.skipif(
        not enabled,
        reason="TPU suite is opt-in: MXNET_TPU_TESTS=1 pytest tests/tpu/")
