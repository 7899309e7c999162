"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's fake-device trick (tests/python/unittest/
test_multi_device_exec.py:35 uses distinct cpu dev_ids as devices): we force
the JAX host platform to expose 8 CPU devices so multi-device / sharding
tests run without TPU hardware.

Must run BEFORE jax is imported anywhere: sets JAX_PLATFORMS=cpu so no
test process asks for a chip.
"""
import os
import sys

# tier-1 runs with the lock-order recorder armed: every base.make_lock
# in the serve/feed/checkpoint/compile_cache thread soup records the
# acquisition graph, and mxnet_tpu.analysis.pytest_plugin fails any
# module that closes an order cycle (or leaks threads/processes).
# Must be set BEFORE mxnet_tpu imports — module-level locks are created
# at import time.
os.environ.setdefault("MXNET_LOCK_CHECK", "1")

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache: the suite (and its many subprocess
# tests) recompiles the same programs — MLP fits, ResNet blocks, glue
# gates — every run; caching them across processes and runs hides that.
# place_jax_cache() exports its choice, so SUBPROCESS tests inherit it.
# Only compiles over half a second are worth a disk write here.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
os.environ.setdefault("JAX_PERSISTENT_CACHE_ENABLE_XLA_CACHES", "all")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from mxnet_tpu.compile_cache import place_jax_cache  # noqa: E402

place_jax_cache()


# per-module thread/child-process leak guard + lock-order cycle check
# (importing the fixture registers it; pytest_plugins in a non-rootdir
# conftest is rejected by pytest >= 8)
from mxnet_tpu.analysis.pytest_plugin import (  # noqa: E402,F401
    _mxnet_analysis_guard)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running example integration test")
    config.addinivalue_line(
        "markers", "tpu_smoke: bounded on-chip tier — one representative "
        "test per TPU mirror subsystem (tests/tpu/test_tpu_smoke.py)")
