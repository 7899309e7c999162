"""The one owner of the ``jax.distributed`` lifecycle.

Every process that joins a global mesh goes through :func:`initialize`
— ``tools/launch.py`` workers and :class:`~mxnet_tpu.dist.fleet.
FleetSupervisor` children via :func:`ensure_from_env` at ``import
mxnet_tpu`` time (``_distributed_boot`` delegates here), tests and
benches programmatically.  Centralizing the call is not cosmetic:

* **CPU collectives.**  A multi-process CPU backend needs a
  cross-process collectives implementation picked BEFORE the backend
  is created; without one every ``psum``/``broadcast_one_to_all``
  fails with "Multiprocess computations aren't implemented on the CPU
  backend" (the historical ``tests/test_dist`` failure mode).  The
  boot selects gloo (``MXNET_DIST_CPU_COLLECTIVES``, default
  ``gloo``; ``none`` disables) exactly once, in the right order.

* **Idempotence.**  A second initialize in one process is a RuntimeError
  from jax; the boot tolerates the "already initialized" case so
  library code can call :func:`ensure_from_env` defensively.

* **Auditability.**  The ``raw-dist-init`` lint rule flags any direct
  ``jax.distributed.initialize`` outside ``mxnet_tpu/dist/`` — the
  coordinator address, process count and rank come from ONE rendezvous
  convention instead of N ad-hoc ones.

This module must stay import-light: it is imported before any JAX
backend initialization, so nothing at module level may touch jax.
"""
from __future__ import annotations

import os

__all__ = ["initialize", "ensure_from_env", "is_initialized",
           "cpu_collectives", "boot_timeout_ms"]

_initialized = False


def is_initialized() -> bool:
    """True once THIS module initialized (or confirmed) the process
    group."""
    return _initialized


def cpu_collectives() -> str:
    """The cross-process CPU collectives implementation
    (``MXNET_DIST_CPU_COLLECTIVES``, default ``gloo``; ``none``
    disables the selection)."""
    from ..base import get_env
    return (get_env("MXNET_DIST_CPU_COLLECTIVES", "gloo") or "").strip()


def boot_timeout_ms() -> int:
    """Coordinator rendezvous timeout (``MXNET_DIST_BOOT_TIMEOUT_MS``,
    default 60000): how long a late worker waits for the coordinator
    before the job fails loudly instead of hanging."""
    from ..base import get_env
    return max(1000, get_env("MXNET_DIST_BOOT_TIMEOUT_MS", 60000, int))


def _configure_cpu_collectives() -> None:
    impl = cpu_collectives()
    if not impl or impl == "none":
        return
    import jax
    try:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    except Exception:
        # a jaxlib without the knob: TPU/GPU backends don't need it,
        # and a CPU multiprocess run will fail loudly downstream with
        # the backend's own message
        pass


def initialize(coordinator_address: str, num_processes: int,
               process_id: int) -> None:
    """Join (or confirm membership in) the jax.distributed process
    group.  Must run before any JAX backend initialization; tolerates
    a process group that is already up (the launcher and a defensive
    library call may race)."""
    global _initialized
    import jax
    _configure_cpu_collectives()
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=int(num_processes),
            process_id=int(process_id),
            initialization_timeout=max(1, boot_timeout_ms() // 1000))
    except RuntimeError as e:
        if "already" not in str(e):
            raise
    _initialized = True


def ensure_from_env() -> bool:
    """Boot from the launcher rendezvous envs (``MXNET_TPU_COORDINATOR``
    / ``_NUM_WORKERS`` / ``_WORKER_ID``) when present; returns whether
    a process group is up.  Called from ``mxnet_tpu._distributed_boot``
    at import time."""
    if _initialized:
        return True
    from ..base import get_env
    coord = get_env("MXNET_TPU_COORDINATOR")
    if coord is None:
        return False
    # lint: allow(raw-env) — rendezvous vars are a set: once the
    # coordinator is present, a missing peer var is a broken launcher
    # and must KeyError loudly, not default
    num = os.environ["MXNET_TPU_NUM_WORKERS"]
    # lint: allow(raw-env) — same rendezvous set as above
    rank = os.environ["MXNET_TPU_WORKER_ID"]
    initialize(coord, int(num), int(rank))
    return True
