"""Device context. Reference: include/mxnet/base.h:90-175 (Context), python/mxnet/context.py.

TPU-native design: ``Context`` is a (device_type, device_id) key exactly like the
reference, but resolves to a ``jax.Device``.  ``mx.tpu()`` is first-class.  The
reference's fake-device trick (distinct cpu dev_ids as independent devices,
tests/python/unittest/test_multi_device_exec.py:35) maps to JAX host platform
devices created with --xla_force_host_platform_device_count, so multi-device
tests run without TPU hardware.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context"]


class Context:
    """Device context (device_type, device_id).

    Mirrors reference Context semantics: usable as a with-statement scope
    (python/mxnet/context.py), hashable, comparable.  ``gpu`` is accepted for
    script compatibility (north star: train_imagenet.py --gpus -> --tpus) and
    resolves to the accelerator the process has.
    """

    # reference include/mxnet/base.h:93-99 device type enum
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # ---- TPU-native: resolve to a jax.Device ------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve this context to a concrete jax.Device.

        cpu -> host platform device[device_id] (fake-device trick: ids
        wrap over the host devices that exist); tpu/gpu -> local
        accelerator device[device_id], and an MXNetError when the
        process has no accelerator or fewer than ``device_id + 1`` — a
        run that asked for the chip never lands on the host silently.
        """
        dt = self.device_type
        if dt in ("cpu", "cpu_pinned"):
            devs = jax.local_devices(backend="cpu")
            return devs[self.device_id % len(devs)]
        # local devices only — in multi-process runs jax.devices()
        # includes remote chips
        devs = jax.local_devices()
        if devs[0].platform == "cpu":
            raise MXNetError(
                "%s asks for an accelerator but this process has none: "
                "jax.local_devices() = %s" % (self, devs))
        if not 0 <= self.device_id < len(devs):
            raise MXNetError(
                "%s is out of range: this process has %d %s device(s): %s"
                % (self, len(devs), devs[0].platform, devs))
        return devs[self.device_id]

    @property
    def platform(self) -> str:
        return self.jax_device().platform


def cpu(device_id: int = 0) -> Context:
    """Return a CPU context (reference python/mxnet/context.py:84)."""
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    """Pinned-memory CPU context; on TPU builds identical to cpu()."""
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accepted for compatibility; resolves to the accelerator (TPU) device."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """Return a TPU context — first-class (north star: BASELINE.json)."""
    return Context("tpu", device_id)


def current_context() -> Context:
    """Return the current context in the with-statement stack (default cpu(0))."""
    cur = getattr(Context._default_ctx, "value", None)
    if cur is None:
        default = tpu(0) if _has_accelerator() else cpu(0)
        Context._default_ctx.value = default
        return default
    return cur


def _has_accelerator() -> bool:
    return jax.local_devices()[0].platform != "cpu"
