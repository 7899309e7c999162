"""JAX's own compile machinery, as every entry point sees it: where its
persistent compilation cache lives, and how many programs it really
compiled.

``place_jax_cache()`` is called once by each entry point (chip_smoke.py,
the bench mains, the test conftests) before the first compile.  The
directory is part of JAX's cache key, so it must never move: it is what
``JAX_COMPILATION_CACHE_DIR`` says when the caller's environment sets it
(nothing is set in code then), else ``<checkout>/.jax_cache``.  Either
way the choice is exported to the environment so child processes land in
the same directory without calling anything.

``count_backend_compiles()`` counts compile *requests* that reached
JAX's backend-compile stage and, separately, how many of those the
persistent cache answered: ``count`` must be zero inside a steady
loop (a retrace is a retrace even when the disk cache absorbs it), and
``compiled`` is zero on a warm start.

``record_compile_spans()`` puts the same events on the trace ring: every
program JAX traces, lowers and hands to its backend leaves
``compile:trace``, ``compile:lower`` and ``compile:backend`` there, named
by function, whoever asked for the program (docs/observability.md).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Optional

from .. import trace as _trace
from ..base import get_env

__all__ = ["jax_cache_dir", "place_jax_cache", "CompileCounter",
           "count_backend_compiles", "record_compile_spans"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
SPAN_OF_EVENT = {TRACE_EVENT: "compile:trace", LOWER_EVENT: "compile:lower",
                 BACKEND_COMPILE_EVENT: "compile:backend"}


def jax_cache_dir(env_value: Optional[str]) -> str:
    """The directory JAX's persistent cache belongs in, given what
    ``JAX_COMPILATION_CACHE_DIR`` holds (None/empty = unset)."""
    return env_value or os.path.join(_CHECKOUT, ".jax_cache")


def place_jax_cache() -> str:
    """Point JAX's persistent compilation cache at ``jax_cache_dir`` and
    return it.  Every program is kept (the default only persists
    compiles over a second), so a second process compiles nothing."""
    import jax
    from_env = get_env("JAX_COMPILATION_CACHE_DIR")
    d = jax_cache_dir(from_env)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", d)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    if get_env("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS") is None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return d


class CompileCounter:
    """Counts JAX compile requests between start() and stop().

    ``count``: programs that reached the backend-compile stage (the
    event fires on a persistent-cache hit too); ``cache_hits``: those
    the persistent cache served; ``compiled``: the rest — real XLA
    compilations."""

    def __init__(self):
        self.count = 0
        self.cache_hits = 0
        self._active = False

    @property
    def compiled(self) -> int:
        return self.count - self.cache_hits

    def _on_duration(self, event, duration_secs, **kwargs):
        del duration_secs, kwargs
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def _on_event(self, event, **kwargs):
        del kwargs
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def start(self) -> "CompileCounter":
        from jax import monitoring
        if not self._active:
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
            monitoring.register_event_listener(self._on_event)
            self._active = True
        return self

    def stop(self) -> int:
        from jax import monitoring
        if self._active:
            monitoring.unregister_event_duration_listener(self._on_duration)
            monitoring.unregister_event_listener(self._on_event)
            self._active = False
        return self.count


@contextlib.contextmanager
def count_backend_compiles():
    """-> CompileCounter live for the block."""
    counter = CompileCounter().start()
    try:
        yield counter
    finally:
        counter.stop()


# -- compile:* spans ---------------------------------------------------------
# JAX's three durations of a program as spans of the trace ring (cat
# ``compile``), each ending when its event arrives, in the ring of the
# thread that compiled: ``compile:trace`` (Python tracing to a jaxpr),
# ``compile:lower`` (the jaxpr to its MLIR module, Mosaic kernels'
# payloads included) and ``compile:backend`` (XLA's compile, or the
# persistent cache's read and load in its place), each with ``fun`` =
# JAX's ``fun_name``.  ``compile:backend`` says which it was: ``cache`` =
# ``hit`` with ``load_s`` (the retrieval time that came with the hit) or
# ``miss``; a hit's two events arrive on the compiling thread before the
# backend event closes, and wait here for it.
_hit = threading.local()
_recording_spans = False


def _spans_on_event(event, **kwargs):
    del kwargs
    if event == CACHE_HIT_EVENT:
        _hit.load_s = 0.0


def _spans_on_duration(event, duration_secs, fun_name=None, **kwargs):
    del kwargs
    name = SPAN_OF_EVENT.get(event)
    if name is None:
        if event == CACHE_LOAD_EVENT and \
                getattr(_hit, "load_s", None) is not None:
            _hit.load_s = duration_secs
        return
    args = {"fun": fun_name}
    if event == BACKEND_COMPILE_EVENT:
        load_s = getattr(_hit, "load_s", None)
        _hit.load_s = None
        if load_s is None:
            args["cache"] = "miss"
        else:
            args.update(cache="hit", load_s=load_s)
    _trace.complete(name, time.perf_counter() - duration_secs,
                    duration_secs, cat="compile", **args)


def record_compile_spans() -> None:
    """Register the two listeners above with ``jax.monitoring``, once a
    process (``mxnet_tpu`` does at import unless ``MXNET_TRACE=0``).
    Starts no backend; with tracing switched off they record nothing."""
    global _recording_spans
    if not _recording_spans:
        from jax import monitoring
        monitoring.register_event_listener(_spans_on_event)
        monitoring.register_event_duration_secs_listener(_spans_on_duration)
        _recording_spans = True
