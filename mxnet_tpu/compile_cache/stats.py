"""Compilation observability: where cold-start time goes, per program.

One process-global ``CompileStats`` (compilation is process-global: the
jit caches and the XLA compiler are shared), fed by every ``cached_jit``
wrapper's AOT path and surfaced through
``mx.profiler.compile_report()/_str()``.

Per program name: trace+lower seconds, backend-compile seconds, the
number of compiles, and a ``steady_retraces`` counter — the number of
times a program object that had ALREADY compiled once compiled again for
a new input signature.  A nonzero steady retrace count is the silent-10x
regression (a shape/dtype wobble re-entering XLA every step) that the
tier-1 recompile guard turns into a test failure.
"""
from __future__ import annotations

from typing import Dict, Optional

from ..base import make_lock


class _ProgramStats:
    __slots__ = ("trace_lower_s", "compile_s", "compiles", "retraces")

    def __init__(self):
        self.trace_lower_s = 0.0
        self.compile_s = 0.0
        self.compiles = 0
        self.retraces = 0

    def report(self) -> dict:
        return {"trace_lower_s": self.trace_lower_s,
                "compile_s": self.compile_s, "compiles": self.compiles,
                "steady_retraces": self.retraces}


class CompileStats:
    """Aggregated per-name compile counters (thread-safe: warmup pools
    compile many programs concurrently)."""

    def __init__(self, name: str = "compile"):
        self.name = name
        self._lock = make_lock("compile_cache.stats")
        self._programs: Dict[str, _ProgramStats] = {}

    def _prog(self, name: str) -> _ProgramStats:
        ps = self._programs.get(name)
        if ps is None:
            ps = self._programs.setdefault(name, _ProgramStats())
        return ps

    # -- recording ---------------------------------------------------------
    def note_trace_lower(self, name: str, seconds: float) -> None:
        with self._lock:
            self._prog(name).trace_lower_s += seconds

    def note_compile(self, name: str, seconds: float,
                     retrace: bool = False) -> None:
        with self._lock:
            ps = self._prog(name)
            ps.compile_s += seconds
            ps.compiles += 1
            if retrace:
                ps.retraces += 1

    # -- reporting ---------------------------------------------------------
    def report(self) -> dict:
        with self._lock:
            progs = {n: p.report() for n, p in sorted(self._programs.items())}
        tot = {"programs": len(progs)}
        for field in ("trace_lower_s", "compile_s", "compiles",
                      "steady_retraces"):
            tot[field] = sum(p[field] for p in progs.values())
        return {"totals": tot, "per_program": progs}

    def report_str(self) -> str:
        r = self.report()
        t = r["totals"]
        lines = ["%s: %d programs, %d compiles (%.2fs), %d steady retraces"
                 % (self.name, t["programs"], t["compiles"], t["compile_s"],
                    t["steady_retraces"])]
        for name, p in r["per_program"].items():
            lines.append(
                "  %-40s lower %6.2fs  compile %6.2fs  x%d"
                % (name[:40], p["trace_lower_s"], p["compile_s"],
                   p["compiles"]))
        return "\n".join(lines)


_global_stats: Optional[CompileStats] = None
_stats_lock = make_lock("compile_cache.stats_registry")


def get_stats() -> CompileStats:
    global _global_stats
    with _stats_lock:
        if _global_stats is None:
            _global_stats = CompileStats()
        return _global_stats


def _reset_stats() -> None:   # test hook
    global _global_stats
    with _stats_lock:
        _global_stats = None
