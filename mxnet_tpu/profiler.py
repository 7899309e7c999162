"""Profiler: step traces and scoped annotations.

Reference era had no timeline profiler (SURVEY §5.1: Monitor + debug_str +
MXNET_ENGINE_INFO were the tools; later MXNet grew mx.profiler).  The
TPU-native build completes the observability story by exposing XLA's real
profiler through the mx surface:

    mx.profiler.profiler_set_config(filename="/tmp/trace")
    mx.profiler.profiler_set_state("run")
    ... training steps ...
    mx.profiler.profiler_set_state("stop")   # trace dir for xprof/tensorboard

    with mx.profiler.scope("data-loading"):  # named regions in the trace
        batch = next(it)

Function names mirror the later-mxnet C API (MXSetProfilerConfig /
MXSetProfilerState) so ported scripts work unchanged.
"""
from __future__ import annotations

import os
import threading
import weakref

from .base import make_lock

__all__ = ["profiler_set_config", "profiler_set_state", "scope",
           "dump_profile", "dump_trace", "state", "register_feed_stats",
           "feed_report", "feed_report_str", "register_checkpoint_stats",
           "checkpoint_report", "checkpoint_report_str", "SuperstepStats",
           "register_superstep_stats", "superstep_report",
           "superstep_report_str", "register_serve_stats", "serve_report",
           "serve_report_str", "register_embed_stats", "embed_report",
           "embed_report_str", "register_moe_stats", "moe_report",
           "moe_report_str", "compile_report", "compile_report_str",
           "register_passes_stats", "passes_report", "passes_report_str",
           "register_autotune_stats", "autotune_report",
           "autotune_report_str", "costmodel_report",
           "costmodel_report_str", "register_faults_stats",
           "faults_report", "faults_report_str",
           "register_online_stats", "online_report", "online_report_str",
           "MultichipStats", "register_multichip_stats",
           "parse_hlo_collectives", "multichip_report",
           "multichip_report_str", "unified_report", "unified_report_str"]

_config = {"filename": "profile_output", "mode": "symbolic"}
_state = "stop"


def profiler_set_config(mode: str = "symbolic",
                        filename: str = "profile_output") -> None:
    """Configure the trace output directory (reference
    MXSetProfilerConfig(mode, filename))."""
    _config["mode"] = mode
    _config["filename"] = filename


def profiler_set_state(state_name: str = "stop") -> None:
    """'run' starts a jax.profiler trace into the configured directory,
    'stop' ends it (reference MXSetProfilerState(1/0))."""
    global _state
    import jax
    if state_name not in ("run", "stop"):
        raise ValueError("state must be 'run' or 'stop'")
    if state_name == "run" and _state != "run":
        out = _config["filename"]
        os.makedirs(out, exist_ok=True)
        jax.profiler.start_trace(out)
        _state = "run"
    elif state_name == "stop" and _state == "run":
        jax.profiler.stop_trace()
        _state = "stop"


def state() -> str:
    return _state


def dump_profile() -> str:
    """Write the Chrome-format span trace for the configured filename and
    return its path (reference MXDumpProfile wrote the json to the
    configured file; the span runtime now honors that contract — the
    returned file loads in chrome://tracing / Perfetto).  XLA's own
    xprof trace, when profiler_set_state("run") was used, streams into
    the configured directory separately."""
    from . import trace as _trace
    out = _config["filename"]
    path = out if out.endswith(".json") else out + ".trace.json"
    return _trace.dump_trace(path)


def dump_trace(path: str) -> str:
    """Write the merged span timeline (this process + registered worker
    spill dirs) as Chrome/Perfetto trace-event JSON; returns ``path``.
    See mxnet_tpu.trace and docs/observability.md."""
    from . import trace as _trace
    return _trace.dump_trace(path)


# -- the shared stats registry ----------------------------------------------
# Every subsystem's live stats objects register here (weakly: a dropped
# pipeline/engine/manager disappears from reports without an unregister
# call).  ONE lock guards every registry's mutation and iteration:
# register_* is called from writer threads (serve engines from request
# threads, checkpoint managers from fit, feed pipelines from pipeline
# construction) while report readers iterate — a WeakValueDictionary
# mutating under iteration is a RuntimeError, so every reader
# snapshot-copies under the lock first.  The per-object counter locks
# (StageStats, ServeStats, ...) stay where they are; this lock only
# covers registry membership.
_registry_lock = make_lock("profiler.registry")


class _Registry:
    """name -> live stats objects, weakly held, creation-ordered."""

    def __init__(self, label: str, empty_str: str):
        self.label = label
        self.empty_str = empty_str
        self._items = weakref.WeakValueDictionary()
        self._seq = 0

    def register(self, obj) -> None:
        with _registry_lock:
            self._seq += 1
            # zero-padded seq so lexicographic order == creation order
            self._items["%s#%06d" % (obj.name, self._seq)] = obj

    def snapshot(self):
        """Strong-referenced (key, obj) list — safe to iterate while
        other threads register/drop."""
        with _registry_lock:
            return sorted(self._items.items())

    def __len__(self) -> int:
        with _registry_lock:
            return len(self._items)

    def report(self, **kw) -> dict:
        return {key: obj.report(**kw) for key, obj in self.snapshot()}

    def report_str(self, **kw) -> str:
        parts = [obj.report_str(**kw) for _, obj in self.snapshot()]
        return "\n\n".join(parts) if parts else self.empty_str


# -- feed-pipeline instrumentation (mxnet_tpu.feed) -------------------------
# Live pipelines register their PipelineStats here (weakly: a dropped
# pipeline disappears from reports without an unregister call), so one
# feed_report() shows every stage of every running input pipeline —
# items/sec, busy time, producer/consumer stall time, queue depth — and
# therefore exactly which stage starves the chip.  Multi-process stages
# (feed.ParallelReader) publish per-worker counters through shared
# memory; their StageStats merges them into every snapshot (a "workers"
# sub-dict with per-process items/s, busy time, restart count and
# liveness, plus aggregated worker_items/worker_busy_s/restarts), so the
# report covers the whole reader process tree, not just the parent.
_feed_registry = _Registry("feed", "(no live feed pipelines)")


def register_feed_stats(pipeline_stats) -> None:
    """Called by feed.Pipeline / feed.DevicePrefetchIter on construction."""
    _feed_registry.register(pipeline_stats)


def feed_report() -> dict:
    """{pipeline key: {stage name: counters}} for every live pipeline,
    including per-worker-process counters for multi-process reader
    stages (see the registry note above)."""
    return _feed_registry.report()


def feed_report_str() -> str:
    """Human-readable per-stage table for every live feed pipeline."""
    out = _feed_registry.report_str()
    if len(_superstep_registry):
        # the chip-side half of the same story: whether the loop is
        # dispatch-bound or compute-bound lives in superstep_report()
        out += ("\n\n(superstep dispatch/wait/stage split: see "
                "mx.profiler.superstep_report_str())")
    return out


# -- superstep instrumentation (module/fused.py build_superstep) -------------
# One SuperstepStats per training Module running fit(superstep=K),
# registered weakly like the feed pipelines.  The counters split the host
# side of every superstep into the three places time can go, so
# "dispatch-bound vs compute-bound" is measured rather than inferred:
#
#   h2d_stage_s     megabatch assembly + the device_put issue time
#   step_dispatch_s enqueueing the K-step program (host->XLA dispatch;
#                   on an async backend this returns before compute ends)
#   device_wait_s   blocking on the drained metric accumulators — i.e.
#                   actual device compute the host had to wait out
_superstep_registry = _Registry("superstep", "(no live superstep loops)")


class SuperstepStats:
    """Counters for the K-steps-per-dispatch training loop.  Cumulative
    totals plus ``window()`` deltas (per-window counters for bench
    loops: call once per measurement window and diff)."""

    def __init__(self, name: str = "superstep"):
        self.name = name
        self.supersteps = 0
        self.steps = 0
        self.h2d_stage_s = 0.0
        self.step_dispatch_s = 0.0
        self.device_wait_s = 0.0
        self._window_base = self._totals()

    def _totals(self) -> dict:
        return {"supersteps": self.supersteps, "steps": self.steps,
                "h2d_stage_s": self.h2d_stage_s,
                "step_dispatch_s": self.step_dispatch_s,
                "device_wait_s": self.device_wait_s}

    def add(self, steps: int, h2d_s: float, dispatch_s: float,
            wait_s: float) -> None:
        self.supersteps += 1
        self.steps += int(steps)
        self.h2d_stage_s += h2d_s
        self.step_dispatch_s += dispatch_s
        self.device_wait_s += wait_s

    def window(self) -> dict:
        """Counters accumulated since the previous window() call."""
        now = self._totals()
        delta = {k: now[k] - self._window_base[k] for k in now}
        self._window_base = now
        return delta

    def report(self) -> dict:
        out = self._totals()
        if self.steps:
            out["host_s_per_step"] = (
                self.h2d_stage_s + self.step_dispatch_s
                + self.device_wait_s) / self.steps
        return out

    def report_str(self) -> str:
        r = self.report()
        lines = ["%s: %d supersteps / %d steps" % (self.name,
                                                   r["supersteps"],
                                                   r["steps"])]
        for key in ("h2d_stage_s", "step_dispatch_s", "device_wait_s"):
            lines.append("  %-16s %10.4f" % (key, r[key]))
        if "host_s_per_step" in r:
            lines.append("  %-16s %10.6f" % ("host_s/step",
                                             r["host_s_per_step"]))
        return "\n".join(lines)


def register_superstep_stats(superstep_stats) -> None:
    """Called by Module.superstep_train on first dispatch."""
    _superstep_registry.register(superstep_stats)


def superstep_report() -> dict:
    """{key: counters} for every live superstep-training module; the
    feed-side view of the same loop is feed_report()."""
    return _superstep_registry.report()


def superstep_report_str() -> str:
    """Human-readable dispatch/wait/stage split per training loop."""
    out = _superstep_registry.report_str()
    if len(_multichip_registry):
        # the mesh-side view of the same loop: collective vs compute
        # split and per-axis usage live in multichip_report()
        out += ("\n\n(per-axis collective/compute split: see "
                "mx.profiler.multichip_report_str())")
    return out


# -- multichip instrumentation (module/fused.py over a device mesh) ----------
# One MultichipStats per FusedTrainStep spanning >1 device, registered
# weakly like the feed pipelines.  The counters answer "where does a mesh
# step's time go, and how much of it is collectives":
#
#   dispatch_s          host time enqueueing the step program (async
#                       backends return before compute ends)
#   sampled_device_s    device wall of superstep windows: the metric
#                       drain waits there anyway, so no sync is added
#                       (a K=1 step is never blocked to be timed; read
#                       its device time from a profiler trace)
#   flops/bytes         XLA cost analysis of the AOT-compiled step —
#                       PER DEVICE (SPMD cost analysis reports one
#                       partition's work)
#   collectives         op counts + per-device payload bytes parsed
#                       from the optimized (post-SPMD-partitioner) HLO
#                       — the REAL all-reduce/all-gather/reduce-scatter
#                       the partitioner inserted for the mesh
#
# ``report(peak_tflops=, ici_gbps=)`` turns the static numbers into a
# collective-vs-compute time split estimate; without them the raw
# counts/bytes and the measured wall splits are reported as-is.
_multichip_registry = _Registry("multichip", "(no live multichip steps)")

_HLO_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
_HLO_ITEMSIZE = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                 "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                 "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1}


def parse_hlo_collectives(hlo_text: str) -> dict:
    """Collective-op census of one post-partitioner HLO module text:
    per-op instruction counts plus the payload bytes of every typed
    collective result.  The partitioned HLO is per-device, so counts
    and bytes are PER DEVICE per program execution.  Async ``-start``
    ops (TPU backends) return a tuple mixing the aliased operand, the
    result and possibly context scalars — the largest element counts
    as the payload, and the ``-done`` halves of the pairs are skipped
    entirely (the -start carries the shape)."""
    import re
    out = {op: {"count": 0, "bytes": 0} for op in _HLO_COLLECTIVES}
    line_pat = re.compile(
        r"=\s*(\([^)]*\)|\S+)\s+(%s)(-start)?\("
        % "|".join(_HLO_COLLECTIVES))
    shape_pat = re.compile(r"([a-z]+\d*)\[([0-9,]*)\]")
    for m in line_pat.finditer(hlo_text or ""):
        shapes, op, started = m.group(1), m.group(2), m.group(3)
        out[op]["count"] += 1
        found = shape_pat.findall(shapes)

        def nbytes(dt, dims):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            return n * _HLO_ITEMSIZE.get(dt, 4)
        sizes = [nbytes(dt, dims) for dt, dims in found]
        if started and len(sizes) > 1:
            # -start tuples mix the aliased operand, the result, and
            # (collective-permute) u32 context scalars — the largest
            # element is the payload; summing would double it and
            # halving would keep the context scalars
            out[op]["bytes"] += max(sizes)
        else:
            out[op]["bytes"] += sum(sizes)
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict))
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


class MultichipStats:
    """Counters for one mesh-spanning fused train step (see the section
    note above).  ``axes`` is the mesh's ((name, size), ...) tuple;
    ``spec_axes`` the axes any per-param sharding spec references."""

    def __init__(self, name: str, axes, spec_axes=()):
        self.name = name
        self.axes = tuple((str(a), int(s)) for a, s in axes)
        self.spec_axes = tuple(spec_axes)
        self.devices = 1
        for _, s in self.axes:
            self.devices *= s
        self.steps = 0
        self.dispatch_s = 0.0
        self.first_step_s = 0.0
        self.sampled_steps = 0
        self.sampled_device_s = 0.0
        self.flops_per_step = 0.0
        self.bytes_per_step = 0.0
        self.collectives = None

    def add_step(self, dispatch_s: float) -> None:
        self.steps += 1
        self.dispatch_s += dispatch_s

    def note_first(self, dispatch_s: float) -> None:
        """The first dispatch blocks through trace+XLA compile (seconds
        on a cold cache) — recording it into dispatch_s would dominate
        dispatch_s_per_step forever, so it gets its own counter."""
        self.steps += 1
        self.first_step_s = dispatch_s

    def add_superstep(self, k: int, dispatch_s: float,
                      wait_s: float = 0.0) -> None:
        """K steps dispatched as ONE scan program (Module.superstep_
        train): the metric drain's wait already measures the device
        wall, so it feeds the sampled column without extra syncs."""
        self.steps += int(k)
        self.dispatch_s += dispatch_s
        if wait_s:
            self.sampled_steps += int(k)
            self.sampled_device_s += wait_s

    def set_cost(self, flops: float = 0.0, bytes_accessed: float = 0.0,
                 collectives=None) -> None:
        self.flops_per_step = float(flops)
        self.bytes_per_step = float(bytes_accessed)
        if collectives is not None:
            self.collectives = dict(collectives)

    def report(self, peak_tflops=None, ici_gbps=None) -> dict:
        out = {
            "mesh": dict(self.axes),
            "devices": self.devices,
            "steps": self.steps,
            "dispatch_s": round(self.dispatch_s, 4),
            "sampled_steps": self.sampled_steps,
            "sampled_device_s": round(self.sampled_device_s, 4),
        }
        # per-axis view: degree + what uses the axis (the batch rides
        # "dp"; tensor-parallel specs ride the axes they reference)
        out["per_axis"] = {
            a: {"size": s,
                "batch_sharded": a == "dp",
                "param_sharded": a in self.spec_axes}
            for a, s in self.axes}
        if self.first_step_s:
            out["first_step_s"] = round(self.first_step_s, 4)
        if self.sampled_steps:
            out["device_s_per_step"] = round(
                self.sampled_device_s / self.sampled_steps, 6)
        steady = self.steps - (1 if self.first_step_s else 0)
        if steady > 0:
            out["dispatch_s_per_step"] = round(
                self.dispatch_s / steady, 6)
        if self.flops_per_step:
            out["flops_per_step"] = self.flops_per_step
            out["bytes_per_step"] = self.bytes_per_step
        if self.collectives is not None:
            out["collectives"] = self.collectives
        # estimated collective-vs-compute split, only when the caller
        # supplies the hardware numbers the estimate needs.  cost
        # analysis of an SPMD executable and the partitioned HLO are
        # both PER DEVICE already (verified: a dp=8 matmul reports 1/8
        # the single-device flops), so neither divides by devices —
        # per-device work over per-device peak / link bandwidth IS the
        # per-device time estimate.
        if peak_tflops and self.flops_per_step:
            out["compute_s_est"] = self.flops_per_step \
                / (peak_tflops * 1e12)
        if ici_gbps and self.collectives and \
                self.collectives.get("total_bytes"):
            out["collective_s_est"] = (self.collectives["total_bytes"]
                                       / (ici_gbps * 1e9))
            if out.get("compute_s_est"):
                tot = out["compute_s_est"] + out["collective_s_est"]
                out["collective_frac_est"] = out["collective_s_est"] / tot
        # measured fallback for the same split: device wall minus the
        # compute estimate when both exist
        if out.get("device_s_per_step") and out.get("compute_s_est"):
            out["collective_s_measured_est"] = max(
                0.0, out["device_s_per_step"] - out["compute_s_est"])
        return out

    def report_str(self, peak_tflops=None, ici_gbps=None) -> str:
        r = self.report(peak_tflops=peak_tflops, ici_gbps=ici_gbps)
        mesh = " x ".join("%s=%d" % (a, s) for a, s in self.axes)
        lines = ["%s: mesh %s (%d devices), %d steps"
                 % (self.name, mesh or "1", r["devices"], r["steps"])]
        if "dispatch_s_per_step" in r:
            lines.append("  dispatch_s/step   %10.6f"
                         % r["dispatch_s_per_step"])
        if "first_step_s" in r:
            lines.append("  first step        %10.4f (trace+compile)"
                         % r["first_step_s"])
        if "device_s_per_step" in r:
            lines.append("  device_s/step     %10.6f (sampled %d)"
                         % (r["device_s_per_step"], r["sampled_steps"]))
        if "flops_per_step" in r:
            lines.append("  flops/step        %10.3e" % r["flops_per_step"])
        c = r.get("collectives")
        if c:
            lines.append("  collectives/step  %d ops, %.3f MB"
                         % (c["total_count"], c["total_bytes"] / 1e6))
            for op in _HLO_COLLECTIVES:
                if c.get(op, {}).get("count"):
                    lines.append("    %-19s %3d ops %10.3f MB"
                                 % (op, c[op]["count"],
                                    c[op]["bytes"] / 1e6))
        if "collective_frac_est" in r:
            lines.append("  collective frac   %10.3f (est @ %s TF/s, %s "
                         "GB/s ICI)" % (r["collective_frac_est"],
                                        peak_tflops, ici_gbps))
        for a, info in r["per_axis"].items():
            use = [u for u, on in (("batch", info["batch_sharded"]),
                                   ("params", info["param_sharded"])) if on]
            lines.append("  axis %-6s size %2d  shards: %s"
                         % (a, info["size"], ", ".join(use) or "(unused)"))
        return "\n".join(lines)


def register_multichip_stats(multichip_stats) -> None:
    """Called by FusedTrainStep when its mesh spans >1 device."""
    _multichip_registry.register(multichip_stats)


def multichip_report(peak_tflops=None, ici_gbps=None) -> dict:
    """{key: counters} for every live mesh-spanning train step; pass
    PER-DEVICE ``peak_tflops`` (e.g. bench.py's probe result) and
    ``ici_gbps`` link bandwidth for the collective-vs-compute time
    estimate."""
    return _multichip_registry.report(peak_tflops=peak_tflops,
                                      ici_gbps=ici_gbps)


def multichip_report_str(peak_tflops=None, ici_gbps=None) -> str:
    """Human-readable per-mesh dispatch/device/collective table."""
    return _multichip_registry.report_str(peak_tflops=peak_tflops,
                                          ici_gbps=ici_gbps)


# -- checkpoint instrumentation (mxnet_tpu.checkpoint) ----------------------
# Live CheckpointManagers register their CheckpointStats here, weakly like
# the feed pipelines above, so one checkpoint_report() shows every
# manager's save/restore wall time, bytes/s, and the train-thread overhead
# each save cost — the numbers BENCH's ckpt leg tracks over rounds.
_ckpt_registry = _Registry("checkpoint", "(no live checkpoint managers)")


def register_checkpoint_stats(ckpt_stats) -> None:
    """Called by checkpoint.CheckpointManager on construction."""
    _ckpt_registry.register(ckpt_stats)


def checkpoint_report() -> dict:
    """{manager key: counters} for every live CheckpointManager."""
    return _ckpt_registry.report()


def checkpoint_report_str() -> str:
    """Human-readable save/restore counters for every live manager."""
    return _ckpt_registry.report_str()


# -- serving instrumentation (mxnet_tpu.serve) ------------------------------
# Every serving component registers its stats object here, weakly like
# the feed pipelines, so one serve_report() is MULTIPLEX-AWARE: a
# process serving N models shows one row per component, each tagged by
# "kind" and carrying its OWN capacity shape — ServeStats rows (kind
# "engine": latency percentiles, queue depth, batch occupancy against
# that engine's max_batch_size, pad waste, per-bucket hits), DecodeStats
# rows (kind "decode": slot occupancy, steps, tokens out), the
# multiplexer's MuxStats (kind "mux": swap-in/eviction counters, live
# bytes vs budget) and the router's RouterStats (kind "router":
# per-replica dispatch/health plus a rollup of the replicas' counters).
_serve_registry = _Registry("serve", "(no live serve engines)")


def register_serve_stats(serve_stats) -> None:
    """Called by serve.ServeEngine / DecodeEngine / ModelMultiplexer /
    ServeRouter on construction (any object with name/report/report_str
    rides along)."""
    _serve_registry.register(serve_stats)


def serve_report() -> dict:
    """{component key: counters} for every live serving component
    (engines, decode engines, multiplexers, routers — see the "kind"
    field per row)."""
    return _serve_registry.report()


def serve_report_str() -> str:
    """Human-readable per-component serving table (latency/occupancy/
    queue per engine, slot occupancy per decode engine, swap-in and
    eviction counters per multiplexer, per-replica rollups per
    router)."""
    return _serve_registry.report_str()


# -- embedding instrumentation (mxnet_tpu.embed) ----------------------------
# Every embedding consumer (a FusedTrainStep with sparse tables, an
# EmbeddingTable, a device_embed kvstore) registers its EmbedStats at
# construction, weakly like the rest; embed_report() shows per-table
# lookup/update counts and the measured dedup ratio on the live id
# distribution.
_embed_registry = _Registry("embed", "(no live embedding tables)")


def register_embed_stats(embed_stats) -> None:
    """Called by embed.EmbeddingTable / FusedTrainStep on construction."""
    _embed_registry.register(embed_stats)


def embed_report() -> dict:
    """{consumer key: per-table counters} for every live embedding
    consumer."""
    return _embed_registry.report()


def embed_report_str() -> str:
    """Human-readable per-table lookup/dedup/update table."""
    return _embed_registry.report_str()


# -- MoE instrumentation (mxnet_tpu.moe) ------------------------------------
# Every MoE consumer (a FusedTrainStep whose graph routes through
# _moe_dispatch, a DecodeEngine sampling its per-slot routing state)
# registers its MoeStats at construction, weakly like the rest;
# moe_report() shows per-block expert hit histograms, the max/mean
# imbalance bench gates as moe_expert_imbalance, and the dropped
# fraction the capacity factor buys.
_moe_registry = _Registry("moe", "(no live MoE blocks)")


def register_moe_stats(moe_stats) -> None:
    """Called by FusedTrainStep / DecodeEngine on construction."""
    _moe_registry.register(moe_stats)


def moe_report() -> dict:
    """{consumer key: per-block routing counters} for every live MoE
    consumer."""
    return _moe_registry.report()


def moe_report_str() -> str:
    """Human-readable per-block expert-traffic table."""
    return _moe_registry.report_str()


# -- pass-pipeline instrumentation (mxnet_tpu.passes) ------------------------
# Every PassPipeline registers its PassStats at construction; one
# passes_report() shows, per live pipeline, the per-pass wall time, node
# counts and rewrite counts of its runs plus the fingerprint the
# compile-cache fast key carries.
_passes_registry = _Registry("passes", "(no pass pipelines)")


def register_passes_stats(passes_stats) -> None:
    """Called by passes.PassPipeline on construction."""
    _passes_registry.register(passes_stats)


def passes_report() -> dict:
    """Per-pipeline, per-pass wall seconds, node counts in/out, rewrite
    counts and the pipeline fingerprint (see mxnet_tpu.passes)."""
    return _passes_registry.report()


def passes_report_str() -> str:
    """Human-readable pass-pipeline table (see passes_report)."""
    return _passes_registry.report_str()


# -- autotune instrumentation (mxnet_tpu.autotune) ---------------------------
# One AutotuneStats per tuning run (fit's superstep search, a serve
# engine's pipeline-variant search).  Registered weakly like every other
# registry; the autotune package ALSO keeps the last N strongly, so a
# report after the tuning call returns still shows what was decided.
_autotune_registry = _Registry("autotune", "(no autotune runs)")


def register_autotune_stats(autotune_stats) -> None:
    """Called by autotune.Autotuner on construction."""
    _autotune_registry.register(autotune_stats)


def autotune_report() -> dict:
    """{run key: record} per tuning run: the store key, whether the
    config was measured or loaded, every candidate's measured cost, and
    the winner (see mxnet_tpu.autotune)."""
    return _autotune_registry.report()


def autotune_report_str() -> str:
    """Human-readable candidate/cost table per tuning run."""
    return _autotune_registry.report_str()


def costmodel_report() -> dict:
    """The shared learned cost model's lifecycle snapshot for this
    backend: version, trained or prior-only, training-sample count, and
    the pickle path (see autotune.costmodel)."""
    from .autotune import costmodel
    return costmodel.report()


def costmodel_report_str() -> str:
    """Human-readable cost-model lifecycle line (see costmodel_report)."""
    r = costmodel_report()
    return ("costmodel v%d backend=%s %s samples=%d path=%s"
            % (r["version"], r["backend"],
               "trained" if r["trained"]
               else ("loaded(prior)" if r["loaded"] else "(not loaded)"),
               r["samples"], r["path"] or "-"))


# -- fault-injection / recovery instrumentation (mxnet_tpu.faults) -----------
# The fault plane's process-global FaultStats (kind "plane": injected
# faults by kind and point) and every live Supervisor's SupervisorStats
# (kind "supervisor": restarts, recovery seconds, backoff waits) share
# one registry, so faults_report() is the single "what broke and how we
# recovered" view of a chaos run.
_faults_registry = _Registry("faults", "(no fault plane or supervisor)")


def register_faults_stats(faults_stats) -> None:
    """Called by faults.install (the plane singleton) and
    faults.Supervisor on construction."""
    _faults_registry.register(faults_stats)


def faults_report() -> dict:
    """Per-component fault counters: the plane row (injected faults by
    kind/point, current attempt) and one row per supervisor (attempts,
    restarts, recovery_s, backoff waits).  See mxnet_tpu.faults."""
    return _faults_registry.report()


def faults_report_str() -> str:
    """Human-readable fault-injection + recovery table."""
    return _faults_registry.report_str()


# -- online-loop instrumentation (mxnet_tpu.online) --------------------------
# The continuous-training loop's three legs share one registry: every
# CaptureWriter (kind "capture": offered/kept/shards sealed — the
# counters that make the sampled capture rate verifiable), OnlineTrainer
# (kind "trainer": fine-tune rounds, last candidate step) and
# PromotionGate (kind "gate": decisions, promoted vs quarantined), so
# online_report() is the loop's single health view.
_online_registry = _Registry("online", "(no online loop)")


def register_online_stats(online_stats) -> None:
    """Called by online.CaptureWriter / OnlineTrainer / PromotionGate
    on construction."""
    _online_registry.register(online_stats)


def online_report() -> dict:
    """Per-component online-loop counters: capture sampling, fine-tune
    rounds, gate decisions.  See mxnet_tpu.online."""
    return _online_registry.report()


def online_report_str() -> str:
    """Human-readable online-loop table."""
    return _online_registry.report_str()


# -- compilation instrumentation (mxnet_tpu.compile_cache) -------------------
# Compilation is process-global (one XLA compiler, one jit cache), so unlike
# the per-instance registries above there is exactly one CompileStats, owned
# by the compile_cache subsystem; these are thin views.

def compile_report() -> dict:
    """Per-program trace/lower/compile seconds, compile count and
    steady-state retrace count (totals + per_program keys)."""
    from .compile_cache import get_stats
    return get_stats().report()


def compile_report_str() -> str:
    """Human-readable compile/cold-start table (see compile_report)."""
    from .compile_cache import get_stats
    return get_stats().report_str()


# -- the unified view --------------------------------------------------------
def unified_report() -> dict:
    """Every subsystem's report under one roof: ``{"feed": ...,
    "superstep": ..., "multichip": ..., "checkpoint": ..., "serve": ...,
    "compile": ..., "trace": ...}`` — the snapshot the run-metrics
    journal (``MXNET_TRACE_JOURNAL``) writes every N steps."""
    out = {
        "feed": feed_report(),
        "superstep": superstep_report(),
        "multichip": multichip_report(),
        "checkpoint": checkpoint_report(),
        "serve": serve_report(),
        "embed": embed_report(),
        "moe": moe_report(),
        "passes": passes_report(),
        "autotune": autotune_report(),
        "costmodel": costmodel_report(),
        "faults": faults_report(),
        "online": online_report(),
    }
    try:
        out["compile"] = compile_report()
    except Exception:   # no backend yet / cache import failure
        out["compile"] = {}
    from . import trace as _trace
    out["trace"] = _trace.trace_report()
    return out


def unified_report_str() -> str:
    """Every subsystem's human-readable table, sectioned."""
    sections = [
        ("feed", feed_report_str),
        ("superstep", superstep_report_str),
        ("multichip", multichip_report_str),
        ("checkpoint", checkpoint_report_str),
        ("serve", serve_report_str),
        ("embed", embed_report_str),
        ("moe", moe_report_str),
        ("passes", passes_report_str),
        ("autotune", autotune_report_str),
        ("costmodel", costmodel_report_str),
        ("faults", faults_report_str),
        ("online", online_report_str),
        ("compile", compile_report_str),
    ]
    parts = []
    for label, fn in sections:
        try:
            body = fn()
        except Exception as e:
            body = "(unavailable: %s)" % e
        parts.append("== %s %s\n%s" % (label, "=" * max(1, 68 - len(label)),
                                       body))
    from . import trace as _trace
    tr = _trace.trace_report()
    parts.append("== trace %s\nenabled=%s events=%d dropped=%d "
                 "spill_dirs=%d journal=%s"
                 % ("=" * 62, tr["enabled"], tr["events"], tr["dropped"],
                    len(tr["spill_dirs"]), tr["journal"] or "-"))
    return "\n\n".join(parts)


def scope(name: str):
    """Named region visible in BOTH trace timelines: the span runtime's
    Chrome/Perfetto dump (mxnet_tpu.trace) and, while
    profiler_set_state("run") holds an xprof trace open, the profile's
    host plane (every mxnet_tpu.trace span is a TraceAnnotation).  Also
    usable around host-side work like data loading.  API unchanged from
    the seed."""
    from . import trace as _trace
    return _trace.span(name, cat="scope")
