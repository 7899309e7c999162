"""Per-layer metric ``ssd_kernel_share``: of the state-space scans the
process traced in the configuration's compute dtype, the share whose TPU
lowering has the kernel pair: ``100 sum(kernel) / samples`` over the
samples of the counter ``ssd:lowering`` whose track is ``<compute
dtype>[..]`` (``ops/ssd.py`` ``ssd_scan`` records one a traced op: the
step's program, the check module's, the warm-up's).  100 on the chip; 0
where a change sends the mixers to the plain chunks unnoticed (a shape
the tiling stops taking, a dtype), which the step's rate would show only
as a slower cell.  Nothing where the program records no such sample (an
older commit, a symbol without the mixer)."""
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "ssd:lowering"


def read(obs):
    try:
        from mxnet_tpu import trace
    except ImportError:
        return None
    events = getattr(trace, "counter_events", None)
    if events is None:
        return None
    dtype = str(obs["config"].get("compute_dtype", ""))
    rows = [e.get("args") or {} for e in events(names=(COUNTER,))
            if str(e.get("id", "")).startswith(dtype + "[")]
    if not rows:
        return None
    took = sum(int(r.get("kernel", 0)) for r in rows)
    return 100.0 * took / len(rows), {"samples": len(rows), "kernel": took}
