"""Per-layer metric ``moe_gmm_kernel_share``: of the routed experts'
grouped products the process traced in the configuration's compute dtype,
the share whose TPU lowering is the repo's kernel pair: ``100 sum(kernel)
/ samples`` over the samples of the counter ``moe:gmm_lowering`` whose
track is ``<compute dtype>[..] x [..]`` (``moe/gmm.py`` ``tiled_matmul``
records one a traced product: the step's program, the check module's).
100 on the chip; 0 where a width the tile rule refuses (an expert width
that is no whole number of 64-lane half tiles, rows that are no whole row
tiles) sends the products to ``lax.ragged_dot`` unnoticed, which the
step's rate would show only as a slower cell.  Nothing where the program
records no such sample (an older commit, a symbol without routed
experts)."""
LAYER = "routed experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "moe:gmm_lowering"


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
