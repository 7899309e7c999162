"""Per-layer metric ``collective_ms``: time in collective operations per step
and device, from the trace; nothing on one chip."""
LAYER = "mesh / collectives"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    tr = obs["trace"]
    if not tr or not tr["steps"] or obs["chips"] < 2:
        return None
    return 1e3 * tr["collective_s"] / tr["steps"]
