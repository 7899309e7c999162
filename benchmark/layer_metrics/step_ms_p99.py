"""Per-layer metric ``step_ms_p99``: 99th percentile of the gaps between
batch_end_callbacks, reported only where at least ten samples lie
beyond it."""
LAYER = "entry points"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
DRIVERS = ("train_fit",)


def read(obs):
    import stats
    value = stats.tail(obs["gaps_ms"], 99.0)
    if value is None:
        return None
    return value, {"samples": len(obs["gaps_ms"])}
