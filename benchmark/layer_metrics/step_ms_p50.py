"""Per-layer metric ``step_ms_p50``: median gap between consecutive
batch_end_callbacks of the window (profiler off)."""
LAYER = "entry points"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
DRIVERS = ("train_fit",)


def read(obs):
    import stats
    if not obs["gaps_ms"]:
        return None
    return stats.median(obs["gaps_ms"]), {"samples": len(obs["gaps_ms"])}
