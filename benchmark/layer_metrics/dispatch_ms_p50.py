"""Per-layer metric ``dispatch_ms_p50``: median duration of the program's
``fused:dispatch`` spans inside the window; nothing where the fused
step did not run."""
LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import stats
    if not obs["dispatch_ms"]:
        return None
    return stats.median(obs["dispatch_ms"]), \
        {"samples": len(obs["dispatch_ms"])}
