"""Per-layer metric ``enqueue_ms_p50``: median, over the window's steps,
of ``fit:forward_backward`` plus ``fit:update``: the host time that hands
the step's programs to the device (one fused program, or the classic
executor's forward, backward and per-array updates).  Nothing where the
program records no ``fit:step``."""
LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import fit_spans
    return fit_spans.median_of(fit_spans.window_steps(obs),
                               plus=("fit:forward_backward", "fit:update"))
