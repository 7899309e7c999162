"""Per-layer metric ``metric_wait_ms_p50``: median duration of the
``fit:update_metric`` span of the window's steps: where ``fit`` reads the
step's outputs, so where the host waits for the device.  Nothing where
the program records no ``fit:step``."""
LAYER = "entry points"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import fit_spans
    return fit_spans.median_of(fit_spans.window_steps(obs),
                               plus=("fit:update_metric",))
