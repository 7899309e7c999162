"""Per-layer metric ``feed_next_ms_p50``: median duration of the
``fit:feed_next`` span of the window's steps (``fit``'s pull from the
iterator); the inside twin of ``feed_wait_share``.  Nothing where the
program records no ``fit:step``."""
LAYER = "feed"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import fit_spans
    return fit_spans.median_of(fit_spans.window_steps(obs),
                               plus=("fit:feed_next",))
