"""Per-layer metric ``setup_compile_trace_s``: the ``compile:trace`` spans
that ended before the window opened, summed over the whole process and
every thread: Python tracing of a function to its jaxpr (a function
traced inside another's trace counts once, in the outer).  A warm run
pays it as a cold one does.  Extras: ``before_training_module_s`` /
``in_training_module_s`` (cut at the training module's first span),
``top`` (the five ``fun`` with most seconds), ``in_window`` (spans that
started between the opening and ``fit``'s return: must read 0).
Nothing where the ring holds no such span."""
LAYER = "compile / cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import setup_spans
    got = setup_spans.compile_seconds(obs, "compile:trace")
    return None if got is None else got[:2]
