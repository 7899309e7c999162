"""Per-layer metric ``setup_compile_lower_s``: the ``compile:lower`` spans
that ended before the window opened, summed over the whole process and
every thread: a jaxpr to its MLIR module (the Mosaic kernels' lowering
reads here).  A warm run pays it as a cold one does.  Extras:
``before_training_module_s`` / ``in_training_module_s`` (cut at the
training module's first span), ``top`` (the five ``fun`` with most
seconds), ``in_window`` (spans that started between the opening and
``fit``'s return: must read 0).  Nothing where the ring holds no such
span."""
LAYER = "compile / cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import setup_spans
    got = setup_spans.compile_seconds(obs, "compile:lower")
    return None if got is None else got[:2]
