"""Per-layer metric ``setup_check_module_s``: from the first span of the
process's first module (``module:*``, ``fit:call``) to the first span of
the training module: what the process did with any earlier module (the
harness's reference check: its bind, init, one step, the float32
reference); 0 where the training module is the first.  The training
module is told from the others by its number (``module``), never by
order or by time.  Nothing where the program records no ``fit:call``."""
LAYER = "entry points"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import setup_spans
    got = setup_spans.read(obs)
    if got is None:
        return None
    return (got["train_first"] - got["first"]) / 1e6
