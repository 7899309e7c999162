"""Per-layer metric ``setup_warmup_s``: the training module's
``fit:step`` spans between ``fit``'s entry and the window's opening,
summed: the steps that compile, load and warm the step's programs.
Extras: ``steps``, and ``first_step_s`` (the one that meets the step's
program).  Nothing where the program records no ``fit:call``."""
LAYER = "train step"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import setup_spans
    got = setup_spans.read(obs)
    if got is None:
        return None
    steps = got["warmup"]
    return sum(e["dur"] for e in steps) / 1e6, {
        "steps": len(steps),
        "first_step_s": steps[0]["dur"] / 1e6 if steps else 0.0}
