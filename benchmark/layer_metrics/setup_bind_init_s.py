"""Per-layer metric ``setup_bind_init_s``: the training module's
``module:bind``, ``module:init_params``, ``module:init_optimizer`` and
``module:prepare`` spans before the window, summed; where two nest (a
bucketing module's ``prepare`` around its buckets' ``bind``) the
outermost counts.  Extras: each by name.  Nothing where the program
records no ``fit:call``."""
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
    by_name = dict.fromkeys(setup_spans.MODULE_SPANS, 0.0)
    for e in got["set_up"]:
        by_name[e["name"]] += e["dur"] / 1e6
    return sum(by_name.values()), \
        {name.split(":", 1)[1] + "_s": s for name, s in by_name.items()}
