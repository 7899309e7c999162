"""Per-layer metric ``setup_train_module_s``: from the training module's
first span to the window's opening (the start of the first of the
window's ``fit:step`` spans).  ``fit_call_to_window_s`` counts from
``fit``'s entry; ``other_s`` is this less ``setup_bind_init_s`` and
``setup_warmup_s`` (between the module's methods, and in ``fit`` around
its steps); ``ring_dropped`` above 0 says the ring wrapped and the
set-up's spans may be gone.  Nothing where the program records no
``fit:call``."""
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
    from mxnet_tpu import trace
    whole = (got["opening"] - got["train_first"]) / 1e6
    parts = sum(e["dur"] for e in got["set_up"] + got["warmup"]) / 1e6
    return whole, {
        "fit_call_to_window_s": (got["opening"] - got["call"]["ts"]) / 1e6,
        "other_s": whole - parts, "ring_dropped": trace.drop_count()}
