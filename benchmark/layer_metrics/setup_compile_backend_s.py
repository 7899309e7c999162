"""Per-layer metric ``setup_compile_backend_s``: the ``compile:backend``
spans that ended before the window opened, summed over the whole process
and every thread: XLA's compile, or the persistent cache's read and load
in its place.  Extras: ``requests`` (the spans: ``programs_at_setup``
counts the same events), ``cache_hits``, ``load_s`` (the hits' retrieval
times), ``miss_s`` (the seconds of the spans the cache did not answer),
and the three compile readers' ``before_training_module_s`` /
``in_training_module_s``, ``top``, ``in_window``.  Nothing where the
ring holds no such span."""
LAYER = "compile / cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import setup_spans
    got = setup_spans.compile_seconds(obs, "compile:backend")
    if got is None:
        return None
    value, extra, spans = got
    hits = [e for e in spans if e["args"].get("cache") == "hit"]
    extra.update(
        requests=len(spans), cache_hits=len(hits),
        load_s=sum(e["args"].get("load_s", 0.0) for e in hits),
        miss_s=sum(e["dur"] for e in spans
                   if e["args"].get("cache") != "hit") / 1e6)
    return value, extra
