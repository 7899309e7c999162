"""Per-layer metric ``mtp_loss_over_main``: the second loss head's mean
over the first's, the median over the window's last tenth of steps (3 at
least), from the ``mtp:loss`` counter that ``Module.fit`` records once a
step for a symbol with a multi-token-prediction head (``main``: the mean
of output 0, ``mtp``: the mean of the module's head over the positions
that have a target, ``weight``).  It watches the second head inside the
timed window, where ``correct`` reads output 0 only: a module whose
gradient does not reach the shared weights, or whose targets are off by
one, trains the trunk alone and reads near ``chance / main`` (about 7
where the main loss has fallen to 1.4 of ln 19 360 = 9.87), a module
that learns reads about 1 to 2 (two tokens ahead is harder than one).
Nothing where the program records no such counter (an older commit, a
symbol with one loss head)."""
LAYER = "prediction heads"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "mtp:loss"


def window_samples(obs):
    """The window's samples of the counter, oldest first: the last
    ``obs["steps_in_window"]`` (the harness ends the epoch at the
    window's deadline and every step feeds one)."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return []
    events = getattr(trace, "counter_events", None)
    n = int(obs.get("steps_in_window") or 0)
    if events is None or not n:
        return []
    rows = [e.get("args") or {} for e in
            sorted(events(names=(COUNTER,)), key=lambda e: e["ts"])]
    return rows[-n:]


def read(obs):
    import stats
    rows = [r for r in window_samples(obs)
            if "main" in r and "mtp" in r and r["main"] > 0]
    if not rows:
        return None
    tenth = rows[-min(len(rows), max(3, len(rows) // 10)):]
    return stats.median([r["mtp"] / r["main"] for r in tenth]), {
        "samples": len(tenth), "main": stats.median([r["main"]
                                                     for r in tenth]),
        "mtp": stats.median([r["mtp"] for r in tenth]),
        "weight": tenth[-1].get("weight")}
