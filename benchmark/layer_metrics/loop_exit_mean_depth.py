"""Per-layer metric ``loop_exit_mean_depth``: the mean depth at which the
exit gate of a looped model lets a row leave, ``sum_t t p_t`` in passes,
the median over the window's last tenth of steps (3 at least), from the
``loop:exit`` counter that ``Module.fit`` records once a step for a symbol
with an exit head (``p1 .. pR``: the rows' mean exit distribution,
``depth``, ``ce_last``: the mean cross entropy at full depth).  It
watches the gate inside the timed window, where ``correct`` holds one
step: a gate with zero bias and a small weight starts at ``p = 1/2, 1/4,
1/8, 1/8``, depth 1.875; a uniform distribution reads 2.5; a gate whose
gradient is cut stays at its start.  What a learning gate read on the
chip (the builder's runs, PR 54, every seed): 1.00-1.02 at the window's
end, ``p_1`` 0.99 within six steps: Normal(0.02) gives the logit a
spread of 0.9 and an offset that differs by seed (the first step's ``p``
reads anything from 0.21 / 0.33 / 0.32 / 0.15 to 0.71 / 0.06 / 0.02 /
0.20), the first pass's cross entropy is the lowest from the start, and
one output over 2048 unit-RMS lanes moves its logit by ~0.65 a step
under Adam at 4e-4; once ``p_1`` is 0.99 the later passes get no
gradient (``ce_last`` stays near 10.5 while the objective falls to 4).
A reading that leaves 1 again would say a later pass has begun to pay.
Nothing where the program records no such counter (an older commit, a
symbol without the head)."""
LAYER = "loop node"
UNIT = "passes"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "loop:exit"


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
    rows = [r for r in window_samples(obs) if "depth" in r]
    if not rows:
        return None
    tenth = rows[-min(len(rows), max(3, len(rows) // 10)):]
    passes = sorted(k for k in tenth[-1] if k[:1] == "p" and k[1:].isdigit())
    return stats.median([r["depth"] for r in tenth]), {
        "samples": len(tenth), "first": rows[0]["depth"],
        "p": [stats.median([r[k] for r in tenth]) for k in passes],
        "ce_last": stats.median([r["ce_last"] for r in tenth])}
