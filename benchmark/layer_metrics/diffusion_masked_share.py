"""Per-layer metric ``diffusion_masked_share``: the share of the window's
clean positions that the noise masked, ``sum(masked) / sum(positions)``
over the window's samples of the ``diffusion:noise`` counter that
``Module.fit`` records once a step for a block-diffusion symbol
(``masked``, ``positions``, ``weight_sum`` from the step's noise head).
The traffic sets it (one ``t ~ eps + (1 - eps) U(0, 1)`` a block: about
half), not the program: a move says the traffic changed, or that the
labels the step scored are not the ones the generator made.  Nothing
where the program records no such counter (an older commit, a symbol
without the head)."""
LAYER = "diffusion objective"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "diffusion:noise"


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
    rows = [r for r in window_samples(obs)
            if r.get("positions") and "masked" in r]
    if not rows:
        return None
    masked = sum(r["masked"] for r in rows)
    positions = sum(r["positions"] for r in rows)
    return 100.0 * masked / positions, {
        "samples": len(rows), "masked": masked, "positions": positions,
        "weight_mean": sum(r.get("weight_sum", 0.0) for r in rows)
        / positions}
