"""Per-layer metric ``moe_act_zero_share``: of the gate lanes ``act(g
Wg)`` of the rows this rank's experts really held in the window, the
share that is exactly 0: ``sum(zeros) / sum(lanes)`` over the window's
samples of the ``moe:act_zeros`` counter, which ``Module.fit`` records
once a step and expert block for a symbol that carries the head
``moe_act_zeros`` (a ReGLU expert layer built with ``act_zeros``; the
lanes are the held rows', never the rows a static bound pads).  Near 50 %
at the start under Normal(0.02) weights (a gate lane is as often negative
as not); training moves it, and a move between two commits on one seed
says the activation or the rows counted changed.  ``by_block``: the
figure of each block.  Nothing where the program records no such counter
(an older commit, a symbol without the head)."""
LAYER = "routed experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "moe:act_zeros"


def window_samples(obs):
    """block -> the window's samples of the counter, oldest first: each
    block's last ``obs["steps_in_window"]`` (the harness ends the epoch
    at the window's deadline and every step feeds one a block)."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return {}
    events = getattr(trace, "counter_events", None)
    n = int(obs.get("steps_in_window") or 0)
    if events is None or not n:
        return {}
    blocks = {}
    for e in sorted(events(names=(COUNTER,)), key=lambda e: e["ts"]):
        blocks.setdefault(e.get("id"), []).append(e.get("args") or {})
    return {b: rows[-n:] for b, rows in blocks.items()}


def read(obs):
    blocks = {b: [r for r in rows if r.get("lanes") and "zeros" in r]
              for b, rows in window_samples(obs).items()}
    blocks = {b: rows for b, rows in blocks.items() if rows}
    if not blocks:
        return None

    def share(rows):
        return 100.0 * sum(r["zeros"] for r in rows) \
            / sum(r["lanes"] for r in rows)

    every = [r for rows in blocks.values() for r in rows]
    return share(every), {
        "samples": len(every), "blocks": len(blocks),
        "lanes": sum(r["lanes"] for r in every),
        "by_block": {b: share(rows) for b, rows in sorted(blocks.items())}}
