"""Per-layer metric ``dsa_kept_pairs_share``: the share of the causal
(query, key) pairs that the learned selection keeps, ``sum(selected_pairs)
/ sum(causal_pairs)`` over the window's samples of the ``dsa:select``
counter that ``Module.fit`` records once a step and block for a symbol
whose attention selects its keys (``rows``, ``selected_pairs``,
``causal_pairs``, ``tiles_hit``, ``tiles_causal``, ``kl`` from the step's
selection head).  The configuration and the traffic set it (row ``t``
keeps ``min(t + 1, topk)``: 43.75 at 8192 rows under a top-2048), not the
program's speed: a move says the selection changed.  ``window_tracks``
and ``read_share`` serve ``dsa_tiles_hit_share`` and ``dsa_index_kl``
too.  Nothing where the program records no such counter (an older
commit, a symbol without the head)."""
LAYER = "learned selection"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)
COUNTER = "dsa:select"


def window_tracks(obs):
    """``{block: the window's samples of the counter, oldest first}``:
    the last ``obs["steps_in_window"]`` of each track (the harness ends
    the epoch at the window's deadline and every step feeds one a
    block)."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return {}
    events = getattr(trace, "counter_events", None)
    n = int(obs.get("steps_in_window") or 0)
    if events is None or not n:
        return {}
    tracks = {}
    for e in sorted(events(names=(COUNTER,)), key=lambda e: e["ts"]):
        tracks.setdefault(e.get("id"), []).append(e.get("args") or {})
    return {track: rows[-n:] for track, rows in sorted(tracks.items())}


def window_samples(obs):
    """Every block's samples of the window, block by block."""
    return [row for rows in window_tracks(obs).values() for row in rows]


def read_share(obs, part: str, whole: str):
    """``100 sum(part) / sum(whole)`` over the window's samples."""
    rows = [r for r in window_samples(obs) if r.get(whole) and part in r]
    if not rows:
        return None
    got, of = sum(r[part] for r in rows), sum(r[whole] for r in rows)
    return 100.0 * got / of, {"samples": len(rows), part: got, whole: of}


def read(obs):
    return read_share(obs, "selected_pairs", "causal_pairs")
