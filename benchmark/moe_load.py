"""The window's ``moe:load`` counter samples, read from the program's own
ring (``mxnet_tpu.trace.counter_events`` of this process).

``Module.fit`` records one sample a step and routed block from the
block's counts head (``FusedTrainStep.note_outputs``): ``max``, ``mean``,
``empty``, ``routed``, ``dropped``, the block's name as the sample's
``id``.  The window's samples are each block's last
``obs["steps_in_window"]``: the harness ends the epoch at the window's
deadline and every step feeds one.  A program that has no such counter
(an older commit, a model without routed blocks) gives no samples and
the readers give None.
"""
from __future__ import annotations

from typing import Dict, List

COUNTER = "moe:load"


def window_samples(obs) -> Dict[str, List[Dict[str, float]]]:
    """block -> its samples' series, oldest first."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return {}
    events = getattr(trace, "counter_events", None)
    n = int(obs.get("steps_in_window") or 0)
    if events is None or not n:
        return {}
    blocks: Dict[str, List[Dict[str, float]]] = {}
    for e in sorted(events(names=(COUNTER,)), key=lambda e: e["ts"]):
        blocks.setdefault(e.get("id"), []).append(e.get("args") or {})
    return {b: rows[-n:] for b, rows in blocks.items()}
