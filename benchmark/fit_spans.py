"""The window's ``fit:step`` spans and their children, read from the
program's own span ring (``mxnet_tpu.trace.span_events`` of this process).

``Module.fit`` records one ``fit:step`` per iteration of its loop, from
before the pull to after its bookkeeping, and inside it, not overlapping:
``fit:feed_next``, ``fit:forward_backward``, ``fit:update``,
``fit:update_metric``, ``fit:batch_end``.  The readers in
``layer_metrics/`` that name a span take their numbers from here, so the
step is split from the inside by the program that ran it, not timed from
outside by the harness's callback.  A program that records no
``fit:step`` (an older commit) gives no steps and the readers give None.
"""
from __future__ import annotations

import threading
from typing import Dict, List

STEP = "fit:step"
CHILDREN = ("fit:feed_next", "fit:forward_backward", "fit:update",
            "fit:update_metric", "fit:batch_end")
# ts and dur are float microseconds made from whole nanoseconds: a child
# that ends with its step may round a hair past it
SLACK_US = 0.01


def window_steps(obs) -> List[Dict[str, float]]:
    """One dict per step of the window, oldest first: ``fit:step`` and
    each child's name -> milliseconds (a child the step lacks: 0.0), and
    ``bucket_key`` -> the step's bucket (None where batches have none).

    The window's steps are the calling thread's last
    ``obs["steps_in_window"]`` ``fit:step`` spans with ``count`` 1: the
    harness ends the epoch at the window's deadline, and the pull that
    ends an epoch records no step."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return []
    tid = threading.get_ident()
    events = [e for e in trace.span_events(names=(STEP,) + CHILDREN)
              if e["tid"] == tid]
    steps = [e for e in events if e["name"] == STEP
             and (e.get("args") or {}).get("count") == 1]
    steps.sort(key=lambda e: e["ts"])
    steps = steps[-int(obs["steps_in_window"]):] \
        if obs["steps_in_window"] else []
    if not steps:
        return []
    children = sorted((e for e in events if e["name"] != STEP
                       and e["ts"] >= steps[0]["ts"]),
                      key=lambda e: e["ts"])
    out, i = [], 0
    for step in steps:
        t0, t1 = step["ts"], step["ts"] + step["dur"] + SLACK_US
        row = dict.fromkeys(CHILDREN, 0.0)
        row[STEP] = step["dur"] / 1e3
        row["bucket_key"] = step["args"].get("bucket_key")
        while i < len(children) and children[i]["ts"] < t0:
            i += 1
        while i < len(children) and children[i]["ts"] < t1:
            c = children[i]
            if c["ts"] + c["dur"] <= t1:
                row[c["name"]] += c["dur"] / 1e3
            i += 1
        out.append(row)
    return out


def median_of(steps, plus=(), minus=()):
    """What a reader returns: the median over ``steps`` (rows of
    ``window_steps``) of the sum of the ``plus`` spans less the ``minus``
    spans, in ms, with the number of steps; None where there is no step."""
    import stats
    if not steps:
        return None
    values = [sum(s[n] for n in plus) - sum(s[n] for n in minus)
              for s in steps]
    return stats.median(values), {"samples": len(values)}
