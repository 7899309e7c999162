"""The set-up's span tree, read from the program's own span ring
(``mxnet_tpu.trace.span_events`` of this process): the twin of
``fit_spans.py`` for everything before the window.

The program records ``fit:call`` around ``fit``, ``module:bind``,
``module:init_params``, ``module:init_optimizer`` and ``module:prepare``
around a module's set-up methods whoever calls them, each with the
module's number (``module``; a bucket's inner module carries its
owner's), and ``compile:trace``, ``compile:lower``, ``compile:backend``
for every program JAX compiles, in the ring of the thread that compiled.
The seven ``setup_*_s`` readers in ``layer_metrics/`` take their numbers
from here:

* the window opens at the start of the first of the window's steps
  (``fit_spans``' rule: the calling thread's last
  ``obs["steps_in_window"]`` ``fit:step`` spans with ``count`` 1);
* the training module is the ``module`` of the calling thread's last
  ``fit:call``; any module with an earlier span (the harness's
  reference check) is told from it by that number alone;
* spans that nest (a bucketing module's ``prepare`` around its buckets'
  ``bind``; a jitted function traced inside another's trace) count
  once, by the outermost.

A program that records no ``fit:call`` (an older commit) gives None and
the readers give None.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

CALL, STEP = "fit:call", "fit:step"
MODULE_SPANS = ("module:bind", "module:init_params", "module:init_optimizer",
                "module:prepare")
COMPILE_SPANS = ("compile:trace", "compile:lower", "compile:backend")
TOP_FUNS = 5
# ts and dur are float microseconds made from whole nanoseconds: a span
# that follows another at once may start a hair before the other's end
SLACK_US = 0.01


def _end(e) -> float:
    return e["ts"] + e["dur"]


def outermost(spans) -> List[Dict]:
    """``spans`` less those that start inside an earlier one of their
    thread, oldest first (a thread's spans nest or lie apart)."""
    out, open_until = [], {}
    for e in sorted(spans, key=lambda e: (e["ts"], -e["dur"])):
        if e["ts"] >= open_until.get(e["tid"], float("-inf")) - SLACK_US:
            out.append(e)
            open_until[e["tid"]] = _end(e)
    return out


def _seconds(spans) -> float:
    return sum(e["dur"] for e in spans) / 1e6


def read(obs) -> Optional[Dict]:
    """The set-up as the ring holds it, or None where it holds no
    ``fit:call`` of the calling thread or no step of the window.  Times
    are the ring's microseconds:

    ``opening``     start of the window's first step
    ``call``        the training module's ``fit:call`` span
    ``first``       start of the first span of any module
    ``train_first`` start of the first span of the training module
    ``set_up``      the training module's outermost ``module:*`` spans
                    that started before the opening
    ``warmup``      its ``fit:step`` spans before the opening
    ``compile``     name -> that name's outermost spans, every thread
    """
    try:
        from mxnet_tpu import trace
    except ImportError:
        return None
    tid = threading.get_ident()
    events = trace.span_events(
        names=(CALL, STEP) + MODULE_SPANS + COMPILE_SPANS)
    calls = sorted((e for e in events
                    if e["name"] == CALL and e["tid"] == tid),
                   key=lambda e: e["ts"])
    steps = sorted((e for e in events
                    if e["name"] == STEP and e["tid"] == tid),
                   key=lambda e: e["ts"])
    in_window = [e for e in steps
                 if (e.get("args") or {}).get("count") == 1]
    in_window = in_window[-int(obs["steps_in_window"]):] \
        if obs["steps_in_window"] else []
    if not calls or not in_window:
        return None
    call = calls[-1]
    module = call["args"]["module"]
    opening = in_window[0]["ts"]
    of_modules = [e for e in events
                  if e["name"] == CALL or e["name"] in MODULE_SPANS]
    mine = [e for e in of_modules if e["args"].get("module") == module]
    return {
        "opening": opening, "call": call,
        "first": min(e["ts"] for e in of_modules),
        "train_first": min(e["ts"] for e in mine),
        "set_up": outermost(e for e in mine if e["name"] in MODULE_SPANS
                            and e["ts"] < opening),
        "warmup": [e for e in steps if call["ts"] <= e["ts"] < opening],
        "compile": {name: outermost(e for e in events if e["name"] == name)
                    for name in COMPILE_SPANS},
    }


def compile_seconds(obs, name):
    """What a ``setup_compile_*_s`` reader returns: the seconds of
    ``name``'s spans that ended before the window opened, whole process,
    with the cut at the training module's first span (a span belongs to
    the side it started on), the ``top`` functions by seconds, how many
    spans started in the window, between its opening and ``fit``'s
    return (a window forbids them; a request for the step's table of
    device scopes AFTER the window traces the step once more, and is
    not counted), and the spans themselves for the reader's own extras.
    None where there is no set-up to read or the ring holds no span of
    that name (no listener: ``MXNET_TRACE=0`` at import)."""
    got = read(obs)
    if got is None or not got["compile"][name]:
        return None
    spans = [e for e in got["compile"][name] if _end(e) <= got["opening"]]
    before = [e for e in spans if e["ts"] < got["train_first"]]
    by_fun: Dict[str, float] = {}
    for e in spans:
        fun = str(e["args"].get("fun"))
        by_fun[fun] = by_fun.get(fun, 0.0) + e["dur"] / 1e6
    top = sorted(by_fun.items(), key=lambda kv: -kv[1])[:TOP_FUNS]
    extra = {
        "before_training_module_s": _seconds(before),
        "in_training_module_s": _seconds(spans) - _seconds(before),
        "top": [[fun, s] for fun, s in top],
        "in_window": sum(1 for e in got["compile"][name]
                         if got["opening"] <= e["ts"] < _end(got["call"])),
    }
    return _seconds(spans), extra, spans
