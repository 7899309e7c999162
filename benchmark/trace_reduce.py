"""From a profiler trace to device metrics.

The reduction works on plain events, ``(name, start_ns, duration_ns)``,
so that it can be checked on hand-built events and on the small recorded
trace beside the tests; ``load_xplane`` is the thin adapter from the
``.xplane.pb`` the JAX profiler writes.  Definitions (the
``on-chip-measurement`` guide's):

* the traced window runs from the end of the first ``bench:batch_end``
  annotation the trace holds to the end of the last, so it holds whole
  steps of the fit loop and nothing of the profiler's start and stop;
* busy: the union of the intervals in which an operation ran on a
  device, clipped to the traced window; idle share = 1 - busy / window;
* a collective's exposed time: the part of its intervals during which no
  other operation runs on that device;
* an idle gap is labelled by the host annotation (``bench:*``, written
  by the harness with ``jax.profiler.TraceAnnotation``) that covers the
  largest part of it, else ``fit-loop-other``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns
Interval = Tuple[int, int]              # start_ns, end_ns

STEP_ANNOTATION = "bench:batch_end"
UNLABELLED_GAP = "fit-loop-other"
BREAKDOWN_ENTRIES = 10

# XLA's names for operations that move data between chips.  The async
# forms end in -start/-done; numbering (".3") follows.
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|ragged-all-to-all)([-.]|$)")


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def short_name(raw: str) -> str:
    """The profiler names a device operation by its whole HLO line
    (``%fusion.70 = (f32[256]{..}, bf16[128,256,56,56]{..}) fusion(...),
    kind=kOutput, ...``).  Keep the instruction's name, its opcode and
    the largest array it produces: ``fusion.70 fusion
    bf16[128,256,56,56]``."""
    name, sep, rest = raw.partition(" = ")
    name = name.strip().lstrip("%")
    if not sep:
        return name
    op = _OPCODE.search(rest)
    produced = rest[:op.start()] if op else rest
    best, best_n = "", -1
    for dtype, dims in _SHAPE.findall(produced):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        if n > best_n:
            best, best_n = "%s[%s]" % (dtype, dims), n
    return " ".join(x for x in (name, op.group(1) if op else "", best) if x)


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.match(name.lstrip("%")))


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intervals_of(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def busy_ns(events: Iterable[Event], window: Interval) -> int:
    return total(merge(clip(intervals_of(events), window)))


def idle_gaps(events: Iterable[Event], window: Interval) -> List[Interval]:
    """The parts of the window in which nothing ran on the device."""
    busy = merge(clip(intervals_of(events), window))
    return subtract([window], busy)


def label_gap(gap: Interval, annotations: Iterable[Event]) -> str:
    """Which host annotation covered most of this gap."""
    best, best_ns = UNLABELLED_GAP, 0
    covered: Dict[str, int] = {}
    for name, s, d in annotations:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > 0:
            covered[name] = covered.get(name, 0) + ov
    for name, ns in sorted(covered.items()):
        if ns > best_ns:
            best, best_ns = name, ns
    # a label must explain at least half of the gap
    return best if 2 * best_ns >= gap[1] - gap[0] else UNLABELLED_GAP


def collective_ns(events: Sequence[Event], window: Interval
                  ) -> Tuple[int, int]:
    """(time in collectives, the part of it with no other operation
    running) on one device, inside the window."""
    coll = merge(clip(intervals_of(e for e in events
                                   if is_collective(e[0])), window))
    other = merge(clip(intervals_of(e for e in events
                                    if not is_collective(e[0])), window))
    return total(coll), total(subtract(coll, other))


def top_ops(events: Iterable[Event], window: Interval,
            k: int = BREAKDOWN_ENTRIES) -> List[List]:
    """[name, seconds] of the operations that took most device time."""
    acc: Dict[str, int] = {}
    for name, s, d in events:
        ov = min(s + d, window[1]) - max(s, window[0])
        if ov > 0:
            acc[name] = acc.get(name, 0) + ov
    ranked = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [[n, ns / 1e9] for n, ns in ranked]


def find_window(annotations: Iterable[Event]) -> Optional[Interval]:
    """From the end of the first step annotation to the end of the
    last; None where the trace holds fewer than two."""
    ends = sorted(s + d for name, s, d in annotations
                  if name == STEP_ANNOTATION)
    if len(ends) < 2:
        return None
    return (ends[0], ends[-1])


def reduce_trace(devices: Dict[str, List[Event]],
                 annotations: List[Event],
                 window: Optional[Interval] = None) -> Dict:
    """Everything the per-layer readers and the result line take from a
    trace.  ``devices`` maps a device's name to its operation events;
    ``annotations`` are the host spans.  Times in seconds."""
    window = window or find_window(annotations)
    if window is None:
        raise ValueError("the trace holds fewer than two %r annotations "
                         "and no window was given" % STEP_ANNOTATION)
    if not devices:
        raise ValueError("the trace holds no device plane")
    wlen = window[1] - window[0]
    # a step ends where its annotation ends; the first one opens the window
    steps = sum(1 for n, s, d in annotations
                if n == STEP_ANNOTATION and window[0] < s + d <= window[1])
    per_dev = {}
    for dev, events in sorted(devices.items()):
        coll, exposed = collective_ns(events, window)
        per_dev[dev] = {"busy_s": busy_ns(events, window) / 1e9,
                        "collective_s": coll / 1e9,
                        "collective_exposed_s": exposed / 1e9}
    n = len(per_dev)
    # the breakdown reads the first device: under data parallelism every
    # device runs the same program
    first = sorted(devices)[0]
    gaps = sorted(idle_gaps(devices[first], window),
                  key=lambda g: (g[0] - g[1], g[0]))[:BREAKDOWN_ENTRIES]
    return {
        "window_s": wlen / 1e9,
        "steps": steps,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "collective_s": sum(d["collective_s"] for d in per_dev.values()) / n,
        "collective_exposed_s":
            sum(d["collective_exposed_s"] for d in per_dev.values()) / n,
        "per_device": per_dev,
        "device_ops": top_ops(devices[first], window),
        "idle_gaps": [[label_gap(g, annotations), (g[1] - g[0]) / 1e9]
                      for g in gaps],
    }


# -- the adapter from the profiler's file -------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench:"


def load_xplane(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(devices, annotations) from an ``.xplane.pb``: the ``XLA Ops``
    line of every ``/device:TPU:<n>`` plane, and every host event whose
    name starts with ``bench:``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    annotations: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append((e.name, int(e.start_ns),
                                            int(e.duration_ns)))
    return devices, annotations


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]
