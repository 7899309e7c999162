#!/usr/bin/env python3
"""Whose fault is the idle device: the longest device-idle gaps of a
profile, each with the program span that covers most of it.

    mx.profiler.profiler_set_config(filename="/tmp/prof")
    mx.profiler.profiler_set_state("run")
    mod.fit(...)                       # a few dozen steps are enough
    mx.profiler.profiler_set_state("stop")

    python tools/idle_gaps.py /tmp/prof [--top 10]

Every ``mx.trace.span`` is also a profiler annotation, so the profile's
host plane holds the program's spans on the device trace's own clock.
A gap gets the child of ``fit:step`` that covers most of it, the span
below that (``fused:dispatch``, ...; ``fit-loop-other`` where none covers
half of the gap) and its split among the children of ``fit:step``.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import trace_reduce as tr  # noqa: E402  (the interval arithmetic)

PREFIXES = ("fit:", "fused:", "executor:", "optimizer:", "superstep:")
STEP = "fit:step"                   # covers everything: never a label


def load(path):
    """(operations of the first TPU plane, program spans of the host
    planes) from an ``.xplane.pb``, as (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData
    device, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    device[plane.name] = [
                        (tr.short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(PREFIXES)]
    if not device:
        raise SystemExit("%s holds no /device:TPU plane" % path)
    return device[min(device)], spans


def shares(gap, spans):
    """{span name: share of the gap it covers}, largest first."""
    covered = {}
    for name, s, d in spans:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > 0:
            covered[name] = covered.get(name, 0) + ov
    return {n: ns / (gap[1] - gap[0]) for n, ns in
            sorted(covered.items(), key=lambda kv: (-kv[1], kv[0]))}


def longest_gaps(device_events, spans, top=10):
    """[(gap_ns, ns after the first operation's start, loop label, inner
    label, shares of the loop's spans)], longest first, between the first
    operation's start and the last one's end."""
    if not device_events:
        return []
    window = (min(s for _, s, _ in device_events),
              max(s + d for _, s, d in device_events))
    loop = [e for e in spans if e[0].startswith("fit:") and e[0] != STEP]
    inner = [e for e in spans if not e[0].startswith("fit:")]
    gaps = sorted(tr.idle_gaps(device_events, window),
                  key=lambda g: (g[0] - g[1], g[0]))[:top]
    return [(g[1] - g[0], g[0] - window[0], tr.label_gap(g, loop),
             tr.label_gap(g, inner), shares(g, loop)) for g in gaps]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("profile_dir", help="profiler_set_config's filename=")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    path = tr.find_xplane(args.profile_dir)
    device_events, spans = load(path)
    print("%s: %d device operations, %d program spans"
          % (path, len(device_events), len(spans)))
    print("%10s %12s  %-22s %-24s %s"
          % ("gap_ms", "at_ms", "in", "below it", "split"))
    for gap_ns, at_ns, loop, inner, split in longest_gaps(
            device_events, spans, args.top):
        print("%10.3f %12.3f  %-22s %-24s %s"
              % (gap_ns / 1e6, at_ns / 1e6, loop,
                 "-" if inner == tr.UNLABELLED_GAP else inner,
                 " ".join("%s %.0f%%" % (n[4:], 100 * v)
                          for n, v in split.items())))


if __name__ == "__main__":
    main()
