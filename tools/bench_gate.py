#!/usr/bin/env python
"""Bench regression gate: newest BENCH_r*.json vs the best prior run.

The driver appends one ``BENCH_rNN.json`` per round; ROADMAP's open
bench questions ("watch the bench numbers") are only answerable if
someone actually compares the trajectory.  This tool does, mechanically::

    python tools/bench_gate.py                       # gate the repo root
    python tools/bench_gate.py --threshold 5 --metrics value,mfu
    python tools/bench_gate.py --dir /path --glob 'BENCH_r*.json'

For every gated metric it finds the BEST prior value across comparable
runs and compares the newest run against it; a drop of more than
``--threshold`` percent (default 10) on any gated metric prints a
REGRESS row and exits 1.  Metrics new in the newest run pass as NEW;
metrics the newest run dropped entirely are flagged MISSING (gated —
silently losing a bench leg is itself a regression).

Comparability filters (the trajectory contains known artifacts):

* runs with nonzero ``rc`` or no parsed metrics are skipped (a
  wedged-device round);
* runs whose headline ``metric``/``unit``/``path`` differ from the
  newest run's are skipped (a round that predates the path label);
* runs whose ``peak_tflops`` probe sits outside the physically sane
  band are skipped (a 66,500 "TF/s" probe is no chip's).

Config keys (``io_host_cores``, ``peak_tflops``, ...) are excluded from
gating by default; ``--metrics`` gives an explicit allowlist instead,
``--lower-is-better`` flips the direction for latency-style metrics.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional

# no single chip probes below 10 or above 1000 TF/s; a round whose probe
# did is not comparable (left for ROADMAP D8 with the other old-round
# filters)
PEAK_SANE_TFLOPS = (10.0, 1000.0)

# keys that describe the run rather than measure it — never gated unless
# explicitly allowlisted via --metrics
DEFAULT_IGNORE = {
    "n", "rc", "peak_tflops", "io_host_cores", "io_threads",
    "train_gflop_per_img_xla",
    # tracks `value` exactly (value / BASELINE); gating both would
    # double-report every headline move
    "vs_baseline",
}

# metrics where SMALLER is better, gated in that direction by default
# (merged with --lower-is-better): latencies, padding waste, and the
# quantized-serving accuracy delta (ISSUE 9: a growing top-1 delta is a
# quantization-quality regression even when its qps improves).  ISSUE 11
# adds the fused/unfused serve-step latencies — their
# RATIO (fused_step_speedup) gates higher-is-better like every speedup.
DEFAULT_LOWER_IS_BETTER = {
    "serve_p50_ms", "serve_p99_ms", "serve_pad_waste_frac",
    "serve_quant_top1_delta",
    "serve_decode_p99_ms", "serve_mux_p99_ms",
    "serve_mux_steady_compiles", "serve_router_restart_drops",
    "fused_step_ms", "unfused_step_ms",
    "embed_sparse_update_ms", "embed_naive_update_ms",
    "embed_sparse_step_ms", "embed_dense_step_ms",
    "train_recovery_s", "serve_failover_dropped",
    "chaos_overhead_frac", "faults_point_ns",
    # ISSUE 16 LLM-serving leg: inter-token latency (chunked prefill's
    # whole point is bounding it), per-stream KV memory and its paged/
    # dense ratio, and mid-generation stream drops (also zero-floored)
    "llm_p99_inter_token_ms", "llm_kv_bytes_per_stream",
    "llm_kv_bytes_per_stream_dense", "llm_kv_bytes_frac",
    "llm_dropped_streams",
    # ISSUE 17 online loop: capture-to-live freshness (plain and with
    # the absorbable chaos plan armed), dropped requests through the
    # rolling promotion (also zero-floored) and the capture seam's
    # flood cost (also ceilinged absolutely)
    "online_freshness_s", "online_freshness_chaos_s",
    "online_promote_dropped", "online_capture_overhead_frac",
    # ISSUE 18 multi-host legs: killed-host recovery seconds, the
    # auto-vs-hand sharding step-time ratio (<= 1.05 is the acceptance
    # bar) and its per-model step times; dist_scaling_eff_2proc stays
    # higher-is-better like every efficiency
    "dist_host_recovery_s", "shardsearch_vs_hand_frac",
    "shardsearch_cnn_hand_step_ms", "shardsearch_cnn_auto_step_ms",
    "shardsearch_lstm_hand_step_ms", "shardsearch_lstm_auto_step_ms",
    # ISSUE 19 routed-MoE leg: fused step times for the routed block
    # and its FLOP-matched dense equivalent; their RATIO
    # (moe_step_speedup) gates higher-is-better like every speedup, and
    # moe_expert_imbalance is absolutely ceilinged below
    "moe_step_ms", "moe_dense_step_ms",
    # ISSUE 20 joint-autotune leg: search wall time and its
    # amortization horizon (steps until the search pays for itself);
    # autotune_joint_speedup gates higher-is-better like every
    # speedup, and the kernel-search parity-gate failure count is
    # zero-floored below — one bitwise-parity failure anywhere is a
    # numerics regression, not a perf tradeoff
    "autotune_search_s", "autotune_amortize_steps",
    "kernelsearch_parity_fail",
}

# Discrete "gated at 0" metrics: a zero best prior means ANY nonzero
# newest value is a regression (dropped requests, steady-loop
# compiles).  Continuous lower-is-better metrics stay out — a noise
# floor that happens to clamp to 0.0 once must not condemn every
# later run (chaos_overhead_frac does exactly that).
ZERO_FLOOR = {
    "serve_router_restart_drops", "serve_mux_steady_compiles",
    "serve_failover_dropped", "llm_dropped_streams",
    "online_promote_dropped", "kernelsearch_parity_fail",
}

# Absolute ceilings, independent of any prior run: a newest value above
# the ceiling is a regression even on the very first run that carries
# the metric (no trajectory needed) and regardless of --threshold.
# online_capture_overhead_frac: the ISSUE 17 contract is that sampling
# live traffic costs serving at most 2% — a capture seam that drags
# more than that would quietly tax every request to feed retraining.
ABS_CEILING = {
    "online_capture_overhead_frac": 0.02,
    # moe_expert_imbalance: max/mean expert hits of the trained router
    # (1.0 = balanced).  A router collapsing onto few experts starves
    # the rest and un-earns the routed speedup — worse than 4x-on-8
    # is a balance regression regardless of any prior run.
    "moe_expert_imbalance": 4.0,
}


class GateError(Exception):
    """The gate cannot run at all (distinct from exit 1 = regression):
    main() turns this into exit 2."""


class Run:
    def __init__(self, path: str, doc: Dict):
        self.path = path
        self.name = os.path.basename(path)
        self.rc = doc.get("rc")
        parsed = doc.get("parsed")
        self.parsed = parsed if isinstance(parsed, dict) else {}

    def round_key(self):
        m = re.search(r"_r(\d+)", self.name)
        return (int(m.group(1)) if m else -1, self.name)

    def headline(self):
        return (self.parsed.get("metric"), self.parsed.get("unit"),
                self.parsed.get("path"))

    def metrics(self) -> Dict[str, float]:
        return {k: float(v) for k, v in self.parsed.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    def invalid_reason(self, ref: Optional["Run"] = None) -> Optional[str]:
        if self.rc not in (0, None):
            return "rc=%s" % self.rc
        if not self.metrics():
            return "no parsed metrics"
        peak = self.parsed.get("peak_tflops")
        if isinstance(peak, (int, float)) and peak and not (
                PEAK_SANE_TFLOPS[0] <= peak <= PEAK_SANE_TFLOPS[1]):
            return "clock-suspect probe (%.1f TF/s)" % peak
        if ref is not None and self.headline() != ref.headline():
            return "different bench configuration %r" % (self.headline(),)
        return None


def load_runs(directory: str, pattern: str) -> List[Run]:
    runs = []
    for path in glob.glob(os.path.join(directory, pattern)):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print("bench_gate: skipping unreadable %s (%s)" % (path, e),
                  file=sys.stderr)
            continue
        runs.append(Run(path, doc))
    runs.sort(key=Run.round_key)
    return runs


def gate(runs: List[Run], threshold: float, metrics=None,
         ignore=DEFAULT_IGNORE, lower_is_better=()):
    """-> (rows, regressions, newest, priors).  Each row:
    (metric, new value or None, best prior or None, prior run name,
    delta_pct or None, status)."""
    if not runs:
        raise GateError("bench_gate: no BENCH files found")
    newest = runs[-1]
    reason = newest.invalid_reason()
    if reason:
        raise GateError("bench_gate: newest run %s is not gateable (%s)"
                        % (newest.name, reason))
    priors = [r for r in runs[:-1] if r.invalid_reason(ref=newest) is None]
    new_metrics = newest.metrics()
    if metrics:
        gated = list(metrics)
    else:
        gated = sorted(set(new_metrics) - set(ignore)
                       | {k for r in priors for k in r.metrics()
                          if k not in ignore})
    rows, regressions = [], []
    for key in gated:
        best = None
        best_run = None
        for r in priors:
            v = r.metrics().get(key)
            if v is None:
                continue
            better = (best is None or
                      (v < best if key in lower_is_better else v > best))
            if better:
                best, best_run = v, r.name
        new = new_metrics.get(key)
        if new is None:
            if best is None:
                # only reachable via an explicit --metrics name that no
                # run carries — almost certainly a typo, but still a
                # failed gate (the named metric is unverifiable)
                rows.append((key, None, None, None, None, "ABSENT"))
                regressions.append(
                    "%s: named in --metrics but present in no run "
                    "(typo?)" % key)
            else:
                rows.append((key, None, best, best_run, None, "MISSING"))
                regressions.append("%s: present in %s, missing from %s"
                                   % (key, best_run, newest.name))
            continue
        ceiling = ABS_CEILING.get(key)
        if ceiling is not None and new > ceiling:
            regressions.append(
                "%s: %.6g exceeds absolute ceiling %.6g (gated "
                "independently of prior runs, threshold does not "
                "apply)" % (key, new, ceiling))
            rows.append((key, new, best, best_run, None, "REGRESS"))
            continue
        if best is None:
            rows.append((key, new, None, None, None, "NEW"))
            continue
        if best == 0:
            # a zero best prior has no percent scale — but for the
            # discrete gated-at-0 class (ZERO_FLOOR), ANY nonzero value
            # is a regression, recorded directly so no --threshold
            # (however large) can wave it through
            if key in ZERO_FLOOR and new > 0:
                regressions.append(
                    "%s: 0 -> %.6g (zero-floor metric: any nonzero "
                    "value is a regression, threshold does not apply)"
                    % (key, new))
                rows.append((key, new, best, best_run, None, "REGRESS"))
                continue
            delta = 0.0
        elif key in lower_is_better:
            delta = (best - new) / abs(best) * 100.0
        else:
            delta = (new - best) / abs(best) * 100.0
        status = "OK"
        if delta < -threshold:
            status = "REGRESS"
            regressions.append(
                "%s: %.6g -> %.6g (%+.1f%% vs best prior %s, threshold "
                "%.1f%%)" % (key, best, new, delta, best_run, threshold))
        rows.append((key, new, best, best_run, delta, status))
    return rows, regressions, newest, priors


def print_table(rows, newest, priors) -> None:
    print("bench_gate: %s vs best of %d comparable prior run(s) %s"
          % (newest.name, len(priors), [r.name for r in priors]))
    fmt = "  %-28s %14s %14s %-16s %9s  %s"
    print(fmt % ("metric", "newest", "best prior", "from", "delta%", ""))
    for key, new, best, best_run, delta, status in rows:
        print(fmt % (
            key,
            "%.6g" % new if new is not None else "-",
            "%.6g" % best if best is not None else "-",
            best_run or "-",
            "%+.1f" % delta if delta is not None else "-",
            status))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=".",
                    help="directory holding the BENCH files (default .)")
    ap.add_argument("--glob", default="BENCH_r*.json",
                    help="bench-file pattern (default BENCH_r*.json)")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="max tolerated regression, percent (default 10)")
    ap.add_argument("--metrics", default=None,
                    help="comma-separated allowlist; default: every "
                         "numeric metric minus the config keys")
    ap.add_argument("--ignore", default=None,
                    help="comma-separated keys to add to the default "
                         "ignore set")
    ap.add_argument("--lower-is-better", default=None,
                    help="comma-separated keys where smaller is better, "
                         "merged with the built-in latency/accuracy-delta "
                         "defaults")
    args = ap.parse_args(argv)

    def split(s):
        return [x for x in (s or "").split(",") if x]
    ignore = set(DEFAULT_IGNORE) | set(split(args.ignore))
    runs = load_runs(args.dir, args.glob)
    skipped = []
    if runs:
        ref = runs[-1]
        skipped = [(r.name, r.invalid_reason(ref=ref))
                   for r in runs[:-1] if r.invalid_reason(ref=ref)]
    try:
        rows, regressions, newest, priors = gate(
            runs, threshold=args.threshold, metrics=split(args.metrics),
            ignore=ignore,
            lower_is_better=(DEFAULT_LOWER_IS_BETTER
                             | set(split(args.lower_is_better))))
    except GateError as e:
        print(str(e), file=sys.stderr)
        return 2
    for name, why in skipped:
        print("bench_gate: skipping %s (%s)" % (name, why))
    print_table(rows, newest, priors)
    if regressions:
        print("\nbench_gate: FAIL — %d regression(s):" % len(regressions))
        for r in regressions:
            print("  " + r)
        return 1
    print("\nbench_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
