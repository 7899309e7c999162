"""tools/bench_gate.py: the bench-trajectory regression gate.

Tier-1 contracts from ISSUE 8, on a synthetic trajectory written into
tmp_path (no chip record is checked in): the gate exits 0 when the
incomparable rounds — a probe outside the physical band, a nonzero rc,
a round that predates the path label — are skipped rather than counted
as regressions, exits nonzero when the newest round regresses a gated
metric past the threshold, and treats a silently dropped bench leg as a
failure too.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "tools", "bench_gate.py")

sys.path.insert(0, os.path.join(REPO, "tools"))
import bench_gate  # noqa: E402

_HEAD = {"metric": "resnet50_train_throughput_per_chip",
         "unit": "images/sec"}
_GOOD = dict(_HEAD, path="module_api_fused", value=1000.0, vs_baseline=7.8,
             mfu=0.30, hfu=0.31, peak_tflops=90.0,
             lstm_tokens_per_sec=1.0e6, lstm_mfu=0.20,
             lstm_h1024_tokens_per_sec=4.0e5, lstm_h1024_mfu=0.70)
# (rc, parsed) per round: r01 predates the path label, r02's probe is
# outside the physical band, r03 lost the device, r04/r05 are comparable
_ROUNDS = {
    1: (0, dict(_HEAD, value=239000.0, vs_baseline=1867.0)),
    2: (0, dict(_HEAD, path="module_api_fused", value=331000.0,
                vs_baseline=2586.0, mfu=0.06, peak_tflops=66500.8)),
    3: (2, dict(_HEAD, value=0.0, vs_baseline=0.0,
                error="device watchdog timeout")),
    4: (0, _GOOD),
    5: (0, dict(_GOOD, value=1100.0, vs_baseline=8.6, mfu=0.33,
                io_host_cores=1, io_jpeg_img_s=650.0)),
}


def _run(args, cwd):
    return subprocess.run([sys.executable, GATE] + args, cwd=str(cwd),
                          capture_output=True, text=True, timeout=60)


def _write_round(directory, n, rc, parsed):
    with open(str(directory / ("BENCH_r%02d.json" % n)), "w") as f:
        json.dump({"n": n, "rc": rc, "parsed": parsed}, f)


@pytest.fixture()
def trajectory(tmp_path):
    """Five synthetic rounds, three of them incomparable."""
    for n, (rc, parsed) in _ROUNDS.items():
        _write_round(tmp_path, n, rc, parsed)
    return tmp_path


def test_gate_passes_and_skips_incomparable_rounds(trajectory):
    res = _run([], trajectory)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "bench_gate: OK" in res.stdout
    # the incomparable rounds are skipped with a reason, not gated
    assert "BENCH_r02.json (clock-suspect" in res.stdout
    assert "BENCH_r03.json (rc=2)" in res.stdout
    assert "BENCH_r01.json (different bench configuration" in res.stdout


def _synthetic_round(tmp_path, n=9, scale=None, drop=None):
    parsed = dict(_ROUNDS[max(_ROUNDS)][1])
    if scale:
        for k, s in scale.items():
            parsed[k] = parsed[k] * s
    for k in drop or ():
        parsed.pop(k, None)
    _write_round(tmp_path, n, 0, parsed)


def test_gate_fails_on_synthetic_regression(trajectory):
    _synthetic_round(trajectory, scale={"value": 0.5})
    res = _run([], trajectory)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "REGRESS" in res.stdout and "value" in res.stdout


def test_gate_fails_on_dropped_metric(trajectory):
    _synthetic_round(trajectory, drop=["lstm_tokens_per_sec"])
    res = _run([], trajectory)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "MISSING" in res.stdout


def test_gate_threshold_and_allowlist(trajectory):
    # a 5% dip passes the default 10% threshold ...
    _synthetic_round(trajectory, scale={"value": 0.95})
    assert _run([], trajectory).returncode == 0
    # ... fails a 2% threshold ...
    assert _run(["--threshold", "2"], trajectory).returncode == 1
    # ... and passes even that when the allowlist excludes `value`
    assert _run(["--threshold", "2", "--metrics", "mfu"],
                trajectory).returncode == 0


def test_gate_improvements_pass(trajectory):
    _synthetic_round(trajectory, scale={"value": 1.5, "mfu": 1.2})
    res = _run([], trajectory)
    assert res.returncode == 0, res.stdout + res.stderr


def test_lower_is_better_direction(tmp_path):
    for n, lat in ((1, 10.0), (2, 30.0)):
        with open(str(tmp_path / ("BENCH_r%02d.json" % n)), "w") as f:
            json.dump({"rc": 0, "parsed": {"metric": "m", "unit": "ms",
                                           "path": "p",
                                           "latency_ms": lat}}, f)
    # higher-is-better default: 10 -> 30 reads as +200%
    assert _run([], tmp_path).returncode == 0
    # flipped: 30ms against a best-prior 10ms is a 200% regression
    assert _run(["--lower-is-better", "latency_ms"],
                tmp_path).returncode == 1


def test_zero_floor_metric_regression_is_caught(tmp_path):
    """ISSUE 15: a ZERO_FLOOR metric (the discrete 'gated at 0' class
    — dropped requests, steady-loop compiles) must fail on ANY nonzero
    value, not ride the no-percent-scale free pass; staying at 0
    passes; continuous lower-is-better metrics (chaos_overhead_frac)
    are exempt so a noise-floor 0.0 cannot condemn later runs."""
    for n, drops in ((1, 0.0), (2, 1.0)):
        with open(str(tmp_path / ("BENCH_r%02d.json" % n)), "w") as f:
            json.dump({"rc": 0, "parsed": {"metric": "m", "unit": "q",
                                           "path": "p",
                                           "serve_failover_dropped":
                                           drops}}, f)
    res = _run([], tmp_path)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "serve_failover_dropped" in res.stdout
    # no threshold can wave a zero-floor hit through
    assert _run(["--threshold", "500"], tmp_path).returncode == 1
    with open(str(tmp_path / "BENCH_r02.json"), "w") as f:
        json.dump({"rc": 0, "parsed": {"metric": "m", "unit": "q",
                                       "path": "p",
                                       "serve_failover_dropped": 0.0}},
                  f)
    assert _run([], tmp_path).returncode == 0
    # continuous metric: prior clamped to 0.0, later normal noise value
    # must still pass (not in ZERO_FLOOR)
    for n, frac in ((1, 0.0), (2, 0.01)):
        with open(str(tmp_path / ("BENCH_r%02d.json" % n)), "w") as f:
            json.dump({"rc": 0, "parsed": {"metric": "m", "unit": "q",
                                           "path": "p",
                                           "chaos_overhead_frac": frac}},
                      f)
    assert _run([], tmp_path).returncode == 0


def test_abs_ceiling_metric_is_gated_without_priors(tmp_path):
    """ISSUE 17: an ABS_CEILING metric fails above its ceiling even on
    the FIRST run carrying it (no trajectory, no percent scale) and
    regardless of --threshold; at/below the ceiling it gates normally."""
    def write(n, frac):
        with open(str(tmp_path / ("BENCH_r%02d.json" % n)), "w") as f:
            json.dump({"rc": 0, "parsed": {"metric": "m", "unit": "q",
                                           "path": "p",
                                           "online_capture_overhead_frac":
                                           frac}}, f)
    write(1, 0.05)                  # first-ever run, over the ceiling
    res = _run([], tmp_path)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "absolute ceiling" in res.stdout
    assert _run(["--threshold", "500"], tmp_path).returncode == 1
    write(1, 0.0)                   # under the ceiling: NEW, passes
    assert _run([], tmp_path).returncode == 0
    write(2, 0.015)                 # noise over a 0.0 prior, under the
    assert _run([], tmp_path).returncode == 0   # ceiling: passes (the
    # continuous zero-clamp exemption — not in ZERO_FLOOR)
    write(2, 0.03)                  # later run crosses the ceiling
    assert _run([], tmp_path).returncode == 1


def test_invalid_newest_run_is_an_error(tmp_path):
    with open(str(tmp_path / "BENCH_r01.json"), "w") as f:
        json.dump({"rc": 2, "parsed": {}}, f)
    res = _run([], tmp_path)
    assert res.returncode not in (0, 1)
    assert "not gateable" in res.stderr + res.stdout


def test_metrics_typo_fails_with_clear_message(trajectory):
    res = _run(["--metrics", "no_such_metric"], trajectory)
    assert res.returncode == 1
    assert "present in no run" in res.stdout


def test_gate_api_rows_shape(trajectory):
    runs = bench_gate.load_runs(str(trajectory), "BENCH_r*.json")
    rows, regressions, newest, priors = bench_gate.gate(runs, threshold=10.0)
    assert newest.name == "BENCH_r%02d.json" % max(_ROUNDS)
    assert not regressions
    keys = {r[0] for r in rows}
    assert "value" in keys and "peak_tflops" not in keys
