"""The driver-facing verification harness must be chip-proof.

A wedged device once cost a round both driver artifacts because
dryrun_multichip touched the real backend before forcing the CPU and
bench.py had no bounded preflight.  These tests pin the fixes:

  - dryrun_multichip forces jax_platforms=cpu BEFORE any backend init and
    runs green in a subprocess with no env help (hermetic);
  - its watchdog emits a parseable failure line and exits 3 on stall;
  - bench.device_preflight bounds a wedged device to seconds, in a child;
  - a bench.py leg that raises is recorded, the run goes on, and the exit
    code is non-zero after the JSON line is printed.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_failed_leg_is_recorded_and_run_continues(capsys):
    """A leg that raises lands in ``failed`` with its traceback on
    stderr; the line keeps what earlier legs measured and later legs
    still run."""
    import bench
    line, failed = {"value": 1.0}, []

    def boom():
        raise ValueError("leg exploded")

    bench._run_leg("bad", boom, line, failed)
    bench._run_leg("good", lambda: {"good_metric": 2.0}, line, failed)
    assert failed == ["bad"]
    assert line == {"value": 1.0, "good_metric": 2.0}
    err = capsys.readouterr().err
    assert "bad leg failed" in err and "ValueError: leg exploded" in err


@pytest.mark.parametrize("resnet_ok", [True, False])
def test_main_exit_code_follows_failed_legs(monkeypatch, capsys, resnet_ok):
    """main() prints the JSON line (device named) and THEN exits: 0 when
    every leg ran, 1 when one raised — b128 failing is a failure, with
    no walk-down to smaller batches."""
    import bench
    import bench_lstm

    def resnet():
        if not resnet_ok:
            raise RuntimeError("b128 out of memory")
        return {"value": 100.0, "peak_tflops": 50.0}

    # main() setdefault()s this; pin it here so monkeypatch restores it
    # and the rest of the session does not train in bf16
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    monkeypatch.setattr(bench, "device_preflight", lambda: None)
    monkeypatch.setattr(bench, "_resnet_leg", resnet)
    monkeypatch.setattr(bench, "_lstm_leg",
                        lambda prefix, peak, mflop, **kw:
                        {prefix + "_tokens_per_sec": 1.0})
    monkeypatch.setattr(bench_lstm, "superstep_leg_json", lambda k: {})
    monkeypatch.setattr(bench, "_LATER_LEGS", ())
    with pytest.raises(SystemExit) as exc:
        bench.main()
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["platform"] == "cpu" and out["device_count"] >= 1
    assert out["lstm_tokens_per_sec"] == 1.0      # later legs still ran
    assert out["lstm_h1024_tokens_per_sec"] == 1.0
    if resnet_ok:
        assert exc.value.code == 0 and out["failed_legs"] == []
        assert out["value"] == 100.0
    else:
        assert exc.value.code == 1
        assert out["failed_legs"] == ["train-batch"] and out["value"] == 0.0


def test_preflight_bounds_a_wedged_device(monkeypatch):
    """A child that never answers must come back as a diagnosis string in
    ~timeout seconds, not hang."""
    import bench
    monkeypatch.setattr(bench, "_PREFLIGHT_CODE",
                        "import time; time.sleep(3600)")
    diag = bench.device_preflight(timeout_s=2, retries=0)
    assert diag is not None and "timed out" in diag


def test_preflight_passes_on_healthy_backend(monkeypatch):
    import bench
    monkeypatch.setattr(bench, "_PREFLIGHT_CODE", "print('ok')")
    assert bench.device_preflight(timeout_s=30, retries=0) is None


def test_preflight_reports_crash_rc(monkeypatch):
    import bench
    monkeypatch.setattr(bench, "_PREFLIGHT_CODE",
                        "import sys; sys.stderr.write('boom'); sys.exit(7)")
    diag = bench.device_preflight(timeout_s=30, retries=0)
    assert diag is not None and "rc=7" in diag and "boom" in diag


def test_preflight_rejects_silent_cpu_fallback():
    """An absent/broken accelerator plugin silently falls back to CPU;
    the preflight child must treat that as UNHEALTHY (publishing CPU
    throughput as chip numbers would be worse than failing).  Run the
    real preflight code with the platform pinned to cpu."""
    import bench
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", bench._PREFLIGHT_CODE],
                       env=env, cwd=REPO, timeout=120,
                       capture_output=True, text=True)
    assert r.returncode == 8, (r.returncode, r.stderr[-300:])
    assert "CPU fallback" in r.stderr


def test_bench_timeout_preserves_measured_primary(monkeypatch, capsys):
    """A wedge in a later leg (probe/LSTM) must not zero out an
    already-measured ResNet number."""
    import bench
    monkeypatch.setattr(bench, "_PARTIAL_LINE",
                        {"metric": "resnet50_train_throughput_per_chip",
                         "value": 123.4, "unit": "images/sec"})
    bench._bench_timeout("lstm")
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 123.4
    assert "later leg" in out["error"] and "phase=lstm" in out["error"]
    monkeypatch.setattr(bench, "_PARTIAL_LINE", None)
    bench._bench_timeout("train-batch")
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 0.0 and "phase=train-batch" in out["error"]


def test_watchdog_restart_not_stale():
    """stop() + untimed gap + start() must not fire from the old deadline,
    and stale loop threads must retire on restart (generation token)."""
    import time as _t
    from harness_watchdog import HeartbeatWatchdog
    fired = []
    wd = HeartbeatWatchdog(fired.append, exit_code=9, budget_s=30,
                           poll_s=0.05)
    wd.feed("a", seconds=0.01)
    wd.stop()
    _t.sleep(0.1)          # old deadline is now expired
    wd.start()             # must re-feed: no fire from the stale deadline
    _t.sleep(0.3)
    wd.stop()
    assert fired == []
    assert wd._gen == 1


def test_dryrun_watchdog_emits_parseable_failure():
    """Simulated stall: the watchdog must print the FAILED line and exit 3
    instead of eating the driver's budget."""
    code = (
        "import time\n"
        "import __graft_entry__ as g\n"
        "g._dryrun_wd = wd = g._make_dryrun_watchdog()\n"
        "wd._poll_s = 1\n"
        "wd.start()\n"
        "wd.feed('simulated', seconds=1)\n"
        "time.sleep(60)\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, timeout=90,
                       capture_output=True, text=True)
    assert r.returncode == 3
    assert "dryrun_multichip FAILED" in r.stdout
    assert "phase=simulated" in r.stdout


@pytest.mark.slow
def test_dryrun_multichip_hermetic_no_env_help():
    """The full 8-device dryrun must succeed in a fresh interpreter with
    JAX_PLATFORMS/XLA_FLAGS scrubbed — i.e. without the driver's env and
    regardless of real-chip health."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        cwd=REPO, env=env, timeout=600, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip OK" in r.stdout


def test_consistent_peak_statistic():
    """The probe's peak statistic: slow windows must not cap the peak
    (max over the consistent set), and one implausibly fast window must
    be discarded (bare max would crown it)."""
    from bench import consistent_peak

    # healthy windows: best consistent window wins
    assert consistent_peak([85.0, 88.0, 90.0, 87.0]) == 90.0
    # one slow window (background work): must not drag the peak down
    assert consistent_peak([40.0, 88.0, 90.0, 87.0]) == 90.0
    # one implausibly fast glitch: must NOT be selected
    assert consistent_peak([85.0, 88.0, 600.0, 87.0]) == 88.0
    # glitch plus slow window together
    assert consistent_peak([40.0, 88.0, 600.0, 87.0]) == 88.0
