"""The driver and the two references on the CPU at tiny widths, and that
a cell, a configuration, a traffic mix and a per-layer metric are added
by new files plus one entry each, in a temporary copy.

A CPU run checks answers and counts; the rates it prints are never
results (``run.py`` refuses any platform but a TPU, see
test_cellbench_manifest.py).
"""
import json
import math
import os
import time

import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return util.tiny_copy(tmp_path_factory.mktemp("cellbench"))


def _run(root, cell_name, seconds=1.5, seed=3):
    import mxnet_tpu as mx
    cell = manifest.Manifest(root).cell(cell_name)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    result = driver.run(cell, [mx.cpu(0)], seed, seconds, False,
                        time.perf_counter(), FAKE_PEAKS, lines.append)
    return cell, result, lines


@pytest.mark.parametrize("cell_name,rate_metric,unit_samples", [
    ("tiny-dev", "train_img_per_s", 8),
    ("tiny-lstm", "train_tok_per_s", None)])
def test_a_cell_added_as_files_runs_and_is_correct(copy, cell_name,
                                                    rate_metric,
                                                    unit_samples):
    """The fifth and sixth cells and the third and fourth configurations
    exist only as files and entries of the copy; the reference check
    (float32 on the CPU, so tight) and the learning check both pass."""
    cell, result, lines = _run(copy, cell_name)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 10
    e2e = result["_e2e"]
    assert set(e2e) == {rate_metric, "setup_s"}
    assert e2e[rate_metric] > 0 and e2e["setup_s"] > 0
    obs = result["_obs"]
    assert obs["compile"]["in_window"] == 0
    assert obs["steps_in_window"] >= 5
    assert len(obs["gaps_ms"]) == obs["steps_in_window"]
    assert 0 <= obs["feed_s"] < obs["window_s"]
    ref = result["_reference"]
    assert ref["loss"] == pytest.approx(ref["reference_loss"], rel=1e-4)
    assert all(err < 0.06 for err in ref["updates"].values())
    assert result["device"]["platform"] == "cpu"
    if unit_samples:
        # every step trains one whole batch
        assert e2e[rate_metric] * obs["window_s"] == pytest.approx(
            unit_samples * obs["steps_in_window"])


def test_the_same_seed_gives_the_same_traffic(copy):
    import mxnet_tpu as mx
    import numpy as np
    cell = manifest.Manifest(copy).cell("tiny-lstm")
    gen = manifest.load_module("generators", cell.traffic["generator"],
                               cell.bench_dir)

    def first_batches(seed):
        t = gen.build(cell.traffic, cell.config, seed, [mx.cpu(0)], None)
        return [t.next().data[0].asnumpy() for _ in range(3)]

    a, b, c = first_batches(5), first_batches(5), first_batches(6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(x.shape == y.shape and np.array_equal(x, y)
                   for x, y in zip(a, c))


def test_a_wrong_reference_tolerance_fails_the_run(copy):
    """``correct`` really rests on the reference: against a reference
    that answers for another learning rate the run is not correct."""
    root = copy
    ref_path = os.path.join(root, "benchmark", "reference", "resnet-tiny.py")
    with open(ref_path) as f:
        src = f.read()
    try:
        with open(ref_path, "w") as f:
            f.write(src.replace('lr, wd = optimizer["learning_rate"]',
                                'lr, wd = 2 * optimizer["learning_rate"]'))
        _, result, lines = _run(root, "tiny-dev", seconds=0.5)
        assert result["correct"] is False
        assert any("FAILED" in ln for ln in lines)
    finally:
        with open(ref_path, "w") as f:
            f.write(src)


def test_a_per_layer_metric_is_added_as_a_file_and_an_entry(copy):
    """A new reader file plus one ``per_layer`` entry: the harness
    reports it; a reader that finds nothing to read is left out."""
    import run as bench_run
    root = copy
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "steps_per_s.py"), "w") as f:
        f.write('LAYER = "entry points"\nUNIT = "1/s"\nBETTER = "higher"\n'
                'SOURCE = "host_clock"\nDRIVERS = ("train_fit",)\n\n\n'
                'def read(obs):\n'
                '    return obs["steps_in_window"] / obs["window_s"]\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                             "better": "higher", "source": "host_clock",
                             "layer": "entry points",
                             "moves": "train_img_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    cell, result, _ = _run(root, "tiny-dev", seconds=0.5)
    got = bench_run.layer_metrics(cell, result["_obs"])
    assert got["steps_per_s"]["unit"] == "1/s"
    assert got["steps_per_s"]["value"] > 0
    assert "step_ms_p50.img" in got and "compiles_in_window" in got
    # no trace was taken: the device readers find nothing and are left
    # out, and the harness can say which
    for absent in ("device_step_ms.img", "mfu.img", "dispatch_ms_p50.img"):
        assert absent not in got
        assert absent in bench_run.absent_metrics(cell, got)
    assert "steps_per_s" not in bench_run.absent_metrics(cell, got)
    assert "collective_ms" not in got
    # a metric that moves another cell's rate is not this cell's
    assert "step_ms_p50.tok" not in got and "step_ms_p50.fed" not in got
    assert all(math.isfinite(m["value"]) for m in got.values())


def test_layer_metrics_read_a_trace_reduction():
    """The device readers, on a reduction made by hand."""
    import run as bench_run
    cell = manifest.Manifest().cell(
        next(w["name"] for w in manifest.Manifest().doc["workloads"]
             if w["chips"] == 4))
    obs = {"driver": "train_fit", "gaps_ms": [50.0] * 2000, "clean_s": 100.0,
           "feed_s": 2.0, "reset_s": 0.5, "resets": 3, "window_s": 100.0,
           "steps_in_window": 2000, "dispatch_ms": [1.0, 2.0, 3.0],
           "trace": {"window_s": 2.0, "steps": 40, "busy_s": 1.8,
                     "collective_s": 0.2, "collective_exposed_s": 0.05},
           "traced_rate": 8000.0, "flops_per_sample": 24.5e9, "chips": 4,
           "peaks": {"bf16_flops_per_s": 197e12},
           "compile": {"in_window": 0, "at_setup": 90, "compiled": 0},
           "memory": {"peak_bytes": 6 * 2 ** 30, "peak_in_use_bytes": 2 ** 31,
                      "peak_reserved_bytes": 2 ** 32}, "rate": 8000.0}
    got = {k: v["value"] for k, v in bench_run.layer_metrics(cell,
                                                             obs).items()}
    assert got["step_ms_p50.img"] == 50.0
    # every metric listed for the cell is on the line: whoever checks a
    # traced line refuses one that lacks any
    assert bench_run.absent_metrics(cell, got) == []
    # the tail's reader has no entry for today's cells (none has 1000
    # clean steps in a window); it reads where the samples allow
    p99 = manifest.load_module("layer_metrics", "step_ms_p99",
                               cell.bench_dir)
    assert p99.read(obs)[0] == 50.0
    assert p99.read(dict(obs, gaps_ms=[50.0] * 999)) is None
    assert got["feed_wait_share.img"] == pytest.approx(2.0)
    assert got["feed_reset_share.img"] == pytest.approx(0.5)
    assert got["dispatch_ms_p50.img"] == 2.0
    assert got["device_step_ms.img"] == pytest.approx(45.0)
    assert got["device_idle_share.img"] == pytest.approx(10.0)
    assert got["mfu.img"] == pytest.approx(
        100 * 8000 * 24.5e9 / (4 * 197e12))
    assert got["collective_ms"] == pytest.approx(5.0)
    assert got["collective_exposed_share"] == pytest.approx(25.0)
    assert got["peak_hbm_gib.img"] == pytest.approx(6.0)
    assert got["compiles_in_window"] == 0.0
    assert got["programs_at_setup"] == 90.0


class _FakeProfiler:
    """Stands in for ``jax.profiler`` under TraceControl."""

    def __init__(self):
        self.calls = []

    class ProfileOptions:
        pass

    def start_trace(self, out_dir, profiler_options=None):
        self.calls.append("start")

    def stop_trace(self):
        self.calls.append("stop")


@pytest.mark.parametrize("step_s,callbacks,want_steps,by", [
    (0.05, 80, 49, "on_step"),      # fast steps: the 50-step cap
    (1.4, 12, 3, "on_step"),        # slow steps: three whole ones, over 3 s
    (2.7, 4, 2, "finish"),          # the window closed first: what it holds
])
def test_trace_control_holds_whole_steps(monkeypatch, tmp_path, step_s,
                                         callbacks, want_steps, by):
    import jax
    driver = manifest.load_module("drivers", "train_fit")
    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    tc = driver.TraceControl(str(tmp_path / "trace"), start_at=0.0)
    for i in range(callbacks):
        tc.on_step(i, (i + 1) * step_s)
    assert tc.state == ("done" if by == "on_step" else "open")
    tc.finish(callbacks - 1)
    assert tc.state == "done" and fake.calls == ["start", "stop"]
    # callback 0 starts the profiler, callback 1's annotation is the
    # first the trace holds; whole steps follow it
    assert tc.first_step == 1
    assert tc.last_step - tc.first_step == want_steps
