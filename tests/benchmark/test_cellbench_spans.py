"""The five readers of the program's ``fit:step`` span tree
(``benchmark/fit_spans.py`` and ``layer_metrics/{fit_step,metric_wait,
loop_other,feed_next,enqueue}_ms_p50.py``): on spans put into the ring by
hand, and on the ring a real ``fit`` leaves behind on the CPU at tiny
widths.  A CPU run checks the arithmetic; its times are never results.
"""
import json
import os
import threading
import time

import pytest

import cellbench_util as util
import manifest

import mxnet_tpu as mx

READERS = {"fit_step_ms_p50": "entry points",
           "metric_wait_ms_p50": "entry points",
           "loop_other_ms_p50": "entry points",
           "feed_next_ms_p50": "feed",
           "enqueue_ms_p50": "train step"}
# ms inside one hand-made step, scaled by the step's factor
FEED, FWD, UPD, METRIC, END, SLACK = 1.0, 2.0, 3.0, 40.0, 0.5, 0.25


@pytest.fixture(autouse=True)
def fresh_ring():
    mx.trace.reset()
    yield
    mx.trace.reset()


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _read(name, steps_in_window):
    got = _reader(name).read({"steps_in_window": steps_in_window})
    return got if got is None else (got[0], got[1]["samples"])


def _put_step(t0, factor=1.0, **attrs):
    """One ``fit:step`` with its five children back to back, then SLACK
    of the loop's own time; -> the clock after it (seconds)."""
    t = t0
    for name, ms in (("fit:feed_next", FEED), ("fit:forward_backward", FWD),
                     ("fit:update", UPD), ("fit:update_metric", METRIC),
                     ("fit:batch_end", END)):
        mx.trace.complete(name, t, ms * factor / 1e3, cat="train")
        t += ms * factor / 1e3
    t += SLACK * factor / 1e3
    mx.trace.complete("fit:step", t0, t - t0, cat="train",
                      **dict({"count": 1}, **attrs))
    return t


def _put_steps(factors, t0=100.0, gap_s=0.001, **kw):
    for f in factors:
        t0 = _put_step(t0, f, **kw) + gap_s
    return t0


WHOLE = FEED + FWD + UPD + METRIC + END + SLACK
EXPECTED = {"fit_step_ms_p50": WHOLE, "metric_wait_ms_p50": METRIC,
            "loop_other_ms_p50": END + SLACK, "feed_next_ms_p50": FEED,
            "enqueue_ms_p50": FWD + UPD}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_takes_the_median_over_the_windows_steps(name):
    _put_steps([1.0, 3.0, 2.0, 5.0, 4.0])
    value, samples = _read(name, 5)
    assert samples == 5
    assert value == pytest.approx(3.0 * EXPECTED[name], rel=1e-6)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_on_an_empty_ring(name):
    assert _read(name, 5) is None
    # children without a step (an older program's ring holds no fit:step)
    mx.trace.complete("fused:dispatch", 1.0, 0.002, cat="train")
    mx.trace.complete("fit:update_metric", 1.0, 0.002, cat="train")
    assert _read(name, 5) is None
    _put_steps([1.0])
    assert _read(name, 0) is None


def test_only_the_last_steps_in_window_are_counted():
    # warm-up steps ten times as long, then the window's four
    _put_steps([10.0] * 6 + [1.0, 2.0, 3.0, 4.0])
    value, samples = _read("fit_step_ms_p50", 4)
    assert samples == 4 and value == pytest.approx(2.5 * WHOLE, rel=1e-6)
    # a window that claims more steps than the ring holds reads them all
    assert _read("fit_step_ms_p50", 50)[1] == 10


def test_children_are_matched_to_their_own_step():
    import fit_spans
    end = _put_steps([1.0, 2.0])
    # a pull between steps (the one that ends an epoch) belongs to none
    mx.trace.complete("fit:feed_next", end, 0.5, cat="train", end=True)
    _put_steps([3.0], t0=end + 0.6)
    rows = fit_spans.window_steps({"steps_in_window": 3})
    assert [r["fit:feed_next"] for r in rows] == \
        pytest.approx([FEED, 2 * FEED, 3 * FEED], rel=1e-6)
    assert [r["fit:update_metric"] for r in rows] == \
        pytest.approx([METRIC, 2 * METRIC, 3 * METRIC], rel=1e-6)
    for r in rows:
        assert sum(r[c] for c in fit_spans.CHILDREN) < r["fit:step"]


def test_a_step_that_lacks_a_child_counts_it_as_zero():
    mx.trace.complete("fit:feed_next", 5.0, 0.001, cat="train")
    mx.trace.complete("fit:step", 5.0, 0.004, cat="train", count=1)
    assert _read("metric_wait_ms_p50", 1) == (0.0, 1)
    assert _read("loop_other_ms_p50", 1)[0] == pytest.approx(3.0)


def test_superstep_groups_and_other_threads_are_left_out():
    end = _put_steps([7.0, 7.0], count=4)          # K=4 groups
    t = threading.Thread(target=_put_steps, args=([9.0] * 3, end + 1.0))
    t.start()
    t.join()
    assert _read("fit_step_ms_p50", 3) is None
    _put_steps([1.0], t0=end + 5.0)
    assert _read("fit_step_ms_p50", 3) == (pytest.approx(WHOLE, rel=1e-6), 1)


def test_the_step_reader_says_whether_the_ring_wrapped():
    _put_steps([1.0] * 3)
    _, extra = _reader("fit_step_ms_p50").read({"steps_in_window": 3})
    assert extra == {"samples": 3, "ring_events": 18, "ring_dropped": 0}


def test_the_step_reader_reports_each_buckets_draws_and_median():
    t = 100.0
    for key, factor in ((10, 1.0), (30, 3.0), (10, 2.0), (40, 4.0),
                        (10, 1.5)):
        t = _put_step(t, factor, bucket_key=key) + 0.001
    value, extra = _reader("fit_step_ms_p50").read({"steps_in_window": 5})
    assert value == pytest.approx(2.0 * WHOLE, rel=1e-6)
    assert extra["by_bucket"] == {
        "10": {"steps": 3, "p50_ms": pytest.approx(1.5 * WHOLE, rel=1e-6)},
        "30": {"steps": 1, "p50_ms": pytest.approx(3.0 * WHOLE, rel=1e-6)},
        "40": {"steps": 1, "p50_ms": pytest.approx(4.0 * WHOLE, rel=1e-6)}}


def test_the_ten_entries_agree_with_their_readers():
    doc = manifest.Manifest().doc
    one_chip = {w["name"] for w in doc["workloads"] if w["chips"] == 1}
    mine = [m for m in doc["per_layer"]
            if m["name"].split(".", 1)[0] in READERS]
    assert sorted(m["name"] for m in mine) == sorted(
        "%s.%s" % (r, tag) for r in READERS for tag in ("img", "tok"))
    # appended: nothing that was there moved
    assert doc["per_layer"][-10:] == mine
    rates = {"img": "train_img_per_s", "tok": "train_tok_per_s"}
    for m in mine:
        name, tag = m["name"].split(".", 1)
        reader = _reader(name)
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("ms", "lower", "program_span", READERS[name])
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.DRIVERS) == ("ms", "lower", "program_span",
                                    READERS[name], ("train_fit",))
        assert m["moves"] == rates[tag]
        # a ring reader has nothing to read in a hand-built obs: one-chip
        # cells only, each by an explicit list
        assert len(m["workloads"]) == 1 and m["workloads"][0] in one_chip
        rate = next(e for e in doc["end_to_end"] if e["name"] == m["moves"])
        assert m["workloads"][0] in rate["workloads"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """tiny_copy, with the five ``.img`` entries listed for its image
    cell too (it extends the ``.tok`` entries itself)."""
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_spans"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for m in doc["per_layer"]:
        if m["name"].split(".", 1) in [[r, "img"] for r in READERS]:
            m["workloads"].append("tiny-dev")
    with open(path, "w") as f:
        json.dump(doc, f)
    return root


@pytest.mark.parametrize("cell_name,tag", [("tiny-dev", "img"),
                                           ("tiny-lstm", "tok")])
def test_readers_on_the_ring_a_real_fit_leaves(copy, cell_name, tag):
    """Module.fit and BucketingModule.fit through the driver: every step
    of the window is in the ring with its five children, and the inside
    step agrees with the callback-to-callback gap."""
    import fit_spans
    import run as bench_run
    cell = manifest.Manifest(copy).cell(cell_name)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    result = driver.run(cell, [mx.cpu(0)], 3, 1.0, False,
                        time.perf_counter(), {"bf16_flops_per_s": 1e12},
                        lambda line: None)
    obs = result["_obs"]
    got = bench_run.layer_metrics(cell, obs)
    for name in READERS:
        m = got["%s.%s" % (name, tag)]
        assert m["samples"] == obs["steps_in_window"] and m["unit"] == "ms"
        assert "%s.%s" % (name, tag) not in bench_run.absent_metrics(cell,
                                                                     got)
    assert got["fit_step_ms_p50." + tag]["ring_dropped"] == 0
    rows = fit_spans.window_steps(obs)
    for r in rows:
        assert all(r[c] > 0 for c in fit_spans.CHILDREN), r
        assert sum(r[c] for c in fit_spans.CHILDREN) <= r["fit:step"]
    # the same steps, timed from inside and from outside (window_s also
    # holds the close of fit, so it bounds the sum from above)
    inside_s = sum(r["fit:step"] for r in rows) / 1e3
    assert 0.5 * obs["window_s"] < inside_s <= obs["window_s"]
