"""The reduction from a profiler trace to device metrics, on hand-built
events and on an excerpt of a trace recorded on the chip."""
import json
import os

import pytest

import cellbench_util as util  # noqa: F401
import trace_reduce as tr

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return (name, int(start_ms * MS), int(dur_ms * MS))


def test_merge_clip_subtract():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 10), (7, 7)]) == \
        [(0, 4), (5, 10)]
    assert tr.total(tr.merge([(0, 4), (2, 6)])) == 6
    assert tr.clip([(0, 4), (5, 10), (12, 14)], (3, 12)) == [(3, 4), (5, 10)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_busy_is_a_union_inside_the_window():
    events = [ev("a", 0, 10), ev("b", 5, 10), ev("nested", 6, 1),
              ev("c", 30, 10), ev("outside", 100, 50)]
    window = (2 * MS, 35 * MS)
    assert tr.busy_ns(events, window) == (13 + 5) * MS
    assert tr.idle_gaps(events, window) == [(15 * MS, 30 * MS)]


@pytest.mark.parametrize("name,yes", [
    ("all-reduce.3", True), ("%all-reduce-start.12", True),
    ("all-reduce-done", True), ("all-gather", True),
    ("reduce-scatter.1", True), ("collective-permute-start", True),
    ("all-to-all.7", True), ("fusion.12", False), ("copy-start.3", False),
    ("all-reduce_fusion", False), ("convolution_add_fusion", False)])
def test_which_operations_are_collectives(name, yes):
    assert tr.is_collective(name) is yes


def test_exposed_collective_time():
    """10 ms of all-reduce, 4 of them under a convolution: 6 exposed."""
    events = [ev("convolution.1", 0, 14), ev("all-reduce.1", 10, 10),
              ev("fusion.2", 30, 5)]
    coll, exposed = tr.collective_ns(events, (0, 40 * MS))
    assert (coll, exposed) == (10 * MS, 6 * MS)


def test_a_gap_takes_the_label_that_covers_most_of_it():
    notes = [ev("bench:feed_next", 10, 8), ev("bench:batch_end", 18, 1)]
    assert tr.label_gap((10 * MS, 20 * MS), notes) == "bench:feed_next"
    assert tr.label_gap((16 * MS, 19 * MS), notes) == "bench:feed_next"
    assert tr.label_gap((18 * MS, 19 * MS), notes) == "bench:batch_end"
    # under half of the gap explained: nobody's
    assert tr.label_gap((0, 40 * MS), notes) == tr.UNLABELLED_GAP
    assert tr.label_gap((50 * MS, 60 * MS), notes) == tr.UNLABELLED_GAP


def _three_steps():
    """Three 10 ms steps: 6 ms of step program, then a 4 ms gap in which
    the host fetches the next batch; the window opens at the end of the
    first batch_end annotation (t=10) and closes at the last (t=40)."""
    ops, notes = [], []
    for i in range(4):
        t = 10 * i
        ops += [ev("fusion.1", t + 0.5, 4), ev("convolution.2", t + 4.5, 1.5)]
        notes += [ev("bench:feed_next", t + 6.2, 3.5),
                  ev("bench:batch_end", t + 9.8, 0.2)]
    return {"/device:TPU:0": ops}, notes


def test_reduce_trace_on_built_events():
    devices, notes = _three_steps()
    got = tr.reduce_trace(devices, notes)
    assert got["steps"] == 3
    assert got["window_s"] == pytest.approx(0.030)
    assert got["busy_s"] == pytest.approx(3 * 0.0055)
    assert got["collective_s"] == 0.0
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.012)]
    assert got["device_ops"][1] == ["convolution.2", pytest.approx(0.0045)]
    assert len(got["idle_gaps"]) == 4         # one is the cut first gap
    assert got["idle_gaps"][0] == ["bench:feed_next", pytest.approx(0.0045)]
    idle = 1 - got["busy_s"] / got["window_s"]
    assert idle == pytest.approx(0.45)


def test_reduce_trace_averages_over_devices_and_sees_collectives():
    devices, notes = _three_steps()
    second = list(devices["/device:TPU:0"]) + [
        ev("all-reduce.9", 16, 2), ev("all-reduce.9", 24.5, 2)]
    devices["/device:TPU:1"] = second
    got = tr.reduce_trace(devices, notes)
    # device 1: 2 ms exposed in step one; 2 ms in step two of which 1.5
    # lie under convolution.2 (24.5..26)
    assert got["per_device"]["/device:TPU:1"]["collective_s"] == \
        pytest.approx(0.004)
    assert got["per_device"]["/device:TPU:1"]["collective_exposed_s"] == \
        pytest.approx(0.0025)
    assert got["collective_s"] == pytest.approx(0.002)
    assert got["busy_s"] == pytest.approx((0.0165 + 0.0165 + 0.0025) / 2)


def test_reduce_trace_refuses_a_trace_it_cannot_read():
    devices, notes = _three_steps()
    with pytest.raises(ValueError, match="fewer than two"):
        tr.reduce_trace(devices, notes[:2])
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_trace({}, notes)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_trace.json")


def test_reduce_trace_on_the_recorded_excerpt():
    """An excerpt of a trace of ResNet-50 b128 steps taken on a TPU v5e
    (operation events cut to a few steps; names as the profiler gave
    them).  What the file says it should reduce to was worked out when
    it was recorded."""
    with open(RECORDED) as f:
        rec = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    notes = [tuple(e) for e in rec["annotations"]]
    got = tr.reduce_trace(devices, notes)
    want = rec["expect"]
    assert got["steps"] == want["steps"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < got["busy_s"] < got["window_s"]
    assert got["device_ops"][0][0] == want["top_op"]
    assert [g[0] for g in got["idle_gaps"][:2]] == want["gap_labels"]
