"""The per-layer entry ``moe_prefix_fit_share`` (PR 40) and its reader:
of the window's ``moe:load`` samples that carry ``bound``, the share with
``held <= bound``; nothing from a program that records no ``bound`` (a
commit before the bound, a model that holds every expert)."""
import pytest

import cellbench_util as util  # noqa: F401
import manifest

NAME = "moe_prefix_fit_share"
CELLS = ("kimi-linear-48b-a3b-train-4k", "glm-4.7-flash-train-4k",
         "sdar-30b-a3b-train-4k")


def check_the_fit_share_entry(doc):
    """One entry, as its reader has it, over the three rank-share cells
    (by membership: later cells are appended to the same list), each of
    which reports the rate it moves; the OLMoE cell, which holds every
    expert, is not on it."""
    entries = [m for m in doc["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1
    entry = dict(entries[0])
    cells = entry.pop("workloads")
    assert set(CELLS) <= set(cells)
    assert "olmoe-1b-7b-train-4k" not in cells
    reader = manifest.load_module("layer_metrics", NAME)
    assert entry == {"name": NAME, "unit": reader.UNIT,
                     "better": reader.BETTER, "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": "train_tok_per_s"}
    rate = next(m for m in doc["end_to_end"]
                if m["name"] == "train_tok_per_s")
    assert set(cells) <= set(rate["workloads"])
    held = next(m for m in doc["per_layer"]
                if m["name"] == "moe_held_rows_share")
    assert entry["layer"] == held["layer"]


def test_the_entry_is_there_and_agrees_with_the_reader():
    check_the_fit_share_entry(manifest.Manifest().doc)


def _samples(mx, rows):
    for block, held, bound in rows:
        extra = {} if bound is None else {"bound": bound}
        mx.trace.counter("moe:load", cat="moe", track=block, max=9.0,
                         mean=4.0, empty=0, routed=64.0, held=held,
                         dropped=0.0, **extra)


@pytest.mark.parametrize("case,rows,window,want", [
    ("no_counter", [], 5, None),
    ("no_window", [("a", 8.0, 16.0)], 0, None),
    ("a_parents_counter_without_bound", [("a", 8.0, None)], 1, None),
    ("every_expert_held", [("a", 64.0, None), ("a", 64.0, None)], 2, None),
    # one warm-up step, then a window of three, two blocks: block a fits
    # under, exactly at and over its bound, block b twice
    ("a_window_of_three", [("a", 60.0, 16.0), ("b", 60.0, 16.0),
                           ("a", 2.0, 16.0), ("b", 16.5, 16.0),
                           ("a", 16.0, 16.0), ("b", 3.0, 16.0),
                           ("a", 40.0, 16.0), ("b", 0.0, 16.0)], 3,
     (100.0 * 4 / 6, {"samples": 6, "fits": 4,
                      "fullest_over_bound": 2.5})),
    ("all_fit", [("a", 1.0, 16.0), ("a", 16.0, 16.0)], 2,
     (100.0, {"samples": 2, "fits": 2, "fullest_over_bound": 1.0})),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_reader_with_and_without_the_field(case, rows, window, want):
    import mxnet_tpu as mx
    reader = manifest.load_module("layer_metrics", NAME)
    mx.trace.reset()
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        _samples(mx, rows)
        got = reader.read({"steps_in_window": window})
    finally:
        mx.trace.set_enabled(was)
        mx.trace.reset()
    if want is None:
        assert got is None
    else:
        assert got[0] == pytest.approx(want[0]) and got[1] == want[1]
