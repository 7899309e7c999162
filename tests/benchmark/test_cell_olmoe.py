"""The ``olmoe-1b-7b`` configuration, its traffic and its readers on the
CPU at tiny widths, added to the temporary copy of
``cellbench_util.tiny_copy`` as files and entries: the cell runs through
the same driver as the others and is correct; the ``moe:load`` readers
read what ``fit`` recorded.  A CPU run checks answers and counts, never
rates."""
import json
import math
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-olmoe"
# The cell's own three per-layer entries, as a ``benchmark`` PR would list
# them for ``olmoe-1b-7b-train-4k``.  ``BENCHMARK.json`` cannot hold them
# yet: ``test_cellbench_spans`` pins the ten span entries to the list's
# end, and an entry put in front of them reads as a change to what was
# there.  The temporary copy appends them, so the readers run here.
OWN_ENTRIES = [
    {"name": "dispatch_ms_p50.tok", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "train step",
     "moves": "train_tok_per_s"},
    {"name": "moe_load_max_over_mean", "unit": "ratio", "better": "lower",
     "source": "program_counter", "layer": "routed experts",
     "moves": "train_tok_per_s"},
    {"name": "moe_dropped_share", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "routed experts",
     "moves": "train_tok_per_s"},
]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_olmoe"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", "olmoe-1b-7b.json"))
    cfg["name"] = "olmoe-tiny"
    cfg["model"]["kwargs"].update(num_layers=2, hidden_size=64, num_heads=4,
                                  num_experts=8, experts_per_tok=2,
                                  expert_width=32, vocab_size=128,
                                  seq_len=32)
    cfg["input"] = {"seq_len": 32, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    names = ["l0_moe_gate_weight", "l1_moe_experts_i2h_weight",
             "l0_q_proj_weight", "embed_weight"]
    cfg["reference"].update(samples=2, weights=names, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(names, 0.02))
    util._dump(cfg, os.path.join(bench, "configs", "olmoe-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", "olmoe-1b-7b.py"),
                os.path.join(bench, "reference", "olmoe-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", "packed-4k-b4.json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "olmoe-tiny", "source": "test",
                           "file": "benchmark/configs/olmoe-tiny.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": "olmoe-tiny",
                             "traffic": "tiny-packed", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "olmoe-1b-7b-train-4k" in m.get("workloads", []):
            m["workloads"].append(CELL)
    doc["per_layer"].extend(dict(e, workloads=[CELL]) for e in OWN_ENTRIES)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def _run(root, seconds=1.5, seed=2300000023):
    import mxnet_tpu as mx
    cell = manifest.Manifest(root).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    # the driver seeds the process's generators; hand them back as found,
    # so a test that runs after this file draws what it would have drawn
    rng = mx.random.get_key_data(), np.random.get_state()
    try:
        result = driver.run(cell, [mx.cpu(0)], seed, seconds, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
    finally:
        mx.random.set_key_data(rng[0])
        np.random.set_state(rng[1])
    return cell, result, lines


def test_the_olmoe_cell_runs_through_the_driver_and_is_correct(copy):
    import run as bench_run
    cell, result, lines = _run(copy)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 10
    obs = result["_obs"]
    assert set(result["_e2e"]) == {"train_tok_per_s", "setup_s"}
    assert obs["compile"]["in_window"] == 0
    # every position of every sequence is a label
    assert result["_e2e"]["train_tok_per_s"] * obs["window_s"] == \
        pytest.approx(2 * 32 * obs["steps_in_window"])
    ref = result["_reference"]
    assert ref["loss"] == pytest.approx(ref["reference_loss"], rel=1e-4)
    assert set(ref["updates"]) == set(cell.config["reference"]["weights"])
    assert all(err < 0.02 for err in ref["updates"].values()), ref
    # the analytic FLOP count is the configuration's, per token
    ref_mod = manifest.load_module("reference", "olmoe-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    got = bench_run.layer_metrics(cell, obs)
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        2 * obs["steps_in_window"]                   # two routed blocks
    load = got["moe_load_max_over_mean"]
    assert 1.0 <= load["value"] <= 8.0 and math.isfinite(load["value"])
    assert load["samples"] == obs["steps_in_window"]
    assert load["block"] in ("l0_moe_dispatch", "l1_moe_dispatch")
    for name in ("step_ms_p50.tok", "feed_wait_share.tok",
                 "peak_hbm_gib.tok", "compiles_in_window"):
        assert name in got, sorted(got)
    # fused:dispatch is read in traced runs only
    assert "dispatch_ms_p50.tok" not in got
    obs["dispatch_ms"] = [1.0, 3.0, 2.0]
    assert bench_run.layer_metrics(cell, obs)["dispatch_ms_p50.tok"] == \
        {"value": 2.0, "unit": "ms", "samples": 3}


def test_the_cells_own_entries_agree_with_their_readers():
    """The three entries this cell would bring agree with their readers,
    and ``BENCHMARK.json`` holds none of them: the new cell is appended
    to lists that were there, and no entry is added or moved."""
    for e in OWN_ENTRIES:
        reader = manifest.load_module("layer_metrics",
                                      e["name"].split(".", 1)[0])
        assert (e["unit"], e["better"], e["source"], e["layer"]) == \
            (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER)
        assert "train_fit" in reader.DRIVERS
    doc = manifest.Manifest().doc
    names = [m["name"] for m in doc["per_layer"]]
    assert not set(names) & {e["name"] for e in OWN_ENTRIES}
    listed = [m["name"] for m in doc["per_layer"]
              if "olmoe-1b-7b-train-4k" in m.get("workloads", [])]
    assert listed == ["step_ms_p50.tok", "feed_wait_share.tok",
                      "feed_reset_share.tok", "device_step_ms.tok",
                      "mfu.tok", "device_idle_share.tok",
                      "peak_hbm_gib.tok"]
    for m in doc["per_layer"]:
        if m["name"] in listed:
            assert m["workloads"][-1] == "olmoe-1b-7b-train-4k"


def test_the_load_readers_find_nothing_without_the_counter():
    """A program that records no ``moe:load`` (the parent commit, a model
    with no routed block): the readers return None and do not raise."""
    import mxnet_tpu as mx
    mx.trace.reset()
    for name in ("moe_load_max_over_mean", "moe_dropped_share"):
        reader = manifest.load_module("layer_metrics", name)
        assert reader.read({"steps_in_window": 5}) is None
        assert reader.read({"steps_in_window": 0}) is None
        assert reader.DRIVERS == ("train_fit",)


def test_the_load_readers_take_the_windows_last_samples():
    import mxnet_tpu as mx
    mx.trace.reset()
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        # two warm-up steps, then a window of three, two blocks
        for mx_, dropped in ((50, 9), (50, 9), (12, 0), (14, 0), (16, 1)):
            for block, scale in (("a", 1.0), ("b", 2.0)):
                mx.trace.counter("moe:load", cat="moe", track=block,
                                 max=mx_ * scale, mean=10.0, empty=0,
                                 routed=99.0, dropped=float(dropped))
    finally:
        mx.trace.set_enabled(was)
    obs = {"steps_in_window": 3}
    value, extra = manifest.load_module(
        "layer_metrics", "moe_load_max_over_mean").read(obs)
    assert value == pytest.approx(2.8) and extra == {"samples": 3,
                                                     "block": "b"}
    value, extra = manifest.load_module(
        "layer_metrics", "moe_dropped_share").read(obs)
    assert value == pytest.approx(100.0 * 2 / (6 * 99 + 2))
    assert extra == {"samples": 6}
    mx.trace.reset()


def test_the_same_seed_gives_the_same_packed_traffic(copy):
    import mxnet_tpu as mx
    cell = manifest.Manifest(copy).cell(CELL)
    gen = manifest.load_module("generators", cell.traffic["generator"],
                               cell.bench_dir)

    def first(seed):
        t = gen.build(cell.traffic, cell.config, seed, [mx.cpu(0)], None)
        b = t.next()
        return b.data[0].asnumpy(), b.label[0].asnumpy(), t

    (a, la, t), (b, _, _), (c, _, _) = first(3000000001), \
        first(3000000001), first(7)
    assert a.dtype == np.int32 and a.shape == (2, 32)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # labels are the stream shifted by one, across sequence borders too
    assert np.array_equal(a.reshape(-1)[1:], la.reshape(-1)[:-1])
    assert 0 <= a.min() and a.max() < 128 and (a == 0).any()
    assert t.samples(None) == 64
    data, labels, key = t.reference_batch(2)
    assert key is None and np.array_equal(data["data"], a)
    assert np.array_equal(labels["softmax_label"], la)


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every width as the published config.json; ``reduced`` names
    exactly the two keys that differ, and the builder's arguments are
    the same numbers."""
    published = {"attention_bias": False, "clip_qkv": None,
                 "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 1024, "max_position_embeddings": 4096,
                 "model_type": "olmoe", "norm_topk_prob": False,
                 "num_attention_heads": 16, "num_experts": 64,
                 "num_experts_per_tok": 8, "num_hidden_layers": 16,
                 "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
                 "rope_scaling": None, "rope_theta": 10000,
                 "tie_word_embeddings": False, "vocab_size": 50304}
    m = manifest.Manifest()
    entry = m.configs["olmoe-1b-7b"]
    cfg = m.cell("olmoe-1b-7b-train-4k").config
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(entry["reduced"]) == \
        ["num_hidden_layers", "vocab_size"]
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["num_heads"],
            kw["num_experts"], kw["experts_per_tok"], kw["expert_width"],
            kw["vocab_size"], kw["seq_len"], kw["rope_theta"],
            kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["intermediate_size"],
        cfg["vocab_size"], cfg["max_position_embeddings"],
        cfg["rope_theta"], cfg["rms_norm_eps"])
    assert cfg["vocab_size"] * 4 == published["vocab_size"]
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    assert json.dumps(cfg)            # plain data
