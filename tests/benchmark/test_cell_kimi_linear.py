"""The ``kimi-linear-48b-a3b`` configuration, its traffic and its reader
on the CPU at tiny widths, added to the temporary copy of
``cellbench_util.tiny_copy`` as files and entries: the cell runs through
the same driver as the others and is correct; ``moe_held_rows_share``
reads what ``fit`` recorded, and nothing where there is nothing.  A CPU
run checks answers and counts, never rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-kimi"
REAL_CELL = "kimi-linear-48b-a3b-train-4k"
CONFIG = "kimi-linear-48b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# a step of the tiny model takes ~40 ms alone and ~1 s beside two dozen
# busy processes on eight cores: the window holds some steps either way,
# and no assertion below asks for more than one
WINDOW_S = 4.0


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_kimi"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "kimi-tiny"
    cfg["model"]["kwargs"].update(
        hidden_size=32, kda_heads=2, kda_head_dim=8, mla_heads=2,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        dense_width=64, num_experts=16, experts_per_tok=4, expert_width=24,
        shared_width=24, vocab_size=128, seq_len=72, experts_held=4,
        first_expert=4)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    names = ["l1_q_proj_weight", "l2_kda_dt_bias", "l4_kv_b_proj_weight",
             "l2_moe_gate_weight", "l3_moe_experts_i2h_weight",
             "embed_weight"]
    cfg["reference"].update(samples=2, weights=names, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(names, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "kimi-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "kimi-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", "packed-4k-b1.json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-b1.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "kimi-tiny", "source": "test",
                           "file": "benchmark/configs/kimi-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "kimi-tiny", "tiny-packed-b1", like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_kimi_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    try:
        result = driver.run(cell, [mx.cpu(0)], 3100000031, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
    finally:
        mx.random.set_key_data(rng[0])
        np.random.set_state(rng[1])
    assert result["correct"] is True, lines
    obs = result["_obs"]
    # in terms of the steps completed, never of how many a loaded machine
    # fits into the window: every batch drawn became a step, the warm-up's
    # three before the window and the rest inside it
    assert result["failed"] == 0 and obs["steps_in_window"] >= 1
    assert result["attempted"] == \
        cell.traffic["warmup_steps"] + obs["steps_in_window"]
    assert set(result["_e2e"]) == {"train_tok_per_s", "setup_s"}
    assert obs["compile"]["in_window"] == 0
    assert result["_e2e"]["train_tok_per_s"] * obs["window_s"] == \
        pytest.approx(2 * 72 * obs["steps_in_window"])
    ref = result["_reference"]
    assert ref["loss"] == pytest.approx(ref["reference_loss"], rel=1e-4)
    assert set(ref["updates"]) == set(cell.config["reference"]["weights"])
    assert all(err < 0.05 for err in ref["updates"].values()), ref
    ref_mod = manifest.load_module("reference", "kimi-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    got = bench_run.layer_metrics(cell, obs)
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        4 * obs["steps_in_window"]                   # four routed blocks
    held = got["moe_held_rows_share"]
    # 4 of 16 experts held: a quarter of the choices under any balance
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 4
    assert held["samples"] == obs["steps_in_window"]
    assert 1.0 <= got["moe_load_max_over_mean"]["value"] <= 16.0
    for name in ("step_ms_p50.tok", "feed_wait_share.tok",
                 "peak_hbm_gib.tok", "compiles_in_window"):
        assert name in got, sorted(got)
    # every per-layer entry the cell is listed under has a reader the
    # run can feed, but those that read a device trace or the spans of
    # a traced run (by their source: later entries join the cell's lists)
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_cells_own_entries(doc):
    """``doc`` holds the cell, the entry it came with as its reader has
    it, and the cell on the lists it shares with the OLMoE cell.  By
    name and by membership, never by a position, a length or "the only
    member": later cells and entries are appended to the same lists
    (``test_cellbench_rehearsal.py`` runs this against such copies).
    That nothing which was there moved is the driver's check and
    ``test_what_was_there_did_not_move``'s."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "packed-4k-b1", 1)
    entries = [m for m in doc["per_layer"]
               if m["name"] == "moe_held_rows_share"]
    assert len(entries) == 1
    entry = dict(entries[0])
    reader = manifest.load_module("layer_metrics", "moe_held_rows_share")
    assert REAL_CELL in entry.pop("workloads")
    assert entry == {"name": "moe_held_rows_share", "unit": reader.UNIT,
                     "better": reader.BETTER, "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": "train_tok_per_s"}
    listed = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if REAL_CELL in m.get("workloads", [])}
    olmoe = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
             if "olmoe-1b-7b-train-4k" in m.get("workloads", [])}
    # every list the OLMoE cell is on but its kernels' rooflines, whose
    # work functions are that cell's
    missing = olmoe - listed
    assert {"attn_roofline", "moe_gmm_roofline"} <= missing
    assert all(name.endswith("_roofline") for name in missing), missing
    # and of its own the one it came with; whatever joined since has a
    # reader file
    own = listed - olmoe
    assert "moe_held_rows_share" in own
    for name in own:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_reader():
    check_the_cells_own_entries(manifest.Manifest().doc)


def test_the_held_share_reader_with_and_without_the_counter():
    import mxnet_tpu as mx
    reader = manifest.load_module("layer_metrics", "moe_held_rows_share")
    mx.trace.reset()
    assert reader.read({"steps_in_window": 5}) is None
    assert reader.read({"steps_in_window": 0}) is None
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        # a parent's counter, without ``held``: nothing to read
        mx.trace.counter("moe:load", cat="moe", track="a", max=9.0,
                         mean=4.0, empty=0, routed=64.0, dropped=0.0)
        assert reader.read({"steps_in_window": 1}) is None
        mx.trace.reset()
        # one warm-up step, then a window of three, two blocks
        for held in (60.0, 2.0, 4.0, 8.0):
            for block, scale in (("a", 1.0), ("b", 3.0)):
                mx.trace.counter("moe:load", cat="moe", track=block,
                                 max=9.0, mean=4.0, empty=0, routed=64.0,
                                 held=held * scale, dropped=0.0)
    finally:
        mx.trace.set_enabled(was)
    value, extra = reader.read({"steps_in_window": 3})
    assert value == pytest.approx(100.0 * (4.0 + 12.0) / 128.0)
    assert extra == {"samples": 3, "blocks": 2}
    mx.trace.reset()


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced``; nested groups
    whole; the builder's arguments are the same numbers."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers_published"], cfg["num_experts_published"],
            cfg["vocab_size_published"]) == (
        published["num_hidden_layers"], published["num_experts"],
        published["vocab_size"])
    kw = cfg["model"]["kwargs"]
    lin = cfg["linear_attn_config"]
    assert (kw["num_layers"], kw["hidden_size"], kw["full_attn_layers"],
            kw["dense_layers"], kw["kda_heads"], kw["kda_head_dim"],
            kw["conv_kernel"], kw["mla_heads"], kw["kv_lora_rank"],
            kw["qk_nope_dim"], kw["qk_rope_dim"], kw["v_head_dim"],
            kw["dense_width"], kw["num_experts"], kw["experts_held"],
            kw["experts_per_tok"], kw["expert_width"], kw["shared_width"],
            kw["routed_scale"], kw["vocab_size"], kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        lin["full_attn_layers"], cfg["first_k_dense_replace"],
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
        cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], cfg["intermediate_size"],
        cfg["num_experts_published"], cfg["num_experts"],
        cfg["num_experts_per_token"], cfg["moe_intermediate_size"],
        cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        cfg["routed_scaling_factor"], cfg["vocab_size"],
        cfg["rms_norm_eps"])
    # the floors: a whole period after the dense layer, 8 experts, an
    # eighth of the vocabulary
    kept = range(1, kw["num_layers"] + 1)
    assert [l in lin["full_attn_layers"] for l in kept] == \
        [False, False, False, True, False]
    assert all((l in lin["kda_layers"]) != (l in lin["full_attn_layers"])
               for l in kept)
    assert kw["experts_held"] == 8
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    assert json.dumps(cfg)            # plain data


def test_the_same_seed_gives_the_same_packed_traffic(copy):
    import mxnet_tpu as mx
    cell = manifest.Manifest(copy).cell(CELL)
    gen = manifest.load_module("generators", cell.traffic["generator"],
                               cell.bench_dir)
    seed = 2 ** 31 + 12345          # more than 32 signed bits hold
    a, b, c = (gen.build(cell.traffic, cell.config, s, [mx.cpu(0)], None)
               .next().data[0].asnumpy() for s in (seed, seed, 7))
    assert a.dtype == np.int32 and a.shape == (2, 72)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert 0 <= a.min() and a.max() < 128
