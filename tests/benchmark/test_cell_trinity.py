"""The ``trinity-mini`` configuration, its cell and its reader
``swa_attn_roofline``: the real entries by name, the configuration's
arithmetic (the parameters held, the FLOPs a token, the attention
kernels' roofline sum over both kinds of layer), and the cell on the CPU
at tiny widths, added to the temporary copy of
``cellbench_util.tiny_copy`` as files and entries, through the same
driver as the others.  A CPU run checks answers and counts, never
rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-trinity"
REAL_CELL = "trinity-mini-train-4k"
SDAR_CELL = "sdar-30b-a3b-train-4k"
CONFIG = "trinity-mini"
TRAFFIC = "packed-4k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# as the GLM file: the window holds some steps on a loaded machine too,
# and no assertion below asks for more than one
WINDOW_S = 4.0
NAMES = ["l1_q_proj_weight", "l1_attn_gate_proj_weight", "l3_q_proj_weight",
         "l3_k_proj_weight", "l1_moe_gate_weight",
         "l2_moe_experts_i2h_weight", "embed_weight", "lm_head_weight"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_trinity"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "trinity-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=4, hidden_size=32,
        layer_types=["sliding", "sliding", "sliding", "full"],
        dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8, window=24,
        dense_width=64, num_experts=16, experts_per_tok=4, expert_width=24,
        shared_width=24, vocab_size=128, seq_len=72, embed_scale=32 ** 0.5,
        experts_held=4, first_expert=4)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "trinity-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "trinity-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-trinity.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "trinity-tiny", "source": "test",
                           "file": "benchmark/configs/trinity-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "trinity-tiny", "tiny-packed-trinity",
                  like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_trinity_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    was = mx.trace.enabled()
    try:
        # the attn:lowering samples are taken while tracing is on, as in
        # a --trace 1 run (the driver switches it on there)
        mx.trace.set_enabled(True)
        mark = time.perf_counter_ns()
        result = driver.run(cell, [mx.cpu(0)], 4100000031, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        lowered = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        # the ring is the process's: the blocks' names of this model must
        # not be there when another cell's test reads its own
        mx.trace.reset()
        mx.trace.set_enabled(was)
        mx.random.set_key_data(rng[0])
        np.random.set_state(rng[1])
    assert result["correct"] is True, lines
    obs = result["_obs"]
    assert result["failed"] == 0 and obs["steps_in_window"] >= 1
    assert result["attempted"] == \
        cell.traffic["warmup_steps"] + obs["steps_in_window"]
    assert set(result["_e2e"]) == {"train_tok_per_s", "setup_s"}
    assert obs["compile"]["in_window"] == 0
    assert result["_e2e"]["train_tok_per_s"] * obs["window_s"] == \
        pytest.approx(2 * 72 * obs["steps_in_window"])
    ref = result["_reference"]
    assert ref["loss"] == pytest.approx(ref["reference_loss"], rel=1e-4)
    assert set(ref["updates"]) == set(NAMES)
    assert all(err < 0.05 for err in ref["updates"].values()), ref
    ref_mod = manifest.load_module("reference", "trinity-tiny",
                                   cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # three routed blocks behind the dense lead
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        3 * obs["steps_in_window"]
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 3
    # each traced op set names three window layers, then the full one
    tracks = [e["id"] for e in lowered]
    assert tracks and len(tracks) % 4 == 0
    assert set(tracks[0::4]) == set(tracks[1::4]) == set(tracks[2::4]) == \
        {"float32[2, 72, 4, 8]/kv2/sliding_window24"}
    assert set(tracks[3::4]) == {"float32[2, 72, 4, 8]/kv2"}
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_trinity_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the entry it came with
    as its reader has it, and the cell on every list the SDAR cell is on
    but that cell's own.  By name and by membership, never by a position
    or a length: later cells and entries are appended to the same lists
    (``test_cellbench_rehearsal.py`` runs this against such copies)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    entries = [m for m in doc["per_layer"]
               if m["name"] == "swa_attn_roofline"]
    assert len(entries) == 1
    entry = dict(entries[0])
    reader = manifest.load_module("layer_metrics", "swa_attn_roofline")
    assert REAL_CELL in entry.pop("workloads")
    assert entry == {"name": "swa_attn_roofline", "unit": reader.UNIT,
                     "better": reader.BETTER, "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": "train_tok_per_s"}
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) == \
        ("%", "higher", "device_trace", "Pallas kernels")

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, sdar = lists_of(REAL_CELL), lists_of(SDAR_CELL)
    # every list the SDAR cell is on but its mask's and its objective's
    assert sdar - listed == {"bd_attn_roofline", "diffusion_masked_share"}
    assert {"train_tok_per_s", "moe_held_rows_share", "scope_attn_ms",
            "moe_prefix_fit_share", "moe_load_max_over_mean",
            "moe_dropped_share", "mfu.tok", "dispatch_ms_p50.tok",
            "scope_other_ms.tok", "peak_hbm_gib.tok"} <= listed
    # not the kernels counted for the causal mask in every layer, for
    # every routed row or for another mask
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline",
                "mla_attn_roofline", "bd_attn_roofline"} & listed
    assert "swa_attn_roofline" in listed - sdar
    for name in listed - sdar:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_reader():
    doc = manifest.Manifest().doc
    check_the_trinity_cells_own_entries(doc)
    # one cell on four chips, the place the benchmark has
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_roofline_reader_with_and_without_a_trace():
    reader = manifest.load_module("layer_metrics", "swa_attn_roofline")
    cell = manifest.Manifest().cell(REAL_CELL)
    peaks = manifest.load_peaks("TPU v5 lite")
    obs = {"config": cell.config, "traffic": cell.traffic, "peaks": peaks,
           "trace": None}
    assert reader.read(obs) is None
    obs["trace"] = {"steps": 2, "op_seconds": {"fusion.1 fusion f32": 1.0}}
    assert reader.read(obs) is None            # no such operation
    # both kinds' kernels share the name and are summed
    obs["trace"]["op_seconds"].update({
        "splash_mha_fwd_residuals.3 custom-call bf16[32,4096,128]": 0.01,
        "splash_mha_fwd_residuals.9 custom-call bf16[32,4096,128]": 0.004,
        "splash_mha_dkv_no_residuals.7 custom-call f32[1024,128]": 0.03})
    value, extra = reader.read(obs)
    assert extra["kernel_ms"] == pytest.approx(22.0)
    assert extra["bound"] == "compute" and extra["steps"] == 2
    assert extra["roofline_ms"] == pytest.approx(9.77, abs=0.01)
    assert value == pytest.approx(100.0 * extra["roofline_ms"] / 22.0)


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced`` (the published
    ``layer_types`` and ``num_dense_layers`` among them, whole: the
    layers BUILT are the builder's arguments); the builder's arguments
    are the same numbers; the cuts are at the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers_published"],
            cfg["num_experts_published"], cfg["vocab_size_published"]) == (
        published["num_hidden_layers"], published["num_experts"],
        published["vocab_size"])
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["window"],
            kw["rope_theta"], kw["dense_width"], kw["num_experts"],
            kw["experts_held"], kw["experts_per_tok"], kw["expert_width"],
            kw["shared_width"], kw["route_scale"], kw["vocab_size"],
            kw["bias_rate"], kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["sliding_window"], cfg["rope_theta"],
        cfg["intermediate_size"], cfg["num_experts_published"],
        cfg["num_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"],
        cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        cfg["route_scale"], cfg["vocab_size"], cfg["load_balance_coeff"],
        cfg["rms_norm_eps"])
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["window"], kw["dense_width"],
            kw["num_experts"], kw["experts_per_tok"], kw["expert_width"],
            kw["route_scale"], kw["rope_theta"]) == (
        2048, 32, 4, 128, 2048, 6144, 128, 8, 1024, 2.826, 1e4)
    assert kw["embed_scale"] == pytest.approx(2048 ** 0.5, rel=1e-12)
    assert cfg["mup_enabled"] is True and cfg["route_norm"] is True
    assert cfg["score_func"] == "sigmoid"
    # the layers built are published ones: one of the two leading dense
    # layers (they count once) and the whole period behind the lead,
    # three window layers to a full one as published
    built = cfg["built_layers"]
    assert built == [0, 4, 5, 6, 7] and len(built) == kw["num_layers"]
    assert kw["layer_types"] == [
        published["layer_types"][l].split("_")[0] for l in built]
    assert kw["layer_types"][1:] == ["sliding"] * 3 + ["full"]
    assert kw["dense_layers"] == 1 == sum(
        l < published["num_dense_layers"] for l in built)
    assert kw["num_layers"] == kw["dense_layers"] \
        + published["global_attn_every_n_layers"]
    # the floors: an eighth of the vocabulary, 8 experts at least, whole
    # ranks of an expert-parallel split
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert kw["experts_held"] in (8, 16) and kw["first_expert"] == 0
    assert kw["num_experts"] % kw["experts_held"] == 0
    assert kw["seq_len"] == 4096
    assert {"output_gate", "sandwich_norms", "head_norm",
            "rotary_on_sliding_layers_only", "rope_pairing", "embed_scale",
            "selection_bias", "route_norm_floor"} <= set(cfg["assumed"])
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    # both kinds of layer and the new parts are held by a limit
    assert {"l1_q_proj_weight", "l1_attn_gate_proj_weight",
            "l4_q_proj_weight", "l4_k_proj_weight", "l1_moe_gate_weight",
            "l1_moe_experts_i2h_weight", "embed_weight", "lm_head_weight"} \
        == set(cfg["reference"]["weights"])
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, FLOPs a token and the attention kernels' roofline
    sum, written out (ISSUE 41's numbers)."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import afmoe_lm
    net = afmoe_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, held = 2048, 25024, kw["experts_held"]
    attention = 3 * D * 4096 + 2 * D * 512       # q, gate, o; k, v
    assert attention == 27_262_976
    norms = 4 * D + 2 * 128                      # four gains, two a head
    expert = 3 * D * 1024
    assert expert == 6_291_456
    dense = attention + norms + 3 * D * 6144
    sparse = attention + norms + 128 * D + expert + held * expert
    assert dense == pytest.approx(65.02e6, rel=1e-4)
    total = 2 * D * V + D + dense + 4 * sparse
    assert sum(sizes.values()) == total
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == D * V
    assert sizes["l1_moe_experts_i2h_weight"] == held * D * 1024
    assert sizes["l1_attn_gate_proj_weight"] == sizes["l1_q_proj_weight"] \
        == 4096 * D
    assert sizes["l4_k_proj_weight"] == 512 * D
    assert sizes["l1_q_norm_gamma"] == sizes["l1_k_norm_gamma"] == 128
    assert "l0_moe_gate_weight" not in sizes and "l0_gate_proj_weight" in sizes
    assert held == 8                 # 16, ISSUE 41's first choice: 705.5 M
    assert sparse == pytest.approx(84.16e6, rel=1e-4)
    assert total == 504_147_200
    assert 16 * total / 2 ** 30 == pytest.approx(7.51, abs=0.005)
    # whole, one expert layer's 128 experts are 805 M of its 839 M
    assert attention + 128 * D + 129 * expert == pytest.approx(839.1e6,
                                                               rel=1e-3)
    # FLOPs a trained token: the allowed pairs by layer kind
    window_pairs, causal_pairs = 6_292_480, 8_390_656
    assert ref.allowed_pairs(4096, 2048) == window_pairs \
        == 2048 * 2049 // 2 + 2048 * 2048
    assert ref.allowed_pairs(4096) == causal_pairs == 4096 * 4097 // 2
    assert window_pairs / causal_pairs == pytest.approx(0.75, abs=0.001)
    scores = 4 * 128 * 32 * (4 * window_pairs + causal_pairs) / 4096
    forward = 5 * 2 * attention + scores + 6 * D * 6144 + 4 * (
        2 * D * 128 + 2 * expert + 8 * held / 128 * 2 * expert) + 2 * D * V
    assert ref.train_flops_per_sample(cfg) == pytest.approx(3 * forward,
                                                            rel=1e-12)
    assert forward == pytest.approx(662.5e6, rel=1e-3)
    assert 4096 * 3 * forward == pytest.approx(8.14e12, rel=1e-3)
    assert scores / forward == pytest.approx(0.203, abs=0.003)
    # the kernels' roofline: four window layers and a full one, 32 heads
    # over 4, the allowed pairs
    reader = manifest.load_module("layer_metrics", "swa_attn_roofline")
    assert reader.allowed_pairs(4096, 2048) == window_pairs
    assert reader.allowed_pairs(4096) == reader.allowed_pairs(4096, 4096) \
        == reader.allowed_pairs(4096, 9999) == causal_pairs
    ops, nbytes = reader.mixed_window_attention_work(cfg, cell.traffic)
    assert ops == 14 * 128 * 32 * (4 * window_pairs + causal_pairs)
    assert ops == pytest.approx(1.9245e12, rel=1e-4)
    assert nbytes == 5 * 2 * 4096 * 128 * 4 * (32 + 4)
    assert nbytes == pytest.approx(0.755e9, rel=1e-3)
    import kernel_rooflines
    seconds, bound = kernel_rooflines.roofline_time(
        (ops, nbytes), manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(9.77e-3,
                                                           rel=1e-3)
    # all five layers under the causal mask would count 9 % more
    every = kernel_rooflines.causal_attention_work(cfg, cell.traffic)[0]
    assert every / ops == pytest.approx(
        5 * 4096 ** 2 / 2 / (4 * window_pairs + causal_pairs), rel=1e-12)
    # the held experts' rows a step against the deployment's
    assert 4096 * 8 * held // 128 // held == 256
    # the rank's sorted layout: the bound (4 balanced shares of 2048
    # rows) is taken, it saves more than BOUND_WORTH_ROWS
    import importlib
    dispatch = importlib.import_module("mxnet_tpu.moe.dispatch")
    bound_rows = dispatch.held_rows_bound(4096 * 8, 128, held)
    assert bound_rows == 8192 <= 4096 * 8 - dispatch.BOUND_WORTH_ROWS
