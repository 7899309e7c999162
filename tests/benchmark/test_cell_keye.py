"""The ``keye-vl-2.0-30b-a3b`` configuration, its cell and its five readers
(``dsa_attn_roofline``, ``scope_dsa_ms``, ``dsa_kept_pairs_share``,
``dsa_tiles_hit_share``, ``dsa_index_kl``): the real entries by name, the
configuration's arithmetic (465.4 M parameters held, the FLOPs a trained
token, the selected-attention work function's sums), each reader on a
hand-built ``obs``, and the cell on the CPU at tiny widths, added to the
temporary copy of ``cellbench_util.tiny_copy`` as files and entries,
through the same driver as the others.  A CPU run checks answers and
counts, never rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-keye"
REAL_CELL = "keye-vl-2.0-30b-a3b-train-8k"
SDAR_CELL = "sdar-30b-a3b-train-4k"
CONFIG = "keye-vl-2.0-30b-a3b"
TRAFFIC = "packed-8k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WINDOW_S = 4.0
NAMES = ["l1_q_proj_weight", "l1_k_proj_weight", "l1_index_q_proj_weight",
         "l1_index_k_proj_weight", "l1_index_w_proj_weight",
         "l1_moe_gate_weight", "l1_moe_experts_i2h_weight", "embed_weight",
         "lm_head_weight"]
READERS = {
    "dsa_attn_roofline": ("%", "higher", "device_trace", "Pallas kernels"),
    "scope_dsa_ms": ("ms", "lower", "device_trace", "Pallas kernels"),
    "dsa_kept_pairs_share": ("%", "higher", "program_counter",
                             "learned selection"),
    "dsa_tiles_hit_share": ("%", "lower", "program_counter",
                            "learned selection"),
    "dsa_index_kl": ("nats/row", "lower", "program_counter",
                     "learned selection")}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_keye"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "keye-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=8, index_heads=2, index_dim=8, topk=16,
        mrope_sections=[1, 1, 2], num_experts=16, experts_per_tok=4,
        expert_width=24, vocab_size=128, seq_len=64, experts_held=4,
        first_expert=4)
    cfg["input"] = {"seq_len": 64, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "keye-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "keye-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.05)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-keye.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "keye-tiny", "source": "test",
                           "file": "benchmark/configs/keye-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "keye-tiny", "tiny-packed-keye", like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_keye_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    was = mx.trace.enabled()
    try:
        # the counter behind the three dsa_* shares is fed while tracing
        # is on, as in a --trace 1 run (the driver switches it on there)
        mx.trace.set_enabled(True)
        result = driver.run(cell, [mx.cpu(0)], 3900000057, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
        mx.random.set_key_data(rng[0])
        np.random.set_state(rng[1])
    assert result["correct"] is True, lines
    obs = result["_obs"]
    steps = obs["steps_in_window"]
    assert result["failed"] == 0 and steps >= 1
    assert set(result["_e2e"]) == {"train_tok_per_s", "setup_s"}
    assert obs["compile"]["in_window"] == 0
    assert result["_e2e"]["train_tok_per_s"] * obs["window_s"] == \
        pytest.approx(2 * 64 * steps)
    ref = result["_reference"]
    assert ref["loss"] == pytest.approx(ref["reference_loss"], rel=1e-4)
    assert set(ref["updates"]) == set(NAMES)
    assert all(err < 0.05 for err in ref["updates"].values()), ref
    ref_mod = manifest.load_module("reference", "keye-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    assert got["moe_dropped_share"]["value"] == 0.0
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 2
    # two sequences of 64 rows under a top-16: 904 of 2080 pairs a
    # sequence, in every block and step; one 64 x 64 tile a sequence
    kept = got["dsa_kept_pairs_share"]
    assert kept["samples"] == 2 * steps
    assert kept["selected_pairs"] == 2 * 904 * 2 * steps
    assert kept["causal_pairs"] == 2 * 2080 * 2 * steps
    assert kept["value"] == pytest.approx(100.0 * 904 / 2080)
    assert ref_mod.selected_pairs(64, 16) == 904
    tiles = got["dsa_tiles_hit_share"]
    assert tiles["value"] == 100.0 and tiles["tiles_causal"] == 2 * steps * 2
    kl = got["dsa_index_kl"]
    assert kl["samples"] == 2 * steps and kl["blocks"] == 2
    assert 0.0 < kl["value"] < 3.0 and kl["first"] > 0 and kl["last"] > 0
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_keye_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the five entries it
    came with as their readers have them, and the cell on every list the
    SDAR cell is on but the three that are its mask's, its objective's
    and its ``attn`` scope's.  By name and by membership, never by a
    position or a length (``test_cellbench_rehearsal.py`` runs this
    against copies to which later cells were appended)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert len(config["why"]) <= 200
    for name, facts in READERS.items():
        entries = [m for m in doc["per_layer"] if m["name"] == name]
        assert len(entries) == 1
        entry = dict(entries[0])
        reader = manifest.load_module("layer_metrics", name)
        assert REAL_CELL in entry.pop("workloads")
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": reader.BETTER, "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": "train_tok_per_s"}
        assert (reader.UNIT, reader.BETTER, reader.SOURCE,
                reader.LAYER) == facts

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, sdar = lists_of(REAL_CELL), lists_of(SDAR_CELL)
    assert sdar - listed == {"bd_attn_roofline", "diffusion_masked_share",
                             "scope_attn_ms"}
    assert set(READERS) <= listed - sdar
    assert {"train_tok_per_s", "mfu.tok", "peak_hbm_gib.tok",
            "moe_held_rows_share", "moe_prefix_fit_share",
            "scope_moe_experts_ms", "scope_moe_layout_ms",
            "scope_lm_loss_ms", "setup_check_module_s",
            "setup_compile_backend_s"} <= listed
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline",
                "swa_attn_roofline", "loop_attn_roofline"} & listed
    for name in listed - sdar:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_keye_cells_own_entries(doc)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def _feed_counter(steps, blocks=2):
    import mxnet_tpu as mx
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        for step in range(steps):
            for l in range(blocks):
                mx.trace.counter(
                    "dsa:select", cat="train", track="l%d" % l, rows=8192.0,
                    selected_pairs=14681088.0, causal_pairs=33558528.0,
                    tiles_hit=130.0 + l, tiles_causal=136.0,
                    kl=2.0 - 0.1 * step + l)
    finally:
        mx.trace.set_enabled(was)


def test_the_counter_readers_with_and_without_the_counter():
    import mxnet_tpu as mx
    readers = {name: manifest.load_module("layer_metrics", name)
               for name in ("dsa_kept_pairs_share", "dsa_tiles_hit_share",
                            "dsa_index_kl")}
    mx.trace.reset()
    for reader in readers.values():
        assert reader.read({"steps_in_window": 5}) is None
        assert reader.read({"steps_in_window": 0}) is None
        assert reader.read({}) is None
    # two warm-up steps, then a window of ten, two blocks
    _feed_counter(12)
    try:
        obs = {"steps_in_window": 10}
        value, extra = readers["dsa_kept_pairs_share"].read(obs)
        assert extra["samples"] == 20
        assert value == pytest.approx(100.0 * 14681088 / 33558528)
        assert value == pytest.approx(43.75, abs=0.01)
        value, extra = readers["dsa_tiles_hit_share"].read(obs)
        assert extra["tiles_causal"] == 20 * 136.0
        assert value == pytest.approx(100.0 * 130.5 / 136.0)
        value, extra = readers["dsa_index_kl"].read(obs)
        assert (extra["samples"], extra["blocks"]) == (20, 2)
        # steps 2..11 of the twelve: kl 1.8 .. 0.9 in block 0, + 1 in 1
        assert value == pytest.approx(1.35 + 0.5)
        assert extra["first"] == pytest.approx(1.8 + 0.5)
        assert extra["last"] == pytest.approx(0.9 + 0.5)
    finally:
        mx.trace.reset()


def test_the_roofline_reader_with_and_without_a_trace():
    reader = manifest.load_module("layer_metrics", "dsa_attn_roofline")
    cell = manifest.Manifest().cell(REAL_CELL)
    peaks = manifest.load_peaks("TPU v5 lite")
    obs = {"config": cell.config, "traffic": cell.traffic, "peaks": peaks,
           "trace": None}
    assert reader.read(obs) is None
    obs["trace"] = {"steps": 2, "op_seconds": {"fusion.1 fusion f32": 1.0}}
    assert reader.read(obs) is None            # no such operation
    obs["trace"]["op_seconds"].update({
        "splash_mha_fwd_residuals.3 custom-call bf16[32,8192,128]": 0.08,
        "splash_mha_dkv_no_residuals.7 custom-call f32[1024,128]": 0.12})
    value, extra = reader.read(obs)
    assert extra["kernel_ms"] == pytest.approx(100.0)
    assert extra["bound"] == "compute" and extra["steps"] == 2
    assert value == pytest.approx(100.0 * extra["roofline_ms"] / 100.0)
    # a configuration whose attention selects nothing has no such share
    sdar = manifest.Manifest().cell(SDAR_CELL)
    assert reader.read(dict(obs, config=sdar.config)) is None


def test_the_scope_reader_sums_the_five_kinds(monkeypatch):
    import scope_seconds
    reader = manifest.load_module("layer_metrics", "scope_dsa_ms")
    assert reader.read({"trace": None}) is None
    obs = {"trace": {"steps": 2, "op_seconds": {
        "fusion.1 fusion f32": 0.010, "fusion.2 fusion f32": 0.004,
        "splash.3 custom-call bf16": 0.040, "fusion.4 fusion f32": 0.020,
        "fusion.5 fusion bf16": 0.002, "fusion.6 fusion f32": 0.5}}}
    monkeypatch.setattr(scope_seconds, "program_table", lambda: None)
    assert reader.read(obs) is None
    monkeypatch.setattr(scope_seconds, "program_table", lambda: {
        "fusion.6": "moe_experts.l0"})
    assert reader.read(obs) is None            # no dsa scope in the step
    monkeypatch.setattr(scope_seconds, "program_table", lambda: {
        "fusion.1": "dsa_score.l0", "fusion.2": "dsa_select.l1",
        "splash.3": "dsa_attn.l0", "fusion.4": "dsa_kl.l3",
        "fusion.5": "dsa_index.l2", "fusion.6": "moe_experts.l0"})
    value, extra = reader.read(obs)
    assert extra["by_kind"] == pytest.approx({
        "dsa_index": 1.0, "dsa_score": 5.0, "dsa_select": 2.0,
        "dsa_attn": 20.0, "dsa_kl": 10.0})
    assert value == pytest.approx(38.0) and extra["steps"] == 2


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced``; the builder's
    arguments are the same numbers; the cuts are at the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
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
    sa = cfg["sa_config"]
    assert (kw["num_layers"], kw["hidden_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["num_experts"],
            kw["experts_held"], kw["experts_per_tok"], kw["expert_width"],
            kw["vocab_size"], kw["rope_theta"], kw["rms_eps"],
            kw["index_heads"], kw["index_dim"], kw["topk"],
            kw["mrope_sections"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["num_local_experts"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["vocab_size"], cfg["rope_theta"], cfg["rms_norm_eps"],
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        cfg["rope_scaling"]["mrope_section"])
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["num_experts"], kw["experts_per_tok"],
            kw["expert_width"], kw["rope_theta"], kw["index_heads"],
            kw["index_dim"], kw["topk"]) == (
        2048, 32, 4, 128, 128, 8, 768, 1e7, 16, 64, 2048)
    assert sa["indexer_num_kv_heads"] == 1
    assert cfg["norm_topk_prob"] is True and cfg["mlp_only_layers"] == []
    # the floors: 16 experts of 8 ranks, an eighth of the vocabulary, four
    # layers
    assert kw["experts_held"] == 16 and kw["first_expert"] == 0
    assert kw["num_experts"] // kw["experts_held"] == 8
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert kw["num_layers"] == 4 and kw["seq_len"] == 8192
    assert {"head_norm", "rope_pairing", "indexer_key_norm",
            "indexer_scales_and_relu", "indexer_rotation", "chunk_sizes",
            "tie_rule", "index_loss", "objective_stage",
            "load_balance"} <= set(cfg["assumed"])
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    assert set(NAMES) <= set(cfg["reference"]["weights"])
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, FLOPs a trained token and the selected-attention
    work function's sums, written out."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import keye_lm
    net = keye_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    held = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    D, V, L, T = 2048, 18992, 4, 8192
    attention = 2 * D * 4096 + 2 * D * 512
    assert attention == 18_874_368
    indexer = D * (16 * 64 + 64 + 16) + 2 * 64
    assert indexer == pytest.approx(2.26e6, rel=2e-3)
    norms = 2 * D + 2 * 128                  # a block's four gains
    expert = 3 * D * 768
    assert expert == 4_718_592
    layer = attention + indexer + norms + 128 * D + 16 * expert
    assert layer == pytest.approx(96.9e6, rel=1e-3)
    total = 2 * D * V + D + L * layer
    assert sum(held.values()) == total
    assert total == pytest.approx(465.4e6, rel=1e-4)
    assert 16 * total / 2 ** 30 == pytest.approx(6.93, abs=0.005)
    assert held["l1_index_q_proj_weight"] == 1024 * D
    assert held["l1_index_k_proj_weight"] == 64 * D
    assert held["l1_index_w_proj_weight"] == 16 * D
    assert held["l1_index_k_norm_gamma"] == held["l1_index_k_norm_beta"] == 64
    assert held["l1_moe_experts_i2h_weight"] == 16 * D * 768
    # the pairs: row t keeps min(t + 1, 2048)
    kept = 2048 * 2049 // 2 + (T - 2048) * 2048
    assert kept == 14_681_088 == ref.selected_pairs(T, 2048)
    causal = T * (T + 1) // 2
    assert causal == 33_558_528
    assert 100.0 * kept / causal == pytest.approx(43.75, abs=0.01)
    # FLOPs a row and layer, forward
    proj = 2 * attention
    index_proj = 2 * D * (16 * 64 + 64 + 16)
    scores = 2 * 16 * 64 * causal / T
    selected = 4 * 128 * 32 * kept / T
    sparse = 2 * D * 128 + 8 * 16 / 128 * 2 * expert
    assert (proj, index_proj) == (37_748_736, 4_521_984)
    assert scores == pytest.approx(8.39e6, rel=1e-3)
    assert selected == pytest.approx(29.36e6, rel=1e-3)
    row = proj + index_proj + scores + selected + sparse
    assert row == pytest.approx(89.98e6, rel=1e-3)
    assert (index_proj + scores + selected) / row == pytest.approx(0.47,
                                                                   abs=0.005)
    forward = L * row + 2 * D * V
    assert forward == pytest.approx(437.7e6, rel=1e-3)
    token = 3 * forward - L * index_proj
    assert ref.train_flops_per_sample(cfg) == pytest.approx(token)
    assert token == pytest.approx(1.295e9, rel=1e-3)
    # the kernels' roofline: L calls, 32 heads over 4, the SELECTED pairs
    reader = manifest.load_module("layer_metrics", "dsa_attn_roofline")
    assert reader.selected_pairs(T, 2048) == kept
    assert reader.selected_pairs(64, 16) == 904
    assert reader.selected_pairs(100, 2048) == 5050
    ops, nbytes = reader.selected_attention_work(cfg, cell.traffic)
    assert ops == L * 14 * 128 * 32 * kept
    assert nbytes == L * 2 * T * 128 * 4 * (32 + 4)
    import kernel_rooflines
    seconds, bound = kernel_rooflines.roofline_time(
        (ops, nbytes), manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute"
    assert ops == pytest.approx(3.367e12, rel=1e-3)
    assert seconds == pytest.approx(17.09e-3, rel=2e-3)
    # the held experts' rows a step against the deployment's
    assert T * 8 * 16 // 128 // 16 == 512 and 8 * 512 == 4096
