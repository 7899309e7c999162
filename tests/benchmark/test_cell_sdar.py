"""The ``sdar-30b-a3b`` configuration, its cell and its two readers
``bd_attn_roofline`` and ``diffusion_masked_share``: the real entries by
name, the configuration's arithmetic (645.6 M parameters held, the FLOPs
a counted token, the attention kernel's roofline sum), the generator's
batches, and the cell on the CPU at tiny widths, added to the temporary
copy of ``cellbench_util.tiny_copy`` as files and entries, through the
same driver as the others.  A CPU run checks answers and counts, never
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
CELL = "tiny-sdar"
REAL_CELL = "sdar-30b-a3b-train-4k"
GLM_CELL = "glm-4.7-flash-train-4k"
CONFIG = "sdar-30b-a3b"
TRAFFIC = "block-noised-4k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# as the GLM file: the window holds some steps on a loaded machine too,
# and no assertion below asks for more than one
WINDOW_S = 4.0
NAMES = ["l1_q_proj_weight", "l1_k_proj_weight", "l1_v_proj_weight",
         "l1_o_proj_weight", "l1_moe_gate_weight",
         "l1_moe_experts_i2h_weight", "embed_weight", "lm_head_weight"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_sdar"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "sdar-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=8, num_experts=16, experts_per_tok=4, expert_width=24,
        vocab_size=128, seq_len=64, experts_held=4, first_expert=4)
    cfg["input"] = {"seq_len": 64, "vocab_size": 128, "mask_id": 127}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "sdar-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "sdar-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.05)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-noised.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "sdar-tiny", "source": "test",
                           "file": "benchmark/configs/sdar-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "sdar-tiny", "tiny-noised", like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_sdar_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    was = mx.trace.enabled()
    try:
        # the counter behind diffusion_masked_share is fed while tracing
        # is on, as in a --trace 1 run (the driver switches it on there)
        mx.trace.set_enabled(True)
        result = driver.run(cell, [mx.cpu(0)], 3900000031, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
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
    # the rate counts the clean positions: 64 a sequence, not the 128
    # rows and not the masked ones
    assert result["_e2e"]["train_tok_per_s"] * obs["window_s"] == \
        pytest.approx(2 * 64 * obs["steps_in_window"])
    ref = result["_reference"]
    assert ref["loss"] == pytest.approx(ref["reference_loss"], rel=1e-4)
    assert set(ref["updates"]) == set(NAMES)
    assert all(err < 0.05 for err in ref["updates"].values()), ref
    ref_mod = manifest.load_module("reference", "sdar-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        2 * obs["steps_in_window"]
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 2
    share = got["diffusion_masked_share"]
    assert share["samples"] == obs["steps_in_window"]
    assert share["positions"] == 2 * 64 * obs["steps_in_window"]
    assert 25.0 < share["value"] < 75.0
    assert share["value"] == pytest.approx(
        100.0 * share["masked"] / share["positions"])
    assert 0.3 < share["weight_mean"] < 3.0       # E[masked / t] = 1
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_sdar_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the two entries it came
    with as their readers have them, and the cell on every list the GLM
    cell is on but that cell's own.  By name and by membership, never by
    a position or a length: later cells and entries are appended to the
    same lists (``test_cellbench_rehearsal.py`` runs this against such
    copies)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for name, facts in (
            ("bd_attn_roofline", ("%", "higher", "device_trace",
                                  "Pallas kernels")),
            ("diffusion_masked_share", ("%", "higher", "program_counter",
                                        "diffusion objective"))):
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

    listed, glm = lists_of(REAL_CELL), lists_of(GLM_CELL)
    # every list the GLM cell is on but what is its mixer's or its
    # prediction module's
    assert glm - listed == {"mla_attn_roofline", "scope_mla_proj_ms",
                            "scope_kda_ms", "scope_mtp_ms",
                            "mtp_loss_over_main"}
    assert {"train_tok_per_s", "moe_held_rows_share", "scope_attn_ms",
            "moe_load_max_over_mean", "moe_dropped_share", "mfu.tok",
            "dispatch_ms_p50.tok", "peak_hbm_gib.tok"} <= listed
    # not the kernels counted for another mask or for every routed row
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline"} & listed
    assert {"bd_attn_roofline", "diffusion_masked_share"} <= listed - glm
    for name in listed - glm:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_sdar_cells_own_entries(doc)
    # seven cells, one of them on four chips
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_share_reader_with_and_without_the_counter():
    import mxnet_tpu as mx
    reader = manifest.load_module("layer_metrics", "diffusion_masked_share")
    mx.trace.reset()
    assert reader.read({"steps_in_window": 5}) is None
    assert reader.read({"steps_in_window": 0}) is None
    assert reader.read({}) is None
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        # two warm-up steps, then a window of four
        for step in range(6):
            mx.trace.counter("diffusion:noise", cat="train",
                             masked=1000.0 + 100 * step, positions=4096.0,
                             weight_sum=4000.0)
    finally:
        mx.trace.set_enabled(was)
    value, extra = reader.read({"steps_in_window": 4})
    assert extra["samples"] == 4 and extra["positions"] == 4 * 4096.0
    assert extra["masked"] == 1200.0 + 1300 + 1400 + 1500
    assert value == pytest.approx(100.0 * 5400.0 / 16384.0)
    assert extra["weight_mean"] == pytest.approx(4000.0 / 4096.0)
    mx.trace.reset()


def test_the_roofline_reader_with_and_without_a_trace():
    reader = manifest.load_module("layer_metrics", "bd_attn_roofline")
    cell = manifest.Manifest().cell(REAL_CELL)
    peaks = manifest.load_peaks("TPU v5 lite")
    obs = {"config": cell.config, "traffic": cell.traffic, "peaks": peaks,
           "trace": None}
    assert reader.read(obs) is None
    obs["trace"] = {"steps": 2, "op_seconds": {"fusion.1 fusion f32": 1.0}}
    assert reader.read(obs) is None            # no such operation
    obs["trace"]["op_seconds"].update({
        "splash_mha_fwd_residuals.3 custom-call bf16[32,8192,128]": 0.04,
        "splash_mha_dkv_no_residuals.7 custom-call f32[1024,128]": 0.08})
    value, extra = reader.read(obs)
    assert extra["kernel_ms"] == pytest.approx(60.0)
    assert extra["bound"] == "compute" and extra["steps"] == 2
    assert value == pytest.approx(100.0 * extra["roofline_ms"] / 60.0)


def test_the_generators_batches():
    """[x_t ; x_0] and the labels' two planes, from the seed; the corpus
    never holds MASK; the same seed the same batches."""
    cell = manifest.Manifest().cell(REAL_CELL)
    gen = manifest.load_module("generators", cell.traffic["generator"])
    traffic = dict(cell.traffic, distinct_batches=3)
    import mxnet_tpu as mx
    a = gen.build(traffic, cell.config, 3900000123, [mx.cpu(0)], None)
    b = gen.build(traffic, cell.config, 3900000123, [mx.cpu(0)], None)
    c = gen.build(traffic, cell.config, 3900000124, [mx.cpu(0)], None)
    assert a.provide_data == [("data", (1, 8192))]
    assert a.provide_label == [("softmax_label", (1, 2, 4096))]
    batches = [a.next() for _ in range(3)]
    with pytest.raises(StopIteration):
        a.next()
    a.reset()
    assert a.samples(batches[0]) == 4096
    data = np.concatenate([x.data[0].asnumpy() for x in batches])
    label = np.concatenate([x.label[0].asnumpy() for x in batches])
    assert data.dtype == np.int32 and label.dtype == np.float32
    assert np.array_equal(data, b._host[0])
    assert not np.array_equal(data, c._host[0])
    noised, clean = data[:, :4096], data[:, 4096:]
    target, weight = label[:, 0], label[:, 1]
    mask_id = cell.config["input"]["mask_id"]
    assert mask_id == cell.config["input"]["vocab_size"] - 1 == 18991
    assert clean.max() < mask_id and clean.min() == 0
    masked = target >= 0
    assert np.array_equal(noised == mask_id, masked)
    assert np.array_equal(noised[~masked], clean[~masked])
    assert np.array_equal(target[masked], clean[masked])
    assert np.array_equal(target[~masked], np.full((~masked).sum(), -1.0))
    assert 0.45 < masked.mean() < 0.55
    assert weight.min() >= 1.0 and weight.max() <= 1000.0
    blocks = weight.reshape(3, 1024, 4)
    assert np.array_equal(blocks.min(-1), blocks.max(-1))   # one t a block
    ref = a.reference_batch(1)
    assert ref[2] is None and ref[0]["data"].shape == (1, 8192)
    assert ref[1]["softmax_label"].shape == (1, 2, 4096)
    assert np.array_equal(ref[0]["data"], data[:1])


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced``; the builder's
    arguments are the same numbers; the cuts are at the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
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
            kw["num_kv_heads"], kw["head_dim"], kw["num_experts"],
            kw["experts_held"], kw["experts_per_tok"], kw["expert_width"],
            kw["vocab_size"], kw["rope_theta"], kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["num_experts_published"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["vocab_size"], cfg["rope_theta"], cfg["rms_norm_eps"])
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["num_experts"], kw["experts_per_tok"],
            kw["expert_width"], kw["rope_theta"], kw["rms_eps"]) == (
        2048, 32, 4, 128, 128, 8, 768, 1e6, 1e-6)
    assert cfg["norm_topk_prob"] is True and cfg["mlp_only_layers"] == []
    # the floors: 16 experts of 8 ranks, an eighth of the vocabulary, at
    # least four layers
    assert kw["experts_held"] == 16 and kw["first_expert"] == 0
    assert kw["num_experts"] // kw["experts_held"] == 8
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert 4 <= kw["num_layers"] <= 6
    assert kw["block_len"] == 4 and kw["seq_len"] == 4096
    assert {"block_length", "noise_schedule", "loss_weight",
            "vectorised_form", "logit_shift", "mask_id",
            "load_balance"} <= set(cfg["assumed"])
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    assert {"embed_weight", "lm_head_weight", "l1_moe_gate_weight"} \
        <= set(cfg["reference"]["weights"])
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"],
                            "mask_id": kw["vocab_size"] - 1}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, FLOPs a counted token and the attention kernel's
    roofline sum, written out."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import sdar_moe_lm
    net = sdar_moe_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 8192),
                                   softmax_label=(1, 2, 4096))
    held = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    D, V, L = 2048, 18992, kw["num_layers"]
    attention = 2 * D * 4096 + 2 * D * 512
    assert attention == 18_874_368
    norms = 2 * D + 2 * 128                  # a block's four gains
    expert = 3 * D * 768
    assert expert == 4_718_592
    layer = attention + norms + 128 * D + 16 * expert
    assert layer == pytest.approx(94.64e6, rel=1e-4)
    total = 2 * D * V + D + L * layer
    assert sum(held.values()) == total
    assert held["embed_weight"] == held["lm_head_weight"] == D * V
    assert held["l1_moe_experts_i2h_weight"] == 16 * D * 768
    assert held["l1_k_proj_weight"] == 512 * D
    assert held["l1_q_norm_gamma"] == held["l1_k_norm_gamma"] == 128
    if L == 6:
        assert total == 645_623_296
        assert 16 * total / 2 ** 30 == pytest.approx(9.62, abs=0.005)
    # whole, a layer's 128 experts are 604 M of its 623 M parameters
    assert attention + 128 * D + 128 * expert == pytest.approx(623.1e6,
                                                               rel=1e-3)
    # FLOPs a row and layer: projections, the allowed pairs, the router,
    # the held share of the 8 chosen experts (one expert a row)
    pairs = 4096 * 4096 + 4096 * 4
    assert ref.allowed_pairs(4096, 4) == pairs
    row = 2 * attention + 4 * 128 * 32 * pairs / 8192 + 2 * D * 128 \
        + 8 * 16 / 128 * 2 * expert
    assert row == pytest.approx(81.3e6, rel=1e-3)
    assert 4 * 128 * 32 * pairs / 8192 / row == pytest.approx(0.413,
                                                              abs=0.002)
    token = 3 * (2 * L * row + 2 * D * V)
    assert ref.train_flops_per_sample(cfg) == pytest.approx(token)
    if L == 6:
        assert token == pytest.approx(3.16e9, rel=2e-3)
        assert 4096 * token == pytest.approx(12.94e12, rel=2e-3)
    # the kernel's roofline: L calls, 32 heads over 4, the allowed pairs
    reader = manifest.load_module("layer_metrics", "bd_attn_roofline")
    assert reader.allowed_pairs(4096, 4) == pairs
    ops, nbytes = reader.block_diffusion_attention_work(cfg, cell.traffic)
    assert ops == L * 14 * 128 * 32 * pairs
    assert nbytes == L * 2 * 8192 * 128 * 4 * (32 + 4)
    import kernel_rooflines
    seconds, bound = kernel_rooflines.roofline_time(
        (ops, nbytes), manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute"
    if L == 6:
        assert ops == pytest.approx(5.778e12, rel=1e-3)
        assert seconds == pytest.approx(29.33e-3, rel=2e-3)
    # the held experts' rows a step against the deployment's
    assert 8192 * 8 * 16 // 128 // 16 == 512 and 8 * 512 == 4096
