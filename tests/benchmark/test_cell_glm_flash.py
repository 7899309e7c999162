"""The ``glm-4.7-flash`` configuration, its cell and its reader
``mtp_loss_over_main``: the real entries by name, the configuration's
arithmetic (706.5 M parameters held, the FLOPs a token, the attention
kernel's roofline sum), and the cell on the CPU at tiny widths, added to
the temporary copy of ``cellbench_util.tiny_copy`` as files and entries,
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
CELL = "tiny-glm"
REAL_CELL = "glm-4.7-flash-train-4k"
KIMI_CELL = "kimi-linear-48b-a3b-train-4k"
CONFIG = "glm-4.7-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# as the Kimi file: the window holds some steps on a loaded machine too,
# and no assertion below asks for more than one
WINDOW_S = 4.0


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_glm"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "glm-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=3, hidden_size=32, heads=2, q_lora_rank=12,
        kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=12,
        dense_width=64, num_experts=16, experts_per_tok=4, expert_width=24,
        shared_width=24, vocab_size=128, seq_len=72, experts_held=4,
        first_expert=4)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    names = ["l1_q_a_proj_weight", "l1_q_b_proj_weight",
             "l1_kv_b_proj_weight", "l1_moe_gate_weight",
             "l2_moe_experts_i2h_weight", "mtp_eh_proj_weight",
             "embed_weight", "lm_head_weight"]
    cfg["reference"].update(samples=2, weights=names, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(names, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "glm-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "glm-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", "packed-4k-b1.json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-glm.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "glm-tiny", "source": "test",
                           "file": "benchmark/configs/glm-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "glm-tiny", "tiny-packed-glm", like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_glm_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    try:
        result = driver.run(cell, [mx.cpu(0)], 3500000031, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
    finally:
        # the ring is the process's: the blocks' names of this model must
        # not be there when another cell's test reads its own
        mx.trace.reset()
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
    assert set(ref["updates"]) == set(cell.config["reference"]["weights"])
    assert all(err < 0.05 for err in ref["updates"].values()), ref
    ref_mod = manifest.load_module("reference", "glm-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # three routed blocks: two of the trunk and the module's
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        3 * obs["steps_in_window"]
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 3
    ratio = got["mtp_loss_over_main"]
    tenth = min(obs["steps_in_window"],
                max(3, obs["steps_in_window"] // 10))
    assert ratio["samples"] == tenth and ratio["weight"] == 0.3
    assert ratio["value"] == pytest.approx(ratio["mtp"] / ratio["main"],
                                           rel=0.2)
    assert 0.5 < ratio["value"] < 3.0        # both heads near each other
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_glm_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the entry it came with
    as its reader has it, and the cell on every list the Kimi cell is on
    but that cell's own kernel's.  By name and by membership, never by a
    position or a length: later cells and entries are appended to the
    same lists (``test_cellbench_rehearsal.py`` runs this against such
    copies)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "packed-4k-b1", 1)
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    entries = [m for m in doc["per_layer"]
               if m["name"] == "mtp_loss_over_main"]
    assert len(entries) == 1
    entry = dict(entries[0])
    reader = manifest.load_module("layer_metrics", "mtp_loss_over_main")
    assert REAL_CELL in entry.pop("workloads")
    assert entry == {"name": "mtp_loss_over_main", "unit": reader.UNIT,
                     "better": reader.BETTER, "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": "train_tok_per_s"}
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) == \
        ("ratio", "lower", "program_counter", "prediction heads")

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, kimi = lists_of(REAL_CELL), lists_of(KIMI_CELL)
    # every list the Kimi cell is on but the linear-attention kernel's
    assert "kda_roofline" in kimi - listed
    assert all(n.endswith("_roofline") for n in kimi - listed), kimi - listed
    assert {"train_tok_per_s", "mla_attn_roofline", "moe_held_rows_share",
            "moe_load_max_over_mean", "moe_dropped_share", "mfu.tok",
            "dispatch_ms_p50.tok", "peak_hbm_gib.tok"} <= listed
    # not the kernels counted for one head size or for every routed row
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline"} & listed
    own = listed - kimi
    assert "mtp_loss_over_main" in own
    for name in own:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_reader():
    check_the_glm_cells_own_entries(manifest.Manifest().doc)


def test_the_ratio_reader_with_and_without_the_counter():
    import mxnet_tpu as mx
    reader = manifest.load_module("layer_metrics", "mtp_loss_over_main")
    mx.trace.reset()
    assert reader.read({"steps_in_window": 5}) is None
    assert reader.read({"steps_in_window": 0}) is None
    assert reader.read({}) is None
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        # two warm-up steps, then a window of forty: the last tenth is
        # the last four
        for step in range(42):
            mx.trace.counter("mtp:loss", cat="train", main=2.0,
                             mtp=2.0 + step, weight=0.3)
    finally:
        mx.trace.set_enabled(was)
    value, extra = reader.read({"steps_in_window": 40})
    assert value == pytest.approx((2.0 + 39.5) / 2.0)
    assert extra == {"samples": 4, "main": 2.0, "mtp": 41.5, "weight": 0.3}
    # a short window: three steps at least, or all there are
    value, extra = reader.read({"steps_in_window": 2})
    assert extra["samples"] == 2 and value == pytest.approx(42.5 / 2.0)
    mx.trace.reset()


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced``; the builder's
    arguments are the same numbers; the cuts are at the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (
        published["num_hidden_layers"], published["n_routed_experts"],
        published["vocab_size"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["dense_layers"],
            kw["heads"], kw["q_lora_rank"], kw["kv_lora_rank"],
            kw["qk_nope_dim"], kw["qk_rope_dim"], kw["v_head_dim"],
            kw["rope_theta"], kw["dense_width"], kw["num_experts"],
            kw["experts_held"], kw["experts_per_tok"], kw["expert_width"],
            kw["shared_width"], kw["routed_scale"], kw["vocab_size"],
            kw["nextn_layers"], kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["first_k_dense_replace"], cfg["num_attention_heads"],
        cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["rope_theta"],
        cfg["intermediate_size"], cfg["n_routed_experts_published"],
        cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"],
        cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        cfg["routed_scaling_factor"], cfg["vocab_size"],
        cfg["num_nextn_predict_layers"], cfg["rms_norm_eps"])
    # the floors: the dense layer and four more, 8 experts, an eighth of
    # the vocabulary; one of 8 ranks
    assert kw["num_layers"] == kw["dense_layers"] + 4
    assert kw["experts_held"] == 8 and kw["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert kw["num_experts"] // kw["experts_held"] == 8
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    assert {"embed_weight", "lm_head_weight", "mtp_eh_proj_weight"} \
        <= set(cfg["reference"]["weights"])
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, FLOPs a token and the attention kernel's
    roofline sum, written out."""
    import kernel_rooflines
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import glm_moe_lite_lm
    net = glm_moe_lite_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    held = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    D, V = 2048, 19360
    mla = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert mla == 21_757_952
    norms = 2 * D + 768 + 512                # a block's four gains
    expert = 3 * D * 1536
    sparse = mla + norms + 64 * D + 9 * expert
    dense = mla + norms + 3 * D * 10240
    module = 2 * D + 2 * D * D + sparse + D
    total = 2 * D * V + D + dense + 4 * sparse + module
    assert sum(held.values()) == total == 706_518_528
    assert held["embed_weight"] == held["lm_head_weight"] == D * V
    assert held["l1_moe_experts_i2h_weight"] == 8 * D * 1536
    assert held["mtp_eh_proj_weight"] == 2 * D * D
    assert 16 * total / 2 ** 30 == pytest.approx(10.53, abs=0.005)
    # FLOPs a trained token: 352.6 M active matmul parameters x 6, and
    # six causal attentions at T / 2 keys a query
    active = 6 * mla + 3 * D * 10240 + 5 * (
        64 * D + expert + 4 * 8 / 64 * expert) + 2 * D * V + 2 * D * D
    assert active == pytest.approx(352.6e6, rel=1e-3)
    attention = 6 * 3 * 4096 * 20 * (256 + 256)
    assert ref.train_flops_per_sample(cfg) == 6 * active + attention
    assert 4096 * ref.train_flops_per_sample(cfg) == \
        pytest.approx(11.757e12, rel=1e-3)
    # the kernel's roofline: six calls, 20 heads, 256 / 256
    ops, nbytes = kernel_rooflines.latent_attention_work(cfg, cell.traffic)
    assert ops == 6 * 20 * 4096 ** 2 * ((256 + 256) + (3 * 256 + 2 * 256))
    assert ops == pytest.approx(3.608e12, rel=1e-3)
    assert nbytes == 6 * 2 * 4096 * 20 * 4 * (256 + 256)
    assert nbytes == pytest.approx(2.013e9, rel=1e-3)
    seconds, bound = kernel_rooflines.roofline_time(
        (ops, nbytes), manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(18.31e-3,
                                                           rel=1e-3)
    # the held experts' rows a step against the deployment's
    assert 4096 * 4 * 8 // 64 // 8 == 256 and 8 * 256 == 2048
