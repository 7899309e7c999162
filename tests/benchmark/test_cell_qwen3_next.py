"""The ``qwen3-next-80b-a3b`` configuration, its cell and its two readers
``gdn_roofline`` and ``gated_attn_roofline``: the real entries by name,
the configuration's arithmetic (the parameters held, the FLOPs a token,
the two kernels' roofline sums), and the cell on the CPU at tiny widths,
added to the temporary copy of ``cellbench_util.tiny_copy`` as files and
entries, through the same driver as the others.  A CPU run checks
answers and counts, never rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-qwen3-next"
REAL_CELL = "qwen3-next-80b-a3b-train-4k"
TRINITY_CELL = "trinity-mini-train-4k"
CONFIG = "qwen3-next-80b-a3b"
TRAFFIC = "packed-4k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = {"gdn_roofline": "linear attention",
           "gated_attn_roofline": "Pallas kernels"}
# as the GLM file: the window holds some steps on a loaded machine too,
# and no assertion below asks for more than one
WINDOW_S = 4.0
NAMES = ["l0_qkvz_proj_weight", "l1_gdn_dt_bias", "l2_conv_weight",
         "l3_q_proj_weight", "l0_moe_shared_gate_weight",
         "l1_moe_gate_weight", "l1_moe_experts_i2h_weight", "embed_weight"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_qwen3_next"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "qwen3-next-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=4, hidden_size=32, gdn_key_heads=2, gdn_value_heads=4,
        gdn_head_dim=8, num_heads=4, num_kv_heads=2, head_dim=16,
        rotary_dim=4, num_experts=16, experts_per_tok=4, expert_width=24,
        shared_width=24, vocab_size=128, seq_len=72, experts_held=4,
        first_expert=4)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "qwen3-next-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "qwen3-next-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic",
                               "tiny-packed-qwen3-next.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "qwen3-next-tiny", "source": "test",
                           "file": "benchmark/configs/qwen3-next-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "qwen3-next-tiny", "tiny-packed-qwen3-next",
                  like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_qwen3_next_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    was = mx.trace.enabled()
    try:
        # the lowering samples are taken while tracing is on, as in a
        # --trace 1 run (the driver switches it on there)
        mx.trace.set_enabled(True)
        mark = time.perf_counter_ns()
        result = driver.run(cell, [mx.cpu(0)], 4100000050, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        rule = mx.trace.counter_events(["gdn:lowering"], since_ns=mark)
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
        kimis = mx.trace.counter_events(["kda:lowering"], since_ns=mark)
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
    ref_mod = manifest.load_module("reference", "qwen3-next-tiny",
                                   cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # four routed blocks
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        4 * obs["steps_in_window"]
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 4
    # each traced op set names three layers of the rule, then attention
    assert rule and len(rule) == 3 * len(attn) and not kimis
    assert {e["id"] for e in rule} == {"float32[2, 72, 4, 8]/k2"}
    assert {(e["args"]["key_heads"], e["args"]["value_heads"])
            for e in rule} == {(2, 4)}
    assert {e["id"] for e in attn} == {"float32[2, 72, 4, 16]/kv2"}
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))
    # no trace: the roofline readers have nothing to read and say so
    assert not set(READERS) & set(got)


def check_the_qwen3_next_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the two entries it came
    with as their readers have them, and the cell on every list the
    Trinity cell is on but that cell's own kernel share, and on the
    rule's scope.  By name and by membership, never by a position or a
    length: later cells and entries are appended to the same lists
    (``test_cellbench_rehearsal.py`` runs this against such copies)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_experts", "vocab_size",
                                 "num_hidden_layers"]
    for name, layer in READERS.items():
        entries = [m for m in doc["per_layer"] if m["name"] == name]
        assert len(entries) == 1
        entry = dict(entries[0])
        reader = manifest.load_module("layer_metrics", name)
        assert REAL_CELL in entry.pop("workloads")
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": reader.BETTER, "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": "train_tok_per_s"}
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) \
            == ("%", "higher", "device_trace", layer)

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, trinity = lists_of(REAL_CELL), lists_of(TRINITY_CELL)
    # every list the Trinity cell is on but its masks' kernel share
    assert trinity - listed == {"swa_attn_roofline"}
    assert {"train_tok_per_s", "moe_held_rows_share", "scope_attn_ms",
            "scope_kda_ms", "scope_moe_experts_ms", "scope_moe_layout_ms",
            "scope_lm_loss_ms", "moe_prefix_fit_share",
            "moe_load_max_over_mean", "moe_dropped_share", "mfu.tok",
            "dispatch_ms_p50.tok", "scope_other_ms.tok",
            "peak_hbm_gib.tok"} <= listed
    # not the shares whose work functions would miscount this model or
    # raise on its keys
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline",
                "mla_attn_roofline", "swa_attn_roofline",
                "bd_attn_roofline"} & listed
    assert listed - trinity == set(READERS) | {"scope_kda_ms"}
    for name in listed - trinity:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_qwen3_next_cells_own_entries(doc)
    # one cell on four chips, the place the benchmark has
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


@pytest.mark.parametrize("name,ops,roofline_ms,bound", [
    ("gdn_roofline", {"kda_chunk_fwd.3 custom-call f32[1,32,64,128,128]":
                      0.012, "kda_chunk_bwd.5 custom-call f32[1,4096,4096]":
                      0.028}, 2.347, "memory"),
    ("gated_attn_roofline", {
        "splash_mha_fwd_residuals.3 custom-call bf16[16,4096,256]": 0.01,
        "splash_mha_dkv_no_residuals.7 custom-call f32[1024,256]": 0.03},
     2.442, "compute")])
def test_a_roofline_reader_with_and_without_a_trace(name, ops, roofline_ms,
                                                    bound):
    reader = manifest.load_module("layer_metrics", name)
    cell = manifest.Manifest().cell(REAL_CELL)
    peaks = manifest.load_peaks("TPU v5 lite")
    obs = {"config": cell.config, "traffic": cell.traffic, "peaks": peaks,
           "trace": None}
    assert reader.read(obs) is None
    obs["trace"] = {"steps": 2, "op_seconds": {"fusion.1 fusion f32": 1.0}}
    assert reader.read(obs) is None            # no such operation
    obs["trace"]["op_seconds"].update(ops)
    value, extra = reader.read(obs)
    assert extra["kernel_ms"] == pytest.approx(20.0)
    assert extra["bound"] == bound and extra["steps"] == 2
    # the issue's numbers, to three digits
    assert "%.3f" % extra["roofline_ms"] == "%.3f" % roofline_ms
    assert value == pytest.approx(100.0 * extra["roofline_ms"] / 20.0)


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced``; the builder's
    arguments are the same numbers; the cuts are at the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
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
        published["vocab_size"]) == (48, 512, 151936)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"],
            kw["full_attention_interval"], kw["gdn_key_heads"],
            kw["gdn_value_heads"], kw["gdn_head_dim"], kw["conv_kernel"],
            kw["num_heads"], kw["num_kv_heads"], kw["head_dim"],
            kw["rotary_dim"], kw["rope_theta"], kw["num_experts"],
            kw["experts_held"], kw["experts_per_tok"], kw["expert_width"],
            kw["shared_width"], kw["vocab_size"], kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["full_attention_interval"], cfg["linear_num_key_heads"],
        cfg["linear_num_value_heads"], cfg["linear_value_head_dim"],
        cfg["linear_conv_kernel_dim"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["partial_rotary_factor"] * cfg["head_dim"], cfg["rope_theta"],
        cfg["num_experts_published"], cfg["num_experts"],
        cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
        cfg["shared_expert_intermediate_size"], cfg["vocab_size"],
        cfg["rms_norm_eps"])
    assert cfg["linear_key_head_dim"] == cfg["linear_value_head_dim"]
    assert (kw["hidden_size"], kw["gdn_key_heads"], kw["gdn_value_heads"],
            kw["gdn_head_dim"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["rotary_dim"], kw["rope_theta"],
            kw["num_experts"], kw["experts_per_tok"], kw["expert_width"],
            kw["shared_width"]) == (
        2048, 16, 32, 128, 16, 2, 256, 64, 1e7, 512, 10, 512, 512)
    assert cfg["norm_topk_prob"] is True and cfg["mlp_only_layers"] == []
    assert cfg["decoder_sparse_step"] == 1
    # the layers built: one whole period, linear, linear, linear, full
    assert cfg["built_layers"] == [0, 1, 2, 3]
    assert kw["num_layers"] == published["full_attention_interval"] == 4
    ref = manifest.load_module("reference", CONFIG)
    assert [ref.is_full(kw, l) for l in range(4)] == [False] * 3 + [True]
    # the floors: an eighth of the vocabulary, whole ranks of an
    # expert-parallel split
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert kw["experts_held"] == 32 and kw["first_expert"] == 0
    assert kw["num_experts"] == 16 * kw["experts_held"]
    assert kw["seq_len"] == 4096 and kw["aux_coef"] == 0.001
    assert {"norm_form", "gdn_projection_layout", "gdn_convolution",
            "gdn_decay", "gdn_parameters_at_start", "attention_gate",
            "head_norm", "rope", "router_aux_loss_coef", "shared_expert",
            "mtp"} <= set(cfg["assumed"])
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"]) == set(NAMES)
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, FLOPs a token and the two kernels' roofline sums,
    written out (ISSUE 50's numbers)."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    from mxnet_tpu.models import qwen3_next_lm
    net = qwen3_next_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, held = 2048, 18992, kw["experts_held"]
    gdn = D * 12288 + D * 64 + 8192 * 4 + 4096 * D      # qkvz, ba, conv, o
    assert gdn == pytest.approx(33.72e6, rel=1e-4)
    assert sizes["l0_qkvz_proj_weight"] == D * 12288 == 25_165_824
    assert sizes["l0_ba_proj_weight"] == D * 64
    assert sizes["l0_conv_weight"] == 8192 * 4
    assert sizes["l0_gdn_a_log_bias"] == sizes["l0_gdn_dt_bias"] == 32
    assert sizes["l0_o_norm_gamma"] == 128
    attention = D * 8192 + 2 * D * 512 + 4096 * D       # [q | gate], k, v, o
    assert attention == 27_262_976
    assert sizes["l3_q_proj_weight"] == D * 8192
    assert sizes["l3_q_norm_gamma"] == sizes["l3_k_norm_gamma"] == 256
    assert "l3_attn_gate_proj_weight" not in sizes
    expert = 3 * D * 512
    assert expert == 3_145_728
    experts = 512 * D + expert + D + held * expert      # router, shared, gate
    assert sizes["l1_moe_experts_i2h_weight"] == held * D * 512
    assert sizes["l0_moe_shared_gate_weight"] == D
    assert experts == pytest.approx(104.86e6, rel=1e-4)
    gains = 2 * D
    total = 2 * D * V + D + 3 * (gdn + 64 + 128 + gains) \
        + (attention + 512 + gains) + 4 * experts
    assert sum(sizes.values()) == total == 625_667_136
    assert 12 * total / 2 ** 30 == pytest.approx(6.99, abs=0.005)
    assert 16 * total / 2 ** 30 == pytest.approx(9.32, abs=0.005)
    # whole, one layer is 1.65 G parameters; 64 held would be 1028 M
    assert gdn + 512 * D + 513 * expert == pytest.approx(1.65e9, rel=2e-3)
    assert total + 4 * 32 * expert == pytest.approx(1028e6, rel=1e-3)
    # the two kernels' work, the issue's numbers
    import kernel_rooflines
    peaks = manifest.load_peaks("TPU v5 lite")
    rule = manifest.load_module("layer_metrics", "gdn_roofline")
    assert rule.linear_layers(cfg) == 3
    ops, nbytes = rule.gdn_chunk_work(cfg, cell.traffic)
    chunks = 32 * 64
    full, half = 2 * 64 * 128 * 128, 64 * 64 * 128
    assert ops == 3 * chunks * (10 * full + 15 * half)
    assert ops == pytest.approx(0.1772e12, rel=1e-3)
    S, c, states = 4096 * 32 * 128, 4 * 4096 * 32, 4 * chunks * 128 * 128
    assert nbytes == 3 * (2 * (4 * 2 * S + 2 * c + states)
                          + (3 * 2 * S + 2 * c))
    assert nbytes == pytest.approx(1.922e9, rel=1e-3)
    seconds, bound = kernel_rooflines.roofline_time((ops, nbytes), peaks)
    assert bound == "memory" and "%.3f" % (1e3 * seconds) == "2.347"
    # Kimi's work function counts a (B, T, H, D) decay: 4 S more a pass
    # and gradient, which a head's decay does not move
    assert 3 * 3 * 4 * S - 3 * 3 * c == pytest.approx(0.599e9, rel=1e-2)
    attn = manifest.load_module("layer_metrics", "gated_attn_roofline")
    assert attn.full_layers(cfg) == 1
    ops, nbytes = attn.interval_attention_work(cfg, cell.traffic)
    assert ops == 14 * 256 * 16 * (4096 * 4097 // 2)
    assert ops == pytest.approx(0.4812e12, rel=1e-4)
    assert nbytes == 2 * 4096 * 256 * 4 * (16 + 2)
    assert nbytes == pytest.approx(0.151e9, rel=1e-3)
    seconds, bound = kernel_rooflines.roofline_time((ops, nbytes), peaks)
    assert bound == "compute" and "%.3f" % (1e3 * seconds) == "2.442"
    # every layer under the causal mask would count four times as much
    every = kernel_rooflines.causal_attention_work(cfg, cell.traffic)[0]
    assert every / ops == pytest.approx(4 * 4096 / 4097, rel=1e-9)
    # the held experts' rows a step against the deployment's
    assert 4096 * 10 // 512 == 80 and 16 * 80 == 1280
    # the rank's sorted layout: the bound (4 balanced shares of 2560
    # rows) is taken, it saves more than BOUND_WORTH_ROWS
    import importlib
    dispatch = importlib.import_module("mxnet_tpu.moe.dispatch")
    bound_rows = dispatch.held_rows_bound(4096 * 10, 512, held)
    assert bound_rows == 10240 <= 4096 * 10 - dispatch.BOUND_WORTH_ROWS
