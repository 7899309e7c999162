"""The ``nemotron-3-nano-30b-a3b`` configuration, its cell and its two
readers (``moe_gmm_kernel_share``, ``moe_held_gmm_roofline``): the real
entries by name, the configuration's numbers against the catalog row's, the
arithmetic of the cut (the parameters held against the bound symbol, the
bytes by the loading rule, the FLOPs a token, the held rows' grouped work
with its sums written out), each reader with and without its input, and
the cell on the CPU at tiny widths, added to the temporary copy of
``cellbench_util.tiny_copy`` as files and entries, through the same driver
as the others.  A CPU run checks answers and counts, never rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-nemotron"
REAL_CELL = "nemotron-3-nano-30b-a3b-train-4k"
GRANITE_CELL = "granite-4.0-h-micro-train-4k"
TRINITY_CELL = "trinity-mini-train-4k"
CONFIG = "nemotron-3-nano-30b-a3b"
TRAFFIC = "packed-4k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
WINDOW_S = 4.0
NAMES = ["l0_in_proj_weight", "l0_conv_weight", "l0_conv_bias",
         "l0_ssm_a_log_bias", "l0_ssm_dt_bias", "l0_ssm_d_gamma",
         "l0_ssm_norm_gamma", "l0_out_proj_weight", "l1_moe_gate_weight",
         "l1_moe_experts_i2h_weight", "l1_moe_experts_h2o_weight",
         "l1_moe_shared_i2h_weight", "l1_moe_shared_h2o_weight",
         "l5_q_proj_weight", "l5_o_proj_weight", "embed_weight",
         "lm_head_weight"]
REDUCED = ["n_routed_experts", "vocab_size", "num_hidden_layers"]
READERS = {"moe_gmm_kernel_share": ("%", "higher", "program_counter"),
           "moe_held_gmm_roofline": ("%", "higher", "device_trace")}
LAYER = "routed experts"
# the rank-share entries the Trinity cell is on that this cell joins
RANK_SHARE = {"moe_load_max_over_mean", "moe_dropped_share",
              "moe_held_rows_share", "moe_prefix_fit_share",
              "scope_moe_experts_ms", "scope_moe_layout_ms"}
# PR 69's seven block-part entries: the benchmark's own test holds their
# lists to the eleven cells they came with, letter for letter
# (test_layer_scope_parts.py), so this cell is on none of them until a
# ``benchmark`` PR loosens that line (PERF.md section 7)
BLOCK_PARTS = {"scope_mlp_ms", "scope_moe_share_ms", "scope_attn_proj_ms",
               "scope_delta_proj_ms", "scope_head_ms", "scope_glue_ms",
               "scope_generic_share.tok"}
BUILT = ["mamba", "moe", "mamba", "moe", "mamba", "attention", "moe",
         "mamba", "moe"]
GIB = 2.0 ** 30


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_nemotron"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "nemotron-tiny"
    cfg["model"]["kwargs"].update(
        hidden_size=32, ssm_heads=4, ssm_head_dim=8, ssm_state=12,
        ssm_groups=2, num_heads=4, num_kv_heads=2, head_dim=8,
        num_experts=8, experts_per_tok=3, expert_width=24, shared_width=40,
        experts_held=4, vocab_size=128, seq_len=72)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "nemotron-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "nemotron-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-nemotron.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({
        "name": "nemotron-tiny", "source": "test",
        "file": "benchmark/configs/nemotron-tiny.json", "reduced": [],
        "why": "test"})
    util.add_cell(doc, CELL, "nemotron-tiny", "tiny-packed-nemotron",
                  like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_nemotron_cell_runs_through_the_driver_and_is_correct(copy):
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
        result = driver.run(cell, [mx.cpu(0)], 7100000071, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        scan = mx.trace.counter_events(["ssd:lowering"], since_ns=mark)
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
        grouped = mx.trace.counter_events(["moe:gmm_lowering"],
                                          since_ns=mark)
        load = mx.trace.counter_events(["moe:load"], since_ns=mark)
    finally:
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
    ref_mod = manifest.load_module("reference", "nemotron-tiny",
                                   cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # four scans for one attention layer a traced program, none of which
    # the kernels take at these sizes; the grouped products fall to
    # ragged_dot (144 x 3 rows are no whole row tile) and the new counter
    # says so
    assert attn and {e["id"] for e in attn} == {"float32[2, 72, 4, 8]/kv2"}
    assert scan and {e["id"] for e in scan} == {"float32[2, 72, 4, 8]/g2n12"}
    assert len(scan) == 4 * len(attn)
    assert grouped and all(e["args"] == {"kernel": 0, "plain": 1}
                           for e in grouped)
    assert {e["id"] for e in grouped} == {"float32[432] x [4, 32, 24]",
                                          "float32[432] x [4, 24, 32]"}
    share = got["moe_gmm_kernel_share"]
    assert share["value"] == 0.0 and share["kernel"] == 0
    assert share["samples"] == len(grouped)
    assert got["ssd_kernel_share"]["value"] == 0.0
    # four expert blocks a step, every choice counted over all 8 experts,
    # none dropped; this rank holds 4 of them
    assert {e["id"] for e in load} == {"l%d_moe_dispatch" % l
                                       for l in (1, 3, 6, 8)}
    assert all(e["args"]["routed"] == 2 * 72 * 3 and e["args"]["dropped"] == 0
               and 0 < e["args"]["held"] <= e["args"]["routed"] for e in load)
    assert got["moe_dropped_share"]["value"] == 0.0
    assert 0.0 < got["moe_held_rows_share"]["value"] < 100.0
    # the traced readers have nothing to read in an untraced run
    assert not {"moe_held_gmm_roofline", "ssd_roofline", "scope_ssm_ms",
                "scope_moe_experts_ms"} & set(got)
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_nemotron_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the two entries it came
    with as their readers have them, and the cell on every list the
    Granite cell is on but ``BLOCK_PARTS``, plus the rank-share entries
    the Trinity cell is on (not ``moe_act_zero_share``, which the builder
    has no argument for, nor ``swa_attn_roofline``: no window).  By name
    and by membership, never by a position or a length."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    for name, (unit, better, source) in READERS.items():
        entries = [m for m in doc["per_layer"] if m["name"] == name]
        assert len(entries) == 1, name
        entry = dict(entries[0])
        reader = manifest.load_module("layer_metrics", name)
        assert REAL_CELL in entry.pop("workloads")
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": reader.BETTER, "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": "train_tok_per_s"}
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) \
            == (unit, better, source, LAYER)
    assert any(m["layer"] == LAYER for m in doc["per_layer"]
               if m["name"] not in READERS)

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed = lists_of(REAL_CELL)
    granite, trinity = lists_of(GRANITE_CELL), lists_of(TRINITY_CELL)
    assert listed == (granite - BLOCK_PARTS) | RANK_SHARE | set(READERS)
    assert RANK_SHARE <= trinity and BLOCK_PARTS <= granite & trinity
    assert trinity - listed == {"swa_attn_roofline"} | BLOCK_PARTS
    assert "moe_act_zero_share" not in listed
    assert {"train_tok_per_s", "step_ms_p50.tok", "mfu.tok",
            "device_idle_share.tok", "peak_hbm_gib.tok", "scope_ssm_ms",
            "ssd_roofline", "ssd_kernel_share", "scope_attn_ms",
            "setup_warmup_s", "setup_compile_backend_s"} <= listed


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_nemotron_cells_own_entries(doc)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    assert len(doc["workloads"]) >= 15
    # no traffic file of its own
    traffic = manifest.Manifest().cell(REAL_CELL).traffic
    assert (traffic["batch_per_chip"], traffic["distinct_batches"],
            traffic["warmup_steps"], traffic["learn_margin"]) == (1, 64, 6,
                                                                  4.0)
    # the bar: ln 16384 - 4.0
    assert np.log(16384) - traffic["learn_margin"] == pytest.approx(
        5.704, abs=0.005)


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced``; the builder's
    arguments are the same numbers; every width is the published one."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == CATALOG_NAME)
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(REDUCED)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers_published"], cfg["vocab_size_published"],
            cfg["n_routed_experts_published"]) \
        == (published["num_hidden_layers"], published["vocab_size"],
            published["n_routed_experts"]) == (52, 131072, 128)
    pattern = published["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    from mxnet_tpu.models.nemotron_h import PATTERN
    assert cfg["built_pattern"] == pattern[:9] == "MEMEM*EME"
    assert cfg["layer_types"] == [PATTERN[c] for c in pattern[:9]] == BUILT
    assert cfg["built_layers"] == list(range(9))
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["layer_types"],
            kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_groups"], kw["conv_kernel"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["num_experts"],
            kw["experts_per_tok"], kw["expert_width"], kw["shared_width"],
            kw["route_scale"], kw["vocab_size"], kw["rms_eps"],
            kw["experts_held"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["layer_types"],
        cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
        cfg["n_groups"], cfg["conv_kernel"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["n_routed_experts_published"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"],
        cfg["routed_scaling_factor"], cfg["vocab_size"], cfg["norm_eps"],
        cfg["n_routed_experts"])
    assert (kw["hidden_size"], kw["ssm_heads"], kw["ssm_head_dim"],
            kw["ssm_state"], kw["ssm_groups"], kw["conv_kernel"],
            kw["num_heads"], kw["num_kv_heads"], kw["head_dim"],
            kw["num_experts"], kw["experts_per_tok"], kw["expert_width"],
            kw["shared_width"], kw["route_scale"], kw["rms_eps"]) == (
        2688, 64, 64, 128, 8, 4, 32, 2, 128, 128, 6, 1856, 3712, 2.5, 1e-5)
    # d_inner is heads x head lanes, NOT expand x the hidden size
    assert kw["ssm_heads"] * kw["ssm_head_dim"] == 4096 \
        != cfg["expand"] * cfg["hidden_size"]
    # the names ssd_roofline's work function reads repeat published keys
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"]) == (
        published["mamba_num_heads"], published["mamba_head_dim"],
        published["n_groups"], published["ssm_state_size"])
    assert {"layer_types", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
            "mamba_d_state"} <= set(cfg["repeated_keys"])
    assert cfg["moe_expert_matrices"] == 2         # plain: no gate projection
    assert cfg["mlp_hidden_act"] == "relu2"
    assert cfg["use_conv_bias"] is True and cfg["mamba_proj_bias"] is False
    assert cfg["tie_word_embeddings"] is False
    assert cfg["norm_topk_prob"] is True
    assert (cfg["n_group"], cfg["topk_group"], cfg["n_shared_experts"]) \
        == (1, 1, 1)
    # the cuts: at the guide's floors or above
    assert kw["experts_held"] == 8 and kw["first_expert"] == 0
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert kw["seq_len"] == 4096 <= published["max_position_embeddings"]
    assert {"attention_positions", "ssm_parameters_at_start",
            "time_step_limit", "chunk_size", "rescale_prenorm_residual",
            "selection_bias", "renormalisation_epsilon", "document_borders",
            "in_proj_order", "gated_norm", "optimizer", "rescale_grad",
            "initializer", "dtype", "activation_memory", "corpus"} \
        <= set(cfg["assumed"])
    assert all("lternative" in cfg["assumed"][k] for k in (
        "attention_positions", "ssm_parameters_at_start", "time_step_limit",
        "chunk_size", "rescale_prenorm_residual", "selection_bias",
        "renormalisation_epsilon", "document_borders"))
    assert "16 chips expert-parallel" in cfg["deployment"]
    assert "128 routed experts (8 held)" in cfg["deployment"]
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"]) == set(NAMES)
    assert cfg["reference"]["loss_rtol"] == 5e-4
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert cfg["chance_loss_classes"] == 16384
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held against the bound symbol, bytes by the loading
    rule, FLOPs a token (ISSUE 71's numbers, written out)."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import nemotron_h_lm
    net = nemotron_h_lm(**kw)
    shapes, _, aux = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, W, S = 2688, 16384, 1856, 3712
    in_proj, out_proj = D * 10304, 4096 * D
    assert 10304 == 4096 + (4096 + 2 * 8 * 128) + 64
    assert in_proj == pytest.approx(27.70e6, rel=1e-3)
    assert out_proj == pytest.approx(11.01e6, rel=1e-3)
    small = 6144 * 4 + 6144 + 3 * 64 + 4096 + D  # taps, bias, A, dt, D, gains
    assert small == pytest.approx(0.04e6, rel=0.1)
    mamba = in_proj + out_proj + small
    assert mamba == pytest.approx(38.74e6, rel=1e-3)
    router, an_expert, shared = 128 * D, 2 * D * W, 2 * D * S
    assert router == pytest.approx(0.34e6, rel=0.02)
    assert an_expert == pytest.approx(9.978e6, rel=1e-3)
    assert shared == pytest.approx(19.96e6, rel=1e-3)
    experts = router + 8 * an_expert + shared + D
    assert experts == pytest.approx(100.13e6, rel=1e-3)
    attention = 2 * 4096 * D + 2 * 256 * D + D
    assert attention == pytest.approx(23.40e6, rel=1e-3)
    tables = 2 * V * D
    assert tables == pytest.approx(88.08e6, rel=1e-3)
    total = 4 * mamba + 4 * experts + attention + tables + D
    assert sum(sizes.values()) == total == 666_962_944
    assert total == pytest.approx(666.96e6, rel=1e-5)
    assert sizes["l0_in_proj_weight"] == in_proj
    assert sizes["l0_conv_weight"] == 6144 * 4
    assert sizes["l0_conv_bias"] == 6144
    assert sizes["l0_ssm_a_log_bias"] == sizes["l0_ssm_dt_bias"] \
        == sizes["l0_ssm_d_gamma"] == 64
    assert sizes["l0_ssm_norm_gamma"] == 4096
    assert sizes["l1_moe_gate_weight"] == router
    assert sizes["l1_moe_experts_i2h_weight"] == 8 * D * W \
        == sizes["l1_moe_experts_h2o_weight"]
    assert not any("i2h_gate" in n for n in sizes)
    assert sizes["l1_moe_shared_i2h_weight"] == S * D
    assert sizes["l5_q_proj_weight"] == 4096 * D
    assert sizes["l5_k_proj_weight"] == 256 * D
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == V * D
    # the selection biases are states, 128 wide: the router's width
    assert dict(zip(net.list_auxiliary_states(), aux)) == {
        "l%d_moe_dispatch_select_bias" % l: (128,) for l in (1, 3, 6, 8)}
    # the loading rule: 12 B a parameter + 0.2 GiB + the temporaries, which
    # hold 4 B a parameter of gradients, of 15.75
    assert 12 * total / GIB == pytest.approx(7.45, abs=0.01)
    assert 4 * total / GIB == pytest.approx(2.48, abs=0.01)
    assert 16 * total / GIB == pytest.approx(9.94, abs=0.01)
    # 16 held experts do not fit: 986 M
    assert total + 4 * 8 * an_expert == pytest.approx(986e6, rel=2e-3)
    # the expert load: 192 rows a held expert under a uniform router, 1/16
    # of the deployment's sixteen ranks' 3072
    assert 4096 * 6 / 128 == 192 and 16 * 192 == 3072
    # FLOPs a trained token: 3 x the forward
    forward = (4 * (2 * in_proj + 2 * out_proj + 4 * 128 * 64 * 64)
               + 4 * (2 * router + 2 * shared + 6 * 8 / 128 * 2 * an_expert)
               + 2 * (2 * 4096 * D + 2 * 256 * D) + 4 * 128 * 32 * 4097 / 2
               + 2 * D * V)
    assert ref.train_flops_per_sample(cfg) == pytest.approx(3 * forward,
                                                            rel=1e-12)
    assert 3 * forward == pytest.approx(2.04e9, rel=0.01)
    assert 4096 * 3 * forward == pytest.approx(8.34e12, rel=0.01)
    parts = ref.forward_flops_per_token(cfg)
    everything = sum(parts.values())
    new_mechanisms = (parts["ssm_proj"] + parts["ssm_scan"]
                      + parts["moe_route"] + parts["moe_shared"]
                      + parts["moe_experts"])
    assert new_mechanisms / everything == pytest.approx(0.75, abs=0.01)
    assert parts["head"] / everything == pytest.approx(0.13, abs=0.005)
    # the chunked rule's work counts EIGHT groups' C B^T
    ssd = manifest.load_module("layer_metrics", "ssd_roofline")
    assert ssd.mamba_layers(cfg) == 4
    ops, _ = ssd.ssd_chunk_work(cfg, cell.traffic)
    chunks, full, half = 32, 2 * 128 * 128 * 64, 128 * 128 * 64
    assert ops == 4 * chunks * (64 * (7 * full + 3 * half)
                                + 8 * 4 * 128 * 128 * 128)


def test_the_held_rows_grouped_work_written_out():
    """The plain form's six products a layer over the rows this rank
    holds: ``2 rows D W`` each, bytes with the held experts' weights once
    a product and layer; no row a tile pads to."""
    import kernel_rooflines
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "moe_held_gmm_roofline")
    assert reader.expert_layers(cell.config) == 4
    D, W = 2688, 1856
    held = 4 * 1536.0        # four blocks of 8 x 192 rows: a uniform router
    ops, nbytes = reader.held_gmm_work(cell.config, held)
    assert ops == 6 * 2 * held * D * W
    assert nbytes == 6 * 2 * (held * (D + W) + 4 * 8 * D * W)
    assert ops == pytest.approx(3.68e11, rel=0.01)
    peaks = manifest.load_peaks("TPU v5 lite")
    seconds, bound = kernel_rooflines.roofline_time((ops, nbytes), peaks)
    assert bound == "memory"              # the weights' fetch bounds it
    assert 2.5e-3 < seconds < 3.0e-3
    # a gated expert runs nine
    gated = dict(cell.config, moe_expert_matrices=3)
    assert reader.held_gmm_work(gated, held)[0] == 1.5 * ops
    # no held row, no rows' work: the weights still move
    assert reader.held_gmm_work(cell.config, 0.0) == (
        0.0, 6 * 2 * 4 * 8 * D * W)


def _obs(cell, op_seconds=None, steps=2, **more):
    obs = dict({"config": cell.config, "traffic": cell.traffic,
                "peaks": manifest.load_peaks("TPU v5 lite")}, **more)
    if op_seconds is not None:
        obs["trace"] = {"steps": steps, "op_seconds": op_seconds}
    return obs


def test_the_kernel_share_reader_over_a_hand_built_ring():
    import mxnet_tpu as mx
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "moe_gmm_kernel_share")
    was = mx.trace.enabled()
    mx.trace.reset()
    try:
        mx.trace.set_enabled(True)
        assert reader.read(_obs(cell)) is None               # empty ring
        # another counter's samples and another dtype's are not counted
        mx.trace.counter("ssd:lowering", cat="ops", kernel=1, plain=0,
                         track="bfloat16[1, 4096, 64, 64]/g8n128")
        mx.trace.counter("moe:gmm_lowering", cat="ops", kernel=0, plain=1,
                         track="float32[1024] x [8, 2688, 1856]")
        assert reader.read(_obs(cell)) is None
        for kernel in (1, 1, 1, 0):
            mx.trace.counter("moe:gmm_lowering", cat="ops", kernel=kernel,
                             plain=1 - kernel,
                             track="bfloat16[6144] x [8, 2688, 1856]")
        value, extra = reader.read(_obs(cell))
        assert value == pytest.approx(75.0)
        assert extra == {"samples": 4, "kernel": 3}
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)


def test_the_roofline_reader_with_and_without_its_input():
    """Over a hand-built ring: ten steps a window, the trace behind the
    window's first quarter, the held rows of the traced steps."""
    import kernel_rooflines
    import mxnet_tpu as mx
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "moe_held_gmm_roofline")
    ops = {"ragged-dot-gmm.1": 0.004, "ragged-dot-tgmm.2": 0.002,
           "fusion.7": 1.0}
    was = mx.trace.enabled()
    mx.trace.reset()
    try:
        mx.trace.set_enabled(True)
        window = dict(steps_in_window=10, window_s=0.0)
        assert reader.read(_obs(cell, **window)) is None     # no trace
        assert reader.read(_obs(cell, ops, **window)) is None    # no sample
        # a program that holds every expert says no ``held``
        mx.trace.counter("moe:load", cat="moe", track="l1_moe_dispatch",
                         routed=24576.0)
        assert reader.read(_obs(cell, ops, **window)) is None
        mx.trace.reset()
        mx.trace.set_enabled(True)
        for step in range(12):         # two warm-up steps, then the window
            for block in ("l1_moe_dispatch", "l3_moe_dispatch"):
                mx.trace.counter("moe:load", cat="moe", track=block,
                                 routed=24576.0, held=1000.0 + step)
        # the window's samples are steps 2..11; a window of no length puts
        # the trace's start at the first of them, and two steps pass
        # before the two traced ones: steps 4 and 5
        value, extra = reader.read(_obs(cell, ops, steps=2, **window))
        assert extra["held_rows_a_step"] == pytest.approx(2 * 1004.5)
        assert extra["samples_a_block"] == 2
        least, bound = kernel_rooflines.roofline_time(
            reader.held_gmm_work(cell.config, 2 * 1004.5),
            _obs(cell)["peaks"])
        assert extra["kernel_ms"] == pytest.approx(3.0)
        assert extra["roofline_ms"] == pytest.approx(1e3 * least)
        assert value == pytest.approx(100.0 * least / 0.003)
        assert extra["bound"] == bound
        assert extra["held_rows_a_step_off"] == [
            pytest.approx(2 * 1003.5), pytest.approx(2 * 1005.5)]
        assert extra["value_a_step_off"] == pytest.approx([value, value],
                                                          rel=1e-3)
        # no operation of the name: another model's step
        assert reader.read(_obs(cell, {"fusion.7": 1.0}, **window)) is None
        # a configuration that does not say its experts' form
        other = manifest.Manifest().cell(TRINITY_CELL)
        assert reader.read(_obs(other, ops, **window)) is None
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)


def test_the_traced_slice_is_the_drivers_and_a_step_off_costs_little():
    """The reader finds the traced steps as the driver does, from the
    driver's own constant; where it lies a step off, the router's drift
    (6.25 % of the choices held at the start, 1.5 % once the selection
    bias has moved) changes the reading by less than a part in a
    hundred."""
    import mxnet_tpu as mx
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "moe_held_gmm_roofline")
    driver = manifest.load_module("drivers", "train_fit")
    assert reader.trace_start_share() == driver.TRACE_START_SHARE
    # 160 steps of 0.125 s: the first behind a quarter of the window arms
    # the profiler, the next opens the trace, 25 traced steps follow
    stamps = [1e6 * 0.125 * i for i in range(160)]
    share = driver.TRACE_START_SHARE
    armed = next(i for i, ts in enumerate(stamps) if ts >= 1e6 * share * 20.0)
    assert reader.traced_slice(stamps, 25, 20.0) == \
        slice(armed + 2, armed + 27)
    assert reader.traced_slice(stamps, 25, 20.0, shift=-1) == \
        slice(armed + 1, armed + 26)
    # a window that closes on the trace: the last steps, never fewer
    assert reader.traced_slice(stamps[:50], 25, 20.0) == slice(25, 50)
    ops = {"ragged-dot-gmm.1": 0.1}
    was = mx.trace.enabled()
    mx.trace.reset()
    try:
        mx.trace.set_enabled(True)
        # 6 warm-up steps and 40 of the window pass before the trace, as on
        # the chip; the held share falls from 6.25 % towards 1.48 % and
        # swings with the step's batch, as the chip's does (a step off
        # moved 25 steps' mean by 6 % there)
        for step in range(146):
            held = 24576.0 * (0.0148 + 0.0477 * np.exp(-step / 12.0)) \
                * (1.0 + 0.8 * np.sin(2.3 * step))
            for block in (1, 3, 6, 8):
                mx.trace.counter("moe:load", cat="moe", routed=24576.0,
                                 track="l%d_moe_dispatch" % block, held=held)
        obs = _obs(cell, ops, steps=25, steps_in_window=100, window_s=0.0)
        value, extra = reader.read(obs)
        rows = extra["held_rows_a_step"]
        for shift, off, read in zip((-1, 1), extra["held_rows_a_step_off"],
                                    extra["value_a_step_off"]):
            assert off == reader.traced_held_rows(obs, shift)[0]
            assert 0.002 * rows < abs(off - rows) < 0.1 * rows
            # the rows are a twenty-fifth of the bytes: the weights' fetch
            # is the work
            assert read != value and read == pytest.approx(value, rel=0.01)
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)


def test_the_float8_control_goes_through_the_harness_own_comparison(copy):
    """``drivers/train_fit.py`` ``reference_check``, the comparison that
    decides ``correct``, handed the reference with its weights rounded to
    float8 e4m3 (the control of ``tests/tpu/test_nemotron_h_tpu.py``, which
    compares reference with reference): it refuses it, by the update
    limits and not by the loss's, and passes the reference as it is."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    ref = manifest.load_module("reference", cell.config_name, cell.bench_dir)
    generator = manifest.load_module("generators",
                                     cell.traffic["generator"],
                                     cell.bench_dir)

    class Coarse:
        @staticmethod
        def reference_step(cfg, before, *rest):
            return ref.reference_step(cfg, {
                n: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                              .astype(jnp.float32))
                for n, v in before.items()}, *rest)

    seed, lines = 7100000072, []
    rng = mx.random.get_key_data(), np.random.get_state()
    try:
        traffic = generator.build(cell.traffic, cell.config, seed,
                                  [mx.cpu(0)], None)
        sound, got = driver.reference_check(cell, ref, traffic, mx.cpu(0),
                                            seed, lines.append)
        ok, coarse = driver.reference_check(cell, Coarse, traffic, mx.cpu(0),
                                            seed, lines.append)
        traffic.close()
    finally:
        mx.random.set_key_data(rng[0])
        np.random.set_state(rng[1])
    limits = cell.config["reference"]
    assert sound is True, lines
    assert ok is False, lines
    # every matrix's limit refuses it alone, with room on both sides: the
    # control reads four times the limit and more, the sound step under a
    # thousandth of it; the gains that start at 1 are float8 numbers and
    # hold nothing
    refused = {n for n in NAMES
               if coarse["updates"][n] > limits["update_rtol"][n]}
    assert refused >= {n for n in NAMES if n.endswith("weight")}, coarse
    assert all(coarse["updates"][n] > 4 * limits["update_rtol"][n]
               for n in NAMES if n.endswith("weight"))
    assert max(got["updates"].values()) < 1e-3 * min(
        limits["update_rtol"].values())
