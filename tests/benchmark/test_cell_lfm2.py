"""The ``lfm2-8b-a1b`` configuration, its cell and its four readers
(``gsc_roofline``, ``scope_gsc_ms``, ``attn64_roofline``,
``gsc_kernel_share``): the real entries by name, the configuration's
numbers against the catalog row's, the arithmetic of the cut (the
parameters held, the bytes by the loading rule, the FLOPs a token, the
kernels' work), each reader with and without its input, and the cell on
the CPU at tiny widths, added to the temporary copy of
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
CELL = "tiny-lfm2"
REAL_CELL = "lfm2-8b-a1b-train-8k"
LIKE_CELL = "smallthinker-21b-a3b-train-8k"
CONFIG = "lfm2-8b-a1b"
TRAFFIC = "packed-8k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WINDOW_S = 4.0
NAMES = ["l2_in_proj_weight", "l2_conv_weight", "l2_out_proj_weight",
         "l1_q_proj_weight", "l1_k_proj_weight", "l2_moe_gate_weight",
         "l2_moe_experts_i2h_weight", "embed_weight"]
REDUCED = ["num_hidden_layers", "num_dense_layers", "num_experts",
           "vocab_size"]
READERS = {"gsc_roofline": ("%", "higher", "device_trace",
                            "linear attention"),
           "scope_gsc_ms": ("ms", "lower", "device_trace",
                            "linear attention"),
           "attn64_roofline": ("%", "higher", "device_trace",
                               "Pallas kernels"),
           "gsc_kernel_share": ("%", "higher", "program_counter",
                                "linear attention")}
BUILT = ["conv", "full_attention", "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_lfm2"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "lfm2-tiny"
    cfg["hidden_size"] = 32
    cfg["model"]["kwargs"].update(
        hidden_size=32, num_heads=4, num_kv_heads=2, head_dim=8,
        dense_width=48, num_experts=16, experts_per_tok=4, expert_width=24,
        vocab_size=128, seq_len=72, experts_held=4, first_expert=4)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "lfm2-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "lfm2-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-lfm2.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({
        "name": "lfm2-tiny", "source": "test",
        "file": "benchmark/configs/lfm2-tiny.json", "reduced": [],
        "why": "test"})
    util.add_cell(doc, CELL, "lfm2-tiny", "tiny-packed-lfm2", like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_lfm2_cell_runs_through_the_driver_and_is_correct(copy):
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
        result = driver.run(cell, [mx.cpu(0)], 6100000061, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
        conv = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
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
    ref_mod = manifest.load_module("reference", "lfm2-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # four routed blocks behind the dense lead
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        4 * obs["steps_in_window"]
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 4
    # one attention layer and four gated convolutions a traced program,
    # none of which the kernels take at these sizes (float32, 72 rows)
    assert attn and {e["id"] for e in attn} == {"float32[2, 72, 4, 8]/kv2"}
    assert conv and {e["id"] for e in conv} == \
        {"float32[2, 72, 96]/gated32"}
    assert len(conv) == 4 * len(attn)
    share = got["gsc_kernel_share"]
    assert share["value"] == 0.0 and share["kernel"] == 0
    assert share["samples"] == len(conv)
    # the traced readers have nothing to read in an untraced run
    assert not {"gsc_roofline", "scope_gsc_ms", "attn64_roofline"} & set(got)
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_lfm2_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the four entries it
    came with as their readers have them, and the cell on every list the
    SmallThinker cell is on but the two that read what this model lacks.
    By name and by membership, never by a position or a length."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    for name, (unit, better, source, layer) in READERS.items():
        entries = [m for m in doc["per_layer"] if m["name"] == name]
        assert len(entries) == 1, name
        entry = dict(entries[0])
        reader = manifest.load_module("layer_metrics", name)
        assert REAL_CELL in entry.pop("workloads")
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": reader.BETTER, "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": "train_tok_per_s"}
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) \
            == (unit, better, source, layer)
        assert any(m["layer"] == layer for m in doc["per_layer"]
                   if m["name"] not in READERS)

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, like = lists_of(REAL_CELL), lists_of(LIKE_CELL)
    assert like - listed == {"swa_attn_roofline", "moe_act_zero_share"}
    assert listed - like == set(READERS)
    assert {"train_tok_per_s", "moe_held_rows_share", "scope_attn_ms",
            "moe_prefix_fit_share", "moe_load_max_over_mean",
            "moe_dropped_share", "mfu.tok", "dispatch_ms_p50.tok",
            "scope_other_ms.tok", "peak_hbm_gib.tok", "scope_lm_loss_ms",
            "device_idle_share.tok", "setup_warmup_s"} <= listed
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline",
                "gdn_roofline", "mla_attn_roofline"} & listed


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_lfm2_cells_own_entries(doc)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    # no traffic file of its own: the SmallThinker and Keye cells'
    cells = [w["name"] for w in doc["workloads"] if w["traffic"] == TRAFFIC]
    assert REAL_CELL in cells and len(cells) >= 3
    traffic = manifest.Manifest().cell(REAL_CELL).traffic
    assert (traffic["batch_per_chip"], traffic["distinct_batches"],
            traffic["warmup_steps"], traffic["learn_margin"]) == (1, 64, 6,
                                                                  2.5)
    # the bar ISSUE 61 names: ln 16384 - 2.5
    assert np.log(16384) - traffic["learn_margin"] == pytest.approx(
        7.20, abs=0.005)


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the four in ``reduced`` (the published
    ``layer_types`` of 24 whole: the layers BUILT are ``built_layers``
    and the builder's arguments); the builder's arguments are the same
    numbers; the cuts are inside the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == sorted(REDUCED)
    assert (cfg["num_hidden_layers_published"],
            cfg["num_dense_layers_published"], cfg["num_experts_published"],
            cfg["vocab_size_published"]) == (
        published["num_hidden_layers"], published["num_dense_layers"],
        published["num_experts"], published["vocab_size"]) \
        == (24, 2, 32, 65536)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["dense_layers"],
            kw["num_heads"], kw["num_kv_heads"], kw["conv_kernel"],
            kw["rope_theta"], kw["dense_width"], kw["num_experts"],
            kw["experts_held"], kw["experts_per_tok"], kw["expert_width"],
            kw["vocab_size"], kw["rms_eps"], kw["route_scale"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_dense_layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["conv_L_cache"], cfg["rope_theta"],
        cfg["intermediate_size"], cfg["num_experts_published"],
        cfg["num_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"], cfg["vocab_size"], cfg["norm_eps"],
        cfg["routed_scaling_factor"])
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["conv_kernel"], kw["dense_width"],
            kw["num_experts"], kw["experts_per_tok"], kw["expert_width"],
            kw["rope_theta"], kw["rms_eps"]) == (
        2048, 32, 8, 64, 3, 7168, 32, 4, 1792, 1e6, 1e-5)
    assert kw["head_dim"] * kw["num_heads"] == kw["hidden_size"]
    assert cfg["conv_bias"] is False and cfg["use_expert_bias"] is True
    assert cfg["norm_topk_prob"] is True
    # the layers built are published ones: a leading dense layer, then
    # one whole period of what follows the dense lead
    built = cfg["built_layers"]
    assert built == [0, 2, 3, 4, 5] and len(built) == kw["num_layers"]
    assert kw["layer_types"] == [published["layer_types"][l]
                                 for l in built] == BUILT
    assert [l for l, k in enumerate(published["layer_types"])
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert published["layer_types"].count("conv") == 18
    assert built[0] < published["num_dense_layers"] <= built[1]
    # the floors: four layers behind the lead, 8 experts, an eighth
    assert kw["num_layers"] - kw["dense_layers"] >= 4
    assert (kw["experts_held"], kw["first_expert"]) == (8, 0)
    assert kw["num_experts"] == 4 * kw["experts_held"]
    assert cfg["vocab_size"] * 4 == published["vocab_size"]
    assert kw["seq_len"] == 8192 <= published["max_position_embeddings"]
    assert {"tie_word_embeddings", "head_dim", "short_convolution",
            "head_norm", "rope_pairing", "router", "selection_bias",
            "load_balance_loss", "sequence_length", "attention_mask",
            "optimizer", "initializer", "activation_memory", "corpus"} \
        <= set(cfg["assumed"])
    assert "4 chips" in cfg["deployment"]
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"]) == set(NAMES)
    assert cfg["reference"]["loss_rtol"] == 5e-4
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, bytes by the loading rule, FLOPs a token and the
    two kernels' work, written out (ISSUE 61's numbers)."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import lfm2_moe_lm
    net = lfm2_moe_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, held = 2048, 16384, kw["experts_held"]
    conv_mixer = 3 * D * D + 3 * D + D * D
    assert conv_mixer == pytest.approx(16.78e6, rel=1e-3)
    attention = 2 * D * D + 2 * D * 512 + 2 * 64
    assert attention == pytest.approx(10.49e6, rel=1e-3)
    dense_mlp, expert = 3 * D * 7168, 3 * D * 1792
    assert dense_mlp == pytest.approx(44.04e6, rel=1e-3)
    assert expert == pytest.approx(11.01e6, rel=1e-3)
    routed = held * expert + 32 * D
    norms = 2 * D
    dense_layer = conv_mixer + dense_mlp + norms
    attn_layer = attention + routed + norms
    conv_layer = conv_mixer + routed + norms
    assert dense_layer == pytest.approx(60.83e6, rel=1e-3)
    assert attn_layer == pytest.approx(98.64e6, rel=1e-3)
    assert conv_layer == pytest.approx(104.93e6, rel=1e-3)
    total = D * V + D + dense_layer + attn_layer + 3 * conv_layer
    assert sum(sizes.values()) == total
    assert total == pytest.approx(507.8e6, rel=1e-3)
    assert sizes["embed_weight"] == D * V and "lm_head_weight" not in sizes
    assert sizes["l2_in_proj_weight"] == 3 * D * D
    assert sizes["l2_conv_weight"] == 3 * D
    assert sizes["l1_q_proj_weight"] == D * D
    assert sizes["l1_k_proj_weight"] == 512 * D
    assert sizes["l2_moe_gate_weight"] == 32 * D
    assert sizes["l2_moe_experts_i2h_weight"] == held * D * 1792
    gib = 2.0 ** 30
    assert 16 * total / gib == pytest.approx(7.57, abs=0.01)
    # Queue R's rule before one activation is kept: the state, the load's
    # 4 B, the gradients once more, float32 logits and their gradient
    logits = 4 * 8192 * V
    rule = (16 + 4 + 4) * total + 2 * logits
    assert rule / gib == pytest.approx(12.35, abs=0.02)
    assert 20 * attn_layer / gib == pytest.approx(1.84, abs=0.01)
    # the model whole, one table: 8.34 B; two tables 8.47 B
    whole = (22 * (32 * expert + 32 * D) + 2 * dense_mlp + 18 * conv_mixer
             + 6 * attention + 65536 * D)
    assert whole == pytest.approx(8.34e9, rel=2e-3)
    assert whole + 65536 * D == pytest.approx(8.47e9, rel=2e-3)
    # FLOPs a trained token
    assert ref.causal_pairs(8192) == 33_558_528
    forward = (4 * 2 * 4 * D * D + 2 * (2 * D * D + 2 * D * 512)
               + 4 * 64 * 32 * 33_558_528 / 8192 + 2 * dense_mlp
               + 4 * (2 * D * 32 + 2 * expert) + 2 * D * V)
    assert ref.train_flops_per_sample(cfg) == pytest.approx(3 * forward,
                                                            rel=1e-12)
    assert 8192 * 3 * forward == pytest.approx(10.63e12, rel=1e-3)
    # the held experts' rows a step against the deployment's
    assert 8192 * 4 * held // 32 // held == 1024
    # the gated convolution's backward pass, the one the kernel runs: 7 D
    # elements a token and layer
    import kernel_rooflines
    gsc = manifest.load_module("layer_metrics", "gsc_roofline")
    assert gsc.conv_layers(cfg) == 4
    ops, nbytes = gsc.gated_conv_work(cfg, cell.traffic)
    assert nbytes == 4 * 8192 * D * 7 * 2
    assert ops == 4 * 8192 * D * (6 * 3 + 6)
    peaks = manifest.load_peaks("TPU v5 lite")
    seconds, bound = kernel_rooflines.roofline_time((ops, nbytes), peaks)
    assert bound == "memory"
    # ISSUE 61: 0.16 + 0.29 ms a layer at 819 GB/s; the backward's
    assert seconds / 4 == pytest.approx(0.287e-3, rel=0.02)
    # the useful attention at 64 lanes: one layer, 14 x 64 a pair and head
    a64 = manifest.load_module("layer_metrics", "attn64_roofline")
    assert a64.attention_layers(cfg) == 1
    ops, nbytes = a64.attn64_work(cfg, cell.traffic)
    assert ops == 14 * 64 * 32 * 33_558_528
    assert nbytes == 2 * 8192 * 64 * 4 * (32 + 8)
    seconds, bound = kernel_rooflines.roofline_time((ops, nbytes), peaks)
    assert bound == "compute"
    assert seconds == pytest.approx(ops / peaks["bf16_flops_per_s"])
    # the grouped-matmul tiles at K, N = 2048, 1792: one k step
    from mxnet_tpu.moe import gmm
    import importlib
    import jax.numpy as jnp
    dispatch = importlib.import_module("mxnet_tpu.moe.dispatch")
    bound_rows = dispatch.held_rows_bound(8192 * 4, 32, held)
    assert gmm.tiles_for(bound_rows, 2048, 1792, held, jnp.bfloat16)[1] \
        == 2048
    assert gmm.tiles_for(bound_rows, 1792, 2048, held, jnp.bfloat16)[1] \
        == 1792


def _obs(cell, op_seconds=None, steps=2):
    obs = {"config": cell.config, "traffic": cell.traffic,
           "peaks": manifest.load_peaks("TPU v5 lite")}
    if op_seconds is not None:
        obs["trace"] = {"steps": steps, "op_seconds": op_seconds}
    return obs


@pytest.mark.parametrize("name, kernels", [
    ("gsc_roofline", ("gated_conv_bwd.1", "gated_conv_bwd.2")),
    ("attn64_roofline", ("splash_mha_fwd.1", "splash_mha_bwd.2"))])
def test_a_roofline_reader_with_and_without_its_kernels(name, kernels):
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", name)
    assert reader.read(_obs(cell)) is None                  # no trace
    # another model's step, or the plain form: no operation of the name
    assert reader.read(_obs(cell, {"fusion.1": 0.5,
                                   "gated_conv_fwd.3": 0.5})) is None
    assert reader.read(_obs(cell, {kernels[0]: 1.0}, steps=0)) is None
    import kernel_rooflines
    work = reader.gated_conv_work if name == "gsc_roofline" \
        else reader.attn64_work
    least, _ = kernel_rooflines.roofline_time(
        work(cell.config, cell.traffic), _obs(cell)["peaks"])
    # two steps in which the kernels took four times their roofline
    ops = {kernels[0]: 3 * least, kernels[1]: 5 * least, "fusion.7": 1.0}
    value, extra = reader.read(_obs(cell, ops, steps=2))
    assert value == pytest.approx(25.0)
    assert extra["kernel_ms"] == pytest.approx(4e3 * least)
    assert extra["roofline_ms"] == pytest.approx(1e3 * least)


def test_the_scope_reader_with_and_without_its_scopes(monkeypatch):
    import scope_seconds
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "scope_gsc_ms")
    ops = {"fusion.1": 0.004, "gated_conv_fwd.2": 0.002, "fusion.3": 0.010,
           "fusion.4": 0.5}
    table = {"fusion.1": "gsc_proj.l0", "gated_conv_fwd.2": "gsc_conv.l0",
             "fusion.3": "gsc_proj.l2", "fusion.4": "attn.l1"}
    assert reader.read(_obs(cell)) is None                  # no trace
    monkeypatch.setattr(scope_seconds, "program_table", lambda: None)
    assert reader.read(_obs(cell, ops)) is None             # no table
    monkeypatch.setattr(scope_seconds, "program_table", lambda: table)
    value, extra = reader.read(_obs(cell, ops, steps=2))
    assert value == pytest.approx(8.0)
    assert extra["by_kind"] == {"gsc_proj": pytest.approx(7.0),
                                "gsc_conv": pytest.approx(1.0)}
    # a step without the mixer (another model's, the parent's)
    monkeypatch.setattr(scope_seconds, "program_table",
                        lambda: {"fusion.4": "attn.l1"})
    assert reader.read(_obs(cell, ops)) is None


def test_the_kernel_share_reader_over_a_hand_built_ring():
    import mxnet_tpu as mx
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "gsc_kernel_share")
    was = mx.trace.enabled()
    mx.trace.reset()
    try:
        mx.trace.set_enabled(True)
        assert reader.read(_obs(cell)) is None               # empty ring
        # the SiLU form's samples and another dtype's are not counted
        mx.trace.counter("conv:lowering", cat="ops", kernel=1, plain=0,
                         track="bfloat16[1, 4096, 12288]/8192")
        mx.trace.counter("conv:lowering", cat="ops", kernel=0, plain=1,
                         track="float32[1, 8192, 6144]/gated2048")
        assert reader.read(_obs(cell)) is None
        for kernel in (1, 1, 1, 0):
            mx.trace.counter("conv:lowering", cat="ops", kernel=kernel,
                             plain=1 - kernel,
                             track="bfloat16[1, 8192, 6144]/gated2048")
        value, extra = reader.read(_obs(cell))
        assert value == pytest.approx(75.0)
        assert extra == {"samples": 4, "kernel": 3}
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
