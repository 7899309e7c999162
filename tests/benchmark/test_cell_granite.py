"""The ``granite-4.0-h-micro`` configuration, its cell and its three
readers (``scope_ssm_ms``, ``ssd_roofline``, ``ssd_kernel_share``): the
real entries by name, the configuration's numbers against the catalog
row's, the arithmetic of the cut (the parameters held, the bytes by the
loading rule, the FLOPs a token, the scan's work), each reader with and
without its input, and the cell on the CPU at tiny widths, added to the
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
CELL = "tiny-granite"
REAL_CELL = "granite-4.0-h-micro-train-4k"
LIKE_CELL = "lfm2-8b-a1b-train-8k"
CONFIG = "granite-4.0-h-micro"
TRAFFIC = "packed-4k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WINDOW_S = 4.0
NAMES = ["l0_in_proj_weight", "l0_conv_weight", "l0_conv_bias",
         "l0_ssm_a_log_bias", "l0_ssm_dt_bias", "l0_ssm_d_gamma",
         "l0_ssm_norm_gamma", "l0_out_proj_weight", "l5_q_proj_weight",
         "l5_o_proj_weight", "l9_input_linear_weight", "embed_weight"]
REDUCED = ["num_hidden_layers", "vocab_size"]
READERS = {"scope_ssm_ms": ("ms", "lower", "device_trace"),
           "ssd_roofline": ("%", "higher", "device_trace"),
           "ssd_kernel_share": ("%", "higher", "program_counter")}
LAYER = "linear attention"
BUILT = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
GIB = 2.0 ** 30


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_granite"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "granite-tiny"
    cfg["model"]["kwargs"].update(
        hidden_size=32, ssm_heads=4, ssm_head_dim=8, ssm_state=12,
        num_heads=4, num_kv_heads=2, head_dim=8, mlp_width=48,
        attention_multiplier=0.125, vocab_size=128, seq_len=72)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "granite-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "granite-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-granite.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({
        "name": "granite-tiny", "source": "test",
        "file": "benchmark/configs/granite-tiny.json", "reduced": [],
        "why": "test"})
    util.add_cell(doc, CELL, "granite-tiny", "tiny-packed-granite",
                  like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_granite_cell_runs_through_the_driver_and_is_correct(copy):
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
        result = driver.run(cell, [mx.cpu(0)], 6700000067, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        scan = mx.trace.counter_events(["ssd:lowering"], since_ns=mark)
        conv = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
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
    ref_mod = manifest.load_module("reference", "granite-tiny",
                                   cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # nine scans and nine biased convolutions for one attention layer a
    # traced program, none of which the kernels take at these sizes
    assert attn and {e["id"] for e in attn} == {"float32[2, 72, 4, 8]/kv2"}
    assert scan and {e["id"] for e in scan} == \
        {"float32[2, 72, 4, 8]/g1n12"}
    assert conv and {e["id"] for e in conv} == \
        {"float32[2, 72, 56]/56+bias"}
    assert len(scan) == len(conv) == 9 * len(attn)
    share = got["ssd_kernel_share"]
    assert share["value"] == 0.0 and share["kernel"] == 0
    assert share["samples"] == len(scan)
    # the traced readers have nothing to read in an untraced run
    assert not {"ssd_roofline", "scope_ssm_ms"} & set(got)
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_granite_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the three entries it
    came with as their readers have them, and the cell on every list the
    LFM2 cell is on but those that read what this model lacks (experts,
    the gated convolution, ``attn64_roofline``, whose work function reads
    another configuration's keys).  By name and by membership, never by
    a position or a length."""
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

    listed, like = lists_of(REAL_CELL), lists_of(LIKE_CELL)
    assert listed - like == set(READERS)
    assert like - listed == {
        "moe_load_max_over_mean", "moe_dropped_share", "moe_held_rows_share",
        "moe_prefix_fit_share", "scope_moe_experts_ms", "scope_moe_layout_ms",
        "gsc_roofline", "scope_gsc_ms", "gsc_kernel_share", "attn64_roofline"}
    assert {"train_tok_per_s", "step_ms_p50.tok", "mfu.tok",
            "device_step_ms.tok", "device_idle_share.tok", "peak_hbm_gib.tok",
            "scope_optimizer_ms.tok", "scope_attn_ms", "scope_lm_loss_ms",
            "scope_other_ms.tok", "scope_unnamed_share.tok",
            "dispatch_ms_p50.tok", "setup_warmup_s",
            "setup_compile_backend_s"} <= listed


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_granite_cells_own_entries(doc)
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    # no traffic file of its own
    cells = [w["name"] for w in doc["workloads"] if w["traffic"] == TRAFFIC]
    assert REAL_CELL in cells and len(cells) >= 3
    traffic = manifest.Manifest().cell(REAL_CELL).traffic
    assert (traffic["batch_per_chip"], traffic["distinct_batches"],
            traffic["warmup_steps"], traffic["learn_margin"]) == (1, 64, 6,
                                                                  4.0)
    # the bar ISSUE 67 names: ln 12544 - 4.0
    assert np.log(12544) - traffic["learn_margin"] == pytest.approx(
        5.44, abs=0.005)


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the two in ``reduced`` and ``layer_types``,
    the published list cut to its first ten entries with the depth; the
    builder's arguments are the same numbers; every width is the
    published one."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(REDUCED + ["layer_types"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == sorted(REDUCED)
    assert cfg["layer_types"] == published["layer_types"][:10] == BUILT
    assert (cfg["num_hidden_layers_published"], cfg["vocab_size_published"]) \
        == (published["num_hidden_layers"], published["vocab_size"]) \
        == (40, 100352)
    assert [l for l, k in enumerate(published["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["layer_types"],
            kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"],
            kw["ssm_groups"], kw["conv_kernel"], kw["num_heads"],
            kw["num_kv_heads"], kw["mlp_width"], kw["vocab_size"],
            kw["embedding_multiplier"], kw["residual_multiplier"],
            kw["attention_multiplier"], kw["logits_scaling"],
            kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["layer_types"],
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
        cfg["mamba_n_groups"], cfg["mamba_d_conv"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["shared_intermediate_size"], cfg["vocab_size"],
        cfg["embedding_multiplier"], cfg["residual_multiplier"],
        cfg["attention_multiplier"], cfg["logits_scaling"],
        cfg["rms_norm_eps"])
    assert (kw["hidden_size"], kw["ssm_heads"], kw["ssm_head_dim"],
            kw["ssm_state"], kw["ssm_groups"], kw["conv_kernel"],
            kw["num_heads"], kw["num_kv_heads"], kw["head_dim"],
            kw["mlp_width"], kw["embedding_multiplier"],
            kw["residual_multiplier"], kw["attention_multiplier"],
            kw["logits_scaling"], kw["rms_eps"]) == (
        2048, 64, 64, 128, 1, 4, 32, 8, 64, 8192, 12, 0.22, 1 / 64, 8, 1e-5)
    assert kw["ssm_heads"] * kw["ssm_head_dim"] == \
        cfg["mamba_expand"] * cfg["hidden_size"]
    assert kw["head_dim"] * kw["num_heads"] == kw["hidden_size"]
    assert cfg["mamba_conv_bias"] is True and cfg["mamba_proj_bias"] is False
    assert cfg["tie_word_embeddings"] is True
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["num_local_experts"] == 0
    # the cuts: one whole period, an eighth of the vocabulary
    assert cfg["built_layers"] == list(range(10))
    assert kw["layer_types"].count("mamba") == 9
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert kw["seq_len"] == 4096 <= published["max_position_embeddings"]
    assert {"ssm_parameters_at_start", "time_step_limit", "mamba_chunk_size",
            "document_borders", "in_proj_order", "gated_norm", "optimizer",
            "rescale_grad", "initializer", "dtype", "activation_memory",
            "corpus"} <= set(cfg["assumed"])
    assert "four pipeline stages" in cfg["deployment"]
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"]) == set(NAMES)
    assert cfg["reference"]["loss_rtol"] == 5e-4
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert cfg["chance_loss_classes"] == 12544
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, bytes by the loading rule, FLOPs a token and the
    scan's work, written out (ISSUE 67's numbers)."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import granite_hybrid_lm
    net = granite_hybrid_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, F = 2048, 12544, 8192
    in_proj, out_proj = D * 8512, 4096 * D
    assert in_proj == pytest.approx(17.43e6, rel=1e-3)
    assert out_proj == pytest.approx(8.39e6, rel=1e-3)
    small = 4352 * 4 + 4352 + 3 * 64 + 4096      # taps, bias, A, dt, D, gain
    assert small == pytest.approx(0.03e6, rel=0.15)
    swiglu = 3 * D * F
    assert swiglu == pytest.approx(50.33e6, rel=1e-3)
    norms = 2 * D
    mamba = in_proj + out_proj + small + swiglu + norms
    attention = 2 * D * D + 2 * D * 512 + swiglu + norms
    assert mamba == pytest.approx(76.18e6, rel=1e-3)
    assert attention == pytest.approx(60.82e6, rel=1e-3)
    total = 9 * mamba + attention + D * V + D
    assert sum(sizes.values()) == total
    assert total == pytest.approx(772.2e6, rel=1e-3)
    assert sizes["embed_weight"] == D * V == 25_690_112
    assert "lm_head_weight" not in sizes
    assert sizes["l0_in_proj_weight"] == in_proj
    assert sizes["l0_conv_weight"] == 4352 * 4
    assert sizes["l0_conv_bias"] == 4352
    assert sizes["l0_ssm_a_log_bias"] == sizes["l0_ssm_dt_bias"] \
        == sizes["l0_ssm_d_gamma"] == 64
    assert sizes["l0_ssm_norm_gamma"] == 4096
    assert sizes["l5_q_proj_weight"] == D * D
    assert sizes["l9_input_linear_weight"] == 2 * F * D
    # the loading rule: 12 B a parameter + 0.2 GiB + the temporaries, which
    # hold 4 B a parameter of gradients, of 15.75
    assert 12 * total / GIB == pytest.approx(8.63, abs=0.01)
    assert 4 * total / GIB == pytest.approx(2.88, abs=0.01)
    assert (12 * total + 4 * total) / GIB + 0.2 == pytest.approx(11.71,
                                                                 abs=0.01)
    # the whole vocabulary does not fit beside ten layers
    assert 100352 * D == pytest.approx(205.5e6, rel=1e-3)
    # FLOPs a trained token: 3 x the forward, the recurrence priced once
    forward = (9 * (2 * in_proj + 2 * out_proj + 4 * 128 * 64 * 64)
               + 2 * (2 * D * D + 2 * D * 512) + 4 * 64 * 32 * 4097 / 2
               + 10 * 2 * swiglu + 2 * D * V)
    assert ref.train_flops_per_sample(cfg) == pytest.approx(3 * forward,
                                                            rel=1e-12)
    assert 3 * forward == pytest.approx(4.80e9, rel=0.02)
    assert 4096 * 3 * forward == pytest.approx(19.7e12, rel=0.02)
    parts = ref.forward_flops_per_token(cfg)
    a_layer = (parts["ssm_proj"] + parts["ssm_scan"]) / 9
    assert a_layer / (a_layer + parts["mlp"] / 10) == pytest.approx(
        0.36, abs=0.015)
    # the head's share here against the model's: 3.2 % for 6.2 %
    assert parts["head"] / sum(parts.values()) == pytest.approx(0.032,
                                                                abs=0.002)
    whole = 4 * (sum(parts.values()) - parts["head"]) + 2 * D * 100352
    assert 2 * D * 100352 / whole == pytest.approx(0.062, abs=0.004)
    # the chunked rule's work: a chunk of 128 against a (128, 64) state
    import kernel_rooflines
    ssd = manifest.load_module("layer_metrics", "ssd_roofline")
    assert ssd.mamba_layers(cfg) == 9
    ops, nbytes = ssd.ssd_chunk_work(cfg, cell.traffic)
    chunks, full, half = 32, 2 * 128 * 128 * 64, 128 * 128 * 64
    assert ops == 9 * chunks * (64 * (7 * full + 3 * half)
                                + 4 * 128 * 128 * 128)
    seq, grp, col = 4096 * 4096, 4096 * 128, 4 * 4096 * 64
    states = 4 * chunks * 64 * 128 * 64
    assert nbytes == 9 * (2 * (2 * 2 * seq + 2 * 2 * grp + col + states)
                          + 2 * seq + 2 * 2 * grp + col)
    # the group's C B^T once a group: one head's share of it 1 / 64
    one_head = 9 * chunks * (64 * (7 * full + 3 * half)
                             + 64 * 4 * 128 * 128 * 128)
    assert ops < one_head
    peaks = manifest.load_peaks("TPU v5 lite")
    seconds, bound = kernel_rooflines.roofline_time((ops, nbytes), peaks)
    assert bound == "memory"
    assert seconds == pytest.approx(nbytes / peaks["hbm_bytes_per_s"])
    assert 3.0e-3 < seconds < 3.8e-3


def _obs(cell, op_seconds=None, steps=2):
    obs = {"config": cell.config, "traffic": cell.traffic,
           "peaks": manifest.load_peaks("TPU v5 lite")}
    if op_seconds is not None:
        obs["trace"] = {"steps": steps, "op_seconds": op_seconds}
    return obs


def test_the_roofline_reader_with_and_without_its_kernels():
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "ssd_roofline")
    assert reader.read(_obs(cell)) is None                  # no trace
    # another model's step, or the plain chunks: no operation of the name
    assert reader.read(_obs(cell, {"fusion.1": 0.5,
                                   "kda_chunk_fwd.3": 0.5})) is None
    assert reader.read(_obs(cell, {"ssd_chunk_fwd.1": 1.0}, steps=0)) is None
    import kernel_rooflines
    least, _ = kernel_rooflines.roofline_time(
        reader.ssd_chunk_work(cell.config, cell.traffic), _obs(cell)["peaks"])
    # two steps in which the kernels took four times their roofline
    ops = {"ssd_chunk_fwd.1": 3 * least, "ssd_chunk_bwd.2": 5 * least,
           "fusion.7": 1.0}
    value, extra = reader.read(_obs(cell, ops, steps=2))
    assert value == pytest.approx(25.0)
    assert extra["kernel_ms"] == pytest.approx(4e3 * least)
    assert extra["roofline_ms"] == pytest.approx(1e3 * least)
    # the other cells' configurations are never asked: no such operation
    other = manifest.Manifest().cell(LIKE_CELL)
    assert reader.read(_obs(other, {"gated_conv_bwd.1": 1.0})) is None


def test_the_scope_reader_with_and_without_its_scopes(monkeypatch):
    import scope_seconds
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "scope_ssm_ms")
    ops = {"fusion.1": 0.004, "causal_conv_bias_fwd.2": 0.002,
           "ssd_chunk_fwd.3": 0.010, "fusion.5": 0.006, "fusion.4": 0.5}
    table = {"fusion.1": "ssm_proj.l0", "causal_conv_bias_fwd.2":
             "ssm_conv.l0", "ssd_chunk_fwd.3": "ssm_scan.l2",
             "fusion.5": "ssm_norm.l9", "fusion.4": "attn.l5"}
    assert reader.read(_obs(cell)) is None                  # no trace
    monkeypatch.setattr(scope_seconds, "program_table", lambda: None)
    assert reader.read(_obs(cell, ops)) is None             # no table
    monkeypatch.setattr(scope_seconds, "program_table", lambda: table)
    value, extra = reader.read(_obs(cell, ops, steps=2))
    assert value == pytest.approx(11.0)
    assert extra["by_kind"] == {
        "ssm_proj": pytest.approx(2.0), "ssm_conv": pytest.approx(1.0),
        "ssm_scan": pytest.approx(5.0), "ssm_norm": pytest.approx(3.0)}
    # a step without the mixer (another model's, the parent's)
    monkeypatch.setattr(scope_seconds, "program_table",
                        lambda: {"fusion.4": "attn.l5"})
    assert reader.read(_obs(cell, ops)) is None


def test_the_kernel_share_reader_over_a_hand_built_ring():
    import mxnet_tpu as mx
    cell = manifest.Manifest().cell(REAL_CELL)
    reader = manifest.load_module("layer_metrics", "ssd_kernel_share")
    was = mx.trace.enabled()
    mx.trace.reset()
    try:
        mx.trace.set_enabled(True)
        assert reader.read(_obs(cell)) is None               # empty ring
        # another counter's samples and another dtype's are not counted
        mx.trace.counter("gdn:lowering", cat="ops", kernel=1, plain=0,
                         track="bfloat16[1, 4096, 32, 128]/k16")
        mx.trace.counter("ssd:lowering", cat="ops", kernel=0, plain=1,
                         track="float32[1, 1024, 64, 64]/g1n128")
        assert reader.read(_obs(cell)) is None
        for kernel in (1, 1, 1, 0):
            mx.trace.counter("ssd:lowering", cat="ops", kernel=kernel,
                             plain=1 - kernel,
                             track="bfloat16[1, 4096, 64, 64]/g1n128")
        value, extra = reader.read(_obs(cell))
        assert value == pytest.approx(75.0)
        assert extra == {"samples": 4, "kernel": 3}
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
