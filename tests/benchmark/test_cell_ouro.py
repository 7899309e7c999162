"""The ``ouro-2.6b`` configuration, its cell and its four readers
``loop_attn_roofline``, ``loop_recompute_share``, ``scope_loop_head_ms``
and ``loop_exit_mean_depth``: the real entries by name, the
configuration's arithmetic (the parameters held, the FLOPs a token, the
attention kernels' roofline sum), the readers on hand-built traces, and
the cell on the CPU at tiny widths, added to the temporary copy of
``cellbench_util.tiny_copy`` as files and entries, through the same
driver as the others.  A CPU run checks answers and counts, never rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-ouro"
REAL_CELL = "ouro-2.6b-train-4k"
OLMOE_CELL = "olmoe-1b-7b-train-4k"
CONFIG = "ouro-2.6b"
TRAFFIC = "packed-4k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = {"loop_attn_roofline": ("Pallas kernels", "%", "higher",
                                  "device_trace"),
           "loop_recompute_share": ("loop node", "%", "lower",
                                    "device_trace"),
           "scope_loop_head_ms": ("loop node", "ms", "lower",
                                  "device_trace"),
           "loop_exit_mean_depth": ("loop node", "passes", "higher",
                                    "program_counter")}
# as the GLM file: the window holds some steps on a loaded machine too,
# and no assertion below asks for more than one
WINDOW_S = 4.0
NAMES = ["l0_q_proj_weight", "l1_down_proj_weight", "l1_up_proj_weight",
         "l0_attn_post_norm_gamma", "final_norm_gamma", "exit_gate_weight",
         "embed_weight", "lm_head_weight"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_ouro"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "ouro-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=4,
        head_dim=8, mlp_width=48, vocab_size=128, seq_len=72)
    cfg.update(num_hidden_layers=2, hidden_size=32, num_attention_heads=4,
               num_key_value_heads=4, head_dim=8)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "ouro-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "ouro-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic", "tiny-packed-ouro.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "ouro-tiny", "source": "test",
                           "file": "benchmark/configs/ouro-tiny.json",
                           "reduced": [], "why": "test"})
    util.add_cell(doc, CELL, "ouro-tiny", "tiny-packed-ouro",
                  like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_ouro_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    was = mx.trace.enabled()
    try:
        # the counters are fed while tracing is on, as in a --trace 1 run
        # (the driver switches it on there)
        mx.trace.set_enabled(True)
        mark = time.perf_counter_ns()
        result = driver.run(cell, [mx.cpu(0)], 5400000054, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        bodies = mx.trace.counter_events(["loop:body"], since_ns=mark)
        exits = mx.trace.counter_events(["loop:exit"], since_ns=mark)
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
    ref_mod = manifest.load_module("reference", "ouro-tiny", cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # one loop node of four passes, recomputed, in every trace of a step
    assert bodies and all(
        e["args"]["num_steps"] == 4 and e["args"]["recompute"] == 1
        and e["args"]["carry_bytes"] == 2 * 72 * 32 * 4 for e in bodies)
    # one sample of the exit head a step; the window's reader takes its
    # last tenth
    assert len(exits) == result["attempted"]
    depth = got["loop_exit_mean_depth"]
    assert 1.5 < depth["value"] < 2.6 and depth["samples"] >= 1
    # medians pass by pass: they sum to 1 nearly, not exactly
    assert sum(depth["p"]) == pytest.approx(1.0, abs=0.02)
    assert len(depth["p"]) == 4 and depth["ce_last"] > 0
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))
    # no trace: the device readers have nothing to read and say so
    assert not {n for n, r in READERS.items()
                if r[3] == "device_trace"} & set(got)


def check_the_ouro_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the four entries it
    came with as their readers have them, and the cell on every list a
    dense decoder reads something for.  By name and by membership, never
    by a position or a length: later cells and entries are appended to
    the same lists (``test_cellbench_rehearsal.py`` runs this against
    such copies)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "17 %" in cell["why"]
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_hidden_layers"]
    for name, (layer, unit, better, source) in READERS.items():
        entries = [m for m in doc["per_layer"] if m["name"] == name]
        assert len(entries) == 1
        entry = dict(entries[0])
        reader = manifest.load_module("layer_metrics", name)
        assert REAL_CELL in entry.pop("workloads")
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": reader.BETTER, "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": "train_tok_per_s"}
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) \
            == (layer, unit, better, source)

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, olmoe = lists_of(REAL_CELL), lists_of(OLMOE_CELL)
    # every list the OLMoE cell is on but its experts' and the share whose
    # work function reads num_hidden_layers once
    assert olmoe - listed == {
        "attn_roofline", "moe_gmm_roofline", "moe_load_max_over_mean",
        "moe_dropped_share", "scope_moe_experts_ms", "scope_moe_layout_ms"}
    assert listed - olmoe == set(READERS)
    assert {"train_tok_per_s", "mfu.tok", "peak_hbm_gib.tok",
            "device_step_ms.tok", "scope_attn_ms", "scope_lm_loss_ms",
            "scope_optimizer_ms.tok", "scope_other_ms.tok",
            "scope_unnamed_share.tok", "dispatch_ms_p50.tok",
            "setup_warmup_s", "setup_compile_backend_s"} <= listed
    for name in READERS:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_readers():
    doc = manifest.Manifest().doc
    check_the_ouro_cells_own_entries(doc)
    # one cell on four chips, the place the benchmark has
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def _obs(trace=None, **more):
    cell = manifest.Manifest().cell(REAL_CELL)
    return dict({"config": cell.config, "traffic": cell.traffic,
                 "peaks": manifest.load_peaks("TPU v5 lite"),
                 "trace": trace}, **more)


def test_the_attention_reader_counts_every_pass():
    reader = manifest.load_module("layer_metrics", "loop_attn_roofline")
    assert reader.read(_obs()) is None
    trace = {"steps": 2, "op_seconds": {"fusion.1 fusion f32": 1.0}}
    assert reader.read(_obs(trace)) is None           # no such operation
    trace["op_seconds"].update({
        "splash_mha_fwd_residuals.3 custom-call bf16[16,4096,128]": 0.1,
        "splash_mha_dkv_no_residuals.7 custom-call f32[1024,128]": 0.1})
    value, extra = reader.read(_obs(trace))
    assert extra["kernel_ms"] == pytest.approx(100.0)
    assert extra["bound"] == "compute" and extra["steps"] == 2
    cell = manifest.Manifest().cell(REAL_CELL)
    ops, nbytes = reader.looped_attention_work(cell.config, cell.traffic)
    # 4 passes x 8 layers x 14 Dh H pairs
    assert ops == 4 * 8 * 14 * 128 * 16 * (4096 * 4097 // 2)
    assert ops == pytest.approx(7.70e12, rel=1e-3)
    assert nbytes == 4 * 8 * 2 * 4096 * 128 * 8 * 16
    assert "%.2f" % extra["roofline_ms"] == "39.08"
    assert value == pytest.approx(100.0 * extra["roofline_ms"] / 100.0)
    # the accepted function reads the layers once: a quarter
    import kernel_rooflines
    once = kernel_rooflines.causal_attention_work(cell.config, cell.traffic)
    assert ops / once[0] == pytest.approx(4 * 4097 / 4096, rel=1e-9)


def test_the_recompute_reader_sums_what_the_program_names(monkeypatch):
    reader = manifest.load_module("layer_metrics", "loop_recompute_share")
    trace = {"steps": 2,
             "per_device": {"/device:TPU:0": {"busy_s": 2.0}},
             "op_seconds": {"fusion.1 fusion bf16[4096,2048]": 0.3,
                            "fusion.2 fusion bf16[4096,2048]": 0.2,
                            "fusion.3 fusion bf16[4096,5632]": 0.9,
                            "while.4 while bf16[4096,2048]": 1.4}}
    names = {"fusion.1": "jit(step_s1)/transpose(jvp(loop))/while/body/"
                         "closed_call/checkpoint/rematted_computation/"
                         "attn_proj.l0/dot_general",
             "fusion.2": "jit(step_s1)/transpose(jvp(loop))/while/body/"
                         "closed_call/checkpoint/rematted_computation/"
                         "loop_head/dot_general",
             "fusion.3": "jit(step_s1)/transpose(jvp(loop))/while/body/"
                         "closed_call/checkpoint/attn_proj.l0/dot_general",
             "while.4": "jit(step_s1)/transpose(jvp(loop))/while"}
    assert reader.read(_obs()) is None                   # no trace
    monkeypatch.setattr(reader, "program_op_names", lambda: None)
    assert reader.read(_obs(trace)) is None              # an older program
    monkeypatch.setattr(reader, "program_op_names", lambda: names)
    value, extra = reader.read(_obs(trace))
    assert value == pytest.approx(25.0)
    assert extra == {"recomputed_ms": pytest.approx(250.0),
                     "busy_ms": pytest.approx(1000.0),
                     "while_ms": pytest.approx(700.0), "steps": 2}
    # a step with no recomputed loop: nothing to read
    monkeypatch.setattr(reader, "program_op_names",
                        lambda: {"fusion.3": names["fusion.3"]})
    assert reader.read(_obs(trace)) is None


def test_the_head_reader_sums_its_two_kinds(monkeypatch):
    import scope_seconds
    reader = manifest.load_module("layer_metrics", "scope_loop_head_ms")
    trace = {"steps": 4,
             "op_seconds": {"fusion.1 fusion f32[4096,49152]": 0.4,
                            "fusion.2 fusion f32[4096]": 0.1,
                            "fusion.3 fusion bf16[4096,2048]": 0.7}}
    table = {"fusion.1": "loop_head", "fusion.2": "lm_loss",
             "fusion.3": "attn_proj.l0"}
    assert reader.read(_obs()) is None
    monkeypatch.setattr(scope_seconds, "program_table", lambda: None)
    assert reader.read(_obs(trace)) is None
    monkeypatch.setattr(scope_seconds, "program_table", lambda: table)
    value, extra = reader.read(_obs(trace))
    assert value == pytest.approx(125.0)
    assert extra["by_kind"] == {"loop_head": pytest.approx(100.0),
                                "lm_loss": pytest.approx(25.0)}
    # another model's step: no such scope, nothing to read
    monkeypatch.setattr(scope_seconds, "program_table",
                        lambda: {"fusion.2": "lm_loss"})
    assert reader.read(_obs(trace)) is None


def test_the_depth_reader_takes_the_windows_last_tenth():
    import mxnet_tpu as mx
    reader = manifest.load_module("layer_metrics", "loop_exit_mean_depth")
    assert reader.read(_obs(steps_in_window=5)) is None
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        for i in range(40):
            mx.trace.counter("loop:exit", cat="train", p1=0.5 - i / 100,
                             p2=0.25, p3=0.125, p4=0.125 + i / 100,
                             depth=1.875 + 3 * i / 100, ce_last=3.0)
        value, extra = reader.read(_obs(steps_in_window=30))
    finally:
        mx.trace.set_enabled(was)
    # the last three of the window's thirty: steps 37, 38, 39
    assert extra["samples"] == 3
    assert value == pytest.approx(1.875 + 3 * 38 / 100)
    assert extra["first"] == pytest.approx(1.875 + 3 * 10 / 100)
    assert extra["p"] == pytest.approx([0.12, 0.25, 0.125, 0.505])
    assert extra["ce_last"] == 3.0


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the one in ``reduced``; the builder's
    arguments are the same numbers; the cut is in depth only."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers"]
    assert cfg["num_hidden_layers_published"] \
        == published["num_hidden_layers"] == 48
    assert cfg["built_layers"] == list(range(8)) \
        and cfg["num_hidden_layers"] == 8
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["mlp_width"],
            kw["vocab_size"], kw["total_ut_steps"], kw["rope_theta"],
            kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
        cfg["total_ut_steps"], cfg["rope_theta"], cfg["rms_norm_eps"])
    # every width, the whole vocabulary and every pass as published
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["mlp_width"], kw["vocab_size"],
            kw["total_ut_steps"]) == (2048, 16, 16, 128, 5632, 49152, 4)
    assert set(cfg["layer_types"]) == {"full_attention"} \
        and len(cfg["layer_types"]) == 48
    assert cfg["tie_word_embeddings"] is False
    assert cfg["use_sliding_window"] is False
    assert kw["seq_len"] == 4096 and kw["exit_beta"] == 0.1
    assert kw["embed_sigma"] == 4.0 and "4.0" in cfg["assumed"]["initializer"]
    assert "recompute" not in kw            # the builder's default: True
    assert {"layer", "attention", "final_norm_every_pass", "exit_gate",
            "objective", "early_exit_threshold", "gate_at_start",
            "activation_memory"} <= set(cfg["assumed"])
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"])
    assert len(cfg["reference"]["weights"]) == 8
    assert "exit_gate_bias" not in cfg["reference"]["weights"]
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert cfg["chance_loss_classes"] == 49152
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held and FLOPs a token, written out (ISSUE 54's
    numbers), and the loop as one node of the real graph."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    from mxnet_tpu.models import ouro_lm
    from mxnet_tpu.symbol import _topo
    net = ouro_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 4096), softmax_label=(1, 4096))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, F = 2048, 49152, 5632
    layer = 4 * D * D + 3 * D * F + 4 * D
    assert layer == 51_388_416
    assert sum(v for n, v in sizes.items() if n.startswith("l3_")) == layer
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == V * D
    assert sizes["exit_gate_weight"] == D and sizes["exit_gate_bias"] == 1
    total = 8 * layer + 2 * V * D + D + D + 1
    assert sum(sizes.values()) == total == 612_438_017
    assert 16 * total / 2 ** 30 == pytest.approx(9.13, abs=0.005)
    assert 16 * total / 1e9 == pytest.approx(9.80, abs=0.005)
    # each weight once: 8 x 11 + the embedding, the final gain, the head,
    # the gate's two
    assert len(sizes) == 8 * 11 + 5
    loops = [n for n in _topo(net._heads)
             if not n.is_variable and n.op.name == "Repeat"]
    assert len(loops) == 1
    p = loops[0].params
    assert (p.num_steps, p.carry, p.recompute) == (4, "loop_rows", True)
    # the body: what ONE pass is made of
    from mxnet_tpu.ops.control_flow import body_op_nodes
    per_layer = 22
    assert len(body_op_nodes(p.body)) == 8 * per_layer + 4
    # FLOPs a token: the issue's ~14.2 G, the head 17 % of them here and
    # 3.4 % at 48 layers
    ref = manifest.load_module("reference", CONFIG)
    flops = ref.train_flops_per_sample(cfg)
    matmul, scores = 2 * (layer - 4 * D), 4 * 128 * 16 * 4097 / 2
    head = 2 * D * (V + 1)
    assert flops == 4 * (8 * (3 * matmul + 3.5 * scores) + 3 * head)
    assert flops == pytest.approx(14.16e9, rel=2e-3)
    assert 4 * 3 * head / flops == pytest.approx(0.17, abs=0.005)
    whole = 4 * (48 * (3 * matmul + 3.5 * scores) + 3 * head)
    assert 4 * 3 * head / whole == pytest.approx(0.034, abs=0.001)
    assert flops * 4096 == pytest.approx(58.0e12, rel=5e-3)
