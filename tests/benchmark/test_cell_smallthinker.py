"""The ``smallthinker-21b-a3b`` configuration, its cell, its traffic and
its reader ``moe_act_zero_share``: the real entries by name, the
configuration's arithmetic (the parameters held, the FLOPs a token, the
attention kernels' roofline sum over both kinds of layer at 8192
tokens), the reader over a hand-built ring, and the cell on the CPU at
tiny widths, added to the temporary copy of ``cellbench_util.tiny_copy``
as files and entries, through the same driver as the others.  A CPU run
checks answers and counts, never rates."""
import json
import os
import shutil
import time

import numpy as np
import pytest

import cellbench_util as util
import manifest

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
CELL = "tiny-smallthinker"
REAL_CELL = "smallthinker-21b-a3b-train-8k"
TRINITY_CELL = "trinity-mini-train-4k"
CONFIG = "smallthinker-21b-a3b"
TRAFFIC = "packed-8k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# as the GLM file: the window holds some steps on a loaded machine too,
# and no assertion below asks for more than one
WINDOW_S = 4.0
NAMES = ["l0_q_proj_weight", "l1_q_proj_weight", "l1_k_proj_weight",
         "l1_moe_gate_weight", "l1_moe_experts_i2h_gate_weight",
         "embed_weight", "lm_head_weight"]
REDUCED = ["moe_num_primary_experts", "vocab_size", "num_hidden_layers"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_smallthinker"))
    bench = os.path.join(root, "benchmark")
    cfg = util._load(os.path.join(bench, "configs", CONFIG + ".json"))
    cfg["name"] = "smallthinker-tiny"
    cfg["model"]["kwargs"].update(
        num_layers=4, hidden_size=32,
        layer_types=["full", "sliding", "sliding", "sliding"],
        num_heads=6, num_kv_heads=2, head_dim=8, window=24, num_experts=16,
        experts_per_tok=3, expert_width=24, vocab_size=128, seq_len=72,
        experts_held=4, first_expert=4)
    cfg["input"] = {"seq_len": 72, "vocab_size": 128}
    cfg["chance_loss_classes"] = 128
    cfg["compute_dtype"] = "float32"
    cfg["optimizer"]["params"]["learning_rate"] = 0.003
    cfg["reference"].update(samples=2, weights=NAMES, loss_rtol=1e-4,
                            update_rtol=dict.fromkeys(NAMES, 0.05))
    util._dump(cfg, os.path.join(bench, "configs", "smallthinker-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", CONFIG + ".py"),
                os.path.join(bench, "reference", "smallthinker-tiny.py"))
    t = util._load(os.path.join(bench, "traffic", TRAFFIC + ".json"))
    t.update(batch_per_chip=2, distinct_batches=8, warmup_steps=3,
             learn_margin=0.1)
    t["corpus"]["length_mean"] = 12.0
    util._dump(t, os.path.join(bench, "traffic",
                               "tiny-packed-smallthinker.json"))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({
        "name": "smallthinker-tiny", "source": "test",
        "file": "benchmark/configs/smallthinker-tiny.json", "reduced": [],
        "why": "test"})
    util.add_cell(doc, CELL, "smallthinker-tiny", "tiny-packed-smallthinker",
                  like=REAL_CELL)
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return root


def test_the_smallthinker_cell_runs_through_the_driver_and_is_correct(copy):
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell(CELL)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    lines = []
    rng = mx.random.get_key_data(), np.random.get_state()
    was = mx.trace.enabled()
    try:
        # the attn:lowering and moe:act_zeros samples are taken while
        # tracing is on, as in a --trace 1 run (the driver switches it on
        # there)
        mx.trace.set_enabled(True)
        mark = time.perf_counter_ns()
        result = driver.run(cell, [mx.cpu(0)], 4700000031, WINDOW_S, False,
                            time.perf_counter(), FAKE_PEAKS, lines.append)
        got = bench_run.layer_metrics(cell, result["_obs"])
        lowered = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
        rows = mx.trace.counter_events(["moe:router_rows"], since_ns=mark)
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
    ref_mod = manifest.load_module("reference", "smallthinker-tiny",
                                   cell.bench_dir)
    assert obs["flops_per_sample"] == \
        ref_mod.train_flops_per_sample(cell.config)
    # four routed blocks, every layer one
    assert got["moe_dropped_share"]["value"] == 0.0
    assert got["moe_dropped_share"]["samples"] == \
        4 * obs["steps_in_window"]
    held = got["moe_held_rows_share"]
    assert 5.0 < held["value"] < 60.0 and held["blocks"] == 4
    zeros = got["moe_act_zero_share"]
    assert 20.0 < zeros["value"] < 80.0
    assert zeros["samples"] == 4 * obs["steps_in_window"]
    assert sorted(zeros["by_block"]) == ["l%d_moe_share" % l
                                         for l in range(4)]
    assert all(0.0 < v < 100.0 for v in zeros["by_block"].values())
    # each traced op set names the full layer, then three window layers
    tracks = [e["id"] for e in lowered]
    assert tracks and len(tracks) % 4 == 0
    assert set(tracks[0::4]) == {"float32[2, 72, 6, 8]/kv2"}
    assert set(tracks[1::4]) == set(tracks[2::4]) == set(tracks[3::4]) == \
        {"float32[2, 72, 6, 8]/kv2/sliding_window24"}
    assert rows and all(e["args"] == {"mixer": 1, "ffn": 0} for e in rows)
    untraced = {m["name"] for m in cell.per_layer
                if m["source"] not in ("device_trace", "program_span")}
    assert untraced <= set(got), sorted(untraced - set(got))


def check_the_smallthinker_cells_own_entries(doc):
    """``doc`` holds the configuration, the cell, the entry it came with
    as its reader has it, and the cell on every list the Trinity cell is
    on.  By name and by membership, never by a position or a length:
    later cells and entries are appended to the same lists
    (``test_cellbench_rehearsal.py`` runs this against such copies)."""
    cell = next(w for w in doc["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/%s.json" % CONFIG
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    entries = [m for m in doc["per_layer"]
               if m["name"] == "moe_act_zero_share"]
    assert len(entries) == 1
    entry = dict(entries[0])
    reader = manifest.load_module("layer_metrics", "moe_act_zero_share")
    assert REAL_CELL in entry.pop("workloads")
    assert entry == {"name": "moe_act_zero_share", "unit": reader.UNIT,
                     "better": reader.BETTER, "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": "train_tok_per_s"}
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) == \
        ("%", "higher", "program_counter", "routed experts")
    assert any(m["layer"] == reader.LAYER for m in doc["per_layer"]
               if m["name"] != "moe_act_zero_share")

    def lists_of(name):
        return {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
                if name in m.get("workloads", [])}

    listed, trinity = lists_of(REAL_CELL), lists_of(TRINITY_CELL)
    # every list the Trinity cell is on, its window kernels' among them
    assert trinity <= listed and "swa_attn_roofline" in trinity
    assert "moe_act_zero_share" in listed - trinity
    assert {"train_tok_per_s", "moe_held_rows_share", "scope_attn_ms",
            "moe_prefix_fit_share", "moe_load_max_over_mean",
            "moe_dropped_share", "mfu.tok", "dispatch_ms_p50.tok",
            "scope_other_ms.tok", "peak_hbm_gib.tok",
            "device_idle_share.tok"} <= listed
    # not the kernels counted for the causal mask in every layer, for
    # every routed row or for another mask
    assert not {"attn_roofline", "moe_gmm_roofline", "kda_roofline",
                "mla_attn_roofline", "bd_attn_roofline"} & listed
    for name in listed - trinity:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".", 1)[0] + ".py")), name


def test_the_cells_entries_are_appended_and_agree_with_the_reader():
    doc = manifest.Manifest().doc
    check_the_smallthinker_cells_own_entries(doc)
    # one cell on four chips, the place the benchmark has
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    traffic = manifest.Manifest().cell(REAL_CELL).traffic
    like = util._load(os.path.join(util.BENCH, "traffic",
                                   "packed-4k-b1.json"))
    # packed-4k-b1's parameters, its own why and learn_margin
    differs = {k for k in set(traffic) | set(like)
               if traffic.get(k) != like.get(k)}
    assert differs <= {"why", "learn_margin"} and "why" in differs
    assert "8192" in traffic["why"] and len(traffic["why"]) > 200


def test_the_zero_share_reader_over_a_hand_built_ring():
    import mxnet_tpu as mx
    reader = manifest.load_module("layer_metrics", "moe_act_zero_share")
    was = mx.trace.enabled()
    mx.trace.reset()
    try:
        mx.trace.set_enabled(True)
        assert reader.read({"steps_in_window": 2}) is None     # empty ring
        # a warm-up step, then the window's two, two blocks a step
        for zeros_a, zeros_b in ((90, 90), (40, 10), (60, 30)):
            mx.trace.counter("moe:act_zeros", cat="moe", track="l0_moe_share",
                             zeros=float(zeros_a), lanes=100.0)
            mx.trace.counter("moe:act_zeros", cat="moe", track="l1_moe_share",
                             zeros=float(zeros_b), lanes=200.0)
        assert reader.read({"steps_in_window": 0}) is None
        value, extra = reader.read({"steps_in_window": 2})
        assert value == pytest.approx(100.0 * (40 + 10 + 60 + 30) / 600)
        assert extra == {"samples": 4, "blocks": 2, "lanes": 600.0,
                         "by_block": {"l0_moe_share": pytest.approx(50.0),
                                      "l1_moe_share": pytest.approx(10.0)}}
        # a step in which the rank held no row counts for nothing
        mx.trace.counter("moe:act_zeros", cat="moe", track="l0_moe_share",
                         zeros=0.0, lanes=0.0)
        value, extra = reader.read({"steps_in_window": 1})
        assert extra["blocks"] == 1 and value == pytest.approx(15.0)
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    # a program without the ring (the parent commit's package has one;
    # a checkout without the program has none)
    assert reader.read({}) is None


def test_the_configuration_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name and
    with the same value but the three in ``reduced`` (the published
    layouts of 52 among them, whole: the layers BUILT are the builder's
    arguments); the builder's arguments are the same numbers; the cuts
    are at the floors."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    m = manifest.Manifest()
    entry = m.configs[CONFIG]
    cfg = m.cell(REAL_CELL).config
    assert entry["source"] == cfg["source"] == row["source_url"]
    published = row["config"]
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == sorted(REDUCED)
    assert (cfg["num_hidden_layers_published"],
            cfg["moe_num_primary_experts_published"],
            cfg["vocab_size_published"]) == (
        published["num_hidden_layers"], published["moe_num_primary_experts"],
        published["vocab_size"]) == (52, 64, 151936)
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    kw = cfg["model"]["kwargs"]
    assert (kw["num_layers"], kw["hidden_size"], kw["num_heads"],
            kw["num_kv_heads"], kw["head_dim"], kw["window"],
            kw["rope_theta"], kw["num_experts"], kw["experts_held"],
            kw["experts_per_tok"], kw["expert_width"], kw["vocab_size"],
            kw["rms_eps"]) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["sliding_window_size"], cfg["rope_theta"],
        cfg["moe_num_primary_experts_published"],
        cfg["moe_num_primary_experts"],
        cfg["moe_num_active_primary_experts"], cfg["moe_ffn_hidden_size"],
        cfg["vocab_size"], cfg["rms_norm_eps"])
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["window"], kw["num_experts"],
            kw["experts_per_tok"], kw["expert_width"], kw["rope_theta"],
            kw["rms_eps"]) == (2560, 28, 4, 128, 4096, 64, 6, 768, 1.5e6,
                               1e-6)
    # the keys the readers take, beside the published one
    assert cfg["sliding_window"] == cfg["sliding_window_size"] == 4096
    assert cfg["moe_primary_router_apply_softmax"] is True
    assert cfg["norm_topk_prob"] is True
    assert cfg["tie_word_embeddings"] is False
    # the layers built are published ones, one whole period: full (no
    # rotation), then three window layers (rotated)
    built = cfg["built_layers"]
    assert built == [0, 1, 2, 3] and len(built) == kw["num_layers"]
    assert kw["layer_types"] == [
        "sliding" if published["sliding_window_layout"][l] else "full"
        for l in built] == ["full", "sliding", "sliding", "sliding"]
    assert published["rope_layout"] == published["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    # the floors: an eighth of the vocabulary, 8 experts, four layers
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert (kw["experts_held"], kw["first_expert"]) == (8, 0)
    assert kw["num_experts"] % kw["experts_held"] == 0
    assert kw["seq_len"] == 8192 <= published["max_position_embeddings"]
    assert kw["act_zeros"] is True
    assert {"router_rows", "bias", "head_norm", "rope", "reglu",
            "secondary_experts", "balance", "sequence_length", "optimizer",
            "initializer", "attention_mask", "activation_memory"} \
        <= set(cfg["assumed"])
    assert "8 chips" in cfg["deployment"]
    assert set(cfg["reference"]["update_rtol"]) == \
        set(cfg["reference"]["weights"]) == set(NAMES)
    assert cfg["input"] == {"seq_len": kw["seq_len"],
                            "vocab_size": kw["vocab_size"]}
    assert json.dumps(cfg)            # plain data


def test_the_configurations_arithmetic():
    """Parameters held, FLOPs a token and the attention kernels' roofline
    sum, written out (ISSUE 47's numbers)."""
    cell = manifest.Manifest().cell(REAL_CELL)
    cfg, kw = cell.config, cell.config["model"]["kwargs"]
    ref = manifest.load_module("reference", CONFIG)
    from mxnet_tpu.models import smallthinker_lm
    net = smallthinker_lm(**kw)
    shapes, _, _ = net.infer_shape(data=(1, 8192), softmax_label=(1, 8192))
    sizes = {n: int(np.prod(s)) for n, s in zip(net.list_arguments(), shapes)
             if n not in ("data", "softmax_label")}
    D, V, held = 2560, 18992, kw["experts_held"]
    attention = 2 * D * 3584 + 2 * D * 512       # q, o; k, v
    assert attention == 20_971_520
    expert = 3 * D * 768
    assert expert == 5_898_240
    layer = attention + 2 * D + 64 * D + held * expert
    assert layer == pytest.approx(68.33e6, rel=1e-4)
    total = 2 * D * V + D + 4 * layer
    assert sum(sizes.values()) == total == 370_547_200
    assert sizes["embed_weight"] == sizes["lm_head_weight"] == D * V
    assert sizes["l1_moe_experts_i2h_gate_weight"] == held * D * 768
    assert sizes["l0_q_proj_weight"] == sizes["l0_o_proj_weight"] == 3584 * D
    assert sizes["l1_k_proj_weight"] == 512 * D
    assert sizes["l1_moe_gate_weight"] == 64 * D
    assert not [n for n in sizes if "norm" in n and "q_" in n]
    assert 16 * total / 2 ** 30 == pytest.approx(5.52, abs=0.005)
    # whole, one layer's 64 experts are 377.5 M of its 398.6 M
    assert attention + 64 * D + 64 * expert == pytest.approx(398.6e6,
                                                             rel=1e-3)
    # FLOPs a trained token: the allowed pairs by layer kind
    window_pairs, causal_pairs = 25_167_872, 33_558_528
    assert ref.allowed_pairs(8192, 4096) == window_pairs \
        == 4096 * 4097 // 2 + 4096 * 4096
    assert ref.allowed_pairs(8192) == causal_pairs == 8192 * 8193 // 2
    assert window_pairs / causal_pairs == pytest.approx(0.75, abs=0.001)
    scores = 4 * 128 * 28 * (3 * window_pairs + causal_pairs) / 8192
    forward = 4 * (2 * attention + 2 * D * 64
                   + 6 * held / 64 * 2 * expert) + scores + 2 * D * V
    assert ref.train_flops_per_sample(cfg) == pytest.approx(3 * forward,
                                                            rel=1e-12)
    assert forward == pytest.approx(492.5e6, rel=1e-3)
    assert 8192 * 3 * forward == pytest.approx(12.10e12, rel=1e-3)
    assert scores / forward == pytest.approx(0.387, abs=0.003)
    # the kernels' roofline: three window layers and a full one, 28 heads
    # over 4, the allowed pairs at 8192 tokens (the reader takes T from
    # the configuration's input.seq_len, not from the traffic file)
    reader = manifest.load_module("layer_metrics", "swa_attn_roofline")
    assert reader.allowed_pairs(8192, 4096) == window_pairs
    assert reader.allowed_pairs(8192) == causal_pairs
    ops, nbytes = reader.mixed_window_attention_work(cfg, cell.traffic)
    assert 3 * window_pairs + causal_pairs == 109_062_144
    assert ops == 14 * 128 * 28 * 109_062_144
    assert ops == pytest.approx(5.472e12, rel=1e-4)
    assert nbytes == 4 * 2 * 8192 * 128 * 4 * (28 + 4)
    import kernel_rooflines
    assert kernel_rooflines._sizes(cfg, cell.traffic)[:2] == (1, 8192)
    seconds, bound = kernel_rooflines.roofline_time(
        (ops, nbytes), manifest.load_peaks("TPU v5 lite"))
    assert bound == "compute" and seconds == pytest.approx(27.78e-3,
                                                           rel=1e-3)
    # the same traffic file under a configuration of 4096 tokens would
    # count 4096: the file alone changes nothing
    short = dict(cfg, input=dict(cfg["input"], seq_len=4096))
    assert kernel_rooflines._sizes(short, cell.traffic)[1] == 4096
    # the held experts' rows a step against the deployment's
    assert 8192 * 6 * held // 64 // held == 768
    # the rank's sorted layout: the bound (4 balanced shares of 6144
    # rows) is taken, it saves more than BOUND_WORTH_ROWS
    import importlib
    dispatch = importlib.import_module("mxnet_tpu.moe.dispatch")
    bound_rows = dispatch.held_rows_bound(8192 * 6, 64, held)
    assert bound_rows == 24576 <= 8192 * 6 - dispatch.BOUND_WORTH_ROWS
    # the grouped-matmul tiles at K = 2560: two k steps for gate and up,
    # two n tiles for down (tiles_for is as it was)
    from mxnet_tpu.moe import gmm
    import jax.numpy as jnp
    assert gmm.tiles_for(bound_rows, 2560, 768, held, jnp.bfloat16) \
        == (256, 1280, 768)
    assert gmm.tiles_for(bound_rows, 768, 2560, held, jnp.bfloat16) \
        == (256, 768, 1280)
