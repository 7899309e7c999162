"""``scope_seconds`` and the ``scope_*`` readers: the join of a trace's
operations with the program's own table of its step, on hand-built
operations and a hand-built table, then on the table of a step that ran
in this process; and the entries of the real ``BENCHMARK.json`` as
``check_*(doc)`` functions, which ``test_cellbench_rehearsal.py`` finds
and runs against copies that later cells and entries were appended to."""
import os
import time

import pytest

import cellbench_util as util
import manifest
import scope_seconds

FAKE_PEAKS = {"bf16_flops_per_s": 1e12}
RESNET = "resnet50-synthetic-b128"
OLMOE = "olmoe-1b-7b-train-4k"
KIMI = "kimi-linear-48b-a3b-train-4k"
GLM = "glm-4.7-flash-train-4k"
IMG, TOK = "train_img_per_s", "train_tok_per_s"
# the last per-layer entry of the file before the scope entries came
LAST_BEFORE = "mtp_loss_over_main"
# name -> (unit, layer, the end-to-end metric it moves, the cells it came
# with), in the order the entries were appended in
ENTRIES = {
    "scope_optimizer_ms.img": ("ms", "train step", IMG, [RESNET]),
    "scope_optimizer_ms.tok": ("ms", "train step", TOK, [OLMOE, KIMI, GLM]),
    "scope_attn_ms": ("ms", "Pallas kernels", TOK, [OLMOE, KIMI, GLM]),
    "scope_mla_proj_ms": ("ms", "Pallas kernels", TOK, [KIMI, GLM]),
    # the GLM cell has no such layer and reads 0: test_cell_glm_flash.py
    # wants it on every list of the Kimi cell but a kernel's roofline
    "scope_kda_ms": ("ms", "linear attention", TOK, [KIMI, GLM]),
    "scope_moe_experts_ms": ("ms", "routed experts", TOK,
                             [OLMOE, KIMI, GLM]),
    "scope_moe_layout_ms": ("ms", "routed experts", TOK, [OLMOE, KIMI, GLM]),
    "scope_lm_loss_ms": ("ms", "ops", TOK, [OLMOE, KIMI, GLM]),
    "scope_mtp_ms": ("ms", "prediction heads", TOK, [GLM]),
    "scope_conv_ms": ("ms", "ops", IMG, [RESNET]),
    "scope_norm_ms": ("ms", "ops", IMG, [RESNET]),
    "scope_other_ms.img": ("ms", "ops", IMG, [RESNET]),
    "scope_other_ms.tok": ("ms", "ops", TOK, [OLMOE, KIMI, GLM]),
    "scope_unnamed_share.img": ("%", "device", IMG, [RESNET]),
    "scope_unnamed_share.tok": ("%", "device", TOK, [OLMOE, KIMI, GLM]),
}

TABLE = {"fusion.1": "attn.l0", "splash_mha_fwd.2": "attn.l1",
         "fusion.3": "mla_q.l0", "fusion.4": "rope.l0",
         "fusion.5": "optimizer.l0_q_proj_weight",
         "fusion.6": "optimizer.embed_weight",
         "fusion.7": "fullyconnected.lm_head", "fusion.8": "rmsnorm.l0_norm",
         "fusion.9": "cast.params", "fusion.10": "mtp.attn",
         "never_ran.11": "kda.l0"}
OPS = {"fusion.1 fusion bf16[4,8]": 0.010, "splash_mha_fwd.2 custom-call": 0.030,
       "fusion.3 fusion f32[8]": 0.004, "fusion.4 fusion f32[8]": 0.002,
       "fusion.5 fusion f32[8,8]": 0.020, "fusion.6 fusion f32[9,8]": 0.001,
       "fusion.7 fusion f32[9,8]": 0.008, "fusion.8 fusion f32[8]": 0.003,
       "fusion.9 fusion bf16[8]": 0.005, "fusion.10 fusion f32[8]": 0.006,
       "copy.12 copy f32[8,8]": 0.007, "fusion.13 fusion f32[2]": 0.004}
STEPS = 2


def _obs(ops=OPS, steps=STEPS, busy_s=None):
    busy = sum(ops.values()) if busy_s is None else busy_s
    return {"driver": "train_fit", "trace": {
        "steps": steps, "op_seconds": dict(ops), "busy_s": busy,
        "per_device": {"/device:TPU:1": {"busy_s": 99.0},
                       "/device:TPU:0": {"busy_s": busy}}}}


def test_split_sums_by_kind_and_keeps_what_has_no_scope():
    kinds, unnamed = scope_seconds.split(OPS, TABLE)
    assert kinds == pytest.approx({
        "attn": 0.040, "mla_q": 0.004, "rope": 0.002, "optimizer": 0.021,
        "fullyconnected": 0.008, "rmsnorm": 0.003, "cast": 0.005,
        "mtp": 0.006})
    assert unnamed == pytest.approx(0.011)
    # whole: nothing is counted twice or left out
    assert sum(kinds.values()) + unnamed == pytest.approx(sum(OPS.values()))
    assert scope_seconds.split({}, TABLE) == ({}, 0.0)
    assert scope_seconds.split(OPS, {}) == ({}, pytest.approx(sum(
        OPS.values())))


def test_the_readers_on_a_hand_built_trace_and_table(monkeypatch):
    monkeypatch.setattr(scope_seconds, "program_table", lambda: TABLE)
    obs = _obs()
    got = {}
    for name in ENTRIES:
        reader = manifest.load_module("layer_metrics", name.split(".")[0])
        value, extra = reader.read(obs)
        got[name.split(".")[0]] = (value, extra)
        assert extra["steps"] == STEPS
    ms = {k: v[0] for k, v in got.items()}
    assert ms["scope_attn_ms"] == pytest.approx(20.0)
    assert ms["scope_optimizer_ms"] == pytest.approx(10.5)
    assert ms["scope_mla_proj_ms"] == pytest.approx(3.0)
    assert got["scope_mla_proj_ms"][1]["by_kind"] == pytest.approx(
        {"mla_q": 2.0, "mla_kv": 0.0, "rope": 1.0})
    assert "by_kind" not in got["scope_attn_ms"][1]
    assert ms["scope_mtp_ms"] == pytest.approx(3.0)
    # a kind the step has not: zero, not nothing
    assert ms["scope_kda_ms"] == 0.0 and ms["scope_conv_ms"] == 0.0
    other, extra = got["scope_other_ms"]
    assert other == pytest.approx(8.0) and extra["kinds"] == 3
    assert list(extra["by_kind"]) == ["fullyconnected", "cast", "rmsnorm"]
    assert extra["by_kind"] == pytest.approx(
        {"fullyconnected": 4.0, "cast": 2.5, "rmsnorm": 1.5})
    share, extra = got["scope_unnamed_share"]
    assert share == pytest.approx(100 * 0.011 / sum(OPS.values()))
    assert extra["unnamed_ms"] == pytest.approx(5.5)
    assert extra["largest_unnamed"] == [
        ["copy.12 copy f32[8,8]", pytest.approx(3.5)],
        ["fusion.13 fusion f32[2]", pytest.approx(2.0)]]
    # the whole split: every reader's kinds, the others and the unnamed
    named = sum(v for k, v in ms.items()
                if k not in ("scope_other_ms", "scope_unnamed_share"))
    assert named + other + extra["unnamed_ms"] == pytest.approx(
        extra["ops_ms"])
    assert extra["scoped_ms"] == pytest.approx(named + other)
    assert extra["ops_ms"] == pytest.approx(extra["busy_ms"])


def test_by_kind_holds_the_eight_largest(monkeypatch):
    table = {"f.%d" % i: "kind%02d.n" % i for i in range(11)}
    ops = {"f.%d fusion" % i: 0.001 * (i + 1) for i in range(11)}
    monkeypatch.setattr(scope_seconds, "program_table", lambda: table)
    value, extra = scope_seconds.read_other_ms(_obs(ops, steps=1))
    assert value == pytest.approx(66.0) and extra["kinds"] == 11
    assert list(extra["by_kind"]) == ["kind%02d" % i
                                      for i in range(10, 2, -1)]


def test_nothing_where_there_is_no_table_or_no_trace(monkeypatch):
    """A program without ``program_scopes`` (the parent commit), a
    process in which no fused step ran, an untraced run: every reader
    gives None and none raises."""
    readers = [manifest.load_module("layer_metrics", n)
               for n in sorted({n.split(".")[0] for n in ENTRIES})]
    assert len(readers) == 12
    for obs in ({"driver": "train_fit", "trace": None},
                {"driver": "train_fit", "trace": {"steps": 0}}):
        monkeypatch.setattr(scope_seconds, "program_table", lambda: TABLE)
        assert [r.read(obs) for r in readers] == [None] * 12
    monkeypatch.setattr(scope_seconds, "program_table", lambda: None)
    assert [r.read(_obs()) for r in readers] == [None] * 12
    monkeypatch.undo()
    import mxnet_tpu as mx
    monkeypatch.delattr(mx.trace, "program_scopes")
    assert scope_seconds.program_table() is None
    assert [r.read(_obs()) for r in readers] == [None] * 12


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return util.tiny_copy(tmp_path_factory.mktemp("cellbench_scopes"))


def test_the_table_of_the_step_that_ran_splits_a_resnets_trace(copy):
    """The tiny ResNet cell through the driver, untraced; then the
    readers of every ``scope_*`` entry the cell is listed under, on a
    trace made up from the table the program gives for the step that
    ran: a second for each of its instructions, and two operations of
    no scope."""
    import mxnet_tpu as mx
    import run as bench_run
    cell = manifest.Manifest(copy).cell("tiny-dev")
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    result = driver.run(cell, [mx.cpu(0)], 2147480037, 0.5, False,
                        time.perf_counter(), FAKE_PEAKS, lambda line: None)
    obs = result["_obs"]
    # an untraced run asks for no table
    assert not mx.trace.span_events(names=[scope_seconds.TABLE_SPAN])
    assert {k: v for k, v in bench_run.layer_metrics(cell, obs).items()
            if k.startswith("scope_")} == {}
    table = scope_seconds.program_table()
    kinds = {scope_seconds.kind_of(s) for s in table.values()}
    assert {"convolution", "batchnorm", "optimizer", "fullyconnected",
            "pooling", "activation", "softmaxoutput"} <= kinds
    # the fit's step, not the reference check's small module's
    assert "optimizer.stem_conv_weight" in table.values()
    ops = {"%s fusion f32[8]" % name: 1.0 for name in table}
    ops.update({"copy.9999 copy f32[8]": 2.0, "copy.9998 copy f32[4]": 1.0})
    obs["trace"] = _obs(ops, steps=4)["trace"]
    listed = [m["name"] for m in cell.per_layer
              if m["name"].startswith("scope_")]
    got = {}
    for name in listed:
        value, extra = manifest.load_module(
            "layer_metrics", cell.reader_of(name), cell.bench_dir).read(obs)
        got[name] = dict(extra, value=value)
    assert sorted(listed) == sorted(n for n in ENTRIES if n.endswith(".img")
                                    or ENTRIES[n][2] == IMG)
    assert set(listed) <= set(got)
    share = got["scope_unnamed_share.img"]
    assert share["value"] == pytest.approx(100 * 3.0 / (len(table) + 3.0))
    assert share["table_build_ms"] > 0
    # the entries the cell is listed under are the whole of the step
    ms = sum(got[n]["value"] for n in listed if n.endswith(
        ("_ms", "_ms.img")))
    assert ms + share["unnamed_ms"] == pytest.approx(share["ops_ms"])
    assert got["scope_conv_ms"]["value"] > 0
    assert got["scope_norm_ms"]["value"] > 0
    assert got["scope_optimizer_ms.img"]["value"] > 0


def check_the_scope_entries(doc):
    """``doc`` holds every ``scope_*`` entry as its reader has it, after
    the entries that were there and in the order they came in, each
    listing the cells it came with.  By name and membership: later cells
    and entries are appended."""
    names = [m["name"] for m in doc["per_layer"]]
    by_name = {m["name"]: m for m in doc["per_layer"]}
    places = [names.index(n) for n in ENTRIES]
    assert places == sorted(places)
    assert places[0] > names.index(LAST_BEFORE)
    for name, (unit, layer, moves, cells) in ENTRIES.items():
        reader = manifest.load_module("layer_metrics", name.split(".")[0])
        assert (reader.UNIT, reader.LAYER, reader.BETTER, reader.SOURCE) \
            == (unit, layer, "lower", "device_trace")
        assert "train_fit" in reader.DRIVERS
        entry = dict(by_name[name])
        listed = entry.pop("workloads")
        assert listed[:len(cells)] == cells
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": "device_trace", "layer": layer,
                         "moves": moves}


def check_a_cell_with_a_split_has_the_whole_of_it(doc):
    """A cell listed under a ``scope_unnamed_share`` entry is listed
    under the ``scope_other_ms`` and ``scope_optimizer_ms`` entries of
    the same tag: the kinds no reader names and the update are parts of
    every fused step.  The classic path's cell and the four-chip cell
    are under none."""
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for tag in ("img", "tok"):
        cells = by_name["scope_unnamed_share." + tag]["workloads"]
        for other in ("scope_other_ms.", "scope_optimizer_ms."):
            assert by_name[other + tag]["workloads"] == cells
        for m in doc["per_layer"]:
            if m["name"].startswith("scope_") and m["moves"] == \
                    by_name["scope_unnamed_share." + tag]["moves"]:
                assert set(m["workloads"]) <= set(cells), m["name"]
    listed = {c for m in doc["per_layer"] if m["name"].startswith("scope_")
              for c in m["workloads"]}
    assert not {"ptb-lstm-bucketed-b1024", "resnet50-dp4-b512"} & listed
    chips = {w["name"]: w["chips"] for w in doc["workloads"]}
    assert all(chips[c] == 1 for c in listed)


def test_the_real_file_holds_the_scope_entries():
    doc = manifest.Manifest().doc
    check_the_scope_entries(doc)
    check_a_cell_with_a_split_has_the_whole_of_it(doc)
    for name in ENTRIES:
        assert os.path.isfile(os.path.join(
            util.BENCH, "layer_metrics", name.split(".")[0] + ".py"))
    assert set(scope_seconds.KINDS) == {n.split(".")[0] for n in ENTRIES} \
        - {"scope_other_ms", "scope_unnamed_share"}
