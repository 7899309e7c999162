"""``scope_parts`` and the seven readers of a decoder block's parts (PR
69): each on a hand-built trace and table (it sums its kinds, leaves a
wrapper event out and says so, reads 0.0 where the step has the table and
none of its kinds, nothing without a trace or a table), the generic share
with the program's ``sort_of`` stood in for, and the entries of the real
``BENCHMARK.json`` as a ``check_*(doc)`` function, which
``test_cellbench_rehearsal.py`` finds and runs against copies that later
cells and entries were appended to."""
import pytest

import cellbench_util  # noqa: F401  (puts benchmark/ on the path)
import manifest
import scope_parts
import scope_seconds

TOK = "train_tok_per_s"
# the one-chip LM cells, in the order the benchmark got them
LM_CELLS = [
    "olmoe-1b-7b-train-4k", "kimi-linear-48b-a3b-train-4k",
    "glm-4.7-flash-train-4k", "sdar-30b-a3b-train-4k",
    "trinity-mini-train-4k", "smallthinker-21b-a3b-train-8k",
    "qwen3-next-80b-a3b-train-4k", "ouro-2.6b-train-4k",
    "keye-vl-2.0-30b-a3b-train-8k", "lfm2-8b-a1b-train-8k",
    "granite-4.0-h-micro-train-4k"]
# entry -> (unit, layer, the kinds it sums; None: by sort, not by kind)
ENTRIES = {
    "scope_mlp_ms": ("ms", "ops", ("mlp",)),
    "scope_moe_share_ms": ("ms", "routed experts", ("moe_share",)),
    "scope_attn_proj_ms": ("ms", "ops", ("attn_proj", "attn_gate")),
    "scope_delta_proj_ms": ("ms", "linear attention",
                            ("kda_proj", "gdn_proj")),
    "scope_head_ms": ("ms", "ops", ("lm_head", "embed", "embed_sparse")),
    "scope_glue_ms": ("ms", "ops", ("block_norm", "residual", "cast")),
    "scope_generic_share.tok": ("%", "device", None),
}

TABLE = {
    "fusion.1": "mlp.l0", "fusion.2": "mlp.l3", "fusion.3": "mtp.mlp",
    "fusion.4": "moe_share.l1", "fusion.5": "attn_proj.l0",
    "fusion.6": "attn_gate.l0", "fusion.7": "kda_proj.l1",
    "fusion.8": "gdn_proj.l0", "fusion.9": "lm_head", "fusion.10": "embed",
    "fusion.11": "embed_sparse.embed_weight", "fusion.12": "block_norm.l0",
    "fusion.13": "residual.l0", "fusion.14": "cast.params",
    "fusion.15": "fullyconnected.l1_moe_gate", "fusion.16": "concat.concat0",
    "fusion.17": "loop", "while.18": "loop",
    "conditional.19": "_moe_share_ffn.l1_moe_share", "call.20": "mlp.l0",
    "ragged-dot.21": "moe_experts", "fusion.22": "attn.l0",
    "never_ran.23": "mlp.l9"}
OPS = {
    "fusion.1 fusion bf16[4,8]": 0.010, "fusion.2 fusion bf16[4,8]": 0.006,
    "fusion.3 fusion bf16[4,8]": 0.050, "fusion.4 fusion f32[8]": 0.002,
    "fusion.5 fusion f32[8]": 0.008, "fusion.6 fusion f32[8]": 0.004,
    "fusion.7 fusion f32[8]": 0.012, "fusion.8 fusion f32[8]": 0.014,
    "fusion.9 fusion f32[9,8]": 0.020, "fusion.10 fusion f32[9,8]": 0.002,
    "fusion.11 fusion f32[9,8]": 0.004, "fusion.12 fusion f32[8]": 0.003,
    "fusion.13 fusion f32[8]": 0.001, "fusion.14 fusion bf16[8]": 0.006,
    "fusion.15 fusion f32[8,4]": 0.005, "fusion.16 fusion f32[2]": 0.001,
    "fusion.17 fusion s32[]": 0.002, "while.18 while bf16[4,8]": 0.400,
    "conditional.19 conditional bf16[4,8]": 0.030,
    "call.20 call bf16[4,8]": 0.016, "ragged-dot.21 custom-call": 0.040,
    "fusion.22 fusion bf16[4,8]": 0.060, "copy.24 copy f32[8,8]": 0.007,
    "fusion.25": 0.003}
STEPS = 2
SORTS = {"loop": "enclosing", "moe_experts": "adopted",
         "fullyconnected.l1_moe_gate": "generic",
         "concat.concat0": "generic",
         "_moe_share_ffn.l1_moe_share": "generic"}


def _sort_of(scope):
    return SORTS.get(scope, "declared" if scope in TABLE.values() else None)


def _obs(ops=OPS, busy_s=None):
    busy = sum(ops.values()) if busy_s is None else busy_s
    return {"driver": "train_fit", "trace": {
        "steps": STEPS, "op_seconds": dict(ops), "busy_s": busy,
        "per_device": {"/device:TPU:1": {"busy_s": 99.0},
                       "/device:TPU:0": {"busy_s": busy}}}}


def _reader(entry):
    return manifest.load_module("layer_metrics", entry.split(".")[0])


@pytest.fixture
def hand_built(monkeypatch):
    monkeypatch.setattr(scope_seconds, "program_table", lambda: TABLE)
    monkeypatch.setattr(scope_parts, "sort_function", lambda: _sort_of)


def test_a_wrapper_is_told_by_the_second_word_of_its_key():
    assert scope_parts.is_wrapper("while.18 while bf16[4,8]")
    assert scope_parts.is_wrapper("conditional.19 conditional")
    assert scope_parts.is_wrapper("call.20 call bf16[4,8]")
    # an instruction named like one, and one the profiler gave no line of
    assert not scope_parts.is_wrapper("while.18 fusion bf16[4,8]")
    assert not scope_parts.is_wrapper("custom-call.3 custom-call f32[8]")
    assert not scope_parts.is_wrapper("while.18")


@pytest.mark.parametrize("entry,ms,by_kind,wrapper_ms", [
    # both layers' scopes, not the prediction module's (kind ``mtp``),
    # and not the ``call`` under ``mlp.l0``, which it reports
    ("scope_mlp_ms", 8.0, None, 8.0),
    ("scope_moe_share_ms", 1.0, None, 0.0),
    ("scope_attn_proj_ms", 6.0, {"attn_proj": 4.0, "attn_gate": 2.0}, 0.0),
    ("scope_delta_proj_ms", 13.0, {"kda_proj": 6.0, "gdn_proj": 7.0}, 0.0),
    ("scope_head_ms", 13.0,
     {"lm_head": 10.0, "embed": 1.0, "embed_sparse": 2.0}, 0.0),
    ("scope_glue_ms", 5.0,
     {"block_norm": 1.5, "residual": 0.5, "cast": 3.0}, 0.0),
])
def test_a_reader_sums_its_kinds_and_leaves_the_wrappers_out(
        hand_built, entry, ms, by_kind, wrapper_ms):
    reader = _reader(entry)
    assert tuple(reader.KINDS) == ENTRIES[entry][2]
    value, extra = reader.read(_obs())
    assert value == pytest.approx(ms)
    assert extra["steps"] == STEPS
    assert extra["wrapper_ms"] == pytest.approx(wrapper_ms)
    if by_kind is None:
        assert "by_kind" not in extra
    else:
        assert extra["by_kind"] == pytest.approx(by_kind)
        assert list(extra["by_kind"]) == list(reader.KINDS)


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_a_reader_gives_zero_with_a_table_and_nothing_without(
        monkeypatch, entry):
    reader = _reader(entry)
    # a step with a table and none of the kinds (and no generic scope)
    monkeypatch.setattr(scope_seconds, "program_table",
                        lambda: {"fusion.22": "attn.l0"})
    monkeypatch.setattr(scope_parts, "sort_function", lambda: _sort_of)
    value, extra = reader.read(_obs())
    assert value == 0.0 and extra["steps"] == STEPS
    # no trace, no whole step, a program that gives no table
    assert reader.read({"driver": "train_fit", "trace": None}) is None
    assert reader.read({"driver": "train_fit"}) is None
    no_step = _obs()
    no_step["trace"]["steps"] = 0
    assert reader.read(no_step) is None
    monkeypatch.setattr(scope_seconds, "program_table", lambda: None)
    assert reader.read(_obs()) is None


def test_the_generic_share_splits_the_step_by_sort(hand_built, monkeypatch):
    reader = _reader("scope_generic_share.tok")
    total = sum(OPS.values())
    value, extra = reader.read(_obs())
    # the gate's projection and the concat; not the conditional
    assert value == pytest.approx(100 * 0.006 / total)
    assert extra["by_kind"] == pytest.approx(
        {"fullyconnected": 2.5, "concat": 0.5})
    assert list(extra["by_kind"]) == ["fullyconnected", "concat"]
    assert extra["kinds"] == 2 and extra["steps"] == STEPS
    assert extra["generic_ms"] == pytest.approx(3.0)
    assert extra["enclosing_ms"] == pytest.approx(1.0)        # not the while
    assert extra["wrapper_ms"] == pytest.approx(223.0)
    assert extra["wrapper_by_kind"] == pytest.approx(
        {"loop": 200.0, "_moe_share_ffn": 15.0, "mlp": 8.0})
    assert list(extra["wrapper_by_kind"]) == ["loop", "_moe_share_ffn", "mlp"]
    assert extra["unnamed_ms"] == pytest.approx(5.0)
    assert extra["busy_ms"] == pytest.approx(1e3 * total / STEPS)
    # whole: every operation under one of five heads
    assert extra["named_ms"] + extra["generic_ms"] + extra["enclosing_ms"] \
        + extra["unnamed_ms"] + extra["wrapper_ms"] \
        == pytest.approx(extra["ops_ms"]) == pytest.approx(extra["busy_ms"])
    # and the named time is the kind readers' and the older readers'
    named = sum(_reader(e).read(_obs())[0] for e in ENTRIES
                if ENTRIES[e][2])
    older = 1e3 * (0.050 + 0.040 + 0.060) / STEPS      # mtp, experts, attn
    assert named + older == pytest.approx(extra["named_ms"])
    # a share of the busy time, which overlapping operations do not fill
    value, extra = reader.read(_obs(busy_s=0.06))
    assert value == pytest.approx(10.0)
    assert reader.read(_obs(busy_s=0.0)) is None
    # only the eight largest kinds ride the line
    many = {"fusion.%d" % i: "kind%d.n" % i for i in range(100, 112)}
    monkeypatch.setattr(scope_seconds, "program_table", lambda: many)
    monkeypatch.setattr(scope_parts, "sort_function",
                        lambda: lambda scope: "generic")
    ops = {"%s fusion f32[8]" % k: 0.001 * (i + 1)
           for i, k in enumerate(many)}
    _, extra = reader.read(_obs(ops))
    assert extra["kinds"] == 12
    assert list(extra["by_kind"]) == ["kind%d" % i
                                      for i in range(111, 103, -1)]
    # an older program has no ``sort_of``: nothing, and no error
    monkeypatch.setattr(scope_parts, "sort_function", lambda: None)
    assert reader.read(_obs()) is None


def test_the_programs_own_sort_of_is_what_the_share_asks():
    from mxnet_tpu.trace import scopes
    assert scope_parts.sort_function() is scopes.sort_of


def check_the_block_parts_entries(doc):
    """``doc`` holds the seven entries as their readers have them, each
    over the eleven one-chip LM cells and no other cell the benchmark
    had.  By name and by membership, never by a position or a length."""
    had = {w["name"] for w in doc["workloads"]} & {
        "resnet50-synthetic-b128", "resnet50-dp4-b512",
        "ptb-lstm-bucketed-b1024"}
    assert len(had) == 3
    for name, (unit, layer, _) in ENTRIES.items():
        entries = [m for m in doc["per_layer"] if m["name"] == name]
        assert len(entries) == 1, name
        entry = dict(entries[0])
        listed = entry.pop("workloads")
        assert set(LM_CELLS) <= set(listed) and not had & set(listed), name
        reader = _reader(name)
        assert entry == {"name": name, "unit": reader.UNIT,
                         "better": reader.BETTER, "source": reader.SOURCE,
                         "layer": reader.LAYER, "moves": TOK}
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) \
            == (unit, "lower", "device_trace", layer)
        assert reader.DRIVERS == ("train_fit",)


def test_the_real_manifest_holds_the_block_parts_entries():
    doc = manifest.Manifest().doc
    check_the_block_parts_entries(doc)
    # as they came: the eleven cells, in the benchmark's order
    for name in ENTRIES:
        entry = next(m for m in doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == LM_CELLS
