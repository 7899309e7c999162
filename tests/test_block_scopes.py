"""The rest of a decoder block has its name on the device (ISSUE 69,
tier-1): every LM builder of ``mxnet_tpu/models`` at toy sizes, through the
fused step, read from the program's own table of it
(``mx.trace.program_scopes``).  A builder that leaves a projection, a norm,
a sum, an embedding or a convolution under the executor's generic scope
fails here, unless the node is listed below with the reason it stays.
Scopes are debug info: the step's operations are the ones they were."""
import json
import re
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.trace import scopes

from test_decoder_symbols import (AFMOE, GLM, KEYE, KIMI, LFM2, NEMOTRON,
                                  OLMOE, OURO, QWEN3_NEXT, SDAR, SMALLTHINKER,
                                  _unscoped)

GRANITE = dict(num_layers=3, hidden_size=32,
               layer_types=["mamba", "attention", "mamba"], ssm_heads=4,
               ssm_head_dim=8, ssm_state=12, ssm_groups=1, conv_kernel=4,
               num_heads=4, num_kv_heads=2, head_dim=8, mlp_width=48,
               vocab_size=50, seq_len=24, embedding_multiplier=12.0,
               residual_multiplier=0.22, attention_multiplier=0.125,
               logits_scaling=8.0, rms_eps=1e-5)
BATCH = 2
# the generic kinds a block's plain parts read under before they had names
GENERIC_KINDS = {"fullyconnected", "rmsnorm", "embedding", "_plus",
                 "elementwisesum", "causalconv1d", "_moe_share_ffn"}
# what may stay under one of them, by name, each with its reason
STAYS_GENERIC = [
    # the router's logits: the node feeds ``_moe_dispatch``, whose
    # ``moe_route`` is ``scope_moe_layout_ms``'s; that entry is the
    # benchmark's, and reads what it did
    re.compile(r"^fullyconnected\.l\d+_moe_gate$"),
]
# a rank's share under a row bound: the ``conditional`` is traced under its
# node's scope, and no declared scope may stand around it (the outermost
# wins, and the second pass's parts keep theirs)
STAYS_UNDER_A_BOUND = STAYS_GENERIC + [
    re.compile(r"^_moe_share_ffn\.l\d+_moe_share$")]
EVERY_BLOCK = {"block_norm", "residual", "lm_head", "embed"}

# builder -> (its arguments, the new kinds its step must hold beside
# EVERY_BLOCK's)
BUILDERS = {
    "olmoe_lm": (OLMOE, {"attn_proj"}),
    "kimi_linear_lm": (KIMI, {"kda_proj", "mlp", "attn_proj"}),
    "glm_moe_lite_lm": (GLM, {"mlp", "attn_proj"}),
    "sdar_moe_lm": (SDAR, {"attn_proj"}),
    "afmoe_lm": (AFMOE, {"mlp", "attn_proj", "attn_gate"}),
    "smallthinker_lm": (SMALLTHINKER, {"attn_proj"}),
    "qwen3_next_lm": (QWEN3_NEXT, {"gdn_proj", "mlp", "attn_gate"}),
    "ouro_lm": (OURO, {"mlp", "attn_proj"}),
    "keye_lm": (KEYE, {"attn_proj"}),
    "lfm2_moe_lm": (LFM2, {"mlp", "attn_proj"}),
    "granite_hybrid_lm": (GRANITE, {"mlp", "attn_proj"}),
    # one-branch layers: ``mlp`` is the shared expert's
    "nemotron_h_lm": (NEMOTRON, {"mlp", "attn_proj", "ssm_proj", "ssm_norm"}),
}


def _symbol(builder, **over):
    with mx.name.NameManager():
        return getattr(models, builder)(**dict(BUILDERS[builder][0], **over))


def _step(net, seq_len, vocab, doubled=False):
    """One fused training step of ``net`` on the CPU -> its module."""
    data = (BATCH, 2 * seq_len) if doubled else (BATCH, seq_len)
    label = (BATCH, 2, seq_len) if doubled else (BATCH, seq_len)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", data)],
             label_shapes=[("softmax_label", label)])
    mod.init_params(mx.init.Normal(0.02))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    rng = np.random.RandomState(0)
    mod.forward_backward(mx.io.DataBatch(
        data=[mx.nd.array(rng.randint(0, vocab, data).astype("float32"))],
        label=[mx.nd.array(rng.randint(0, vocab, label).astype("float32"))],
        pad=0))
    mod.update()
    assert mod._fused is not None
    return mod


def _stepped(builder, **over):
    kwargs = dict(BUILDERS[builder][0], **over)
    return _step(_symbol(builder, **over), kwargs["seq_len"],
                 kwargs["vocab_size"], doubled=builder == "sdar_moe_lm")


def _left_generic(table, allowed):
    """The scopes of ``table`` of a generic sort and one of
    GENERIC_KINDS that no pattern of ``allowed`` lists."""
    return sorted({
        s for s in table.values()
        if scopes.sort_of(s) == "generic"
        and scopes.kind_of(s) in GENERIC_KINDS
        and not any(p.match(s) for p in allowed)})


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_no_plain_part_of_a_block_is_left_generic(builder):
    _stepped(builder)
    table = mx.trace.program_scopes("fused:step")
    names = mx.trace.program_op_names("fused:step")
    assert _left_generic(table, STAYS_GENERIC) == []
    kinds = {scopes.kind_of(s) for s in table.values()
             if scopes.sort_of(s) == "declared"}
    assert EVERY_BLOCK | BUILDERS[builder][1] <= kinds
    # the loss node's operations are the loss's: the head's scope is not
    # around the node (the outermost declared scope would win)
    of_the_loss = [i for i, n in names.items() if "softmaxceloss." in n]
    assert of_the_loss
    assert {table[i] for i in of_the_loss} <= {"lm_loss", "mtp.lm_loss"}
    # every block has its index behind the kind
    layers = BUILDERS[builder][0]["num_layers"]
    first = 1 if builder == "kimi_linear_lm" else 0
    assert {"block_norm.l%d" % l for l in range(first, first + layers)} \
        <= set(table.values())


def test_a_prediction_modules_parts_keep_the_prefix_they_had():
    """``mtp.`` stays a prefix: the module's shared expert is ``mtp.mlp``,
    kind ``mtp``, its head ``mtp.lm_head`` and its second sum
    ``mtp.residual``; what the module made outside its prefix (its block
    norms, its first sum, its output projection) is named without one, so
    the kind ``mtp`` holds the operations it held."""
    _stepped("glm_moe_lite_lm")
    found = set(mx.trace.program_scopes("fused:step").values())
    assert {"mtp.mlp", "mlp.l0", "mlp.l1", "mlp.l2", "mtp.eh_proj",
            "mtp.attn", "mtp.mla_q", "mtp.moe_experts", "mtp.lm_head",
            "mtp.lm_loss", "mtp.residual"} <= found
    assert scopes.sort_of("mtp.mlp") == "declared"
    assert scopes.kind_of("mtp.mlp") == "mtp"
    # the trunk's embedding, not the module's second use of the table
    assert "embed" in found and "mtp.embed" not in found
    assert {"block_norm", "residual", "attn_proj"} <= found
    assert not {"mtp.block_norm", "mtp.attn_proj"} & found
    # under the prefix the router's logits alone stay generic
    assert {s for s in found if s.startswith("mtp.")
            and scopes.sort_of(s) == "generic"} \
        == {"mtp.fullyconnected.mtp_moe_gate"}


def test_a_builders_own_sum_scope_wins_over_residual():
    """SDAR's first sum lies in its ``o_proj``'s scope, the builder's
    choice through ``sum_scopes``; the second takes the skeleton's."""
    doc = json.loads(_symbol("sdar_moe_lm").tojson())
    sums = [n["attr"]["__scope__"] for n in doc["nodes"]
            if n["op"] == "_plus"]
    assert sums == ["attn_proj.l0", "residual.l0", "attn_proj.l1",
                    "residual.l1"]


def test_a_row_bounds_own_operations_are_moe_share_and_no_parts(
        monkeypatch):
    """Under a row bound ``_moe_share_ffn`` declares ``moe_share`` around
    the bound's test and the sums, never around a part or the ``cond``:
    both passes' gather, experts and combine keep their scopes."""
    # the module, not the function ``mxnet_tpu.moe`` exports under its name
    share_rule = sys.modules["mxnet_tpu.moe.dispatch"]
    monkeypatch.setattr(share_rule, "BOUND_WORTH_ROWS", 0)
    _stepped("sdar_moe_lm", seq_len=72, experts_held=2, vocab_size=40)
    table = mx.trace.program_scopes("fused:step")
    names = mx.trace.program_op_names("fused:step")
    assert _left_generic(table, STAYS_UNDER_A_BOUND) == []
    assert {"moe_share.l0", "moe_share.l1"} <= set(table.values())
    parts = ("moe_route.l", "moe_experts.l", "moe_combine.l")
    for instruction, scope in table.items():
        if scopes.kind_of(scope) == "moe_share":
            assert not any(p in names[instruction] for p in parts), \
                names[instruction]
    # the second pass, inside the conditional's branch, under its parts
    behind = {scopes.kind_of(table[i]) for i, n in names.items()
              if "/cond/" in n and i in table}
    assert {"moe_route", "moe_experts", "moe_combine"} <= behind
    assert "moe_share" not in behind


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_FRAMES = re.compile(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                     r"\n(?:.+\n)*\n", re.M)


def _bare(hlo_text):
    """Optimized HLO less what a scope can touch: every instruction's
    metadata with the module's tables of the stack frames it points
    into, and the scheme in the module's name."""
    return re.sub(r"step_s\d+", "step",
                  _FRAMES.sub("", _METADATA.sub("", hlo_text)))


def test_scopes_and_their_scheme_leave_the_steps_operations_alone(
        monkeypatch):
    """The optimized step less its metadata is one text whatever the
    scheme, and whether the symbol carries the builders' scopes or none:
    a scope is debug info, and the scheme a part of the module's name."""
    def step_text(net):
        return _step(net, KIMI["seq_len"], KIMI["vocab_size"]) \
            ._fused._step.optimized_hlo()

    scoped = _symbol("kimi_linear_lm", num_layers=4)
    text = step_text(scoped)
    assert text.startswith("HloModule jit_step_s%d," % scopes.SCHEME)
    assert "kda_proj.l1" in text and "block_norm.l4" in text
    monkeypatch.setattr(scopes, "SCHEME", 1)
    was = step_text(scoped)
    assert was.startswith("HloModule jit_step_s1,") and was != text
    assert _bare(was) == _bare(text)
    # a scheme of its own: under the scoped step's module name JAX's
    # persistent cache would serve that step's executable, names and all
    monkeypatch.setattr(scopes, "SCHEME", 0)
    unscoped = step_text(mx.sym.load_json(_unscoped(scoped.tojson())))
    assert "kda_proj.l1" not in unscoped
    assert "rmsnorm.l1_mixer_norm" in unscoped
    assert _bare(unscoped) == _bare(text)
