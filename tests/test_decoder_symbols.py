"""The decoder builders' symbols, node for node (ISSUE 46, tier-1).

The LM builders of ``mxnet_tpu/models`` (five then, ten now) are
assembled from one skeleton, ``models/decoder.py``.  What holds that assembly still is the
graph each builder returns: under a fresh ``NameManager`` the symbol's
JSON (every node's op, name, keywords, attributes and inputs, the unnamed
nodes numbered in the order they were made) hashes to what the commit
before the skeleton (945e6c8, each builder its own trunk) gave for the
same arguments.  Same symbol -> same graph program -> same lowered step.

Every hash here was taken at that commit by this file's own ``_build``:
the five cells' ``model.kwargs`` (``benchmark/configs/*.json``) and a
tiny-width case for each branch a builder has.  Beside them the lowered
text of a tiny step of the three builders whose step no other test holds
(OLMoE's is in ``tests/test_sdar_moe.py``, SDAR's in
``tests/test_afmoe.py``).  A new branch takes a new case, its hash from
the commit that adds it; a hash is never re-taken to make a refactor
pass.  ISSUE 53 meant to move two builders' graphs and took theirs
again (every ``kimi*`` and ``qwen3-next*`` symbol and the two steps:
``CausalConv1D`` carries its SiLU, and Qwen3-Next's reads the fused
projection where it lies), and so did ISSUE 68 (the same symbols and
steps: the mixers' output stage is the one node ``GatedRMSNorm`` on the
rows as the rule writes them, where ``RMSNorm``, ``Activation`` and a
product stood between three ``Reshape``s); every other builder's
stood.  ISSUE 70 meant to move six builders' graphs (SDAR, AFMoE,
SmallThinker, Ouro, Keye, LFM2: between a q or k projection and the
core op stands ONE ``HeadNormRotary`` on the rows and a ``Reshape``,
where ``Reshape``, ``RMSNorm`` and ``RotaryEmbedding`` stood) and took
their hashes again in both tables and the AFMoE and SmallThinker steps;
OLMoE's, Kimi's, GLM's and Qwen3-Next's symbols and steps and latent
attention's stood, and ``SIGNATURE_WAS`` holds every symbol's
arguments, outputs and states to the parent's, name for name and shape
for shape.  ISSUE 69 moved every symbol's ``__scope__`` attributes and
nothing else (the skeleton names the rest of a block for the device
trace: ``mlp``, ``block_norm``, ``residual``, ``lm_head``, ``embed``,
...): all the symbol hashes were taken again, and ``UNSCOPED_WAS`` holds
that the op nodes, their order, their keywords and their inputs are the
parent's: each graph less its ``__scope__`` attributes hashes to what
the parent's (cc9ea8d) did, by this file's own ``_unscoped``.  The
lowered steps carry no scope and stand."""
import hashlib
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import symbol as sym
from mxnet_tpu.executor import _GraphProgram
from mxnet_tpu.models import decoder
from mxnet_tpu.models.latent_attention import latent_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))

from symbol_signature import signature                    # noqa: E402

OLMOE = dict(num_layers=2, hidden_size=32, num_heads=2, num_experts=8,
             experts_per_tok=2, expert_width=16, vocab_size=64, seq_len=16)
KIMI = dict(num_layers=5, hidden_size=32, full_attn_layers=[4, 8],
            dense_layers=1, kda_heads=2, kda_head_dim=8, conv_kernel=4,
            mla_heads=2, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
            v_head_dim=8, dense_width=64, num_experts=16, experts_per_tok=4,
            expert_width=24, shared_width=24, routed_scale=2.446,
            vocab_size=50, seq_len=72, experts_held=4, first_expert=4,
            bias_rate=1e-3, rms_eps=1e-5)
GLM = dict(num_layers=3, hidden_size=32, dense_layers=1, heads=2,
           q_lora_rank=12, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
           v_head_dim=12, rope_theta=1e6, dense_width=64, num_experts=16,
           experts_per_tok=4, expert_width=24, shared_width=24,
           routed_scale=1.8, vocab_size=50, seq_len=24, nextn_layers=1,
           mtp_weight=0.3, experts_held=4, first_expert=4, bias_rate=1e-3,
           rms_eps=1e-5)
SDAR = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, num_experts=16, experts_per_tok=4, expert_width=24,
            vocab_size=50, seq_len=16, block_len=4, rope_theta=1e6,
            rms_eps=1e-6, aux_coef=0.001, experts_held=4, first_expert=4)
AFMOE = dict(num_layers=4, hidden_size=32,
             layer_types=["sliding", "sliding", "sliding", "full"],
             dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
             window=6, rope_theta=1e4, dense_width=48, num_experts=16,
             experts_per_tok=4, expert_width=24, shared_width=24,
             route_scale=2.826, vocab_size=50, seq_len=16,
             embed_scale=32 ** 0.5, experts_held=4, first_expert=4,
             bias_rate=1e-3, rms_eps=1e-5)
SMALLTHINKER = dict(num_layers=4, hidden_size=32,
                    layer_types=["full", "sliding", "sliding", "sliding"],
                    num_heads=6, num_kv_heads=2, head_dim=8, window=6,
                    rope_theta=1.5e6, num_experts=16, experts_per_tok=3,
                    expert_width=24, vocab_size=50, seq_len=16,
                    experts_held=4, first_expert=4, rms_eps=1e-6)
QWEN3_NEXT = dict(num_layers=4, hidden_size=32, full_attention_interval=4,
                  gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=8,
                  conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=16,
                  rotary_dim=4, rope_theta=1e7, num_experts=16,
                  experts_per_tok=4, expert_width=24, shared_width=24,
                  vocab_size=50, seq_len=24, rms_eps=1e-6, aux_coef=0.001,
                  experts_held=4, first_expert=4)
OURO = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=4,
            head_dim=8, mlp_width=48, vocab_size=50, seq_len=16,
            total_ut_steps=4, rope_theta=1e6, rms_eps=1e-6, exit_beta=0.1)
KEYE = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, index_heads=2, index_dim=4, topk=8, num_experts=16,
            experts_per_tok=4, expert_width=24, vocab_size=50, seq_len=16,
            mrope_sections=(1, 1, 2), rope_theta=1e7, rms_eps=1e-6,
            aux_coef=0.001, experts_held=4, first_expert=4)
LFM2 = dict(num_layers=5, hidden_size=32,
            layer_types=["conv", "full_attention", "conv", "conv", "conv"],
            dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
            conv_kernel=3, rope_theta=1e6, dense_width=48, num_experts=16,
            experts_per_tok=4, expert_width=24, vocab_size=50, seq_len=16,
            route_scale=1.0, experts_held=4, first_expert=4, bias_rate=1e-3,
            rms_eps=1e-5)
GRANITE = dict(num_layers=3, hidden_size=32,
               layer_types=["mamba", "attention", "mamba"], ssm_heads=4,
               ssm_head_dim=8, ssm_state=12, ssm_groups=1, conv_kernel=4,
               num_heads=4, num_kv_heads=2, head_dim=8, mlp_width=48,
               vocab_size=50, seq_len=24, embedding_multiplier=12.0,
               residual_multiplier=0.22, attention_multiplier=0.125,
               logits_scaling=8.0, rms_eps=1e-5)
NEMOTRON = dict(num_layers=5, hidden_size=32,
                layer_types=["mamba", "moe", "attention", "moe", "mamba"],
                ssm_heads=4, ssm_head_dim=8, ssm_state=12, ssm_groups=2,
                conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=8,
                num_experts=8, experts_per_tok=3, expert_width=24,
                shared_width=40, route_scale=2.5, vocab_size=50, seq_len=24,
                rms_eps=1e-5, bias_rate=1e-3, experts_held=4, first_expert=0)
WHOLE = dict(experts_held=0, first_expert=0)
# latent attention by itself: seq_len, hidden_size, heads, kv_lora_rank,
# qk_nope_dim, qk_rope_dim, v_head_dim, rms_eps
MLA = (40, 32, 2, 16, 8, 4, 12, 1e-5)


def _cell(config):
    """A cell's own builder and arguments, as ``benchmark/`` reads them."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        model = json.load(f)["model"]
    module, name = model["builder"].rsplit(".", 1)
    return getattr(importlib.import_module(module), name), model["kwargs"]


def _tiny(builder, base, **over):
    return getattr(models, builder), dict(base, **over)


def _mla(**kwargs):
    return (lambda: latent_attention(sym.Variable("h"), "l4_", *MLA,
                                     layer=4, **kwargs)), {}


# id -> (builder, its arguments)
SYMBOLS = {
    # the cells' configurations: the hashes of ISSUE 46's table
    "olmoe-1b-7b": _cell("olmoe-1b-7b"),
    "kimi-linear-48b-a3b": _cell("kimi-linear-48b-a3b"),
    "glm-4.7-flash": _cell("glm-4.7-flash"),
    "sdar-30b-a3b": _cell("sdar-30b-a3b"),
    "trinity-mini": _cell("trinity-mini"),
    # the sixth builder, from the commit that added it (ISSUE 47): its
    # cell, a rank's share with and without the counter's head, the whole
    # layer
    "smallthinker-21b-a3b": _cell("smallthinker-21b-a3b"),
    "smallthinker-share": _tiny("smallthinker_lm", SMALLTHINKER),
    "smallthinker-counted": _tiny("smallthinker_lm", SMALLTHINKER,
                                  act_zeros=True),
    "smallthinker-whole": _tiny("smallthinker_lm", SMALLTHINKER, **WHOLE),
    # the seventh builder, from the commit that added it (ISSUE 50): its
    # cell, a rank's share, the whole layer, no balance heads, every lane
    # rotated, attention in every second layer
    "qwen3-next-80b-a3b": _cell("qwen3-next-80b-a3b"),
    "qwen3-next-share": _tiny("qwen3_next_lm", QWEN3_NEXT),
    "qwen3-next-whole": _tiny("qwen3_next_lm", QWEN3_NEXT, **WHOLE),
    "qwen3-next-aux-0": _tiny("qwen3_next_lm", QWEN3_NEXT, aux_coef=0.0),
    "qwen3-next-all-rotated": _tiny("qwen3_next_lm", QWEN3_NEXT,
                                    rotary_dim=16),
    "qwen3-next-every-second": _tiny("qwen3_next_lm", QWEN3_NEXT,
                                     full_attention_interval=2),
    # the eighth builder, from the commit that added it (ISSUE 54): its
    # cell, four passes and one as ONE loop node (the body rides the
    # node's ``body`` parameter, so the hash holds the body too), the body
    # kept and not formed again, fewer key/value heads, the embedding's
    # own initializer
    "ouro-2.6b": _cell("ouro-2.6b"),
    "ouro-tiny": _tiny("ouro_lm", OURO),
    "ouro-one-pass": _tiny("ouro_lm", OURO, total_ut_steps=1),
    "ouro-kept": _tiny("ouro_lm", OURO, recompute=False),
    "ouro-grouped": _tiny("ouro_lm", OURO, num_kv_heads=2),
    "ouro-wide-embedding": _tiny("ouro_lm", OURO, embed_sigma=4.0),
    # the ninth builder, from the commit that added it (ISSUE 57): its
    # cell, a rank's share, the whole layer, no balance heads, a
    # positions input, the embedding's own initializer (the expert layers
    # are marked for recomputation in every one)
    "keye-vl-2.0-30b-a3b": _cell("keye-vl-2.0-30b-a3b"),
    "keye-share": _tiny("keye_lm", KEYE),
    "keye-whole": _tiny("keye_lm", KEYE, **WHOLE),
    "keye-aux-0": _tiny("keye_lm", KEYE, aux_coef=0.0),
    "keye-positions": _tiny("keye_lm", KEYE, positions=True),
    "keye-wide-embedding": _tiny("keye_lm", KEYE, embed_sigma=1.0),
    # the tenth builder, from the commit that added it (ISSUE 61): its
    # cell, a rank's share, the whole layer, attention first and in every
    # second layer with no dense lead, the published two dense layers in
    # front, the last rank's share
    "lfm2-8b-a1b": _cell("lfm2-8b-a1b"),
    "lfm2-share": _tiny("lfm2_moe_lm", LFM2),
    "lfm2-whole": _tiny("lfm2_moe_lm", LFM2, **WHOLE),
    "lfm2-every-second": _tiny(
        "lfm2_moe_lm", LFM2, dense_layers=0,
        layer_types=["full_attention", "conv"] * 2 + ["full_attention"]),
    "lfm2-two-dense": _tiny("lfm2_moe_lm", LFM2, dense_layers=2),
    "lfm2-last-rank": _tiny("lfm2_moe_lm", LFM2, first_expert=12),
    # the eleventh builder (ISSUE 67), taken at 47367aa, the parent of
    # ISSUE 70, whose ``gqa_attention`` its attention layers must pass
    # untouched (no head norms, no positions: no node): its cell, a tiny
    # one
    "granite-4.0-h-micro": _cell("granite-4.0-h-micro"),
    "granite-tiny": _tiny("granite_hybrid_lm", GRANITE),
    # the twelfth builder, from the commit that added it (ISSUE 71):
    # one-branch layers; its cell, a rank's share, the whole layer
    "nemotron-3-nano-30b-a3b": _cell("nemotron-3-nano-30b-a3b"),
    "nemotron-share": _tiny("nemotron_h_lm", NEMOTRON),
    "nemotron-whole": _tiny("nemotron_h_lm", NEMOTRON, **WHOLE),
    # OLMoE: its load-balance heads stay on at coefficient 0
    "olmoe-tiny": _tiny("olmoe_lm", OLMOE),
    "olmoe-aux-0": _tiny("olmoe_lm", OLMOE, aux_coef=0.0),
    # Kimi: a rank's share and the whole layer; which layers attend in
    # full and which are dense, counted from 1
    "kimi-share": _tiny("kimi_linear_lm", KIMI),
    "kimi-whole": _tiny("kimi_linear_lm", KIMI, **WHOLE),
    "kimi-mixed": _tiny("kimi_linear_lm", KIMI, full_attn_layers=[1, 3, 5],
                        dense_layers=3),
    # GLM: with and without its prediction module and query compression
    "glm-share": _tiny("glm_moe_lite_lm", GLM),
    "glm-whole": _tiny("glm_moe_lite_lm", GLM, **WHOLE),
    "glm-no-mtp": _tiny("glm_moe_lite_lm", GLM, nextn_layers=0),
    "glm-plain-q": _tiny("glm_moe_lite_lm", GLM, q_lora_rank=0),
    # SDAR: its load-balance heads go at coefficient 0
    "sdar-share": _tiny("sdar_moe_lm", SDAR),
    "sdar-whole": _tiny("sdar_moe_lm", SDAR, **WHOLE),
    "sdar-aux-0": _tiny("sdar_moe_lm", SDAR, aux_coef=0.0),
    # AFMoE: the embedding's scale, each kind of layer alone, the dense
    # layers
    "afmoe-share": _tiny("afmoe_lm", AFMOE),
    "afmoe-whole": _tiny("afmoe_lm", AFMOE, **WHOLE),
    "afmoe-unscaled": _tiny("afmoe_lm", AFMOE, embed_scale=1.0),
    "afmoe-sliding": _tiny("afmoe_lm", AFMOE, layer_types=["sliding"] * 4),
    "afmoe-full": _tiny("afmoe_lm", AFMOE, layer_types=["full"] * 4),
    "afmoe-no-dense": _tiny("afmoe_lm", AFMOE, dense_layers=0),
    "afmoe-all-dense": _tiny("afmoe_lm", AFMOE, dense_layers=4),
    # latent attention by itself: no scope attribute at all, and the
    # compressed, rotated form under a prediction module's prefix
    "mla-unscoped": _mla(),
    "mla-mtp": _mla(q_lora_rank=12, rope_theta=1e6, scope="mtp."),
}

# sha256 of each symbol's JSON, all taken again at ISSUE 69 (the scopes
# moved, ``UNSCOPED_WAS`` holds the rest), the six builders' of ISSUE 70
# at that issue; first the five cells of ISSUE
# 46's table, the four behind them the sixth builder's
SYMBOL_WAS = {
    # the twelfth builder's, at the commit that added it (ISSUE 71)
    "nemotron-3-nano-30b-a3b":
        "035bca8d3252dec3e47e76eca065c0a4126c25bafc6b2b06470e5c82ccbe8e3e",
    "nemotron-share":
        "7ca4333e3df14197b537eccdadf201faa56f2b166e1ceb68a905def537b5f364",
    "nemotron-whole":
        "206931b10d7727090418ea56ee0008c52dbc3d131d8f3b2c7ec185dcd9ca49c0",
    "granite-4.0-h-micro":
        "4ac25b1380f9eac9c32b04777c960ad89be7dda40738cf164ff1c9e476d6df00",
    "granite-tiny":
        "b7e713e51828655c8be72197d106eb1e61c6f05609d5b768c6071fb2ffe85651",
    "olmoe-1b-7b":
        "292ca4805a7d2c60c90cfa7c66f804e6494628cd78e82f8e42cedc598fd161c8",
    "kimi-linear-48b-a3b":
        "31e18ea5af2aacdb914cafed3b52c3e6a40acf02f1c47201a2a062601d3172e6",
    "glm-4.7-flash":
        "a8c96d68fb7e7165301540a48986d2f38c6bd94010b92b18b75894be1df75bb0",
    "sdar-30b-a3b":
        "44229e83914ed6403e08dcb8baaacd7e853805194c53162f2bcd96572366dbc1",
    "trinity-mini":
        "b6ebc1a0d763e4a30ebffa7ae8cb7df8280ea4a13c68f0a815c8ff595ebd913f",
    # taken at the commit that added the builder (ISSUE 47)
    "smallthinker-21b-a3b":
        "9b47f58acb085188a87a000aad880a947485c9eceffa4e80fda7fcb6544c451f",
    "smallthinker-share":
        "619e67e92687b6165d60b2ba05097e8ca075b13109046a2acfa710d9808e25cf",
    "smallthinker-counted":
        "1a4dafdd0e73031746de555bed095725c0b41abd060763be2129fdbbb9215f98",
    "smallthinker-whole":
        "390e62aac7323a14a90ac984c1d139bbbe613df19b18b68069539cc9601f7bae",
    # taken at the commit that added the builder (ISSUE 50), again at
    # ISSUE 53 and at ISSUE 68, which meant to move them
    "qwen3-next-80b-a3b":
        "e48c8c7eefbd71e36a5aff657b8d5601459c7bf71c425dde3739d03f2dbbea1c",
    "qwen3-next-share":
        "9f25a1cdd04695f638a35c93c978c27fa187062ce3e9da9b6d80be76d245d638",
    "qwen3-next-whole":
        "2f6738445844c419db1b90157539813266d60082a04cdfa99d92b0ef46df2edf",
    "qwen3-next-aux-0":
        "0df6fca437447172ee67ecf1d41eaa0f4b06cee7827d72e13cb26b1b56063abe",
    "qwen3-next-all-rotated":
        "84637bc8ba5387763746b96959fc0c92629c860c834abdb741f7bb0ff615401b",
    "qwen3-next-every-second":
        "de0bcf6f0437f57cf9479d08883fa361145883eb91303b4dba8673a9f6a9834f",
    # taken at the commit that added the builder (ISSUE 54)
    "ouro-2.6b":
        "a7617532335086b3843c2ff3c53e56980dc7674e918b6441f6d4c4a73a33ed02",
    "ouro-tiny":
        "38fd7fe3fc2e3e9721d12d7437067ab262662d485b28881a3956d5005926c351",
    "ouro-one-pass":
        "084816b8ff9301eff15732676b69645ffe74f6a7584482de992cdac97b305ff1",
    "ouro-kept":
        "7525efd1370968046fbbf766b6ac8a440067352f9a1bad98f994ba4522211dc6",
    "ouro-grouped":
        "a2d63752278ed5639afc8e0868a6e128954d359fd3a2b40ac9ad9e5aa672bf4a",
    "ouro-wide-embedding":
        "861d1834472e3cbc8658ff3d4e7556ba36c5cbb8eb18de6477a2d905f059980a",
    # taken at the commit that added the builder (ISSUE 57)
    "keye-aux-0":
        "5764be0c181aedafc35c76637e7e73b1351061c4e9eb6ae1cd18341dd156e089",
    "keye-positions":
        "ee8ba385f8fb417f824891740b114d4733a75e8f2a357dfa07cd3d37a2e84697",
    "keye-share":
        "ab35c4d847b97347a479778a74f144e7a82d4201e3af0dce07ca6e22af113a53",
    "keye-vl-2.0-30b-a3b":
        "bbc6f0a2b9e7d37d14f96e3a68a0a477f7b5879b4c4802df489d9afbfe15a177",
    "keye-whole":
        "065d611036f2125cb71e78c4b183af587e1342863e40943216757c01f842f167",
    "keye-wide-embedding":
        "f6585a8596ef17a64892f0dfa7672dafe719857ae8cdd8600582a1cc287daff8",
    # taken at the commit that added the builder (ISSUE 61)
    "lfm2-8b-a1b":
        "0216fea5c69162b4c22dc7c5ab5c8e29e63a9765fc041449995a068b97534461",
    "lfm2-every-second":
        "d83db6e3148ca237901ea0553d48253a88e91a5daaa453ee148e95282b4c34c3",
    "lfm2-last-rank":
        "b658e2e189b12b286188d5fd62b270bdca79ce6555d3b4345a36582eee536a3b",
    "lfm2-share":
        "4e2431324ca742d30b25ad0f14f177a588b6ecdb7447a8e21e7a93dd6e1eb764",
    "lfm2-two-dense":
        "8039db6a73463b8547d425742b9fbf454b2fe7d2c0e6a7b513b961e3cbc61313",
    "lfm2-whole":
        "9caf9fab5ce66ca93b6d56575ec5f73ff4c484bef38075d451cbef7ce6fcbec2",
    "olmoe-tiny":
        "550564dc2380c65c09baacccb0a3323def4451a8a52d0a4fe506bf1bdd73dd0c",
    "olmoe-aux-0":
        "2b05c74c3698dfa6307c665cccc1fdcd3b7bb60dc8375ae29489a851b606bcb0",
    "kimi-share":
        "456625b096a4323e8f5a7e8dd8c337802eecd87f4ead69a5bf97f5207618007c",
    "kimi-whole":
        "fae443b0f6fe0b1e740eba09c09050272b16ab1fb1586f9e95d3a6d573468418",
    "kimi-mixed":
        "f8bdf76465e163604882f13c41385c621a9b04bc61d7d324c49f31b84718ce7e",
    "glm-share":
        "353b4b06fdea4895c8e3f919c4db986411210924ca1fbbabdf790061fe6f0c3a",
    "glm-whole":
        "cb7c43f5675506fccafaa9b7c0d9f19b895b5115b378d6db97918050f8049575",
    "glm-no-mtp":
        "10fd309892f748fe34695de78c5686ab614e657ed4f94e2bc57725debce237d5",
    "glm-plain-q":
        "5483431b3de208fe7a57c4af34cc942d902678b2d03f05b924002558cf58ccbc",
    "sdar-share":
        "366c4da84586954803c575a5ea19da9dbe2975152040925b7d1c1b54fd6e15d3",
    "sdar-whole":
        "dd04081b223d42c4b6d05de4cdaee3c328eb117191540f4f95f270e32c6f70f1",
    "sdar-aux-0":
        "8c920b0b6953944678d671a372e374dadb0ff0d23fc69aeb0b59d82586f1af23",
    "afmoe-share":
        "d6028b2778f352c011c5d756486c52837e5d460344d43df154e8a8d52f0197c9",
    "afmoe-whole":
        "e2b33b4d7d1863a71cef1979b9e541666ec0fddc38866bd940e5805596d72dc6",
    "afmoe-unscaled":
        "2cef77d38b0f0aaeed1c167a9b54d8ef6ffa392cf8a2ec3b4ec1cabe246fcc22",
    "afmoe-sliding":
        "b3e735575b9ad8d63d0a1bf1d56ce3109bf1767742b108c705b0468065a8137d",
    "afmoe-full":
        "701cdf81ec357c71b5ee602f8eda8e93c3919b4aa0e5769d95550497ea09b8c7",
    "afmoe-no-dense":
        "3690369c180b7b1c662b0633ca43f81d5ca4504dfffca27f3e6533abaede4a61",
    "afmoe-all-dense":
        "b51dffe9741bdb16180a72d5b171183694ca2fdc2b28a4d32ce66c307cba30e1",
    "mla-unscoped":
        "abfabac33e4456b26e6087fd726edff992019de2edcfabf201ea739a7275c832",
    "mla-mtp":
        "e730f9c676502fc0221c889b2e5b176cd466f0818030584758550e36dfd05e63",
}

# sha256 of each symbol's JSON less every ``__scope__`` attribute
# (``_unscoped``), taken at cc9ea8d, the parent of ISSUE 69 (the six
# builders ISSUE 70 meant to move: at that issue)
UNSCOPED_WAS = {
    # the twelfth builder's, at the commit that added it (ISSUE 71)
    "nemotron-3-nano-30b-a3b":
        "3cd8a0a55b56590c96ad065a2dec7ede419307bb95bdeac6bf98f3634fccc8ef",
    "nemotron-share":
        "e63c016d482b7f57277e312bfb25470a9897833f1bb39cb485783c987daa8b01",
    "nemotron-whole":
        "de87ce80f8bfe9e20d4bf480ff9686d7f8d1a1b883155a047e517af031701bd1",
    "granite-4.0-h-micro":
        "571ed1dab116db74869733e5f1e6ed6babacc71ee4945c0b122975974abcd384",
    "granite-tiny":
        "8432123b9a1f0d9af7b9996486fa2df63b86b062e5b01f8eebf59d3e06264df9",
    "afmoe-all-dense":
        "75589a5d9720645d815c3f9e2a0eaa8ab8b94fddbba9b7f264cf462f4fc8ca4d",
    "afmoe-full":
        "f87c991e49697e01ea5b8511e3ca0140ee4d818780d4434ac6bb5511348bfb8e",
    "afmoe-no-dense":
        "040f9a1c43de370437b26b597fbf89f9198c0eca04614153535bb133c10ae8fa",
    "afmoe-share":
        "8fc6f252c7b95e0f63e4a370ea83317cafd1032b7434de4f7d227d59963893d8",
    "afmoe-sliding":
        "35516981d094063cb1b4399d33ea89b86a3d20b36db40073709e97abe396e419",
    "afmoe-unscaled":
        "649338fd4a9e99e009356273868a0f2a5ada0a81e8a6f1a6a9037792d5e79eef",
    "afmoe-whole":
        "1fee16324dc70463ffd4b33560b26bdf92d8c9ddc9703feae6b8c5e92be7ccae",
    "glm-4.7-flash":
        "d4d19b79e11053cb45695e9bbb582b36c60201c351ab5f7741953be45509566f",
    "glm-no-mtp":
        "1109d2f659648df5682fec0e89f7dbcfea6a4d661aa134ac951d2ead81b1b8bb",
    "glm-plain-q":
        "1c34c6b2a6c7e3a794ddd8d783731c7cfcab15f36be28ef91adbde36c7f48f5a",
    "glm-share":
        "ef9d60b0726a5c95c13f084e61a7bc05f620877588abb6a7bd62250749652e93",
    "glm-whole":
        "11baf419ec2c196826dd7a669fafdeb8f5b2742146d81b554c338a9f6817e7bc",
    "keye-aux-0":
        "4ee3a69c532ddcc4c666b3fe54995298df8a879fd854785121f2772a40efab35",
    "keye-positions":
        "8e0840cf2df3dfdfff5a3cd962311824f251b57d9e5a24d57e1e15164a0e9bb7",
    "keye-share":
        "5dd69da7d7fdd281b959aec0db11fd67e8cdc2bb8bc31503be549925a1507007",
    "keye-vl-2.0-30b-a3b":
        "278b5c4084e2429190f1a522e688cf547ca3b885eca5eabfcdca38ab55c519bf",
    "keye-whole":
        "7d05838bd119b66e88dbbc1b146239e48e4c661812cefb47aeff22b89f3b6275",
    "keye-wide-embedding":
        "b0784359d932f3f0499b513a3c0f505149fd3f8aa925416613493809a2a08a32",
    "kimi-linear-48b-a3b":
        "a0bc397151a9e2432cb4628de918ce465b06bb7dc11a0644878de4d0dea1f4c9",
    "kimi-mixed":
        "a13502b45a4537a43b9e314a406aed7ce54094fdee49f79b6dce388503fe65dc",
    "kimi-share":
        "60314449c363688af0cf92b6fd437bc81ad387a43210dd692208e5f9ad0cc4a3",
    "kimi-whole":
        "8451e99ddb128f59fe8130e638cdf3d468e973990826a1d542a00163affa517e",
    "lfm2-8b-a1b":
        "d651f0c76866545bccfacd65e3df8470f1dafd417a32018dbf3d40ac7e9f0dcc",
    "lfm2-every-second":
        "a155d60bcd0c2561fd1479f9d169f27cec7cda2735164c976b815607ec96606e",
    "lfm2-last-rank":
        "5658ad21a632534a061d106346e79fc0e12e536280a823304ccbbcaf04fff005",
    "lfm2-share":
        "f7ae7309df635078acb466be88c42b3332b783b2e33ababbe57d492b51a63a42",
    "lfm2-two-dense":
        "a9c382fe8ced644b00f7211cfd40569b736cd730b07239a509e4d73d67dce5ef",
    "lfm2-whole":
        "fd2032df8ada8ee944193747161946146f5e2e3c522e7dc6a1d07fc6970fe4c7",
    "mla-mtp":
        "5f9d1e6d7026e44df8c7d950f84acb81976a36a66d09edb3a2483b2b2c03ceb6",
    "mla-unscoped":
        "3bf38fa8129599dd1ae0db2acd856fe414f0241d17e5b72c680aa38e28bf99b4",
    "olmoe-1b-7b":
        "5de1d98f9c66eec063517b973039b1362de643cc6ff7dc172b5561a1539441c5",
    "olmoe-aux-0":
        "25dc6e59ca59a7d2f7666b352ff591f9fcd33b6ae0e7efed0367c25ffa4c4b00",
    "olmoe-tiny":
        "65cb6a5d95d38a1d1e74170cfecaefd3849bb0f7cceeb90b6b8f4016f58290b5",
    "ouro-2.6b":
        "84d63043613b1bc366432e653e00485dbe56dff8f9f19e3dad30e856cf0ac9ff",
    "ouro-grouped":
        "a13772172fbea2a1943b53d9a7d67dfd5e24d9af19382e508d8d076e60c3f2f0",
    "ouro-kept":
        "d30889e7b677d512480bee1c52fc7bd52269d704a487803fe50fd441c5db8cf2",
    "ouro-one-pass":
        "f88ae3f1f694e73192640ff454494b2e8b528c783ca85a9244120665e90e36d0",
    "ouro-tiny":
        "4b6aef4695e2fd350e878cd0803ebc993dbd84596923b20cb49ff3bf44a8f41e",
    "ouro-wide-embedding":
        "3f7f701ed2184b47c04bac2bae03cb5d65cf2f9f635572540dffce6d2ef22067",
    "qwen3-next-80b-a3b":
        "6eb31cdde1fd23b82eef8e3219373acc48000e860cc8c46d4751dec8391ec210",
    "qwen3-next-all-rotated":
        "39a248ba624661e1fb87fc387f6105a48f6f3747283bf07c315567882243be81",
    "qwen3-next-aux-0":
        "e702ccb5401f4c75079f849633c67bb784d213ad870b34ecf82af3f2de044570",
    "qwen3-next-every-second":
        "04ae8ab1a898d049508c5242789eb29d1cffa80508ae52ec287fa57c47e3a64a",
    "qwen3-next-share":
        "ef0d0d150af5c666e1cbde207c9324d5a7d8f5f3bef836ab31626941a7243612",
    "qwen3-next-whole":
        "ad0a64b92badfb232b450af8cc7ee19dd4c1f5a45b2df4690c724b22391b308d",
    "sdar-30b-a3b":
        "2b26702344e04d8b655aa9d4541c43b7a4a22cb4ce13e7a219a41248c8e7ab35",
    "sdar-aux-0":
        "56095097ab7e07cae7757b36b08d8b9465c5b8eeae61557b9eaaf4bbbd833551",
    "sdar-share":
        "90629b4b42e89395ad102f48f4a22c274b17c86d5887b3ca02e2bb9bd8c3b242",
    "sdar-whole":
        "0abfc432edb38e1bc16f8c9bf721d26405babc470a942bf65ea2b8f799b80f4f",
    "smallthinker-21b-a3b":
        "60577a36890ee1804142c6078da313777451dedc8beef9e474a072622cc91cb3",
    "smallthinker-counted":
        "4afb6dab9fd525c997c2242a83adafae6cce8b78cff1072a1e6dde857e6d0a84",
    "smallthinker-share":
        "dcc36396d6ef56017cc9b6483c5e5d892d4e7a9ea0875e095495341e8bb04c58",
    "smallthinker-whole":
        "daa1554ef420699a9971ad74dabeaf076991b4636ef6e7176a2437af9d6f1024",
    "trinity-mini":
        "11723e739c041f3328b5a212c6fe60026ed3b98b0d826b02ba108cd9c12577d0",
}


def _build(builder, kwargs):
    with mx.name.NameManager():
        return builder(**kwargs)


def _unscoped(text):
    """A symbol's JSON with no node's ``__scope__``, a loop node's body
    (a symbol's JSON in its ``body`` keyword) included."""
    doc = json.loads(text)
    for node in doc["nodes"]:
        node.get("attr", {}).pop("__scope__", None)
        body = node.get("param", {}).get("body")
        if body is not None:
            node["param"]["body"] = _unscoped(body)
    return json.dumps(doc, sort_keys=True)


@pytest.mark.parametrize("case", sorted(SYMBOLS))
def test_the_symbol_is_node_for_node_what_it_was(case):
    text = _build(*SYMBOLS[case]).tojson()
    assert hashlib.sha256(text.encode()).hexdigest() == SYMBOL_WAS[case]


@pytest.mark.parametrize("case", sorted(SYMBOLS))
def test_less_its_scopes_the_symbol_is_the_parents(case):
    """A scope is an attribute: naming a block's parts moved no op node,
    no keyword and no input."""
    text = _unscoped(_build(*SYMBOLS[case]).tojson())
    assert hashlib.sha256(text.encode()).hexdigest() == UNSCOPED_WAS[case]


# id -> (builder, its arguments), the step's inputs
STEPS = {
    "kimi": (_tiny("kimi_linear_lm", KIMI, num_layers=4),
             dict(data=(2, 72), softmax_label=(2, 72))),
    "glm": (_tiny("glm_moe_lite_lm", GLM),
            dict(data=(2, 24), softmax_label=(2, 24))),
    "afmoe": (_tiny("afmoe_lm", AFMOE),
              dict(data=(2, 16), softmax_label=(2, 16))),
    "smallthinker": (_tiny("smallthinker_lm", SMALLTHINKER, act_zeros=True),
                     dict(data=(2, 16), softmax_label=(2, 16))),
    "qwen3-next": (_tiny("qwen3_next_lm", QWEN3_NEXT),
                   dict(data=(2, 24), softmax_label=(2, 24))),
}

# sha256 of each step's lowered text at 945e6c8, taken again at PR 66 for
# what that PR meant to move and nothing else (at its parent the five read
# what they did): a rank's share with no row bound, as every tiny one is,
# lowers as the one window ``(0, T*k)`` (``_moe_share_ffn``)
STEP_WAS = {
    "kimi":
        "46bf3071246cac5e0a7e69a64243f23fb52194a50198bf1f19c73fa851117150",
    "glm":
        "292685084b552e7e7ed132e853a86133ee00d17abc2c8962486f503997fd3cae",
    # both taken again at ISSUE 70, which meant to move them: q and k
    # pass ``HeadNormRotary``, whose plain form is the old statements
    # between two more reshapes (rows -> heads -> rows, then the builder's
    # ``Reshape`` to heads again)
    "afmoe":
        "9983d99f54b79b1d772042fc37085d6a0b2a292a58ad0f098d0f62407f09a0f4",
    # first taken at the commit that added the builder (ISSUE 47)
    "smallthinker":
        "a108c931485575e9bde725b93a9d7597a388d39919e85fc9623d6b0e28e7d157",
    # taken at the commit that added the builder (ISSUE 50), again at
    # ISSUE 53 and at ISSUE 68
    "qwen3-next":
        "d65ce1bfaf36f97e584e8f105def2d48f7de15010a41520c3bca866f96575c3f",
}


@pytest.mark.parametrize("case", sorted(STEPS))
def test_the_symbols_lowered_step_is_what_it_was(case):
    """Forward and every gradient of a tiny step lower to the text the
    commit before the skeleton gave (lowering only: nothing compiles)."""
    built, inputs = STEPS[case]
    net = _build(*built)
    shapes, _, aux_shapes = net.infer_shape(**inputs)
    args = {n: jax.ShapeDtypeStruct(s, jnp.int32 if n in inputs
                                    else jnp.float32)
            for n, s in zip(net.list_arguments(), shapes)}
    aux = {n: jax.ShapeDtypeStruct(s, jnp.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    prog = _GraphProgram(net, {}, None, do_mirror=False)

    def loss(a, x):
        outs = prog.eval(a, x, jax.random.PRNGKey(0), True)[0]
        return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)

    def step(p, x, d, l):
        return jax.value_and_grad(
            lambda p: loss(dict(p, data=d, softmax_label=l), x))(p)

    params = {k: v for k, v in args.items() if k not in inputs}
    text = jax.jit(step).lower(params, aux, args["data"],
                               args["softmax_label"]).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == STEP_WAS[case]


# -- ISSUE 70: q's and k's norm and rotation as one node on the rows --------
# ``gqa_attention`` as the builders call it: (query heads, key/value
# heads, a head's lanes, head norms, the rotation's keywords or None, a
# positions input, what else the builder says)
BLOCKS = {
    "sdar": (4, 2, 128, True, dict(theta=1e6, period=8), False,
             dict(mask="block_diffusion", block=4)),
    "trinity-sliding": (4, 2, 128, True, dict(theta=1e4), False,
                        dict(gated=True, mask="sliding_window", window=6)),
    "trinity-full": (4, 2, 128, True, None, False, dict(gated=True)),
    "smallthinker": (6, 2, 128, False, dict(theta=1.5e6), False,
                     dict(mask="sliding_window", window=6)),
    "keye": (4, 2, 128, True, dict(theta=1e7, sections=(16, 24, 24)),
             False, {}),
    "keye-positions": (4, 2, 128, True,
                       dict(theta=1e7, sections=(16, 24, 24)), True, {}),
    "ouro": (4, 4, 128, False, dict(theta=1e6), False, {}),
    "lfm2": (4, 2, 64, True, dict(theta=1e6), False, {}),
}
BLOCK_ROWS, BLOCK_BATCH, BLOCK_HIDDEN = 16, 2, 64


def _toy_block(case, on_rows):
    """One ``gqa_attention`` over ``h``: with the rotation's keywords
    (the node on the rows) or, the nodes of every commit before ISSUE
    70, with a function of the heads."""
    heads, kv, d, norms, rotation, positions, how = BLOCKS[case]
    where = {}
    if positions:
        where = dict(positions=sym.Variable("positions"),
                     with_positions=True)
    if on_rows:
        rotate = dict(rotation, **where) if rotation else None
    elif rotation:
        def rotate(t):
            return sym.RotaryEmbedding(t, **dict(rotation, **where))
    else:
        def rotate(t):
            return t
    with mx.name.NameManager():
        return decoder.gqa_attention(
            sym.Variable("h"), "l0_", 0, BLOCK_ROWS, heads, kv, d,
            BLOCK_HIDDEN, 1e-6, rotate=rotate, head_norms=norms, **how)


def _toy_step(net, dtype):
    """The block's output and every gradient of ``sum(w * output)``."""
    inputs = dict(h=(BLOCK_BATCH * BLOCK_ROWS, BLOCK_HIDDEN))
    if "positions" in net.list_arguments():
        inputs["positions"] = (BLOCK_BATCH, 3, BLOCK_ROWS)
    shapes, _, _ = net.infer_shape(**inputs)
    rng = np.random.RandomState(70)
    args = {}
    for n, s in zip(net.list_arguments(), shapes):
        if n == "positions":
            args[n] = jnp.asarray(rng.randint(0, 64, s), jnp.float32)
        else:
            scale = 1.0 if n == "h" or n.endswith("gamma") else 0.1
            args[n] = jnp.asarray(
                scale * rng.standard_normal(s) + n.endswith("gamma"), dtype)
    w = jnp.asarray(rng.standard_normal(inputs["h"]), jnp.float32)
    prog = _GraphProgram(net, {}, None, do_mirror=False)

    def loss(a):
        out = prog.eval(a, {}, jax.random.PRNGKey(0), True)[0][0]
        return jnp.sum(w * out.astype(jnp.float32)), out

    params = {k: v for k, v in args.items() if k != "positions"}
    (_, out), grads = jax.value_and_grad(
        lambda p: loss(dict(args, **p)), has_aux=True)(params)
    return dict(grads, output=out), dict(zip(net.list_arguments(), shapes))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_a_training_step_of_the_block_is_the_old_nodes(case, dtype):
    """The block with ``HeadNormRotary`` on the rows against the block
    with ``Reshape``, ``RMSNorm`` and ``RotaryEmbedding`` over the heads:
    the same arguments under the same names and shapes, and the output
    and every gradient within the dtype's rounding (a CPU program holds
    the plain form in both)."""
    new, old = _toy_block(case, True), _toy_block(case, False)
    assert new.list_arguments() == old.list_arguments()
    _, _, d, norms, rotation, _, _ = BLOCKS[case]
    made = [n.op.name for n in mx.symbol._topo(new._heads)
            if not n.is_variable]
    assert made.count("HeadNormRotary") == 2
    assert "RMSNorm" not in made and "RotaryEmbedding" not in made
    got, shapes = _toy_step(new, jnp.dtype(dtype))
    want, old_shapes = _toy_step(old, jnp.dtype(dtype))
    assert shapes == old_shapes
    if norms:
        assert shapes["l0_q_norm_gamma"] == shapes["l0_k_norm_gamma"] == (d,)
    assert sorted(got) == sorted(want)
    tol = {"float32": 1e-5, "bfloat16": 2e-2}[dtype]
    for name in want:
        a, b = (np.asarray(x[name], np.float32) for x in (got, want))
        assert np.abs(b).max() > 0, name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), name


# sha256 of each symbol's arguments, outputs and states (names and shapes,
# in order: ``common/symbol_signature.py``) at 47367aa, the parent of ISSUE
# 70, which moved q's and k's norm and rotation into one node and meant
# to move nothing a checkpoint or a reference's weights map by
SIGNATURE_WAS = {
    # the twelfth builder's, at the commit that added it (ISSUE 71)
    "nemotron-3-nano-30b-a3b":
        "9157e895b7e02c3bae9a025ee249454e4d38911b0fb6f36b7a6b7d2368bdec9e",
    "nemotron-share":
        "8a35990f2b15cd43b03cf6f206dcb81013c7fbad2e5c27164a7e07d337b2c46d",
    "nemotron-whole":
        "8cf9b92beef0e0007ce897f456ea993c1c792b76ec7e32cb0f43e7dc39a3086b",
    "granite-4.0-h-micro":
        "54a8611fba569b409ff54b118ebdca5cd793000123b8c0476ab8c5320a4d1dc1",
    "granite-tiny":
        "7422ee0320d7da089b33fe0516291f31b70b0507e6571c2b2553d74bd68a6777",
    "afmoe-all-dense":
        "33d7a6cf3bdc139fbaeab94a4747fb8cc7ca509e2778b3fee508856f8e7adb0c",
    "afmoe-full":
        "0e7e7564c7f0fd4fcaa5b7b8653eb8f2354d43e2c98dd7490781ae03832b5e76",
    "afmoe-no-dense":
        "86cfc400caf69a1e8b1513e4cebc19f350bc8850422f150560ac9af49183f008",
    "afmoe-share":
        "0e7e7564c7f0fd4fcaa5b7b8653eb8f2354d43e2c98dd7490781ae03832b5e76",
    "afmoe-sliding":
        "0e7e7564c7f0fd4fcaa5b7b8653eb8f2354d43e2c98dd7490781ae03832b5e76",
    "afmoe-unscaled":
        "0e7e7564c7f0fd4fcaa5b7b8653eb8f2354d43e2c98dd7490781ae03832b5e76",
    "afmoe-whole":
        "17a3470c335c42c2828aa404b8057563ac0245caffb70f5d3dfe2a26fe427b19",
    "glm-4.7-flash":
        "1cc9a7e47b229a4547a8c7703b1e8d5176525bf6eb446e1542f58e7f1d1a72e5",
    "glm-no-mtp":
        "39126d212501ad20a9585ea1877b8d2128a37eaf25d66c8aad37272df118be8b",
    "glm-plain-q":
        "1618baed021283b5f4efa48c9495d1ec42e5e35d068c883d907ad50be1666b40",
    "glm-share":
        "b1342050b8ee99ee82a4417c65340129ea967254454308792faad8e72fbaa38b",
    "glm-whole":
        "4933344c4ae7df8c0bbef4e6b1e1cd297c65ec915c7368dd6fea900c0d0fb9fe",
    "keye-aux-0":
        "3cb0a9829232e0abeb726ffda4e46705cc9cb708198cf8d367d9a2a01f48c60f",
    "keye-positions":
        "428a2e9abc296eeafa95d5a9b67273b3be442798f34496124d02036b7206edfd",
    "keye-share":
        "0e67e6be3f722099f838e3c98a76e2bd54d310e2e189d97853d193ce05f3f13a",
    "keye-vl-2.0-30b-a3b":
        "8fdcd6fd802c413dcc4c977b574b9a7c36a5abe59ec3b5abd6852bf48df0410f",
    "keye-whole":
        "4a10075ae6119fe5307ddfb4c6a68ec720d670a4287590f47b678dbe9f6f0b28",
    "keye-wide-embedding":
        "0e67e6be3f722099f838e3c98a76e2bd54d310e2e189d97853d193ce05f3f13a",
    "kimi-linear-48b-a3b":
        "61b87bedb5279d790b41c5ea1c3ba0ea93dbc6c5e89e19da501bce92d5b67302",
    "kimi-mixed":
        "28c053587e2a6f9fa1b06cb3a53b754a18c200e0c995b935d2f2ee2c8437cdda",
    "kimi-share":
        "0681ed4ac795d882aa07dd954774479f8ad19594adf9997ca629f1452f5b731a",
    "kimi-whole":
        "c249b2c61c19776bef8fad10b5676ca85a1f780ee9f6499d6608a91fc768c5a6",
    "lfm2-8b-a1b":
        "dc509411ef6fc778a6f298c15ac0e0d6d72cbbf3ae580dd50d91fb9008c40179",
    "lfm2-every-second":
        "341faccf20556cbc006e25c998a122c5b9452e66634412eb0d629ba7eb5111a7",
    "lfm2-last-rank":
        "defca9a62176e732d409520e857b412be2811bed87b6e4fd899616d90e0cd089",
    "lfm2-share":
        "defca9a62176e732d409520e857b412be2811bed87b6e4fd899616d90e0cd089",
    "lfm2-two-dense":
        "3661bc156576d3f010efae4c52d5dec71c494da11c04f71c790a39a612891936",
    "lfm2-whole":
        "4ffa559c09cc2703be6a447c65c173a31551b52438c169385834208281c4bc33",
    "mla-mtp":
        "d327afce9ccbf2c7050f53499dc6a107332e5363f899c620d24611fcca35e207",
    "mla-unscoped":
        "c4ce339be703b52e98c3316c6a8871ca0649196a50050adf594c69e3de677829",
    "olmoe-1b-7b":
        "15b930fa34a817a60c16c627f0ab24333fb44f757fea88f0d34530439733b283",
    "olmoe-aux-0":
        "b7455aca70d1244239f798fb3f0bba429f28c5c210ce7c3dd704e3554a5e7080",
    "olmoe-tiny":
        "b7455aca70d1244239f798fb3f0bba429f28c5c210ce7c3dd704e3554a5e7080",
    "ouro-2.6b":
        "dba6c5eb5518aef88d67ce74e9f62fdf76d0a38b1075afdfa65e2faa6436f5a0",
    "ouro-grouped":
        "904f64ba73d4c43451d0b876b332b0330f963b20d034553df1a80113b7917fad",
    "ouro-kept":
        "79c31609a44645697debaee34a79345aaefa494b035d4d4ce170b756fa493d60",
    "ouro-one-pass":
        "2483b7d08ce278b6d6e2cf167f9542b9ae2b310690d575786310620ad4424c22",
    "ouro-tiny":
        "79c31609a44645697debaee34a79345aaefa494b035d4d4ce170b756fa493d60",
    "ouro-wide-embedding":
        "79c31609a44645697debaee34a79345aaefa494b035d4d4ce170b756fa493d60",
    "qwen3-next-80b-a3b":
        "1bc64c778f1eab7245996e200419e75d8649e9ab79f02817d79b1d34242f8b68",
    "qwen3-next-all-rotated":
        "364dc18e3c94c45b4f4d1f523528f78419dba0c23beb332c04f7b8b7d15745c5",
    "qwen3-next-aux-0":
        "11593607812470042105099c440baa3a44ccc1c08ccbe473ff88ffd5e898e67b",
    "qwen3-next-every-second":
        "9148b1b2537d6aab87d3cffe4cd7b1a0c14f9e75f1779f140edf4a5b99d7d0db",
    "qwen3-next-share":
        "364dc18e3c94c45b4f4d1f523528f78419dba0c23beb332c04f7b8b7d15745c5",
    "qwen3-next-whole":
        "ac1765a6166013bd22313b49fbc9962287e13910a8ac835615661db2d32489a4",
    "sdar-30b-a3b":
        "c7d3b0da627df7152d47a66e2aa887ae446f6f31298bee3cae595fd18c2d6e19",
    "sdar-aux-0":
        "c36b6d86e6f4c4aba167d93f5f88386f338ce41980a7e52526bcf47095982346",
    "sdar-share":
        "669e228fbbd37f9c290c0e435904c05a1dc847e6d3a281dda9127ceb6e7add35",
    "sdar-whole":
        "1d4725d4716c07d8a3be8d85a3b9c0a07c5355989b39c9a9752299873f20a69e",
    "smallthinker-21b-a3b":
        "f4ad653d974628c1d7fe804cb0aea3af726eab69ac6e4e962e39112a6b51508a",
    "smallthinker-counted":
        "3e811990d7dad6837ea50171733c07167c0db4894a6eae4f32ce55f922c85bc1",
    "smallthinker-share":
        "1e5805642f8b56d2ea17ac00f5cd60bd0a059f82018cfed406e7c9fcf72dee78",
    "smallthinker-whole":
        "a76cecacda4b6074348071071e031f9cc88caf1adb6985293a34714f3074b4cc",
    "trinity-mini":
        "9f9dd1973626afd841a5b06ac78f4dd2f25686590dac39dd5483a7197b4759e8",
}


def _signature_inputs(case, kwargs):
    if case.startswith("mla"):
        return dict(h=(2 * MLA[0], MLA[1]))
    rows = kwargs["seq_len"]
    batch = 1 if rows >= 4096 else 2
    if case.startswith("sdar"):
        return dict(data=(batch, 2 * rows), softmax_label=(batch, 2, rows))
    inputs = dict(data=(batch, rows), softmax_label=(batch, rows))
    if kwargs.get("positions"):
        inputs["positions"] = (batch, 3, rows)
    return inputs


@pytest.mark.parametrize("case", sorted(SYMBOLS))
def test_arguments_outputs_and_states_are_the_parents(case):
    builder, kwargs = SYMBOLS[case]
    net = _build(builder, kwargs)
    assert signature(net, **_signature_inputs(case, kwargs)) \
        == SIGNATURE_WAS[case]
