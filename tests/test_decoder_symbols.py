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
stand."""
import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu import symbol as sym
from mxnet_tpu.executor import _GraphProgram
from mxnet_tpu.models.latent_attention import latent_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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

# sha256 of each symbol's JSON at 945e6c8; the first five are ISSUE 46's
# table, the four behind them the sixth builder's
SYMBOL_WAS = {
    "olmoe-1b-7b":
        "3010508f9af6d214d25b4ea18ba84994901ecdb99e011aa1a16b0b8e402fb4b7",
    "kimi-linear-48b-a3b":
        "dff53698a00ec2819599b157cfe8cda630ea56f97e2efbb13412fa15d568f87f",
    "glm-4.7-flash":
        "787b1b2114b39c79c8efd33a4dd949f218c07bf94a39b900e6b0333c00100433",
    "sdar-30b-a3b":
        "c0ee067be3ca69380c71a9e30645329c7717f3e867b201153c5744dd812f7d83",
    "trinity-mini":
        "3e5e7ce8c564e558445076d7db9f435b29431733b2063a6ccb1fbe3797ed571f",
    # taken at the commit that added the builder (ISSUE 47)
    "smallthinker-21b-a3b":
        "d610d2522f3a17701ea206b16b6426c8054b081f462b43aebb18f96e733d2bcd",
    "smallthinker-share":
        "28eb66fcbf3cedccff08db0eeee123e1a831d4492d975664c1273ee8b6952324",
    "smallthinker-counted":
        "4dd9b03064d193eab8d657784f597d6d779c00b8cae09e19fdec3613e90ebdfd",
    "smallthinker-whole":
        "b7b3aee331521264f4ec20024a1d4ea0c553144c1c2666ef2e413f8688f07b58",
    # taken at the commit that added the builder (ISSUE 50), again at
    # ISSUE 53 and at ISSUE 68, which meant to move them
    "qwen3-next-80b-a3b":
        "4f741640321eef15e5496d3a410e1463afa70bf4ad2b94aaa8975cf4f8f8dd1d",
    "qwen3-next-share":
        "6e0104974da04939fcf104c7935a85575b3b8ed12cea2e7894585b04bc1fd616",
    "qwen3-next-whole":
        "cf277e4ccb52f1e3c15a167127e43ff82e20133c715add8722c35e6e17407614",
    "qwen3-next-aux-0":
        "fadda8e584d0522584fd2f87270fa13ab51bb2a2c42b09922c14465fe4faac86",
    "qwen3-next-all-rotated":
        "42a6201dd4a9f54d1b7352de868b075bab670d3d9cc7ce648c3df4d9073178c3",
    "qwen3-next-every-second":
        "3ffc25bad70427762a66ee4f5e08204614979f97f8c7370daa865822233e474d",
    # taken at the commit that added the builder (ISSUE 54)
    "ouro-2.6b":
        "a6237f2acd13d5d18d5a2fe516b57a08d4d8b58d51e529a1552893f9c88f15f3",
    "ouro-tiny":
        "8a7127bf3a21a28c0c279c69149be96d239a3ae5f2b5f37214cefe815e313acb",
    "ouro-one-pass":
        "eb9d2058606bdfc102781e3c565a1364fc79778f24df8e43c2ca7e303f1223fd",
    "ouro-kept":
        "8ea406b517cee9237e488a330e5aa8c741db56f5ca2bf347f33c69a28ea97b19",
    "ouro-grouped":
        "d25c2955f4329377c144aff6f552bcb7cf7ae56f513f95b566bd62bbf88e150d",
    "ouro-wide-embedding":
        "1e32bfb3be57e690901828997a7b534b564ad51ad7752defadbcae93627279b1",
    # taken at the commit that added the builder (ISSUE 57)
    "keye-aux-0":
        "3f7802952bb019a6771000414b3ef2f3634497074a95fa27e4ae84ce3a94d45f",
    "keye-positions":
        "4037d94f8faded7ae89a1a73980939db69b25ec97e94ffec749da899209dba4f",
    "keye-share":
        "32539ecb44ecda162787cfb26a3d358093ae442744e32337438cdc5bd867d91f",
    "keye-vl-2.0-30b-a3b":
        "99be1309ec9a0b073e9146315671dc85e58b65d2fb5edd0195a5fa2bf2df08e1",
    "keye-whole":
        "e01e43dbaa2a055c54b06713a3b97c17c95a6be4a113fe94d976fa0a9a1e0232",
    "keye-wide-embedding":
        "eb834439a3fe90b9840ee600f5fba26d34a9ff58d2c29f71042b45c519c297d7",
    # taken at the commit that added the builder (ISSUE 61)
    "lfm2-8b-a1b":
        "15f583703e2dc8409244e0723eb87bb853fd278540264834e2282b762c4080b0",
    "lfm2-every-second":
        "dc8a1c9ffb890736cef38d94045e5c2b597b8c9d734b4c35ed00d85f5755107c",
    "lfm2-last-rank":
        "ab255caf1c0e0763c47ca9469312e496f0cb654dd079a8fd5f92351a49e41406",
    "lfm2-share":
        "039659a5f095be401af2baddca332e5b490894fbbeb5333646fd4b13f8a76288",
    "lfm2-two-dense":
        "00d398f0d54af22e8b993c9d3e873abefa1411c0aeb4896c5750be8fcb59c5bc",
    "lfm2-whole":
        "3df3c390ba50dcd9af29227efe2351edbfc4e35f0d22a930fe0bae2d4ae496e2",
    "olmoe-tiny":
        "af8dc705e9e7aeb26b2806967a870d607de9f4892070d6b09552c77d2034efc0",
    "olmoe-aux-0":
        "56de2d26c2c517cb4762f53be03544ae0560a3f024c25b59df3c4986f064eddb",
    "kimi-share":
        "f13a444520f419ed1046d68efe2cdec276313c32adbe7771aba2b93f9b185fbd",
    "kimi-whole":
        "cf827c6317ed0d1673ff6ae133ca492a141cf8198bc69cee42a991da90198446",
    "kimi-mixed":
        "4aa4c2c3e28b649b673bb631f7e0d50530edb8b21f09f18239213b7f99f19196",
    "glm-share":
        "8fcc0fbc997e1497b3671ef97ad37ddaf922eea12d3c38139aebc9553073a9a8",
    "glm-whole":
        "0701683aeeffa8ac821097b06c39f3f81e4255258222735c908a2ef4a3ee6e36",
    "glm-no-mtp":
        "177747956dddc152220380fad55c4483f1af51aec890e7265bf15a9b62719104",
    "glm-plain-q":
        "6388f2f59006d9c6031358443a95311a8d690772ed624c4b3c4c673f5fb69b79",
    "sdar-share":
        "2d83d30a65d2bcf58ab95c9077e927f7e8a2326837e7cfc66c47ea0a67bccad0",
    "sdar-whole":
        "13409b7766155319f82afed3b1c2536b86b9993abbcfc854f68494f61894e479",
    "sdar-aux-0":
        "6adca4ee43a9aa21f1fd67750ea029348468f27f65a5fd7de4fe0477c26136ac",
    "afmoe-share":
        "132bec7ac759c3c3632747c8cde3f428bc6442ad740b9d5f0d007dab36be2c18",
    "afmoe-whole":
        "5e07e8ae61be77e12a75d79aa493dd2170f420e491d96ae13df5f51563f80d67",
    "afmoe-unscaled":
        "1c81e3650bab94348e9d11383067421b02ae37f743c703507066b76e61495fa2",
    "afmoe-sliding":
        "9113a9b5c92a50d0a10a74e9a8cc5b9dba602b1142e62b48de2c96ff94f424cd",
    "afmoe-full":
        "f8d42f162b8401c7ab71763eef66a3bee5833dbe2e091b0bef39ff04e6ef447e",
    "afmoe-no-dense":
        "24a718720548b59203a2426cc1ec455419000c8b861142763e21da7c6e09ff57",
    "afmoe-all-dense":
        "cf725ecb8b1155d7d3e7943bc4c8360d8ae70a52464164bc47b0a2693269c410",
    "mla-unscoped":
        "abfabac33e4456b26e6087fd726edff992019de2edcfabf201ea739a7275c832",
    "mla-mtp":
        "cf9dd247a23b054411934d6c18ae766ffc59b532cb7a19ce457da1040d143806",
}


def _build(builder, kwargs):
    with mx.name.NameManager():
        return builder(**kwargs)


@pytest.mark.parametrize("case", sorted(SYMBOLS))
def test_the_symbol_is_node_for_node_what_it_was(case):
    text = _build(*SYMBOLS[case]).tojson()
    assert hashlib.sha256(text.encode()).hexdigest() == SYMBOL_WAS[case]


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
    "afmoe":
        "76ff959066a5b7e3909615e0613827be95b09e7afaa1c6227c7527dd644113c4",
    # taken at the commit that added the builder (ISSUE 47)
    "smallthinker":
        "95b031f02386400a58b5e0493e1d76a80030c0e9412c1cf011c168736e2f10dd",
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
