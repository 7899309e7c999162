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
stood.  ISSUE 69 moved every symbol's ``__scope__`` attributes and
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

# sha256 of each symbol's JSON, all taken again at ISSUE 69 (the scopes
# moved, ``UNSCOPED_WAS`` holds the rest); first the five cells of ISSUE
# 46's table, the four behind them the sixth builder's
SYMBOL_WAS = {
    "olmoe-1b-7b":
        "292ca4805a7d2c60c90cfa7c66f804e6494628cd78e82f8e42cedc598fd161c8",
    "kimi-linear-48b-a3b":
        "31e18ea5af2aacdb914cafed3b52c3e6a40acf02f1c47201a2a062601d3172e6",
    "glm-4.7-flash":
        "a8c96d68fb7e7165301540a48986d2f38c6bd94010b92b18b75894be1df75bb0",
    "sdar-30b-a3b":
        "beca2ba133113521e71048d1b2c2e6ae3d62c8dbe772a9348fcfc09dc012ce4e",
    "trinity-mini":
        "c99dcd1e44dd3fb8356f3d9f19d572d609ff2aab54a2e0087bb4c62d917cf4be",
    # taken at the commit that added the builder (ISSUE 47)
    "smallthinker-21b-a3b":
        "033c467024b1c9fe645173aceb507ad22cf1607bac2643b21271ced499aa97ee",
    "smallthinker-share":
        "3e28061f486216de8f7065ab09e036fcfa2e7756d8d4689e2a311e3f72452de3",
    "smallthinker-counted":
        "1baeb038ce2614acb37b9f86701f3a5ea72e30a2274d89a14cb44d944bfd32c2",
    "smallthinker-whole":
        "2fc6e7c24ba8fba01fcd4d7cbecae108cb4d4425bac9eb1483971a898d2b4ae9",
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
        "fa238b0166d330fa195af38ee897c171d283b100092f937136a271d8dd9ec827",
    "ouro-tiny":
        "d2235ef6fa90eff6f7972105d6ae09983343887b63b482a8a6c66f5c5e553533",
    "ouro-one-pass":
        "47f5e6e4fdf76cf370c25abd2f0f22a7e96bcc6e2d931e6ff6bd7fdf707fafaf",
    "ouro-kept":
        "6c4ba28714b5e6655de1341196a26d94cca263eaa3724fcbc0e0952c79ecafef",
    "ouro-grouped":
        "db06b56e0ff5aa99cc032c9ff00d564b931431d1ce51b5220fb96b1e36658993",
    "ouro-wide-embedding":
        "c2deeac264e2acd4ece3aae4381ebdb3a9fcbae71978067e29dab4c4e541096f",
    # taken at the commit that added the builder (ISSUE 57)
    "keye-aux-0":
        "be41c905547f39db66d13d9d811bc1954751f1d68efc5cf4b452604bc4d038d5",
    "keye-positions":
        "a6b8c84aab36f520d4298230fd5dcac8b656051d27a499cfa4f842ebdb6ac79d",
    "keye-share":
        "aeeef10379d28f175ac22fecef5ffce387b09d98d37523cd3e927be426d2de7f",
    "keye-vl-2.0-30b-a3b":
        "23de62d96a80016657f1b6b580f0d80be5a9d2689cb245145136ed0a6abc7417",
    "keye-whole":
        "5bfb3997be65d6137780372bad0278621ae72c80335ba926dd9d70e507ee72d0",
    "keye-wide-embedding":
        "da58b11a60f9d2ec808b4e8b0ce0ddb43564f4920f21d0024a8aeb78d4b2e665",
    # taken at the commit that added the builder (ISSUE 61)
    "lfm2-8b-a1b":
        "ee113a9952156390ff1ebf4cd74d4ac16fecffbdb96e63b983cc3cf7e3ac926c",
    "lfm2-every-second":
        "59489e5182b542f656ff457b8ea0a02cd280b87644224fe712c4744d529dcdc7",
    "lfm2-last-rank":
        "a4937d56484b125e47a8f67dbf347189b750fb28803649a837588e291e0622e8",
    "lfm2-share":
        "fd1a98ba3e1faf2c2cbf5ef4e80f941f6507be4f457050a9ed10ce10228fcb2b",
    "lfm2-two-dense":
        "6ea9498e004cf5ce47ff96dc63f4404f1ad693ed4167382b9d8b1eccc44fd9ba",
    "lfm2-whole":
        "de5737612e1498efecb6dab3a8103c0f053fd9f21bd3c277259eb650048a125e",
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
        "b1f93dcb9e2b8f23fcb169a37423439c6d9eb30e16df544f40eaf60cce9e3e03",
    "sdar-whole":
        "73334241c7ac2e9f6af95c9f19d1bab4349ad973d1928396bc895ddd0b03a83f",
    "sdar-aux-0":
        "81022914c84b3d0e67486eebcc54da82e6b0c02707bb225b61806bea31297b50",
    "afmoe-share":
        "d3048c79a329b3cddd62a3fc5a44a530bc6696f9e05feac05ea8449f0c9cbe2f",
    "afmoe-whole":
        "b70fbf703e1bc2af6e8c95644da855ef574f64255aeeafad864ee6091d9ae9ed",
    "afmoe-unscaled":
        "0d154d8c18056c732877f73d37df477deab8e1465cfbc2bea4724e6cd3a1340d",
    "afmoe-sliding":
        "a454f85b09da41a91d7ffe2b0df84d4b8ecf459a448c05f712f1969c23b8e1c8",
    "afmoe-full":
        "a51d096bb9f42f7c4222829edd92ff3632adbc40c18cc19bc4424c4737991fac",
    "afmoe-no-dense":
        "6b0e2f4ade81b4fd2582590886cff8d22b9a3bb71424ee1cdfecd8f4e1432299",
    "afmoe-all-dense":
        "ec87c53c1e4f91691b03fa6ec0d639c3d84af6d7405f75371515c6748880ff43",
    "mla-unscoped":
        "abfabac33e4456b26e6087fd726edff992019de2edcfabf201ea739a7275c832",
    "mla-mtp":
        "e730f9c676502fc0221c889b2e5b176cd466f0818030584758550e36dfd05e63",
}

# sha256 of each symbol's JSON less every ``__scope__`` attribute
# (``_unscoped``), taken at cc9ea8d, the parent of ISSUE 69
UNSCOPED_WAS = {
    "afmoe-all-dense":
        "72a00872405886c2e409dbe251702b5df869600ee4e6b2833f4d561a5f921e2e",
    "afmoe-full":
        "f2931962e02cee8898ad4be16e550d436ac4a7c78ed7a5530bd7227dd6219b99",
    "afmoe-no-dense":
        "bcc8812b46742559b808640f895636b71e7ae65e83235ecb4f8faebfcba80b3b",
    "afmoe-share":
        "2f263df8d1bd873c3f6b111176dc8d031f573939a8a04e30cc06bdd95c9d01f1",
    "afmoe-sliding":
        "52df43b942bab0f3235b2a2007a41aed0b92b8f72ffee3b4b36c0b760b1094f4",
    "afmoe-unscaled":
        "3b08edf58505c7e814637e0e58d4e74ec11d23f14db2ba471badccb051f6e452",
    "afmoe-whole":
        "b7b6e04c42c89538f92051bfd2eddaf68da28523a222e7c46282fd9249069c7a",
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
        "3554b453b80ea7cc495c7adbb7f471c563470812c7445459c8438dc3e9a44007",
    "keye-positions":
        "eb9bf2ebc11ba4d888800dd09e44ceb51b9184b2e751a034307e69d1b22111da",
    "keye-share":
        "990c009be7c548e78758471b25b0446cf797f2c7f648c1f6c63f5fcad29b6128",
    "keye-vl-2.0-30b-a3b":
        "81f94cc78f5c8da8a1aa3c09c81164046e49be65494e1626e84318c34bc5e275",
    "keye-whole":
        "104f2856af3bfefd9d38a0e545f55378c00579eaad70fabccec8aee8fa6d92a7",
    "keye-wide-embedding":
        "16e2d441a39f01621dc28ce2127a371a710ce0891d23c8a89d5f552a99f2886e",
    "kimi-linear-48b-a3b":
        "a0bc397151a9e2432cb4628de918ce465b06bb7dc11a0644878de4d0dea1f4c9",
    "kimi-mixed":
        "a13502b45a4537a43b9e314a406aed7ce54094fdee49f79b6dce388503fe65dc",
    "kimi-share":
        "60314449c363688af0cf92b6fd437bc81ad387a43210dd692208e5f9ad0cc4a3",
    "kimi-whole":
        "8451e99ddb128f59fe8130e638cdf3d468e973990826a1d542a00163affa517e",
    "lfm2-8b-a1b":
        "9714d6ef4cacdd901a3776ae9ae19743cc5873faeb99c45b731fe86487852df0",
    "lfm2-every-second":
        "0c18d7caedd02e6f4233f6db37f0443b45ce77a04eab97cd85373613a86dca0c",
    "lfm2-last-rank":
        "89f97d5d1f479b950d678ee853e0962cee90df81ce77c85613bbbc93290039a8",
    "lfm2-share":
        "44529f45c013355f9f824362be92d03182224e5c969d2c8d17369b2487be6ec9",
    "lfm2-two-dense":
        "029d48c3d763a986d05534ef5cb17ba5c1052abb4ac1682fb7c0f40cf166b5f2",
    "lfm2-whole":
        "3a032dbfecdfc32b2d2833527422f3387425342a8a4bf7bb3139013b1b7e6f34",
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
        "55b196ec8fbb109cb9033ddb5cab7065f0eb5a542dbf0ec0f4f3d089855b1939",
    "ouro-grouped":
        "723796663e50d769735a7bc2c47d49c5fe356b58ef9991b0a9fb06a4d5dfa6ee",
    "ouro-kept":
        "df509a51a2d415ee3cd01a8717973a1ab9eda204edd76d447d221bae897ee495",
    "ouro-one-pass":
        "5b9173c160db0189203db1531270c924cf1ff5546c93a8170ffb0466cfd6aa56",
    "ouro-tiny":
        "2e9fccf47068f65306078425671ea30a8f20392539eef83cd70190a2c45555ad",
    "ouro-wide-embedding":
        "cc063a7e36accf69cfd047cef59e758edf4fdd8cf066d429b160f905ccef040a",
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
        "1c18b15682d1cc6a8efb9b7c3cdfd695c02449c91faaed1e52d0e974ecad898d",
    "sdar-aux-0":
        "b0c6c9539383dcd9b6747288bd4766d59e26f7bc9b92e68cdcdcb91aa8c07703",
    "sdar-share":
        "e566cb082f3a7cdc3b43777928c5ec38e7720efd6f70f26e5141a358bf638567",
    "sdar-whole":
        "a2a3afe53fc1b1824b5f28374025516600dd0a95eea2f903e383cd6cff07d1d2",
    "smallthinker-21b-a3b":
        "e337b60ca0ebe787781e86164756b416041ca8a432935bfea6c37eb9145a4e1d",
    "smallthinker-counted":
        "4e2205e39943e94b2e474a96d78ef249ce03a455651d5821636de831d9286d90",
    "smallthinker-share":
        "0d1be0c22132d8f57fb200c642e4e01500088637c62aed17d726c84bb6c2c10c",
    "smallthinker-whole":
        "3ea5154542d6d9151528a95e0a0220454b603d182108b46110be20803b63cba4",
    "trinity-mini":
        "c592cd30d5124c19e6ae03ee0db2aba798d08437e26e169d0460a4ce4f57e65e",
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
