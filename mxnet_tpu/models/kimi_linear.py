"""Kimi Linear: a pre-norm decoder whose mixers differ by layer.

"Kimi Linear: An Expressive, Efficient Attention Architecture"
(arXiv:2510.26692; ``model_type`` ``kimi_linear``): token embedding ->
L x [x + Mixer_l(RMSNorm(x)), x + MLP_l(RMSNorm(x))] -> RMSNorm ->
untied vocabulary head.  Layers are counted from 1, as the published
config counts them.

``Mixer_l`` is Kimi Delta Attention (``sym.KimiDeltaAttention``: the
gated delta rule with a per-channel decay) unless ``l`` is in
``full_attn_layers``, where it is latent attention (MLA) with no
positional embedding.  KDA: q, k, v projections, each through a
depthwise causal convolution of ``conv_kernel`` taps and SiLU; a
low-rank decay gate (``hidden -> kda_head_dim -> heads * kda_head_dim``)
and a per-head write gate; the output through a per-head RMSNorm gated
by ``sigmoid`` of a second low-rank projection (the one bias of the
model is on its up-projection; ``GatedRMSNorm``, on the rows as the op
writes them), then ``o_proj``.  MLA: queries of
``qk_nope_dim + qk_rope_dim`` a head; keys and values decompressed from
one ``kv_lora_rank`` latent (RMSNorm'ed) plus one ``qk_rope_dim`` key
part shared by all heads; values of ``v_head_dim``; no rotary embedding
on either part (``mla_use_nope``): ``latent_attention``, the assembly
this package's decoders share, without its query compression and its
rotation.

``MLP_l`` is a SwiGLU of ``dense_width`` for the first ``dense_layers``
layers and after them the routed expert layer: ``sigmoid`` router over
``num_experts``, top ``experts_per_tok`` by score plus a selection bias
(an aux state, no gradient), weights renormalized over the chosen and
multiplied by ``routed_scale``, and one shared expert.  ``experts_held``
> 0 builds one expert-parallel rank's share (``MoEFeedForward``):
experts ``first_expert ..`` only, the router still ``num_experts`` wide.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids; outputs the
per-token loss head and the expert blocks' ``moe_load`` head.  The loss
head normalizes its own gradient, so ``rescale_grad`` is 1; there is no
load-balance loss (the selection bias balances).
"""
from .. import symbol as sym
from ..moe.layer import with_load_heads
from .decoder import (block, embed, lm_head_loss, proj,
                      routed_experts, scoped, swiglu)
from .latent_attention import latent_attention


def kimi_linear_lm(num_layers, hidden_size, full_attn_layers, dense_layers,
                   kda_heads, kda_head_dim, conv_kernel, mla_heads,
                   kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim,
                   dense_width, num_experts, experts_per_tok, expert_width,
                   shared_width, routed_scale, vocab_size, seq_len,
                   experts_held=0, first_expert=0, bias_rate=1e-3,
                   rms_eps=1e-5):
    """The training symbol; see the module docstring.  No KDA core is
    marked ``force_mirroring``: where the op's kernels run (heads of 128,
    whole chunks, a TPU) its backward pass keeps the op's inputs, the
    chunks' entry states and the three products the forward kernel
    formed (``A``, ``Bs``, ``T``), and the backward kernel reads them
    (134 MB + 100.7 MB a layer at 4096 tokens of the published widths)."""
    full_attn_layers = set(full_attn_layers)
    kda_width = kda_heads * kda_head_dim

    def kda(h, pre, l):
        def conv(x, name):
            x = sym.Reshape(x, shape=(-1, seq_len, kda_width))
            x = sym.CausalConv1D(x, kernel=conv_kernel, act_type="silu",
                                 name=name)
            return sym.Reshape(x, shape=(-1, seq_len, kda_heads,
                                         kda_head_dim))

        # ``kda_proj.l<l>`` around the op's own ``kda.l<l>``, never around
        # the op: the outermost declared scope wins
        with scoped("", "kda_proj", l):
            q, k, v = (conv(proj(h, pre + s + "_proj", kda_width),
                            pre + s + "_conv") for s in "qkv")
            decay = proj(proj(h, pre + "f_down", kda_head_dim),
                         pre + "f_up", kda_width)
            decay = sym.Reshape(decay, shape=(-1, seq_len, kda_heads,
                                              kda_head_dim))
            beta = sym.Reshape(proj(h, pre + "beta_proj", kda_heads),
                               shape=(-1, seq_len, kda_heads))
        o = sym.KimiDeltaAttention(q, k, v, decay, beta, layer=l,
                                   name=pre + "kda")
        with scoped("", "kda_proj", l):
            gate = proj(proj(h, pre + "g_down", kda_head_dim),
                        pre + "g_up", kda_width, bias=True)
            o = sym.GatedRMSNorm(sym.Reshape(o, shape=(-1, kda_width)),
                                 gate=gate, head_dim=kda_head_dim,
                                 eps=rms_eps, act_type="sigmoid",
                                 name=pre + "o_norm")
            return proj(o, pre + "o_proj", hidden_size)

    def mla(h, pre, l):
        return latent_attention(h, pre, seq_len, hidden_size, mla_heads,
                                kv_lora_rank, qk_nope_dim, qk_rope_dim,
                                v_head_dim, rms_eps, layer=l, scope="")

    def mlp(h, pre, l):
        if l <= dense_layers:
            return swiglu(h, pre, dense_width, hidden_size, l)
        return routed_experts(
            h, pre, l, num_experts, experts_per_tok, expert_width,
            hidden_size, renormalize=True, score="sigmoid", scale=routed_scale,
            bias_rate=bias_rate, shared_hidden=shared_width,
            experts_held=experts_held, first_expert=first_expert)

    x = embed(sym.Variable("data"), vocab_size, hidden_size)
    for l in range(1, num_layers + 1):
        pre = "l%d_" % l
        mixer = mla if l in full_attn_layers else kda
        x = block(x, pre, rms_eps, lambda h: mixer(h, pre, l),
                  lambda h: mlp(h, pre, l), mixer_norm="mixer_norm",
                  layer=l)
    return with_load_heads(lm_head_loss(x, vocab_size, rms_eps))
