"""LFM2-MoE (``model_type`` ``lfm2_moe``, LiquidAI's LFM2-8B-A1B): a
pre-norm decoder whose mixers are double-gated short convolutions in
three layers of four and grouped-query attention in the fourth, over a
sigmoid-routed expert layer, with ONE weight for embedding and head.

Token embedding -> L x [x + Mixer_l(RMSNorm(x)), x + MLP_l(RMSNorm(x))] ->
RMSNorm -> the head ``x E^T`` with ``E`` the embedding.  No projection
has a bias.

``layer_types`` names the mixer of every layer BUILT, one of
``MIXER_KINDS`` (the published list is no fixed period).  A ``conv``
layer: ``[B | C | u] = h W_in`` (``D -> 3 D``, the thirds in that order),
``y = (C * conv(B * u)) W_out`` with ``conv`` a depthwise causal
convolution of ``conv_kernel`` taps over the sequence, zeros before it:
no activation, no norm inside.  It is ONE node, ``CausalConv1D(gated=
True)``, which reads the projection where it lies
(``ops/causal_conv.py`` ``gated_conv``).  A ``full_attention`` layer:
``num_heads`` query heads over ``num_kv_heads`` key/value heads of
``head_dim``, an RMSNorm over each head's lanes of q and of k (one gain
vector each), rotary embedding over all lanes (``rope_theta``,
half-split pairing), causal softmax attention, ``o Wo``.

``MLP_l`` is a SwiGLU of ``dense_width`` for the first ``dense_layers``
layers and after them the routed expert layer: ``sigmoid`` router over
``num_experts``, top ``experts_per_tok`` by score plus a selection bias
(an aux state, no gradient, moved by this rank's counts), weights
renormalized over the chosen and multiplied by ``route_scale``, SwiGLU
experts of ``expert_width``, no shared expert.  ``experts_held`` > 0
builds one expert-parallel rank's share (``MoEFeedForward``): experts
``first_expert ..`` only, the router still ``num_experts`` wide.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids.  Outputs, by
name: ``lm_output`` the per-token loss head (first, where the metric
reads it) and ``moe_load_output`` the expert blocks' load head.
``embed_weight`` is used twice in the graph (the lookup and the head)
and its gradient is the sum of both uses.  The loss head normalizes its
own gradient, so ``rescale_grad`` is 1; there is no load-balance loss.

Device scopes: ``gsc_proj.l<i>`` (a convolution layer's in- and
out-projection) and ``gsc_conv.l<i>`` (the convolution with its two
gates, either lowering), ``attn_proj.l<i>`` beside the ops' own
``attn.l<i>``, ``moe_*.l<i>`` and ``lm_loss``.
"""
from .. import symbol as sym
from ..moe.layer import with_load_heads
from .decoder import (block, embed, gqa_attention, layer_kinds, lm_head_loss,
                      proj, routed_experts, scoped, swiglu)

MIXER_KINDS = ("conv", "full_attention")


def lfm2_moe_lm(num_layers, hidden_size, layer_types, dense_layers,
                num_heads, num_kv_heads, head_dim, conv_kernel, rope_theta,
                dense_width, num_experts, experts_per_tok, expert_width,
                vocab_size, seq_len, route_scale=1.0, experts_held=0,
                first_expert=0, bias_rate=1e-3, rms_eps=1e-5):
    """The training symbol; see the module docstring."""
    layer_types = layer_kinds(layer_types, num_layers, MIXER_KINDS)
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))

    def short_conv(h, pre, l):
        with scoped("", "gsc_proj", l):
            gates_and_rows = sym.Reshape(
                proj(h, pre + "in_proj", 3 * hidden_size),
                shape=(-1, seq_len, 3 * hidden_size))
        with scoped("", "gsc_conv", l):
            y = sym.CausalConv1D(gates_and_rows, kernel=conv_kernel,
                                 gated=True, name=pre + "conv")
        with scoped("", "gsc_proj", l):
            return proj(sym.Reshape(y, shape=(-1, hidden_size)),
                        pre + "out_proj", hidden_size)

    def mixer(h, pre, l, kind):
        if kind == "conv":
            return short_conv(h, pre, l)
        return gqa_attention(
            h, pre, l, seq_len, num_heads, num_kv_heads, head_dim,
            hidden_size, rms_eps,
            rotate=dict(theta=rope_theta))

    def mlp(h, pre, l):
        if l < dense_layers:
            return swiglu(h, pre, dense_width, hidden_size, l)
        return routed_experts(
            h, pre, l, num_experts, experts_per_tok, expert_width,
            hidden_size, renormalize=True, score="sigmoid",
            scale=route_scale, bias_rate=bias_rate,
            experts_held=experts_held, first_expert=first_expert)

    table = sym.Variable("embed_weight")
    x = embed(sym.Variable("data"), vocab_size, hidden_size, weight=table)
    for l, kind in enumerate(layer_types):
        pre = "l%d_" % l
        x = block(x, pre, rms_eps, lambda h: mixer(h, pre, l, kind),
                  lambda h: mlp(h, pre, l), mixer_norm="operator_norm",
                  layer=l)
    return with_load_heads(lm_head_loss(x, vocab_size, rms_eps,
                                        head_weight=table))
