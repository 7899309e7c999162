"""AFMoE (``model_type`` ``afmoe``, Arcee's Trinity family): a decoder
whose layers mix sliding-window and full attention, with gated attention
outputs under sandwich norms and a sigmoid-routed expert layer.

Token embedding times ``embed_scale`` -> L x [x + N2(Attn_l(N1(x))),
x + N4(MLP_l(N3(x)))] -> RMSNorm -> untied vocabulary head.  Every ``N*``
is an RMSNorm with a gain: one before and one AFTER each sublayer, the
second inside the residual branch.

``layer_types`` names the kind of every layer BUILT, ``sliding`` or
``full``.  Both kinds are one block: grouped-query attention
(``num_heads`` query heads over ``num_kv_heads`` key/value heads of
``head_dim``) with an RMSNorm over each head's lanes of q and of k (one
gain vector each) and an output gate, ``(a * sigmoid(h Wg)) Wo`` with
``a`` the heads' outputs side by side and ``Wg`` as wide as they are.
The kind picks two things.  A ``sliding`` layer rotates q and k
(``rope_theta``, half-split pairing) and reads under
``CausalSelfAttention``'s ``sliding_window`` mask of ``window``: a query
sees itself and the ``window - 1`` positions before it.  A ``full``
layer rotates NOTHING (it has no positions but the causal order) and
reads under the causal mask.

``MLP_l`` is a SwiGLU of ``dense_width`` for the first ``dense_layers``
layers and after them the routed expert layer: ``sigmoid`` router over
``num_experts``, top ``experts_per_tok`` by score plus a selection bias
(an aux state, no gradient), weights renormalized over the chosen and
multiplied by ``route_scale``, and one shared expert of ``shared_width``.
``experts_held`` > 0 builds one expert-parallel rank's share
(``MoEFeedForward``): experts ``first_expert ..`` only, the router still
``num_experts`` wide.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids.  Outputs, by
name: ``lm_output`` the per-token loss head (first, where the metric
reads it) and ``moe_load_output`` the expert blocks' load head.  The
loss head normalizes its own gradient, so ``rescale_grad`` is 1; there
is no load-balance loss.

Device scopes (``__scope__`` attributes, ``ops.transformer.node_scope``):
``attn_proj.l<i>`` (the q, k, v and o projections, head norms,
rotation) and ``attn_gate.l<i>`` (the gate's projection, sigmoid and
product) beside the ops' own ``attn.l<i>``, ``moe_*.l<i>`` and
``lm_loss``.
"""
from .. import symbol as sym
from ..moe.layer import with_load_heads
from .decoder import (block, embed, kind_attention, layer_kinds, lm_head_loss,
                      routed_experts, scoped, swiglu)


def afmoe_lm(num_layers, hidden_size, layer_types, dense_layers, num_heads,
             num_kv_heads, head_dim, window, rope_theta, dense_width,
             num_experts, experts_per_tok, expert_width, shared_width,
             route_scale, vocab_size, seq_len, embed_scale=1.0,
             experts_held=0, first_expert=0, bias_rate=1e-3, rms_eps=1e-5):
    """The training symbol; see the module docstring."""
    layer_types = layer_kinds(layer_types, num_layers)
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))

    def mlp(h, pre, layer):
        if layer < dense_layers:
            return swiglu(h, pre, dense_width, hidden_size, layer)
        return routed_experts(
            h, pre, layer, num_experts, experts_per_tok, expert_width,
            hidden_size, renormalize=True, score="sigmoid", scale=route_scale,
            bias_rate=bias_rate, shared_hidden=shared_width,
            experts_held=experts_held, first_expert=first_expert)

    x = embed(sym.Variable("data"), vocab_size, hidden_size)
    if embed_scale != 1.0:
        with scoped("", "embed"):
            x = x * float(embed_scale)
    for l, kind in enumerate(layer_types):
        pre = "l%d_" % l
        x = block(x, pre, rms_eps,
                  lambda h: kind_attention(
                      h, pre, l, kind, window, rope_theta, seq_len,
                      num_heads, num_kv_heads, head_dim, hidden_size,
                      rms_eps, gated=True),
                  lambda h: mlp(h, pre, l),
                  post_norms=("attn_post_norm", "ffn_post_norm"), layer=l)
    return with_load_heads(lm_head_loss(x, vocab_size, rms_eps))
