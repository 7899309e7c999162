"""SmallThinker (``model_name`` ``smallthinker_*``, PowerInfer): a
pre-norm decoder whose layers mix sliding-window and position-free full
attention, and whose router reads the rows attention reads.

Token embedding -> L x [x' = x + Attn_l(h), x'' = x' + MoE(N2(x') ; h)]
with ``h = N1(x)`` -> RMSNorm -> untied vocabulary head.  Two RMSNorms
with gains a layer, no norm over a head's lanes, no bias anywhere, no
dense layer, no shared expert, no embedding scale.

``layer_types`` names the kind of every layer BUILT, ``sliding`` or
``full``.  Both kinds are one block: grouped-query attention
(``num_heads`` query heads over ``num_kv_heads`` key/value heads of
``head_dim``).  The kind picks two things and nothing else.  A
``sliding`` layer rotates q and k (``rope_theta``, half-split pairing,
all lanes) and reads under ``CausalSelfAttention``'s ``sliding_window``
mask of ``window``: a query sees itself and the ``window - 1`` positions
before it.  A ``full`` layer rotates NOTHING (it has no positions but
the causal order) and reads under the causal mask.

The expert layer: the router's logits are ``h Wr`` over all
``num_experts``, read from the MIXER's normed rows ``h`` (the router is
placed before attention), not from the rows the experts read.  The top
``experts_per_tok`` logits are chosen and softmaxed among themselves
(softmax over all, renormalized over the chosen: the same weights);
each expert is a ReGLU of ``expert_width``, ``(relu(g Wg) * (g Wu)) Wd``
over ``g = N2(x')``.  ``experts_held`` > 0 builds one expert-parallel
rank's share (``MoEFeedForward``): experts ``first_expert ..`` only, the
router still ``num_experts`` wide.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids.  Outputs, by
name: ``lm_output`` the per-token loss head (first, where the metric
reads it), ``moe_load_output`` the expert blocks' load head and, with
``act_zeros`` (a rank's share only), ``moe_act_zeros_output``: a block's
``(zeros, lanes)`` of the gate lanes ``relu(g Wg)`` of the rows the rank
really held, which ``Module.fit`` records as the counter
``moe:act_zeros`` while tracing is on.  The loss head normalizes its own
gradient, so ``rescale_grad`` is 1; there is no load-balance loss and no
selection bias.

Device scopes: ``attn_proj.l<i>`` (the q, k, v and o projections, the
rotation) beside the ops' own ``attn.l<i>``, ``moe_*.l<i>`` and
``lm_loss``.
"""
from .. import symbol as sym
from ..moe.layer import with_act_zeros_head, with_load_heads
from .decoder import (block, embed, kind_attention, layer_kinds, lm_head_loss,
                      routed_experts)


def smallthinker_lm(num_layers, hidden_size, layer_types, num_heads,
                    num_kv_heads, head_dim, window, rope_theta, num_experts,
                    experts_per_tok, expert_width, vocab_size, seq_len,
                    experts_held=0, first_expert=0, rms_eps=1e-6,
                    act_zeros=False):
    """The training symbol; see the module docstring."""
    layer_types = layer_kinds(layer_types, num_layers)
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))

    x = embed(sym.Variable("data"), vocab_size, hidden_size)
    for l, kind in enumerate(layer_types):
        pre = "l%d_" % l
        x = block(x, pre, rms_eps,
                  lambda h: kind_attention(
                      h, pre, l, kind, window, rope_theta, seq_len,
                      num_heads, num_kv_heads, head_dim, hidden_size,
                      rms_eps, head_norms=False),
                  lambda g, h: routed_experts(
                      g, pre, l, num_experts, experts_per_tok, expert_width,
                      hidden_size, act_type="relu", renormalize=True,
                      score="softmax", router_data=h,
                      experts_held=experts_held, first_expert=first_expert,
                      act_zeros=act_zeros),
                  mlp_sees_mixer_rows=True, layer=l)
    return with_act_zeros_head(with_load_heads(
        lm_head_loss(x, vocab_size, rms_eps)))
