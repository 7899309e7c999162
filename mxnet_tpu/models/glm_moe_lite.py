"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): a DeepSeek-V3-shaped
pre-norm decoder (arXiv:2412.19437) with a multi-token-prediction module.

Token embedding -> L x [x + MLA(RMSNorm(x)), x + MLP_l(RMSNorm(x))] ->
RMSNorm -> untied vocabulary head.  Layers are counted from 0, as the
published config counts them.

The mixer of every layer is latent attention (``latent_attention``, the
assembly this package's decoders share) with the queries compressed
through a normed low-rank pair (``q_lora_rank``) and the rope part of
every query head and the one shared key part rotated at ``rope_theta``.
``MLP_l`` is a SwiGLU of ``dense_width`` for the first ``dense_layers``
layers and after them the routed expert layer: ``sigmoid`` router over
``num_experts``, top ``experts_per_tok`` by score plus a selection bias
(an aux state, no gradient; no group limit), weights renormalized over
the chosen and multiplied by ``routed_scale``, and one shared expert.
``experts_held`` > 0 builds one expert-parallel rank's share
(``MoEFeedForward``): experts ``first_expert ..`` only, the router still
``num_experts`` wide.

``nextn_layers`` prediction modules follow (DeepSeek-V3 section 2.2;
this builder takes 0 or 1).  With ``x`` the trunk's last residual state
(before its final norm) and ``t`` the tokens, position ``i`` of the
module reads ``[RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_i)] W_eh`` (``2 D ->
D``), runs one more block of the kind above with its own weights, and
predicts ``t_{i+2}`` through an RMSNorm of its own and THE TRUNK'S head;
``Emb`` is THE TRUNK'S embedding: ``embed_weight`` and
``lm_head_weight`` are each used twice in the graph, and their gradients
are the sums of both uses.  ``t_{i+1}`` is ``softmax_label`` and the
target is ``softmax_label`` moved one place; the last position of each
sequence has no target (its label is ``SoftmaxCELoss``'s
``ignore_label``) and is outside the second loss and its normalization.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids.  Outputs, by
name: ``lm_output`` the per-token loss head (first, where the metric
reads it), ``mtp_output`` the module's per-token loss head at
``mtp_weight`` (absent with no module), ``moe_load_output`` the expert
blocks' load head.  Both loss heads normalize their own gradients, so
``rescale_grad`` is 1; there is no load-balance loss.

Device scopes (``__scope__`` attributes, ``ops.transformer.node_scope``):
``mla_q.l<i>``, ``mla_kv.l<i>``, ``rope.l<i>`` beside the ops' own
``attn.l<i>`` and ``moe_*.l<i>``; the module's under ``mtp.``
(``mtp.eh_proj``, ``mtp.mla_q``, ``mtp.attn``, ``mtp.moe_experts``,
``mtp.lm_head``, ``mtp.residual``, ``mtp.lm_loss``, ...).  The shared
skeleton names the rest (``embed``, ``block_norm.l<i>``,
``residual.l<i>``, ``mlp.l<i>``, ``attn_proj.l<i>``, ``lm_head``); the
module's block norms, first sum and output projection take those names
with no index and no prefix: they were never under ``mtp.``.
"""
from .. import symbol as sym
from ..moe.layer import with_load_heads
from .decoder import (block, embed, lm_head_loss, norm, proj,
                      routed_experts, scoped, swiglu)
from .latent_attention import latent_attention


def glm_moe_lite_lm(num_layers, hidden_size, dense_layers, heads,
                    q_lora_rank, kv_lora_rank, qk_nope_dim, qk_rope_dim,
                    v_head_dim, rope_theta, dense_width, num_experts,
                    experts_per_tok, expert_width, shared_width,
                    routed_scale, vocab_size, seq_len, nextn_layers=1,
                    mtp_weight=0.3, experts_held=0, first_expert=0,
                    bias_rate=1e-3, rms_eps=1e-5):
    """The training symbol; see the module docstring."""
    if nextn_layers not in (0, 1):
        raise ValueError("glm_moe_lite_lm builds 0 or 1 prediction "
                         "modules, not %r" % (nextn_layers,))

    def layer_block(x, pre, layer, dense, scope):
        """One decoder block; ``layer`` is the ops' trace index (-1:
        none), ``scope`` the prefix of its device scopes."""
        def mla(h):
            return latent_attention(
                h, pre, seq_len, hidden_size, heads, kv_lora_rank,
                qk_nope_dim, qk_rope_dim, v_head_dim, rms_eps, layer=layer,
                q_lora_rank=q_lora_rank, rope_theta=rope_theta, scope=scope)

        def mlp(h):
            if dense:
                return swiglu(h, pre, dense_width, hidden_size, layer, scope)
            with scoped(scope):
                return routed_experts(
                    h, pre, layer, num_experts, experts_per_tok,
                    expert_width, hidden_size, renormalize=True,
                    score="sigmoid", scale=routed_scale,
                    bias_rate=bias_rate, shared_hidden=shared_width,
                    experts_held=experts_held, first_expert=first_expert)

        return block(x, pre, rms_eps, mla, mlp, mixer_norm="mixer_norm",
                     sum_scopes=(None, scoped(scope, "residual")
                                 if scope and not dense else None),
                     layer=layer)

    def flat(label):
        return sym.Reshape(label, shape=(-1,))

    embed_weight = sym.Variable("embed_weight")
    lm_head = sym.Variable("lm_head_weight")
    label = sym.Variable("softmax_label")
    x = embed(sym.Variable("data"), vocab_size, hidden_size,
              weight=embed_weight)
    for l in range(num_layers):
        x = layer_block(x, "l%d_" % l, l, l < dense_layers, "")
    heads_out = [lm_head_loss(x, vocab_size, rms_eps, label=flat(label),
                              head_weight=lm_head)]
    if nextn_layers:
        with scoped("mtp.", "eh_proj"):
            u = proj(sym.Concat(
                norm(embed(label, vocab_size, hidden_size, "mtp_embed",
                           scope=None, weight=embed_weight), "mtp_enorm",
                     rms_eps),
                norm(x, "mtp_hnorm", rms_eps), dim=1),
                "mtp_eh_proj", hidden_size)
        u = layer_block(u, "mtp_", -1, False, "mtp.")
        # the target of position i is the token after its label: the
        # labels moved one place, the sequence's last position left out
        last = sym.slice_axis(label, axis=1, begin=0, end=1) * 0 - 1
        target = sym.Concat(sym.slice_axis(label, axis=1, begin=1,
                                           end=seq_len), last, dim=1)
        with scoped("mtp.", "lm_head"):
            logits = proj(norm(u, "mtp_final_norm", rms_eps), "mtp_lm_head",
                          vocab_size, weight=lm_head)
        with scoped("mtp."):        # the loss names itself: mtp.lm_loss
            loss = sym.SoftmaxCELoss(logits, flat(target), name="mtp_loss",
                                     use_ignore=True, ignore_label=-1)
        heads_out.append(sym.MakeLoss(loss, grad_scale=float(mtp_weight),
                                      normalization="valid", name="mtp"))
    return with_load_heads(sym.Group(heads_out))
