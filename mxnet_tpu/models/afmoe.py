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
from ..moe.layer import MoEFeedForward, with_load_heads
from .latent_attention import scoped

LAYER_KINDS = ("sliding", "full")


def afmoe_lm(num_layers, hidden_size, layer_types, dense_layers, num_heads,
             num_kv_heads, head_dim, window, rope_theta, dense_width,
             num_experts, experts_per_tok, expert_width, shared_width,
             route_scale, vocab_size, seq_len, embed_scale=1.0,
             experts_held=0, first_expert=0, bias_rate=1e-3, rms_eps=1e-5):
    """The training symbol; see the module docstring."""
    layer_types = list(layer_types)
    if len(layer_types) != num_layers \
            or any(kind not in LAYER_KINDS for kind in layer_types):
        raise ValueError("layer_types %r: %d layers, each one of %s"
                         % (layer_types, num_layers, LAYER_KINDS))
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_eps, name=name)

    def proj(x, name, width):
        return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                                  name=name)

    def heads(x, n):
        return sym.Reshape(x, shape=(-1, seq_len, n, head_dim))

    def attention(h, pre, layer, sliding):
        """h (B*T, D) -> (B*T, D).  The kind is the op's mask and
        whether the heads are rotated; nothing else differs."""
        def placed(x):
            return sym.RotaryEmbedding(x, theta=rope_theta) if sliding else x

        with scoped("", "attn_proj", layer):
            q = placed(norm(heads(proj(h, pre + "q_proj",
                                       num_heads * head_dim), num_heads),
                            pre + "q_norm"))
            k = placed(norm(heads(proj(h, pre + "k_proj",
                                       num_kv_heads * head_dim),
                                  num_kv_heads), pre + "k_norm"))
            v = heads(proj(h, pre + "v_proj", num_kv_heads * head_dim),
                      num_kv_heads)
        mask = dict(mask="sliding_window", window=window) if sliding else {}
        a = sym.CausalSelfAttention(q, k, v, layer=layer, name=pre + "attn",
                                    **mask)
        with scoped("", "attn_gate", layer):
            gate = sym.Activation(proj(h, pre + "attn_gate_proj",
                                       num_heads * head_dim),
                                  act_type="sigmoid")
            a = sym.Reshape(a, shape=(-1, num_heads * head_dim)) * gate
        with scoped("", "attn_proj", layer):
            return proj(a, pre + "o_proj", hidden_size)

    def mlp(h, pre, layer, dense):
        if dense:
            gate = sym.Activation(proj(h, pre + "gate_proj", dense_width),
                                  act_type="silu")
            return proj(gate * proj(h, pre + "up_proj", dense_width),
                        pre + "down_proj", hidden_size)
        return MoEFeedForward(
            h, num_hidden=expert_width, num_experts=num_experts,
            k=experts_per_tok, capacity_factor=0.0, name=pre + "moe",
            act_type="silu", gated=True, no_bias=True, layer=layer,
            renormalize=True, score="sigmoid", scale=route_scale,
            bias_rate=bias_rate, shared_hidden=shared_width,
            output_dim=hidden_size, experts_held=experts_held,
            first_expert=first_expert)

    x = sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                      output_dim=hidden_size, name="embed")
    x = sym.Reshape(x, shape=(-1, hidden_size))             # (B*T, D)
    if embed_scale != 1.0:
        x = x * float(embed_scale)
    for l, kind in enumerate(layer_types):
        pre = "l%d_" % l
        x = x + norm(attention(norm(x, pre + "attn_norm"), pre, l,
                               kind == "sliding"), pre + "attn_post_norm")
        x = x + norm(mlp(norm(x, pre + "ffn_norm"), pre, l,
                         l < dense_layers), pre + "ffn_post_norm")
    logits = proj(norm(x, "final_norm"), "lm_head", vocab_size)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    loss = sym.SoftmaxCELoss(logits, label, name="lm_loss")
    return with_load_heads(sym.MakeLoss(loss, normalization="batch",
                                        name="lm"))
