"""Qwen3-Next (``model_type`` ``qwen3_next``): a pre-norm decoder whose
mixers are Gated DeltaNet in three layers of four and gated softmax
attention in the fourth, over a wide sparse-expert MLP in every layer.

Token embedding -> L x [x + Mixer_l(RMSNorm(x)), x + MoE(RMSNorm(x))] ->
RMSNorm -> untied head.  Layers are counted from 0; layer ``l`` attends
in full where ``(l + 1) % full_attention_interval == 0``.  No projection
has a bias.

Gated DeltaNet (``sym.GatedDeltaNet``: the gated delta rule with ONE
decay a head and token), ``gdn_key_heads`` key heads under
``gdn_value_heads`` value heads of ``gdn_head_dim``: one fused projection
``qkvz_proj`` laid out key head by key head ``[q | k | v of its value
heads | z of its value heads]`` and one ``ba_proj`` ``[b | a]`` likewise;
q, k and v through one depthwise causal convolution of ``conv_kernel``
taps and SiLU, applied to the projection where it lies (``CausalConv1D``
takes a key head's q, k and v lanes and gives the channels in the
published filter's order, ``[q heads | k heads | v heads]``, and beside
them the z lanes as they are); the op (q and k L2-normalized, a key head
repeated for its value heads, ``beta = sigmoid(b)``, ``g = -exp(A_log)
softplus(a + dt_bias)``); the output through a per-head RMSNorm times
``silu(z)`` (``GatedRMSNorm``, on the rows as the op writes them and the
z lanes as the convolution hands them on), then ``o_proj``.

Gated attention: ``num_heads`` query heads over ``num_kv_heads``
key/value heads of ``head_dim``; ``q_proj`` is twice as wide as the
heads, head by head ``[q | gate]``; an RMSNorm over each head's lanes of
q and of k; rotary embedding (``rope_theta``, half-split pairing) on the
first ``rotary_dim`` lanes of every q and k head, the rest untouched;
causal softmax attention; ``(a * sigmoid(gate)) Wo``.

Every MLP is ``num_experts`` SwiGLU experts of ``expert_width``, softmax
over all router logits, the top ``experts_per_tok`` renormalized, plus a
shared expert of ``shared_width`` times ``sigmoid(h w_sg)``, one number a
token.  ``experts_held`` > 0 builds one expert-parallel rank's share
(``MoEFeedForward``): experts ``first_expert ..`` only, the router still
``num_experts`` wide.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids.  Outputs, by
name: ``lm_output`` the per-token loss head (first, where the metric
reads it), one ``*_aux_output`` load-balance head a block (absent with
``aux_coef`` 0) and ``moe_load_output``.  The loss head normalizes its
own gradient, so ``rescale_grad`` is 1.

Device scopes: ``gdn_proj.l<i>`` (the mixer's projections, convolution
and gated norm) around the op's own ``kda.l<i>`` (the rule: both front
ends of ``ops/linear_attention.py`` run it), ``attn_proj.l<i>`` and
``attn_gate.l<i>`` around ``attn.l<i>``, ``moe_*.l<i>`` and ``lm_loss``.
"""
from .. import symbol as sym
from ..moe.layer import with_aux_loss, with_load_heads
from .decoder import (block, cut, embed, gqa_attention, lm_head_loss, proj,
                      routed_experts, scoped)


def qwen3_next_lm(num_layers, hidden_size, full_attention_interval,
                  gdn_key_heads, gdn_value_heads, gdn_head_dim, conv_kernel,
                  num_heads, num_kv_heads, head_dim, rotary_dim, rope_theta,
                  num_experts, experts_per_tok, expert_width, shared_width,
                  vocab_size, seq_len, rms_eps=1e-6, aux_coef=0.001,
                  experts_held=0, first_expert=0):
    """The training symbol; see the module docstring."""
    if num_heads % num_kv_heads or gdn_value_heads % gdn_key_heads:
        raise ValueError("%d query heads over %d key/value heads, %d value "
                         "heads over %d key heads"
                         % (num_heads, num_kv_heads, gdn_value_heads,
                            gdn_key_heads))
    if not 0 < rotary_dim <= head_dim or rotary_dim % 2:
        raise ValueError("rotary_dim %d of a head of %d"
                         % (rotary_dim, head_dim))
    hk, hv, d = gdn_key_heads, gdn_value_heads, gdn_head_dim
    group = hv // hk

    def gdn(h, pre, l):
        with scoped("", "gdn_proj", l):
            qkvz = sym.Reshape(
                proj(h, pre + "qkvz_proj", hk * (2 + 2 * group) * d),
                shape=(-1, seq_len, hk, (2 + 2 * group) * d))
            ba = sym.Reshape(proj(h, pre + "ba_proj", 2 * hv),
                             shape=(-1, seq_len, hk, 2 * group))
            b, a = (sym.Reshape(x, shape=(-1, seq_len, hv))
                    for x in cut(ba, 3, group, group))
            mixed = sym.CausalConv1D(qkvz, kernel=conv_kernel,
                                     act_type="silu",
                                     lanes=(d, d, group * d),
                                     name=pre + "conv")
            q, k, v = (sym.Reshape(x, shape=(-1, seq_len, n, d))
                       for x, n in zip(cut(mixed[0], 2, hk * d, hk * d,
                                           hv * d), (hk, hk, hv)))
        o = sym.GatedDeltaNet(q, k, v, a, b, layer=l, name=pre + "gdn")
        with scoped("", "gdn_proj", l):
            o = sym.GatedRMSNorm(
                sym.Reshape(o, shape=(-1, seq_len, hv * d)), gate=mixed[1],
                head_dim=d, eps=rms_eps, act_type="silu",
                name=pre + "o_norm")
            return proj(sym.Reshape(o, shape=(-1, hv * d)), pre + "o_proj",
                        hidden_size)

    def rotate(x):
        """The first ``rotary_dim`` lanes of every head rotated, the
        rest as they are."""
        if rotary_dim == head_dim:
            return sym.RotaryEmbedding(x, theta=rope_theta)
        turned, kept = cut(x, 3, rotary_dim, head_dim - rotary_dim)
        return sym.Concat(sym.RotaryEmbedding(turned, theta=rope_theta),
                          kept, dim=3)

    def attention(h, pre, l):
        return gqa_attention(h, pre, l, seq_len, num_heads, num_kv_heads,
                             head_dim, hidden_size, rms_eps, rotate=rotate,
                             gated="query")

    x = embed(sym.Variable("data"), vocab_size, hidden_size)
    for l in range(num_layers):
        pre = "l%d_" % l
        mixer = attention if (l + 1) % full_attention_interval == 0 else gdn
        x = block(x, pre, rms_eps, lambda h: mixer(h, pre, l),
                  lambda h: routed_experts(
                      h, pre, l, num_experts, experts_per_tok, expert_width,
                      hidden_size, renormalize=True, score="softmax",
                      shared_hidden=shared_width, shared_gate=True,
                      experts_held=experts_held, first_expert=first_expert),
                  mixer_norm="mixer_norm", layer=l)
    net = lm_head_loss(x, vocab_size, rms_eps)
    if aux_coef:
        net = with_aux_loss(net, grad_scale=aux_coef)
    return with_load_heads(net)
