"""OLMoE: a pre-norm decoder whose every MLP is a routed expert layer.

Muennighoff et al. 2024 (arXiv:2409.02060), as the ``olmoe`` modelling
code lays it out: token embedding -> L x [x + Attn(RMSNorm(x)),
x + MoE(RMSNorm(x))] -> RMSNorm -> untied vocabulary head.  Attention is
plain multi-head with RMSNorm over the whole q and k projections before
the heads are split, and rotary position embedding.  The MLP is
``num_experts`` SwiGLU experts of width ``expert_width``, softmax over
all router logits then top-``experts_per_tok`` without renormalizing,
no shared expert, and no token-choice dropped.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids; outputs the
per-token loss head, one load-balance head a block (coefficient
``aux_coef``) and each block's counts/dropped heads.  The loss head
normalizes its own gradient (1 / tokens), so the optimizer's
``rescale_grad`` is 1 and the objective is ``mean CE + aux_coef *
sum(load balance)``.
"""
from .. import symbol as sym
from ..moe.layer import MoEFeedForward, with_aux_loss, with_load_heads


def olmoe_lm(num_layers, hidden_size, num_heads, num_experts,
             experts_per_tok, expert_width, vocab_size, seq_len,
             rope_theta=10000.0, rms_eps=1e-5, aux_coef=0.01):
    """The training symbol; see the module docstring."""
    head_dim = hidden_size // num_heads
    if head_dim * num_heads != hidden_size:
        raise ValueError("hidden_size %d is not num_heads %d x head_dim"
                         % (hidden_size, num_heads))

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_eps, name=name)

    def proj(x, name, width=hidden_size):
        return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                                  name=name)

    def heads(x):
        return sym.Reshape(x, shape=(-1, seq_len, num_heads, head_dim))

    x = sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                      output_dim=hidden_size, name="embed")
    x = sym.Reshape(x, shape=(-1, hidden_size))           # (B*T, D)
    for l in range(num_layers):
        pre = "l%d_" % l
        h = norm(x, pre + "attn_norm")
        q = norm(proj(h, pre + "q_proj"), pre + "q_norm")
        k = norm(proj(h, pre + "k_proj"), pre + "k_norm")
        v = proj(h, pre + "v_proj")
        q = sym.RotaryEmbedding(heads(q), theta=rope_theta)
        k = sym.RotaryEmbedding(heads(k), theta=rope_theta)
        a = sym.CausalSelfAttention(q, k, heads(v), layer=l,
                                    name=pre + "attn")
        a = sym.Reshape(a, shape=(-1, hidden_size))
        x = x + proj(a, pre + "o_proj")
        h = norm(x, pre + "ffn_norm")
        x = x + MoEFeedForward(h, num_hidden=expert_width,
                               num_experts=num_experts, k=experts_per_tok,
                               capacity_factor=0.0, name=pre + "moe",
                               act_type="silu", gated=True, no_bias=True,
                               layer=l)
    logits = proj(norm(x, "final_norm"), "lm_head", vocab_size)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    loss = sym.SoftmaxCELoss(logits, label, name="lm_loss")
    net = sym.MakeLoss(loss, normalization="batch", name="lm")
    return with_load_heads(with_aux_loss(net, grad_scale=aux_coef))
