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
from ..moe.layer import with_aux_loss, with_load_heads
from .decoder import (block, embed, lm_head_loss, norm, proj,
                      routed_experts, scoped)


def olmoe_lm(num_layers, hidden_size, num_heads, num_experts,
             experts_per_tok, expert_width, vocab_size, seq_len,
             rope_theta=10000.0, rms_eps=1e-5, aux_coef=0.01):
    """The training symbol; see the module docstring."""
    head_dim = hidden_size // num_heads
    if head_dim * num_heads != hidden_size:
        raise ValueError("hidden_size %d is not num_heads %d x head_dim"
                         % (hidden_size, num_heads))

    def heads(x):
        return sym.Reshape(x, shape=(-1, seq_len, num_heads, head_dim))

    def attention(h, pre, l):
        # ``attn_proj.l<l>`` around the op's own ``attn.l<l>``
        with scoped("", "attn_proj", l):
            q, k = (sym.RotaryEmbedding(heads(norm(
                proj(h, pre + s + "_proj", hidden_size), pre + s + "_norm",
                rms_eps)), theta=rope_theta) for s in "qk")
            v = heads(proj(h, pre + "v_proj", hidden_size))
        a = sym.CausalSelfAttention(q, k, v, layer=l, name=pre + "attn")
        with scoped("", "attn_proj", l):
            return proj(sym.Reshape(a, shape=(-1, hidden_size)),
                        pre + "o_proj", hidden_size)

    x = embed(sym.Variable("data"), vocab_size, hidden_size)
    for l in range(num_layers):
        pre = "l%d_" % l
        x = block(x, pre, rms_eps, lambda h: attention(h, pre, l),
                  lambda h: routed_experts(h, pre, l, num_experts,
                                           experts_per_tok, expert_width),
                  layer=l)
    net = lm_head_loss(x, vocab_size, rms_eps)
    return with_load_heads(with_aux_loss(net, grad_scale=aux_coef))
