"""SDAR-MoE (``model_type`` ``sdar_moe``): a Qwen3-MoE-shaped pre-norm
decoder trained by diffusion over blocks (block diffusion,
arXiv:2503.09573, in its vectorised form).

A training row is a DOUBLED sequence of ``2 T`` ids, ``[x_t ; x_0]``: the
noised copy (some positions replaced by the MASK id) and then the clean
one, row ``n`` at position ``n mod T`` in block ``(n mod T) // block_len``.
Token embedding -> L x [x + Attn(RMSNorm(x)), x + MoE(RMSNorm(x))] ->
RMSNorm -> untied head, over the NOISED half only: row ``i`` predicts the
clean token of position ``i`` (no shift).

Attention is grouped-query (``num_heads`` query heads over
``num_kv_heads`` key/value heads of ``head_dim``), with an RMSNorm over
each head's lanes of q and of k (one gain vector each), rotary embedding
at ``n mod T``, and the ``block_diffusion`` mask of
``CausalSelfAttention``: a noised row sees its own block's noised rows
and the clean rows of earlier blocks, a clean row the clean rows of its
own and earlier blocks.  Every MLP is ``num_experts`` SwiGLU experts of
``expert_width``, softmax over all router logits, the top
``experts_per_tok`` renormalized, no shared expert.  ``experts_held`` > 0
builds one expert-parallel rank's share (``MoEFeedForward``): experts
``first_expert ..`` only, the router still ``num_experts`` wide.

Inputs, through ``Module.fit``'s two default names: ``data`` ``(B, 2 T)``
ids and ``softmax_label`` ``(B, 2, T)`` float32 (ids are exact there):
``[:, 0]`` the clean id where the position is masked and -1 where it is
not, ``[:, 1]`` its block's weight ``1 / t``.  The loss row of position
``i`` is ``weight_i * CE(logits_i, x_0^i)`` where masked and exactly 0
elsewhere, and the head's gradient is ``1 / (B T)`` a row, so the
objective is ``(1 / T) sum_i [masked] CE_i / t`` a sequence (``ln V`` at
chance) ``+ aux_coef * sum(load balance)``; ``rescale_grad`` is 1.

Outputs, by name: ``lm_output`` the weighted per-position loss (first,
where the metric reads it), one ``*_aux_output`` a block (absent with
``aux_coef`` 0), ``moe_load_output``, and ``diffusion_noise_output``: the
step's ``(masked positions, positions, sum of the masked positions'
weights)``, no gradient, which ``Module.fit`` records as the counter
``diffusion:noise`` while tracing is on.

Device scopes: ``attn_proj.l<i>`` (projections, head norms, rotation)
beside the ops' own ``attn.l<i>``, ``moe_*.l<i>`` and ``lm_loss``.
"""
from .. import symbol as sym
from ..moe.layer import with_aux_loss, with_load_heads
from ..trace.heads import DIFFUSION_NOISE
from .decoder import (block, embed, gqa_attention, lm_head_loss,
                      routed_experts, scoped)


def sdar_moe_lm(num_layers, hidden_size, num_heads, num_kv_heads, head_dim,
                num_experts, experts_per_tok, expert_width, vocab_size,
                seq_len, block_len=4, rope_theta=1e6, rms_eps=1e-6,
                aux_coef=0.001, experts_held=0, first_expert=0):
    """The training symbol; ``seq_len`` is the clean length ``T``.  See
    the module docstring."""
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))
    if seq_len % block_len:
        raise ValueError("seq_len %d is not whole blocks of %d"
                         % (seq_len, block_len))
    rows = 2 * seq_len
    x = embed(sym.Variable("data"), vocab_size, hidden_size)  # (B*2T, D)
    for l in range(num_layers):
        pre = "l%d_" % l
        # this builder's first sum lies in its ``o_proj``'s scope
        x = block(
            x, pre, rms_eps,
            lambda h: gqa_attention(
                h, pre, l, rows, num_heads, num_kv_heads, head_dim,
                hidden_size, rms_eps, mask="block_diffusion", block=block_len,
                rotate=dict(theta=rope_theta, period=seq_len)),
            lambda h: routed_experts(
                h, pre, l, num_experts, experts_per_tok, expert_width,
                hidden_size, renormalize=True, score="softmax",
                experts_held=experts_held, first_expert=first_expert),
            sum_scopes=(scoped("", "attn_proj", l), None), layer=l)
    # the head reads the noised half: rows 0..T-1 of each sequence
    with scoped("", "lm_head"):
        noised = sym.slice_axis(
            sym.Reshape(x, shape=(-1, rows, hidden_size)), axis=1, begin=0,
            end=seq_len)
        noised = sym.Reshape(noised, shape=(-1, hidden_size))
    label = sym.Variable("softmax_label")                  # (B, 2, T)
    target, weight = (sym.Reshape(sym.slice_axis(label, axis=1, begin=i,
                                                 end=i + 1), shape=(-1,))
                      for i in (0, 1))
    net = lm_head_loss(noised, vocab_size, rms_eps, label=target,
                       row_weight=weight, use_ignore=True, ignore_label=-1)
    if aux_coef:
        net = with_aux_loss(net, grad_scale=aux_coef)
    masked = sym.sign(target + 1.0)             # 1 where there is a target
    noise = sym.Concat(*(sym.Reshape(sym.sum(s), shape=(1,)) for s in (
        masked, masked * 0.0 + 1.0, masked * weight)), dim=0)
    return sym.Group([with_load_heads(net),
                      sym.BlockGrad(noise, name=DIFFUSION_NOISE.name)])
