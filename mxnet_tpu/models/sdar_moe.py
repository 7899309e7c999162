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
from ..moe.layer import MoEFeedForward, with_aux_loss, with_load_heads
from .latent_attention import scoped

NOISE_HEAD = "diffusion_noise"


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

    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_eps, name=name)

    def proj(x, name, width):
        return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                                  name=name)

    def heads(x, n):
        return sym.Reshape(x, shape=(-1, rows, n, head_dim))

    def rotate(x):
        return sym.RotaryEmbedding(x, theta=rope_theta, period=seq_len)

    x = sym.Embedding(sym.Variable("data"), input_dim=vocab_size,
                      output_dim=hidden_size, name="embed")
    x = sym.Reshape(x, shape=(-1, hidden_size))           # (B*2T, D)
    for l in range(num_layers):
        pre = "l%d_" % l
        h = norm(x, pre + "attn_norm")
        with scoped("", "attn_proj", l):
            q = rotate(norm(heads(proj(h, pre + "q_proj",
                                       num_heads * head_dim), num_heads),
                            pre + "q_norm"))
            k = rotate(norm(heads(proj(h, pre + "k_proj",
                                       num_kv_heads * head_dim),
                                  num_kv_heads), pre + "k_norm"))
            v = heads(proj(h, pre + "v_proj", num_kv_heads * head_dim),
                      num_kv_heads)
        a = sym.CausalSelfAttention(q, k, v, layer=l, name=pre + "attn",
                                    mask="block_diffusion", block=block_len)
        with scoped("", "attn_proj", l):
            x = x + proj(sym.Reshape(a, shape=(-1, num_heads * head_dim)),
                         pre + "o_proj", hidden_size)
        x = x + MoEFeedForward(
            norm(x, pre + "ffn_norm"), num_hidden=expert_width,
            num_experts=num_experts, k=experts_per_tok, capacity_factor=0.0,
            name=pre + "moe", act_type="silu", gated=True, no_bias=True,
            layer=l, renormalize=True, score="softmax",
            output_dim=hidden_size, experts_held=experts_held,
            first_expert=first_expert)
    # the head reads the noised half: rows 0..T-1 of each sequence
    noised = sym.slice_axis(sym.Reshape(x, shape=(-1, rows, hidden_size)),
                            axis=1, begin=0, end=seq_len)
    logits = proj(norm(sym.Reshape(noised, shape=(-1, hidden_size)),
                       "final_norm"), "lm_head", vocab_size)
    label = sym.Variable("softmax_label")                  # (B, 2, T)
    target, weight = (sym.Reshape(sym.slice_axis(label, axis=1, begin=i,
                                                 end=i + 1), shape=(-1,))
                      for i in (0, 1))
    loss = sym.SoftmaxCELoss(logits, target, use_ignore=True,
                             ignore_label=-1, name="lm_loss") * weight
    net = sym.MakeLoss(loss, normalization="batch", name="lm")
    if aux_coef:
        net = with_aux_loss(net, grad_scale=aux_coef)
    masked = sym.sign(target + 1.0)             # 1 where there is a target
    noise = sym.Concat(*(sym.Reshape(sym.sum(s), shape=(1,)) for s in (
        masked, masked * 0.0 + 1.0, masked * weight)), dim=0)
    return sym.Group([with_load_heads(net),
                      sym.BlockGrad(noise, name=NOISE_HEAD)])
