"""Granite 4.0-H (``model_type`` ``granitemoehybrid``, IBM's
granite-4.0-h-micro): a pre-norm decoder whose mixers are Mamba-2
state-space mixers in nine layers of ten and grouped-query attention
with no positions in the tenth, over a dense SwiGLU in every layer, with
scaled residual adds and ONE weight for embedding and head.

``h = embedding_multiplier * E[ids]``; a layer is

    h = h + residual_multiplier * Mixer_l(RMSNorm(h))
    h = h + residual_multiplier * W_out(silu(g) * u),  [g | u] = W_in RMSNorm(h)

then ``logits = RMSNorm(h) E^T / logits_scaling`` and the mean cross
entropy.  No projection has a bias.

``layer_types`` names the mixer of every layer BUILT, one of
``MIXER_KINDS``.  A ``mamba`` layer (Mamba-2, arXiv:2405.21060): ``[z |
xBC | dt] = u W_in`` (``ssm_heads * ssm_head_dim`` | that + ``2
ssm_groups ssm_state`` | ``ssm_heads`` wide, in that order); ``xBC =
silu(conv(xBC) + b)``, a depthwise causal convolution of ``conv_kernel``
taps with a bias (``CausalConv1D(no_bias=False)``); ``[x | B | C] =
xBC``; the scan ``SSDScan`` (``ops/ssd.py``: ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``, ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t
x_t^T``, ``y_t = C_t^T S_t + D x_t`` a head, float32); ``y = RMSNorm(y *
silu(z))`` over ALL the mixer's lanes, with a gain; ``y W_out``.  An
``attention`` layer: ``num_heads`` query heads over ``num_kv_heads``
key/value heads of ``head_dim``, no bias, no head norm, no rotation (the
causal order is the only position), ``softmax(q k^T *
attention_multiplier + causal) v``, ``o Wo``.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids; the output
``lm_output`` is the per-token loss head.  ``embed_weight`` is used twice
in the graph (the lookup and the head) and its gradient is the sum of
both uses.  The loss head normalizes its own gradient, so
``rescale_grad`` is 1.  ``A_log`` and ``dt_bias`` are the scan node's
``*_a_log_bias`` and ``*_dt_bias`` (zero under this package's
initializers), ``D`` its ``*_d_gamma`` (one).

No node is marked ``force_mirroring``: the mark is a node's own
(``executor.py`` checkpoints node by node), so what passes between nodes
is kept either way and a marked graph only stops XLA from fusing across
them.  Compiled for a described v5e at the published widths, ten layers
of 4096 rows hold 4.42 GiB of temporaries unmarked and 6.82 GiB with
every layer's nodes marked
(``benchmark/configs/granite-4.0-h-micro.json`` has the bytes).

Device scopes: ``ssm_proj.l<i>`` (a mixer's in- and out-projection),
``ssm_conv.l<i>`` (the convolution, either lowering), the scan's own
``ssm_scan.l<i>``, ``ssm_norm.l<i>`` (the gate and the norm),
``attn_proj.l<i>`` beside ``attn.l<i>``, and ``lm_loss``.
"""
from .. import symbol as sym
from .decoder import (block, cut, embed, gqa_attention, layer_kinds,
                      lm_head_loss, mamba_mixer, proj, scoped)

MIXER_KINDS = ("mamba", "attention")


def granite_hybrid_lm(num_layers, hidden_size, layer_types, ssm_heads,
                      ssm_head_dim, ssm_state, ssm_groups, conv_kernel,
                      num_heads, num_kv_heads, head_dim, mlp_width, vocab_size,
                      seq_len, embedding_multiplier, residual_multiplier,
                      attention_multiplier, logits_scaling, rms_eps=1e-5):
    """The training symbol; see the module docstring."""
    layer_types = layer_kinds(layer_types, num_layers, MIXER_KINDS)
    if num_heads % num_kv_heads or ssm_heads % ssm_groups:
        raise ValueError("%d query heads over %d key/value heads, %d "
                         "state-space heads over %d groups"
                         % (num_heads, num_kv_heads, ssm_heads, ssm_groups))

    def mixer(h, pre, l, kind):
        if kind == "mamba":
            y = mamba_mixer(h, pre, l, seq_len, hidden_size, ssm_heads,
                            ssm_head_dim, ssm_state, ssm_groups, conv_kernel,
                            rms_eps)
        else:
            y = gqa_attention(
                h, pre, l, seq_len, num_heads, num_kv_heads, head_dim,
                hidden_size, rms_eps, head_norms=False,
                scale=attention_multiplier)
        with scoped("", "residual", l):
            return y * residual_multiplier

    def mlp(h, pre, l):
        with scoped("", "mlp", l):
            gate, up = cut(proj(h, pre + "input_linear", 2 * mlp_width), 1,
                           mlp_width, mlp_width)
            return proj(sym.Activation(gate, act_type="silu") * up,
                        pre + "output_linear", hidden_size) \
                * residual_multiplier

    table = sym.Variable("embed_weight")
    x = embed(sym.Variable("data"), vocab_size, hidden_size, weight=table)
    with scoped("", "embed"):
        x = x * embedding_multiplier
    for l, kind in enumerate(layer_types):
        pre = "l%d_" % l
        x = block(x, pre, rms_eps, lambda h: mixer(h, pre, l, kind),
                  lambda h: mlp(h, pre, l), mixer_norm="mixer_norm", layer=l)
    return lm_head_loss(x, vocab_size, rms_eps, head_weight=table,
                        logits_divisor=logits_scaling)
