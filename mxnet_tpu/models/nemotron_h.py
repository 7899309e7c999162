"""Nemotron-H (``model_type`` ``nemotron_h``, NVIDIA's Nemotron-3-Nano-
30B-A3B): a pre-norm decoder whose layers are ONE branch each, by a
published pattern (``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
``E`` a routed expert layer, ``*`` attention), with an untied head.

``h = E[ids]``; every layer is

    h = h + Mixer_l(RMSNorm(h))

and NOTHING else (no MLP behind a mixer, no mixer before an expert
layer); then ``logits = RMSNorm(h) W_head`` and the mean cross entropy.
No projection has a bias.

``layer_types`` names the branch of every layer BUILT, one of
``LAYER_KINDS``.  ``mamba`` (``decoder.mamba_mixer``, arXiv:2405.21060):
``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``; ``[x | B | C] =
xBC`` with ``B`` and ``C`` in ``ssm_groups`` groups of ``ssm_state``, head
``j`` reading group ``j // (ssm_heads / ssm_groups)``; the scan
``SSDScan`` (``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, ``S_t =
exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t^T S_t + D x_t``,
float32); ``y = y * silu(z)``, then an RMSNorm whose statistic is over EACH
GROUP's ``ssm_heads * ssm_head_dim / ssm_groups`` lanes (the gate before
the norm) times ONE gain over all lanes; ``y W_out``.  ``moe``: ``s =
sigmoid(u W_r)`` over all ``num_experts`` in float32; the
``experts_per_tok`` largest of ``s + b`` chosen (``b`` the selection bias:
it enters the choice only and moves by ``bias_rate * sign(mean load -
load)`` a step); weights the chosen ``s`` over their sum, times
``route_scale``; an expert is PLAIN, two matrices, ``relu(u W_up)^2
W_down`` (``expert_width`` wide, no gate projection); plus one shared
expert of the same form, ``shared_width`` wide, on every token.
``experts_held`` > 0 is one expert-parallel rank's share: the router, the
renormalisation and the load head stay ``num_experts`` wide, the stacked
weights hold experts ``first_expert`` on, and rows that chose another
expert add nothing (``moe.layer.MoEFeedForward``).  ``attention``:
``num_heads`` query heads over ``num_kv_heads`` key/value heads of
``head_dim``, no bias, no head norm, NO rotation (the causal order is the
only position), ``softmax(q k^T / sqrt(head_dim) + causal) v``, ``o Wo``.

The symbol trains through ``Module.fit`` as it stands: inputs ``data`` and
``softmax_label``, both ``(batch, seq_len)`` token ids; the output
``lm_output`` is the per-token loss head, grouped with the load head
``moe_load`` (``with_load_heads``).  The loss head normalizes its own
gradient, so ``rescale_grad`` is 1.  ``A_log`` and ``dt_bias`` are the scan
node's ``*_a_log_bias`` and ``*_dt_bias`` (zero under this package's
initializers), ``D`` its ``*_d_gamma`` (one).  No node is marked
``force_mirroring`` (``models/granite_hybrid.py`` says why).

Device scopes: ``ssm_proj`` / ``ssm_conv`` / ``ssm_scan`` / ``ssm_norm``,
``moe_route`` / ``moe_experts`` / ``moe_combine`` / ``moe_share`` and
``mlp`` (the shared expert), ``attn_proj`` / ``attn``, each ``.l<i>``,
``block_norm.l<i>``, ``residual.l<i>``, ``embed``, ``lm_head``,
``lm_loss``.
"""
from .. import symbol as sym
from ..moe.layer import with_load_heads
from .decoder import (embed, gqa_attention, layer_kinds, lm_head_loss,
                      mamba_mixer, one_branch_block, routed_experts)

LAYER_KINDS = ("mamba", "moe", "attention")
# ``hybrid_override_pattern``'s characters
PATTERN = {"M": "mamba", "E": "moe", "*": "attention"}


def nemotron_h_lm(num_layers, hidden_size, layer_types, ssm_heads,
                  ssm_head_dim, ssm_state, ssm_groups, conv_kernel, num_heads,
                  num_kv_heads, head_dim, num_experts, experts_per_tok,
                  expert_width, shared_width, route_scale, vocab_size,
                  seq_len, rms_eps=1e-5, bias_rate=1e-3, experts_held=0,
                  first_expert=0):
    """The training symbol; see the module docstring."""
    layer_types = layer_kinds(layer_types, num_layers, LAYER_KINDS)
    if num_heads % num_kv_heads or ssm_heads % ssm_groups:
        raise ValueError("%d query heads over %d key/value heads, %d "
                         "state-space heads over %d groups"
                         % (num_heads, num_kv_heads, ssm_heads, ssm_groups))

    def branch(h, pre, l, kind):
        if kind == "mamba":
            return mamba_mixer(h, pre, l, seq_len, hidden_size, ssm_heads,
                               ssm_head_dim, ssm_state, ssm_groups,
                               conv_kernel, rms_eps, norm_groups=ssm_groups)
        if kind == "moe":
            return routed_experts(
                h, pre, l, num_experts, experts_per_tok, expert_width,
                hidden_size, act_type="relu2", gated=False, renormalize=True,
                score="sigmoid", scale=route_scale, bias_rate=bias_rate,
                shared_hidden=shared_width, experts_held=experts_held,
                first_expert=first_expert)
        return gqa_attention(h, pre, l, seq_len, num_heads, num_kv_heads,
                             head_dim, hidden_size, rms_eps, head_norms=False)

    x = embed(sym.Variable("data"), vocab_size, hidden_size)
    for l, kind in enumerate(layer_types):
        pre = "l%d_" % l
        x = one_branch_block(x, pre, rms_eps,
                             lambda h: branch(h, pre, l, kind), layer=l)
    return with_load_heads(lm_head_loss(x, vocab_size, rms_eps))
