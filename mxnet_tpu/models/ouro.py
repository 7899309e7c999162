"""Ouro (``model_type`` ``ouro``, ByteDance): a looped language model.
One stack of layers is applied ``total_ut_steps`` times to the same rows
WITH THE SAME WEIGHTS (Zhu et al. 2025, arXiv:2510.25741, "Scaling Latent
Reasoning via Looped Language Models").

Token embedding -> ``total_ut_steps`` x [L x [x + N2(Attn_l(N1(x))), x +
N4(MLP_l(N3(x)))] -> RMSNorm] -> untied vocabulary head.  Every ``N*`` is
an RMSNorm with a gain, the second of each pair inside the residual
branch (sandwich norms).  The final norm closes EVERY pass: its output
``h_t`` is what the head and the exit gate read after pass ``t`` and what
pass ``t + 1`` starts from.  Attention is plain multi-head over
``num_kv_heads`` key/value heads of ``head_dim`` with no bias and no head
norm, q and k rotated over all their lanes (``rope_theta``, half-split
pairing), causal; the MLP a SwiGLU of ``mlp_width``.

The objective.  After pass ``t`` the one head gives the per-row cross
entropy ``ce_t`` and the exit gate, ``Linear(hidden_size, 1)`` with a
bias, the logit ``g_t``.  With ``lambda_t = sigmoid(g_t)`` a row leaves
after pass ``t`` with probability

    p_1 = lambda_1,   p_t = lambda_t prod_{j<t} (1 - lambda_j),
    p_R = prod_{j<R} (1 - lambda_j)          (the last pass takes the rest)

and the loss of a row is the expected cross entropy less an entropy term,
``sum_t p_t ce_t - exit_beta * H(p)``, ``H(p) = -sum_t p_t log p_t``.  It
is formed in log space (``log lambda = -softplus(-g)``, ``log (1 -
lambda) = -softplus(g)``) in float32.

The graph.  The passes are ONE node, ``sym.Repeat`` (``loop``): its body
holds the L blocks, the final norm (the carry), the head, the cross
entropy and the gate, so each weight is an argument once and the lowered
step does not grow with the passes.  The head is inside the body on purpose:
a pass's float32 logits live only inside that pass, which the backward
pass forms again from the pass's carry (``recompute``), one pass at a
time, but for the last pass, which it reads as the forward left it.  The
per-pass outputs ``ce_t`` and ``g_t`` leave the loop stacked
``(R, rows)``; the objective behind the loop is plain ops.

The symbol trains through ``Module.fit`` as it stands: inputs ``data``
and ``softmax_label``, both ``(batch, seq_len)`` token ids.  Outputs, by
name: ``lm_output`` the per-row objective (first, where the metric reads
it; its gradient is 1 / rows) and ``loop_exit_output``, ``(R + 1,)``
behind a ``BlockGrad``: the rows' summed ``p_1 .. p_R`` and their summed
full-depth cross entropy ``ce_R``, which ``Module.fit`` turns into the
trace counter ``loop:exit`` once a step.

Device scopes: the blocks keep the decoder's names in every pass
(``attn_proj.l<i>``, the ops' own ``attn.l<i>``, the generic
``fullyconnected.l<i>_gate_proj``...), so the ``scope_*`` readers sum
the passes with no edit; the head's and the gate's projections inside
the body and the objective behind it are ``loop_head``; the cross
entropy keeps the op's own ``lm_loss``.
"""
from .. import symbol as sym
from ..attribute import AttrScope
from ..initializer import Normal
from ..trace.heads import LOOP_EXIT
from .decoder import (block, embed, gqa_attention, norm, proj, scoped,
                      swiglu)


def exit_objective(ce, gate, num_steps, exit_beta):
    """``(rows' objective (N,), p (R, N))`` from the stacked cross
    entropies ``ce`` ``(R, N)`` and gate logits ``gate`` ``(R, N)``,
    both float32."""
    def step(x, t):
        return sym.slice_axis(x, axis=0, begin=t, end=t + 1)

    log_exit = -sym.Activation(-gate, act_type="softrelu")
    log_stay = -sym.Activation(gate, act_type="softrelu")
    stayed = step(gate, 0) * 0.0        # sum_{j<t} log(1 - lambda_j)
    log_p = []
    for t in range(num_steps - 1):
        log_p.append(step(log_exit, t) + stayed)
        stayed = stayed + step(log_stay, t)
    log_p = sym.Concat(*log_p, stayed, dim=0)   # the last pass: the rest
    p = sym.exp(log_p)
    rows = sym.sum_axis(p * ce, axis=0) \
        + float(exit_beta) * sym.sum_axis(p * log_p, axis=0)
    return rows, p


def ouro_lm(num_layers, hidden_size, num_heads, num_kv_heads, head_dim,
            mlp_width, vocab_size, seq_len, total_ut_steps=4,
            rope_theta=1e6, rms_eps=1e-6, exit_beta=0.1, recompute=True,
            embed_sigma=None):
    """The training symbol; see the module docstring.  ``embed_sigma``:
    the embedding starts ``Normal(embed_sigma)`` whatever initializer the
    module is handed (the variable's own, ``Variable(init=)``)."""
    if num_heads % num_kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (num_heads, num_kv_heads))
    steps = int(total_ut_steps)

    # one pass: the stack, the norm that closes it, the head and the gate
    h = sym.Variable("loop_rows")
    for l in range(num_layers):
        pre = "l%d_" % l
        h = block(h, pre, rms_eps,
                  lambda r: gqa_attention(
                      r, pre, l, seq_len, num_heads, num_kv_heads, head_dim,
                      hidden_size, rms_eps, rotate=dict(theta=rope_theta),
                      head_norms=False),
                  lambda r: swiglu(r, pre, mlp_width, hidden_size, l),
                  post_norms=("attn_post_norm", "ffn_post_norm"), layer=l)
    with scoped("", "lm_head"):
        h = norm(h, "final_norm", rms_eps)
    with AttrScope(__scope__="loop_head"):
        logits = proj(h, "lm_head", vocab_size)
        gate = proj(h, "exit_gate", 1, bias=True)
    ce = sym.SoftmaxCELoss(logits, sym.Variable("loop_label"),
                           name="lm_loss")
    body = sym.Group([h, ce, gate])

    table = {} if embed_sigma is None else {"weight": sym.Variable(
        "embed_weight", init=Normal(float(embed_sigma)))}
    x = embed(sym.Variable("data"), vocab_size, hidden_size, **table)
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    loop = sym.Repeat(body, {"loop_rows": x}, steps, name="loop",
                      recompute=recompute, loop_label=label)
    with AttrScope(__scope__="loop_head"):
        ce = loop[1]
        gate = sym.Cast(sym.Reshape(loop[2], shape=(steps, -1)),
                        dtype="float32")
        rows, p = exit_objective(ce, gate, steps, exit_beta)
        full_depth = sym.slice_axis(ce, axis=0, begin=steps - 1, end=steps)
        exits = sym.BlockGrad(
            sym.Concat(sym.sum_axis(p, axis=1),
                       sym.sum_axis(full_depth, axis=1), dim=0),
            name=LOOP_EXIT.name)
        return sym.Group([sym.MakeLoss(rows, normalization="batch",
                                       name="lm"), exits])
