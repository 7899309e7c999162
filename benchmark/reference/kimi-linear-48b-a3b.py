"""Plain reference of the ``kimi-linear-48b-a3b`` configuration: Kimi
Linear ("Kimi Linear: An Expressive, Efficient Attention Architecture",
arXiv:2510.26692; ``model_type`` ``kimi_linear``) forward, loss,
gradients, one Adam step and the selection bias's first move, in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, with no
program code.

Layer ``l`` (from 1): ``x + Mixer_l(RMSNorm(x))``, ``x + MLP_l(RMSNorm
(x))``; a final RMSNorm and an untied head.  No projection has a bias
but one (below).

KDA (every layer not in ``full_attn_layers``), per head of ``K = V =
kda_head_dim``, TOKEN BY TOKEN (a ``lax.scan`` over T; the program's
chunked form is not used here):

    q_t = l2norm(silu(conv(h Wq)_t)), k_t likewise, v_t = silu(conv(h Wv)_t)
    g_t = -exp(a_log[head]) * softplus(h_t Wf_down Wf_up + dt_bias)
    beta_t = sigmoid(h_t w_beta[head])
    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(K)
    y_t = [rmsnorm_head(o_t) * sigmoid(h_t Wg_down Wg_up + b_g)] Wo

``conv`` is a depthwise causal convolution over time (``conv_kernel``
taps a channel, no bias), ``l2norm(x) = x / sqrt(sum(x^2) + 1e-6)``.
From the paper and its published modelling code, not ``config.json``:
the low-rank width (= the head size), the decay's form, the sigmoid on
the output gate and its bias ``b_g``.  The state starts at zero and is
not reset between packed documents.

MLA (``full_attn_layers``): ``q = h Wq`` in heads of ``qk_nope_dim +
qk_rope_dim``; ``[c, k_r] = h Wkv_a``; ``[k_n, v] = rmsnorm(c) Wkv_b`` a
head; ``k = [k_n, k_r]`` with the one ``k_r`` shared by all heads;
causal softmax of ``q k^T / sqrt(qk_nope_dim + qk_rope_dim)``; ``y =
concat_heads(P v) Wo``.  No rotary embedding on either part
(``mla_use_nope``).

MLP: SwiGLU of ``dense_width`` for the first ``dense_layers`` layers;
after them the expert layer: ``s = sigmoid(h Wr)`` over all
``num_experts``; chosen = top ``experts_per_tok`` of ``s + b``; ``w_e =
routed_scale * s_e / sum_chosen(s)``; ``y = sum over the chosen experts
HELD HERE of w_e Expert_e(h) + Shared(h)``: a loop over the
``experts_held`` experts from ``first_expert`` on.  What the absent
experts would have added is left out, here as in the program; the
weights are renormalized over all chosen experts, held or not.  ``b``
(``*_select_bias``) enters the choice only; its move after a step is
``bias_rate * sign(mean load - load)`` (DeepSeek-V3's rule; the config
has no training recipe).

Weight names and layouts are the program's
(``mxnet_tpu.models.kimi_linear``): projections ``(out, in)``,
convolutions ``(channels, taps)``, stacked experts ``(held, D, H)``,
``(held, D, H)``, ``(held, H, D)``.
"""
from __future__ import annotations

# tokens between two kept states of the KDA recurrence: the backward pass
# replays one stretch at a time, so T/STRETCH + STRETCH states live at once
STRETCH = 64


def _model(config):
    return dict(config["model"]["kwargs"])


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward (3 x forward), matrix
    products only (2mnk).  A KDA layer: q, k, v, o projections (4 x 2 D
    W, W = heads x head size), the two low-rank gates (2 x 2 (D r + r
    W)), beta (2 D heads), the convolutions (3 x 2 taps W), and the
    recurrence's three products with the state a head (6 K V: S^T k,
    k u^T, S^T q).  An MLA layer: q, kv_a, kv_b, o projections and
    causal attention as T/2 keys a query (T H (Dqk + Dv)).  The dense
    MLP 3 x 2 D F.  An expert layer: the router over all experts, the
    shared expert, and the HELD share of the k chosen experts (k x
    held / experts x 3 x 2 D H: 0.25 expert a token at 8 of 256).  The
    head over the vocabulary rows held.  Norms, gates' non-linearities,
    the sort and the optimizer are not counted."""
    m = _model(config)
    D, T = m["hidden_size"], m["seq_len"]
    W, r, H = (m["kda_heads"] * m["kda_head_dim"], m["kda_head_dim"],
               m["kda_heads"])
    kda = (4 * 2 * D * W + 2 * 2 * (D * r + r * W) + 2 * D * H
           + 3 * 2 * m["conv_kernel"] * W + 6 * H * r * r)
    qk = m["qk_nope_dim"] + m["qk_rope_dim"]
    Hm, dv, c = m["mla_heads"], m["v_head_dim"], m["kv_lora_rank"]
    mla = (2 * D * Hm * qk + 2 * D * (c + m["qk_rope_dim"])
           + 2 * c * Hm * (m["qk_nope_dim"] + dv) + 2 * Hm * dv * D
           + T * Hm * (qk + dv))
    dense = 3 * 2 * D * m["dense_width"]
    E = m["num_experts"]
    held = m.get("experts_held") or E
    sparse = (2 * D * E + 3 * 2 * D * m["shared_width"]
              + m["experts_per_tok"] * held / E
              * 3 * 2 * D * m["expert_width"])
    total = 2 * D * m["vocab_size"]
    for l in range(1, m["num_layers"] + 1):
        total += mla if l in m["full_attn_layers"] else kda
        total += dense if l <= m["dense_layers"] else sparse
    return 3.0 * total


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def l2norm(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def causal_conv(x, w):
    """x (B, T, C), w (C, taps): y_t = sum_j w[:, j] x_{t - taps + 1 + j}."""
    import jax.numpy as jnp
    taps, t = w.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t, :] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k, g (B, T, H, K), v (B, T, H,
    V), beta (B, T, H) -> (B, T, H, V).  Two nested scans over the same
    tokens in order; the outer one's body is checkpointed, so the
    backward pass keeps one state a STRETCH and replays the tokens."""
    import jax
    import jax.numpy as jnp
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % STRETCH

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S                    # diag(exp g) S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt))
        S = S + kt[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt) * dk ** -0.5

    @jax.checkpoint
    def stretch(S, xs):
        return jax.lax.scan(token, S, xs)

    def by_stretch(x):      # (B, T, ..) -> (T / STRETCH, STRETCH, B, ..)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((-1, STRETCH) + x.shape[1:])

    _, o = jax.lax.scan(stretch, jnp.zeros((b, h, dk, dv), jnp.float32),
                        tuple(by_stretch(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :t]


def kda(p, pre, x, m):
    """x (B, T, D) -> (B, T, D)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    nh, dh = m["kda_heads"], m["kda_head_dim"]

    def branch(s):
        y = causal_conv(x @ p[pre + s + "_proj_weight"].T,
                        p[pre + s + "_conv_weight"])
        return jax.nn.silu(y).reshape(b, t, nh, dh)

    q, k, v = l2norm(branch("q")), l2norm(branch("k")), branch("v")
    f = x @ p[pre + "f_down_weight"].T @ p[pre + "f_up_weight"].T
    g = -jnp.exp(p[pre + "kda_a_log_bias"])[:, None] * jax.nn.softplus(
        f.reshape(b, t, nh, dh) + p[pre + "kda_dt_bias"].reshape(nh, dh))
    beta = jax.nn.sigmoid(x @ p[pre + "beta_proj_weight"].T)
    o = rms_norm(delta_rule(q, k, v, g, beta), p[pre + "o_norm_gamma"],
                 m["rms_eps"])
    gate = x @ p[pre + "g_down_weight"].T @ p[pre + "g_up_weight"].T \
        + p[pre + "g_up_bias"]
    o = o * jax.nn.sigmoid(gate.reshape(b, t, nh, dh))
    return o.reshape(b, t, nh * dh) @ p[pre + "o_proj_weight"].T


def mla(p, pre, x, m):
    """x (B, T, D) -> (B, T, D): latent attention, no positional part."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    nh, dn, dr, dv = (m["mla_heads"], m["qk_nope_dim"], m["qk_rope_dim"],
                      m["v_head_dim"])
    c = m["kv_lora_rank"]
    q = (x @ p[pre + "q_proj_weight"].T).reshape(b, t, nh, dn + dr)
    kv_a = x @ p[pre + "kv_a_proj_weight"].T
    latent = rms_norm(kv_a[..., :c], p[pre + "kv_a_norm_gamma"],
                      m["rms_eps"])
    kv = (latent @ p[pre + "kv_b_proj_weight"].T).reshape(b, t, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kv_a[:, :, None, c:],
                                        (b, t, nh, dr))], -1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((t, t), bool))[None]

    @jax.checkpoint          # one head's (T, T) scores at a time
    def one_head(qkv):
        qh, kh, vh = qkv
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * (dn + dr) ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh)

    a = jax.lax.map(one_head, tuple(y.transpose(2, 0, 1, 3)
                                    for y in (q, k, v)))    # (H, B, T, Dv)
    return a.transpose(1, 2, 0, 3).reshape(b, t, nh * dv) \
        @ p[pre + "o_proj_weight"].T


def swiglu(x, wg, wu, wd):
    """Projections as FullyConnected keeps them, (out, in)."""
    import jax
    return (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd.T


def moe(p, pre, x, m):
    """x (N, D) -> ((N, D), choices per expert (E,)): the held experts'
    part plus the shared expert."""
    import jax
    import jax.numpy as jnp
    E, k = m["num_experts"], m["experts_per_tok"]
    held = m.get("experts_held") or E
    first = m.get("first_expert", 0)
    s = jax.nn.sigmoid(x @ p[pre + "moe_gate_weight"].T)        # (N, E)
    bias = p.get(pre + "moe_dispatch_select_bias", jnp.zeros((E,)))
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    chosen = (top_e[..., None] == jnp.arange(E)).any(axis=1)    # (N, E)
    w = m["routed_scale"] * s * chosen \
        / jnp.sum(s * chosen, axis=-1, keepdims=True)
    y = swiglu(x, p[pre + "moe_shared_i2h_gate_weight"],
               p[pre + "moe_shared_i2h_weight"],
               p[pre + "moe_shared_h2o_weight"])
    for e in range(held):
        wg, wu, wd = (p[pre + "moe_experts_%s_weight" % n][e]
                      for n in ("i2h_gate", "i2h", "h2o"))
        y = y + w[:, first + e, None] * ((jax.nn.silu(x @ wg) * (x @ wu))
                                         @ wd)
    return y, chosen.sum(axis=0).astype(jnp.float32)


def layer(p, l, x, m):
    """Layer ``l`` (from 1): x (B, T, D) -> (x, choices per expert or
    None)."""
    b, t, _ = x.shape
    pre = "l%d_" % l
    mixer = mla if l in m["full_attn_layers"] else kda
    x = x + mixer(p, pre, rms_norm(x, p[pre + "mixer_norm_gamma"],
                                   m["rms_eps"]), m)
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], m["rms_eps"])
    if l <= m["dense_layers"]:
        return x + swiglu(h, p[pre + "gate_proj_weight"],
                          p[pre + "up_proj_weight"],
                          p[pre + "down_proj_weight"]), None
    y, counts = moe(p, pre, h.reshape(b * t, -1), m)
    return x + y.reshape(b, t, -1), counts


def forward(p, tokens, m):
    """tokens (B, T) int -> (logits (B*T, V), {block: choices per
    expert}).  Each layer is checkpointed: the backward pass holds one
    layer's activations at a time."""
    import jax
    b, t = tokens.shape
    x = p["embed_weight"][tokens]                               # (B, T, D)
    counts = {}
    for l in range(1, m["num_layers"] + 1):
        x, c = jax.checkpoint(lambda x, l=l: layer(p, l, x, m))(x)
        if c is not None:
            counts["l%d_moe_dispatch" % l] = c
    x = rms_norm(x, p["final_norm_gamma"], m["rms_eps"])
    return x.reshape(b * t, -1) @ p["lm_head_weight"].T, counts


def objective(p, tokens, labels, m):
    """-> (mean CE, (logits, counts))"""
    import jax
    import jax.numpy as jnp
    logits, counts = forward(p, tokens, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels.reshape(-1)[:, None], 1).mean()
    return ce, (logits, counts)


def loss_and_grads(config, params, tokens, labels, names=None):
    """float32, highest precision.  -> dict: ``loss`` (mean CE, what the
    program's metric reads), ``logits`` (B*T, V), ``counts`` (choices
    per expert, per expert block), ``grads`` of ``names`` (every
    parameter where None).  ``params`` may hold the blocks'
    ``*_select_bias`` states; a block without one has a zero bias."""
    import jax
    import jax.numpy as jnp
    m = _model(config)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    wanted = {k: p.pop(k) for k in (
        [k for k in p if not k.endswith("select_bias")]
        if names is None else names)}
    tk = jnp.asarray(tokens).astype(jnp.int32)
    lb = jnp.asarray(labels).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        # data and weights are arguments, not constants of the program
        (ce, (logits, counts)), grads = jax.jit(
            jax.value_and_grad(
                lambda w, rest, a, b: objective({**rest, **w}, a, b, m),
                has_aux=True))(wanted, p, tk, lb)
    return {"loss": float(ce), "logits": logits, "counts": counts,
            "grads": grads}


def adam_first_step(g, optimizer):
    """The first Adam step's change of a weight whose gradient is ``g``
    (state zero, t = 1, weight decay 0): ``-lr_1 * m / (sqrt(v) + eps)``
    with ``m = (1 - b1) g``, ``v = (1 - b2) g^2`` and the bias-corrected
    ``lr_1 = lr * sqrt(1 - b2) / (1 - b1)``."""
    import jax.numpy as jnp
    lr = optimizer["learning_rate"]
    b1, b2 = optimizer.get("beta1", 0.9), optimizer.get("beta2", 0.999)
    eps = optimizer.get("epsilon", 1e-8)
    lr_1 = lr * (1.0 - b2) ** 0.5 / (1.0 - b1)
    m, v = (1.0 - b1) * g, (1.0 - b2) * g * g
    return -lr_1 * m / (jnp.sqrt(v) + eps)


def select_bias_move(counts, rate):
    """One step of a block's selection bias from that step's choices per
    expert: ``rate * sign(mean load - load)``."""
    import jax.numpy as jnp
    return rate * jnp.sign(counts.mean() - counts)


def reference_step(config, params, data, labels, optimizer, names):
    """Mean cross-entropy per position, the first Adam step's change of
    ``names`` and each expert block's first selection-bias move.  The
    loss head scales its own gradient (1 / positions) and the
    optimizer's ``rescale_grad`` is 1."""
    import jax
    if optimizer.get("wd", 0.0) or optimizer.get("rescale_grad", 1.0) != 1.0:
        raise ValueError("the reference's Adam step has no weight decay "
                         "and no gradient rescale: %r" % (optimizer,))
    out = loss_and_grads(config, params, data["data"],
                         labels["softmax_label"], names)
    rate = _model(config).get("bias_rate", 1e-3)
    return {"loss": out["loss"],
            "updates": {n: jax.device_get(adam_first_step(out["grads"][n],
                                                          optimizer))
                        for n in names},
            "bias_moves": {b: jax.device_get(select_bias_move(c, rate))
                           for b, c in out["counts"].items()}}
