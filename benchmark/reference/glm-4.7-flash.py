"""Plain reference of the ``glm-4.7-flash`` configuration: GLM-4.7-Flash
(``model_type`` ``glm4_moe_lite``, a DeepSeek-V3-shaped decoder,
arXiv:2412.19437) forward, both losses, gradients, one Adam step and the
selection bias's first move, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, with no program code.

Layer ``l`` (from 0): ``x + MLA(RMSNorm(x))``, ``x + MLP_l(RMSNorm(x))``;
a final RMSNorm and an untied head.  No projection has a bias.

MLA, every layer, per head: ``c_q = rmsnorm(h Wq_a)``; ``q = c_q Wq_b``
in heads of ``[q_n (qk_nope_dim) | q_r (qk_rope_dim)]``; ``[c, k_r] = h
Wkv_a``; ``[k_n, v] = rmsnorm(c) Wkv_b`` a head; ``q_r`` of every head
and the one ``k_r`` (shared by all heads) are rotated at positions
``0..T-1``: lane ``i`` of the part pairs with lane ``i + qk_rope_dim /
2`` (half-split, as the program's ``RotaryEmbedding``; the published
modelling code may pair neighbours, at the same cost), angle ``pos *
rope_theta ** (-2 i / qk_rope_dim)``; ``k = [k_n, rot(k_r)]``; causal
softmax of ``q k^T / sqrt(qk_nope_dim + qk_rope_dim)``; ``y =
concat_heads(P v) Wo``.

MLP: SwiGLU of ``dense_width`` for the first ``dense_layers`` layers;
after them the expert layer: ``s = sigmoid(h Wr)`` over all
``num_experts``; chosen = top ``experts_per_tok`` of ``s + b`` (no group
limit); ``w_e = routed_scale * s_e / sum_chosen(s)``; ``y = sum over the
chosen experts HELD HERE of w_e Expert_e(h) + Shared(h)``: a loop over
the ``experts_held`` experts from ``first_expert`` on.  What the absent
experts would have added is left out, here as in the program; the
weights are renormalized over all chosen experts, held or not.  ``b``
(``*_select_bias``) enters the choice only; its move after a step is
``bias_rate * sign(mean load - load)`` (DeepSeek-V3's rule).

The prediction module (``nextn_layers`` 1, DeepSeek-V3 section 2.2):
with ``x`` the trunk's last residual state (before ``final_norm``) and
``t`` the tokens, ``u_i = [rmsnorm(Emb(t_{i+1})) ; rmsnorm(x_i)] W_eh``,
one more block (MLA + expert layer, its own weights), an RMSNorm of its
own, THE TRUNK'S head, cross-entropy against ``t_{i+2}``; ``Emb`` is THE
TRUNK'S embedding.  ``t_{i+1}`` is the label of position ``i`` and
``t_{i+2}`` the label of position ``i + 1``; the last position of every
sequence has no target and is outside the second loss and its mean.
The objective is ``L_main + mtp_weight * L_mtp``.

Weight names and layouts are the program's
(``mxnet_tpu.models.glm_moe_lite``): projections ``(out, in)``, stacked
experts ``(held, D, H)``, ``(held, D, H)``, ``(held, H, D)``.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's 8 GiB of state, and the whole model's float32 weights beside
that do not fit.  Two block programs (dense, expert: shared by the four
expert layers and the module's block) and one head program (both
heads): no token-by-token scan, so its compiles are cheap.
"""
from __future__ import annotations


def _model(config):
    return dict(config["model"]["kwargs"])


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward (3 x forward), matrix
    products only (2mnk).  An MLA mixer: the five projections (q_a, q_b,
    kv_a, kv_b, o) and causal attention as T/2 keys a query (T H (Dqk +
    Dv)).  The dense MLP 3 x 2 D F.  An expert layer: the router over
    all experts, the shared expert, and the HELD share of the k chosen
    experts (k x held / experts x 3 x 2 D H: 0.5 expert a token at 8 of
    64).  The head over the vocabulary rows held.  A prediction module:
    the 2 D -> D projection, one more mixer and expert layer, the head
    once more.  Norms, the rotation, the embedding lookups, the sort and
    the optimizer are not counted."""
    m = _model(config)
    D, T, H = m["hidden_size"], m["seq_len"], m["heads"]
    qk = m["qk_nope_dim"] + m["qk_rope_dim"]
    dv, c, rq = m["v_head_dim"], m["kv_lora_rank"], m["q_lora_rank"]
    mla = (2 * D * rq + 2 * rq * H * qk + 2 * D * (c + m["qk_rope_dim"])
           + 2 * c * H * (m["qk_nope_dim"] + dv) + 2 * H * dv * D
           + T * H * (qk + dv))
    dense = 3 * 2 * D * m["dense_width"]
    E = m["num_experts"]
    held = m.get("experts_held") or E
    sparse = (2 * D * E + 3 * 2 * D * m["shared_width"]
              + m["experts_per_tok"] * held / E
              * 3 * 2 * D * m["expert_width"])
    head = 2 * D * m["vocab_size"]
    L, first = m["num_layers"], min(m["dense_layers"], m["num_layers"])
    total = head + L * mla + first * dense + (L - first) * sparse
    total += m.get("nextn_layers", 1) * (2 * 2 * D * D + mla + sparse + head)
    return 3.0 * total


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def rotate(x, theta):
    """x (B, T, H, Dr) at positions 0..T-1, lane i with lane i + Dr/2."""
    import jax.numpy as jnp
    t, dr = x.shape[1], x.shape[3]
    half = dr // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dr)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla(p, pre, x, m):
    """x (B, T, D) -> (B, T, D)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    nh, dn, dr, dv = (m["heads"], m["qk_nope_dim"], m["qk_rope_dim"],
                      m["v_head_dim"])
    c, eps, theta = m["kv_lora_rank"], m["rms_eps"], m["rope_theta"]
    c_q = rms_norm(x @ p[pre + "q_a_proj_weight"].T,
                   p[pre + "q_a_norm_gamma"], eps)
    q = (c_q @ p[pre + "q_b_proj_weight"].T).reshape(b, t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], theta)], -1)
    kv_a = x @ p[pre + "kv_a_proj_weight"].T
    latent = rms_norm(kv_a[..., :c], p[pre + "kv_a_norm_gamma"], eps)
    kv = (latent @ p[pre + "kv_b_proj_weight"].T).reshape(b, t, nh, dn + dv)
    k_r = rotate(kv_a[:, :, None, c:], theta)              # one, shared
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (b, t, nh, dr))], -1)
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


ROWS = 1024      # rows a block of by_rows: the hidden activations of one


def by_rows(fn, x):
    """``fn`` over the rows of ``x`` (N, D), ROWS at a time where N is
    whole blocks of them, each block checkpointed: the backward pass
    holds one block's hidden activations, not all N rows'."""
    import jax
    n = x.shape[0]
    if n <= ROWS or n % ROWS:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(n // ROWS, ROWS, -1))
    return out.reshape(n, -1)


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
    @jax.checkpoint          # one expert's hidden activations at a time
    def expert(x, w_e, wg, wu, wd):
        return w_e[:, None] * ((jax.nn.silu(x @ wg) * (x @ wu)) @ wd)

    for e in range(held):
        y = y + expert(x, w[:, first + e], *(
            p[pre + "moe_experts_%s_weight" % n][e]
            for n in ("i2h_gate", "i2h", "h2o")))
    return y, chosen.sum(axis=0).astype(jnp.float32)


def block(p, pre, x, m, dense):
    """One decoder block: x (B, T, D) -> (x, choices per expert or
    None).  The mixer is checkpointed by itself, so that a backward pass
    holds its activations or the MLP's, not both."""
    import jax
    b, t, _ = x.shape
    eps = m["rms_eps"]
    x = x + jax.checkpoint(lambda x: mla(
        p, pre, rms_norm(x, p[pre + "mixer_norm_gamma"], eps), m))(x)
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    if dense:
        y = by_rows(lambda rows: swiglu(
            rows, p[pre + "gate_proj_weight"], p[pre + "up_proj_weight"],
            p[pre + "down_proj_weight"]), h.reshape(b * t, -1))
        return x + y.reshape(b, t, -1), None
    y, counts = moe(p, pre, h.reshape(b * t, -1), m)
    return x + y.reshape(b, t, -1), counts


def trunk(p, tokens, m):
    """tokens (B, T) int -> (the last residual state (B, T, D), {block:
    choices per expert}).  Each block is checkpointed: the backward pass
    holds one block's activations at a time."""
    import jax
    x = p["embed_weight"][tokens]                               # (B, T, D)
    counts = {}
    for l in range(m["num_layers"]):
        pre = "l%d_" % l
        x, c = jax.checkpoint(
            lambda x, pre=pre, d=l < m["dense_layers"]:
            block(p, pre, x, m, d))(x)
        if c is not None:
            counts[pre + "moe_dispatch"] = c
    return x, counts


def head(p, x, gamma, m):
    """A residual state (B, T, D) through its final norm ``gamma`` and
    the one output head -> logits (B*T, V)."""
    b, t, _ = x.shape
    return rms_norm(x, p[gamma], m["rms_eps"]).reshape(b * t, -1) \
        @ p["lm_head_weight"].T


def prediction_module(p, x, labels, m):
    """The trunk's last residual state and the labels (B, T) -> (the
    module's logits (B*T, V), its block's choices per expert).  Reads
    ``embed_weight`` and ``lm_head_weight`` of ``p``: the trunk's, where
    ``p`` is the trunk's dict."""
    import jax
    import jax.numpy as jnp
    eps = m["rms_eps"]
    u = jnp.concatenate(
        [rms_norm(p["embed_weight"][labels], p["mtp_enorm_gamma"], eps),
         rms_norm(x, p["mtp_hnorm_gamma"], eps)], -1) \
        @ p["mtp_eh_proj_weight"].T
    u, c = jax.checkpoint(lambda u: block(p, "mtp_", u, m, False))(u)
    return head(p, u, "mtp_final_norm_gamma", m), c


def cross_entropy(logits, target):
    """Per-row -log softmax(logits)[target]."""
    import jax
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, target.reshape(-1)[:, None], 1)[:, 0]


def mtp_loss(logits2, labels):
    """The module's mean loss: position i predicts the label of position
    i + 1; the last position of every sequence has no target."""
    b, t = labels.shape
    return cross_entropy(
        logits2.reshape(b, t, -1)[:, :-1].reshape(b * (t - 1), -1),
        labels[:, 1:]).mean()


def loss_and_grads(config, params, tokens, labels, names=None):
    """float32, highest precision, BLOCK BY BLOCK: the weights stay on
    the host and one block's are on the device at a time, with the
    residual states between blocks; the backward pass walks the blocks
    from the last with ``jax.vjp`` of the same block function, which
    forms the block again.  At 4096 tokens of the published widths the
    device holds about 1.5 GB for this, beside a bound module's 8 GiB.

    -> dict: ``loss`` (the main head's mean CE, what the program's
    metric reads), ``mtp_loss`` (the second head's mean over its
    positions), ``counts`` (choices per expert, per expert block),
    ``grads`` of ``names`` (every parameter where None) of ``loss +
    mtp_weight * mtp_loss``.  ``params`` may hold the blocks'
    ``*_select_bias`` states; a block without one has a zero bias."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(k for k in host if not k.endswith("select_bias")) \
        if names is None else set(names)
    tk = jnp.asarray(tokens).astype(jnp.int32)
    lb = jnp.asarray(labels).astype(jnp.int32)
    weight = m.get("mtp_weight", 0.3)
    grads, counts = {}, {}

    def part(pre):
        """One block's weights on the device, the prefix taken off (the
        expert layers and the module's block then share one program), as
        (those whose gradient is wanted, the rest)."""
        mine = {k[len(pre):]: (k, jnp.asarray(v)) for k, v in host.items()
                if k.startswith(pre)}
        return ({k: v for k, (name, v) in mine.items() if name in wanted},
                {k: v for k, (name, v) in mine.items()
                 if name not in wanted})

    def keep(pre, block_grads):
        for k, g in block_grads.items():
            if pre + k in wanted:
                grads[pre + k] = grads.get(pre + k, 0.0) + np.asarray(g)

    def block_programs(dense):
        def fwd(p, rest, x):
            return block({**rest, **p}, "", x, m, dense)

        def bwd(p, rest, x, g):
            return jax.vjp(lambda p, x: fwd(p, rest, x)[0], p, x)[1](g)
        return jax.jit(fwd), jax.jit(bwd)

    def head_loss(w, x, target, share):
        """sum(share * CE(head(x), target)); w = (final gain, head)."""
        logits = head({"g": w[0], "lm_head_weight": w[1]}, x, "g", m)
        return jnp.sum(share * cross_entropy(logits, target))

    def module_input(w, x, labels):
        """w = (embedding, enorm, hnorm, eh_proj) -> u (B, T, D)."""
        eps = m["rms_eps"]
        return jnp.concatenate([rms_norm(w[0][labels], w[1], eps),
                                rms_norm(x, w[2], eps)], -1) @ w[3].T

    head_grad = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1)))
    blocks = [("l%d_" % l, l < m["dense_layers"])
              for l in range(m["num_layers"])]
    programs = {d: block_programs(d) for d in (True, False)}
    b, t = tk.shape
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        lm_head = jnp.asarray(host["lm_head_weight"])
        states = [embed[tk]]                       # x before each block
        for pre, dense in blocks:
            x, c = programs[dense][0](*part(pre), states[-1])
            states.append(x)
            if c is not None:
                counts[pre + "moe_dispatch"] = c
        # the main head: every position, 1 / positions each
        main, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]), lm_head), states[-1],
            lb, jnp.full((b * t,), 1.0 / (b * t), jnp.float32))
        keep("", {"final_norm_gamma": d_gain, "lm_head_weight": d_head})
        d_embed, mtp = 0.0, jnp.float32(0.0)
        if m.get("nextn_layers", 1):
            # position i is scored against the label of position i + 1;
            # the last position of every sequence has no target
            target = jnp.roll(lb, -1, axis=1)
            share = (jnp.tile(jnp.arange(t) < t - 1, b)
                     / (b * (t - 1.0))).astype(jnp.float32)
            w_in = (embed,) + tuple(jnp.asarray(host["mtp_" + n]) for n in (
                "enorm_gamma", "hnorm_gamma", "eh_proj_weight"))
            u, vjp_in = jax.vjp(module_input, w_in, states[-1], lb)
            p_block = part("mtp_")
            u2, counts["mtp_moe_dispatch"] = programs[False][0](*p_block, u)
            mtp, ((d_gain, d_head), du2) = head_grad(
                (jnp.asarray(host["mtp_final_norm_gamma"]), lm_head), u2,
                target, share)
            keep("", {"mtp_final_norm_gamma": weight * d_gain,
                      "lm_head_weight": weight * d_head})
            d_block, du = programs[False][1](*p_block, u, weight * du2)
            keep("mtp_", d_block)
            d_in, dx2, _ = vjp_in(du)
            keep("mtp_", dict(zip(("enorm_gamma", "hnorm_gamma",
                                   "eh_proj_weight"), d_in[1:])))
            d_embed, dx = d_embed + d_in[0], dx + dx2
            del p_block, d_block, u, u2, du, du2, vjp_in, d_in, dx2
        for (pre, dense), x in zip(reversed(blocks), reversed(states[:-1])):
            p_block = part(pre)
            d_block, dx = programs[dense][1](*p_block, x, dx)
            keep(pre, d_block)
            del p_block, d_block
        keep("", {"embed_weight": d_embed + jnp.zeros_like(embed)
                  .at[tk].add(dx)})
    return {"loss": float(main), "mtp_loss": float(mtp), "counts": counts,
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
    """The main head's mean cross-entropy (``loss``), the second head's
    (``mtp_loss``), the first Adam step's change of ``names`` under
    ``loss + mtp_weight * mtp_loss`` and each expert block's first
    selection-bias move.  The loss heads scale their own gradients and
    the optimizer's ``rescale_grad`` is 1."""
    import jax
    if optimizer.get("wd", 0.0) or optimizer.get("rescale_grad", 1.0) != 1.0:
        raise ValueError("the reference's Adam step has no weight decay "
                         "and no gradient rescale: %r" % (optimizer,))
    out = loss_and_grads(config, params, data["data"],
                         labels["softmax_label"], names)
    rate = _model(config).get("bias_rate", 1e-3)
    return {"loss": out["loss"], "mtp_loss": out["mtp_loss"],
            "updates": {n: jax.device_get(adam_first_step(out["grads"][n],
                                                          optimizer))
                        for n in names},
            "bias_moves": {b: jax.device_get(select_bias_move(c, rate))
                           for b, c in out["counts"].items()}}
