"""Plain reference of the ``qwen3-next-80b-a3b`` configuration:
Qwen3-Next-80B-A3B (``model_type`` ``qwen3_next``) forward, loss with its
load-balance term, gradients and one Adam step, in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, with no program code.

``x = Emb(ids)``; layer ``l`` (from 0): ``x + Mixer_l(N(x))``, ``x +
MoE(N(x))``; a final RMSNorm, an untied head, next-token cross-entropy.
``N`` is an RMSNorm with a gain that is one at the start (the published
``1 + w`` with ``w`` zero at the start: the same function and, with no
weight decay, the same updates).  No projection has a bias.  ``Mixer_l``
is gated attention where ``(l + 1) % full_attention_interval == 0``,
Gated DeltaNet elsewhere.

Gated DeltaNet, ``Hk`` key heads under ``Hv`` value heads of ``d``
(``G = Hv / Hk``), TOKEN BY TOKEN (a ``lax.scan`` over T; the program's
chunked form is not used here):

    [q | k | v (G d) | z (G d)] a key head = h Wqkvz;  [b (G) | a (G)] = h Wba
    [q; k; v] <- silu(conv([q; k; v]))        depthwise, causal, no bias
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   a value head
    q, k <- l2norm a head; value head j reads key head j // G
    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t / sqrt(d)
    y_t = [rmsnorm_head(o_t) * silu(z_t)] Wo

``l2norm(x) = x / sqrt(sum(x^2) + 1e-6)``.  The state starts at zero and
is not reset between packed documents.

Gated attention, H query heads over Hkv key/value heads of Dh: ``[q |
gate] a head = h Wq`` (twice as wide as the heads), ``k = h Wk``, ``v = h
Wv``; an RMSNorm over each head's lanes of q and of k (one gain vector
each); the first ``rotary_dim`` lanes of every q and k head rotated at
positions ``0..T-1`` (lane ``i`` with lane ``i + rotary_dim / 2``, angle
``pos * theta ** (-2 i / rotary_dim)``), the other lanes untouched; query
head ``n`` reads key/value head ``n // (H / Hkv)``; causal softmax of the
scores times ``Dh ** -0.5``; ``y = (concat_heads(P v) * sigmoid(gate))
Wo``.

MoE: ``p = softmax(h Wr)`` over all ``num_experts``; chosen = top
``experts_per_tok``; ``w_e = p_e / sum_chosen(p)``; ``y = sum over the
chosen experts HELD HERE of w_e Expert_e(h) + sigmoid(h w_sg) *
Shared(h)``: one after another over the ``experts_held`` experts from
``first_expert`` on.  What the absent experts would have added is left
out, here as in the program; the weights are normalized over all chosen
experts, held or not.  A block's load-balance score is ``E sum_e
mean_rows(p_e) share_e`` with ``share_e`` the fraction of the rows'
choices that fell on expert ``e`` (no gradient through it); the objective
is the cross-entropy plus ``aux_coef`` times the blocks' scores.

Weight names and layouts are the program's
(``mxnet_tpu.models.qwen3_next``): projections ``(out, in)``, the
convolution ``(channels, taps)``, stacked experts ``(held, D, W)``,
``(held, D, W)``, ``(held, W, D)``.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's state, and the whole model's float32 weights beside that do
not fit.  One block program a kind of mixer and one head program.
"""
from __future__ import annotations

# tokens between two kept states of the recurrence: the backward pass
# replays one stretch at a time, so T/STRETCH + STRETCH states live at once
STRETCH = 64


def _model(config):
    return dict(config["model"]["kwargs"])


def is_full(m, layer: int) -> bool:
    return (layer + 1) % m["full_attention_interval"] == 0


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward (3 x forward), matrix
    products only (2mnk).  A Gated DeltaNet layer: the fused q, k, v, z
    projection, the b, a projection, the convolution (2 taps a channel),
    o, and the recurrence's three products with the state a value head
    (6 d d: S^T k, k u^T, S^T q).  A gated attention layer: the doubled
    q projection, k, v, o and attention over the causal pairs (2 x 2 Dh
    H a pair, T (T + 1) / 2 of them).  An expert layer: the router over
    all experts, the shared expert with its gate, and the HELD share of
    the k chosen experts (k x held / experts x 3 x 2 D W: 0.625 expert a
    token at 32 of 512).  The head over the vocabulary rows held.  Norms,
    the rotation, the gates' products, the embedding lookup, the sort and
    the optimizer are not counted."""
    m = _model(config)
    D, T = m["hidden_size"], m["seq_len"]
    hk, hv, d = m["gdn_key_heads"], m["gdn_value_heads"], m["gdn_head_dim"]
    channels = (2 * hk + hv) * d
    gdn = (2 * D * (channels + hv * d) + 2 * D * 2 * hv
           + 2 * m["conv_kernel"] * channels + 2 * hv * d * D
           + 6 * hv * d * d)
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = (2 * D * dh * (2 * H + 2 * Hkv) + 2 * H * dh * D
            + 4 * dh * H * (T * (T + 1) // 2) / T)
    E = m["num_experts"]
    held = m.get("experts_held") or E
    sparse = (2 * D * E + 3 * 2 * D * m["shared_width"] + 2 * D
              + m["experts_per_tok"] * held / E
              * 3 * 2 * D * m["expert_width"])
    total = 2 * D * m["vocab_size"]
    for l in range(m["num_layers"]):
        total += (attn if is_full(m, l) else gdn) + sparse
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
    """The recurrence, token by token: q, k (B, T, H, K), v (B, T, H, V),
    g and beta (B, T, H), ONE log-decay a head and token -> (B, T, H, V).
    Two nested scans over the same tokens in order; the outer one's body
    is checkpointed, so the backward pass keeps one state a STRETCH and
    replays the tokens."""
    import jax
    import jax.numpy as jnp
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % STRETCH

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None, None] * S
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


def gated_delta_net(p, pre, x, m):
    """x (B, T, D) -> (B, T, D)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    hk, hv, d = m["gdn_key_heads"], m["gdn_value_heads"], m["gdn_head_dim"]
    G = hv // hk
    qkvz = (x @ p[pre + "qkvz_proj_weight"].T).reshape(b, t, hk, -1)
    q, k, v, z = jnp.split(qkvz, [d, 2 * d, (2 + G) * d], axis=-1)
    ba = (x @ p[pre + "ba_proj_weight"].T).reshape(b, t, hk, 2 * G)
    beta = jax.nn.sigmoid(ba[..., :G].reshape(b, t, hv))
    g = -jnp.exp(p[pre + "gdn_a_log_bias"]) * jax.nn.softplus(
        ba[..., G:].reshape(b, t, hv) + p[pre + "gdn_dt_bias"])
    mixed = jnp.concatenate([q.reshape(b, t, -1), k.reshape(b, t, -1),
                             v.reshape(b, t, -1)], axis=-1)
    mixed = jax.nn.silu(causal_conv(mixed, p[pre + "conv_weight"]))
    q, k, v = jnp.split(mixed, [hk * d, 2 * hk * d], axis=-1)
    q = jnp.repeat(l2norm(q.reshape(b, t, hk, d)), G, axis=2)
    k = jnp.repeat(l2norm(k.reshape(b, t, hk, d)), G, axis=2)
    o = delta_rule(q, k, v.reshape(b, t, hv, d), g, beta)
    o = rms_norm(o, p[pre + "o_norm_gamma"], m["rms_eps"]) \
        * jax.nn.silu(z.reshape(b, t, hv, d))
    return o.reshape(b, t, hv * d) @ p[pre + "o_proj_weight"].T


def rotate(x, theta, lanes):
    """x (B, T, H, Dh) at positions 0..T-1: the first ``lanes`` lanes of
    every head rotated, lane i with lane i + lanes / 2; the rest as they
    are."""
    import jax.numpy as jnp
    t, half = x.shape[1], lanes // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / lanes)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., lanes:]], axis=-1)


def attention(p, pre, x, m):
    """x (B, T, D) -> (B, T, D)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["rms_eps"]
    qg = (x @ p[pre + "q_proj_weight"].T).reshape(b, t, H, 2 * dh)
    q = rms_norm(qg[..., :dh], p[pre + "q_norm_gamma"], eps)
    gate = jax.nn.sigmoid(qg[..., dh:]).reshape(b, t, H * dh)
    k = rms_norm((x @ p[pre + "k_proj_weight"].T).reshape(b, t, Hkv, dh),
                 p[pre + "k_norm_gamma"], eps)
    v = (x @ p[pre + "v_proj_weight"].T).reshape(b, t, Hkv, dh)
    q = rotate(q, m["rope_theta"], m["rotary_dim"])
    k = rotate(k, m["rope_theta"], m["rotary_dim"])
    mask = jnp.tril(jnp.ones((t, t), bool))
    kv_of = jnp.arange(H) // (H // Hkv)       # query head n reads n // group

    @jax.checkpoint          # one head's (T, T) scores at a time
    def one_head(args):
        qh, n = args
        kh, vh = k[:, :, n], v[:, :, n]
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * dh ** -0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh)

    a = jax.lax.map(one_head, (q.transpose(2, 0, 1, 3), kv_of))
    a = a.transpose(1, 2, 0, 3).reshape(b, t, H * dh)
    return (a * gate) @ p[pre + "o_proj_weight"].T


def swiglu(x, wg, wu, wd):
    """Projections as FullyConnected keeps them, (out, in)."""
    import jax
    return (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd.T


def moe(p, pre, x, m):
    """x (N, D) -> ((N, D) the held experts' part plus the gated shared
    expert, the block's load-balance score, choices per expert (E,))."""
    import jax
    import jax.numpy as jnp
    n = x.shape[0]
    E, k = m["num_experts"], m["experts_per_tok"]
    held = m.get("experts_held") or E
    first = m.get("first_expert", 0)
    probs = jax.nn.softmax(x @ p[pre + "moe_gate_weight"].T, axis=-1)
    _, top_e = jax.lax.top_k(probs, k)
    chosen = (top_e[..., None] == jnp.arange(E)).any(axis=1)    # (N, E)
    w = probs * chosen / jnp.sum(probs * chosen, axis=-1, keepdims=True)
    counts = chosen.sum(axis=0).astype(jnp.float32)
    aux = E * jnp.sum(probs.mean(axis=0)
                      * jax.lax.stop_gradient(counts) / (n * k))
    y = jax.nn.sigmoid(x @ p[pre + "moe_shared_gate_weight"].T) * swiglu(
        x, p[pre + "moe_shared_i2h_gate_weight"],
        p[pre + "moe_shared_i2h_weight"], p[pre + "moe_shared_h2o_weight"])

    @jax.checkpoint          # one expert's hidden activations at a time
    def expert(y, held_expert):
        w_e, wg, wu, wd = held_expert
        return y + w_e[:, None] * ((jax.nn.silu(x @ wg) * (x @ wu)) @ wd), \
            None

    # a scan over the held experts, one after another: one expert's
    # program however many are held
    y, _ = jax.lax.scan(expert, y, (w[:, first:first + held].T,) + tuple(
        p[pre + "moe_experts_%s_weight" % s]
        for s in ("i2h_gate", "i2h", "h2o")))
    return y, aux, counts


def block(p, pre, x, m, full):
    """One decoder block: x (B, T, D) -> (x, load-balance score, choices
    per expert).  The mixer is checkpointed by itself, so that a
    backward pass holds its activations or the MLP's, not both."""
    import jax
    b, t, _ = x.shape
    eps = m["rms_eps"]
    mixer = attention if full else gated_delta_net
    x = x + jax.checkpoint(lambda x: mixer(
        p, pre, rms_norm(x, p[pre + "mixer_norm_gamma"], eps), m))(x)
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    y, aux, counts = moe(p, pre, h.reshape(b * t, -1), m)
    return x + y.reshape(b, t, -1), aux, counts


def head_loss(w, x, target, m):
    """w = (final gain, head); x (B, T, D) the last residual state ->
    the mean next-token cross-entropy."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    logits = rms_norm(x, w[0], m["rms_eps"]).reshape(b * t, -1) @ w[1].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, target.reshape(-1)[:, None],
                                         1)[:, 0])


def loss_and_grads(config, params, tokens, labels, names=None):
    """float32, highest precision, BLOCK BY BLOCK: the weights stay on
    the host and one block's are on the device at a time, with the
    residual states between blocks; the backward pass walks the blocks
    from the last with ``jax.vjp`` of the same block function, which
    forms the block again.

    -> dict: ``loss`` (the mean cross-entropy, what the program's metric
    reads), ``aux`` (each block's load-balance score), ``counts``
    (choices per expert, per block), ``grads`` of ``names`` (every
    parameter where None) of ``loss + aux_coef * sum(aux)``."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(host) if names is None else set(names)
    tk = jnp.asarray(np.asarray(tokens)).astype(jnp.int32)
    lb = jnp.asarray(np.asarray(labels)).astype(jnp.int32)
    coef = float(m.get("aux_coef", 0.0))
    grads, counts, auxes = {}, {}, []

    def part(pre):
        """One block's weights on the device, the prefix taken off (the
        layers of one kind then share one program), as (those whose
        gradient is wanted, the rest)."""
        mine = {k[len(pre):]: (k, jnp.asarray(v)) for k, v in host.items()
                if k.startswith(pre)}
        return ({k: v for k, (name, v) in mine.items() if name in wanted},
                {k: v for k, (name, v) in mine.items()
                 if name not in wanted})

    def keep(pre, block_grads):
        for k, g in block_grads.items():
            if pre + k in wanted:
                grads[pre + k] = np.asarray(g)

    def block_programs(full):
        def fwd(p, rest, x):
            return block({**rest, **p}, "", x, m, full)

        def bwd(p, rest, x, g):
            """The cotangents of (x out, the block's score): (g, coef)."""
            out, vjp = jax.vjp(lambda p, x: fwd(p, rest, x)[:2], p, x)
            return vjp((g, jnp.asarray(coef, out[1].dtype)))
        return jax.jit(fwd), jax.jit(bwd)

    head_grad = jax.jit(jax.value_and_grad(
        lambda w, x, t: head_loss(w, x, t, m), argnums=(0, 1)))
    blocks = [("l%d_" % l, is_full(m, l)) for l in range(m["num_layers"])]
    programs = {full: block_programs(full)
                for full in sorted(set(f for _, f in blocks))}
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [embed[tk]]                       # x before each block
        for pre, full in blocks:
            x, aux, c = programs[full][0](*part(pre), states[-1])
            states.append(x)
            auxes.append(float(aux))
            counts[pre + "moe_dispatch"] = c
        loss, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]),
             jnp.asarray(host["lm_head_weight"])), states[-1], lb)
        keep("", {"final_norm_gamma": d_gain, "lm_head_weight": d_head})
        del d_head
        for (pre, full), x in zip(reversed(blocks), reversed(states[:-1])):
            p_block = part(pre)
            d_block, dx = programs[full][1](*p_block, x, dx)
            keep(pre, d_block)
            del p_block, d_block
        if "embed_weight" in wanted:
            keep("", {"embed_weight": jnp.zeros_like(embed).at[tk].add(dx)})
    return {"loss": float(loss), "aux": auxes, "counts": counts,
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


def reference_step(config, params, data, labels, optimizer, names):
    """The mean cross-entropy (``loss``) and the first Adam step's change
    of ``names`` under ``loss + aux_coef * sum(load balance)``.  The loss
    head scales its own gradient and the optimizer's ``rescale_grad`` is
    1."""
    import jax
    if optimizer.get("wd", 0.0) or optimizer.get("rescale_grad", 1.0) != 1.0:
        raise ValueError("the reference's Adam step has no weight decay "
                         "and no gradient rescale: %r" % (optimizer,))
    out = loss_and_grads(config, params, data["data"],
                         labels["softmax_label"], names)
    return {"loss": out["loss"], "aux": out["aux"],
            "updates": {n: jax.device_get(adam_first_step(out["grads"][n],
                                                          optimizer))
                        for n in names}}
