"""Plain reference of the ``olmoe-1b-7b`` configuration: OLMoE
(Muennighoff et al. 2024, arXiv:2409.02060; ``model_type`` ``olmoe``)
forward, loss, gradients and one Adam step in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, with no program code.

The block, as the ``olmoe`` modelling code: ``x + Attn(RMSNorm(x))`` with
RMSNorm over the whole q and k projections before the heads are split
and half-split rotary embedding; ``x + MoE(RMSNorm(x))`` where the
router takes a softmax over all E logits, then the top k WITHOUT
renormalizing, and each expert is ``(silu(x Wg) * (x Wu)) Wd``.  The
experts are a dense loop over E with a mask: every token goes through
every expert and the gate (0 for the experts it did not choose) weights
the sum.  No sorting, no buckets, no capacity: nothing is dropped.

Objective: mean cross-entropy over all label positions + ``aux_coef`` x
the sum over layers of the load-balance loss ``E * sum_e(mean router
probability of e * share of the T*k choices that went to e)`` (a uniform
router scores 1; the share carries no gradient).  Departures from the
paper, the configuration's: no router z-loss; Adam with the weight decay
left out.

Weight names and layouts are the program's (``mxnet_tpu.models.olmoe``):
projections are ``(out, in)`` as FullyConnected keeps them, stacked
expert tensors ``(E, D, H)``, ``(E, D, H)``, ``(E, H, D)``.

The analytic FLOP count is the active-parameter one, per token, with
the causal convention that a query at position t meets t keys, T/2 on
average: scores and values are 2 x 2 x (T/2) x D = 2 T D.
"""
from __future__ import annotations


def _model(config):
    return dict(config["model"]["kwargs"])


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward (3 x forward):
    3 x [L x (4 x 2 D^2  q,k,v,o projections
              + 2 T D    causal attention: half of 4 T D
              + 2 D E    router
              + k x 3 x 2 D H   the k chosen experts' three matmuls)
         + 2 D V]        vocabulary head.
    Norms, rotary, softmaxes, the sort and the optimizer are not counted."""
    m = _model(config)
    L, D, T = m["num_layers"], m["hidden_size"], m["seq_len"]
    E, k, H, V = (m["num_experts"], m["experts_per_tok"],
                  m["expert_width"], m["vocab_size"])
    layer = 4 * 2 * D * D + 2 * T * D + 2 * D * E + k * 3 * 2 * D * H
    return 3.0 * (L * layer + 2 * D * V)


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def rotary(x, theta):
    """(B, T, H, Dh): dimension i pairs with i + Dh/2."""
    import jax.numpy as jnp
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, pre, x, m):
    """x (B, T, D) -> (B, T, D): causal multi-head attention."""
    import jax
    import jax.numpy as jnp
    b, t, d = x.shape
    nh = m["num_heads"]
    dh = d // nh
    q = rms_norm(x @ p[pre + "q_proj_weight"].T, p[pre + "q_norm_gamma"],
                 m["rms_eps"])
    k = rms_norm(x @ p[pre + "k_proj_weight"].T, p[pre + "k_norm_gamma"],
                 m["rms_eps"])
    v = x @ p[pre + "v_proj_weight"].T
    q = rotary(q.reshape(b, t, nh, dh), m["rope_theta"])
    k = rotary(k.reshape(b, t, nh, dh), m["rope_theta"])
    v = v.reshape(b, t, nh, dh)
    causal = jnp.tril(jnp.ones((t, t), bool))[None]

    @jax.checkpoint          # one head's (T, T) scores at a time
    def one_head(qkv):
        qh, kh, vh = qkv                                        # (B, T, Dh)
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * dh ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh)

    a = jax.lax.map(one_head, tuple(x.transpose(2, 0, 1, 3)
                                    for x in (q, k, v)))        # (H, B, T, Dh)
    return a.transpose(1, 2, 0, 3).reshape(b, t, d) \
        @ p[pre + "o_proj_weight"].T


def moe(p, pre, x, m):
    """x (N, D) -> ((N, D), load-balance loss, choices per expert (E,)).
    A dense loop over the experts, each masked by its gate."""
    import jax
    import jax.numpy as jnp
    n = x.shape[0]
    E, k = m["num_experts"], m["experts_per_tok"]
    probs = jax.nn.softmax(x @ p[pre + "moe_gate_weight"].T, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                      # (N, k)
    chosen = top_e[..., None] == jnp.arange(E)                  # (N, k, E)
    gate = (top_p[..., None] * chosen).sum(axis=1)              # (N, E)
    counts = chosen.sum(axis=(0, 1)).astype(jnp.float32)
    aux = E * jnp.sum(probs.mean(axis=0)
                      * jax.lax.stop_gradient(counts) / (n * k))

    @jax.checkpoint          # one expert's activations at a time
    def one_expert(acc, w):
        wg, wu, wd, g = w
        y = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
        return acc + g[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p[pre + "moe_experts_i2h_gate_weight"],
         p[pre + "moe_experts_i2h_weight"],
         p[pre + "moe_experts_h2o_weight"], gate.T))
    return out, aux, counts


def forward(p, tokens, m):
    """tokens (B, T) int -> (logits (B*T, V), [aux per layer],
    [choices per expert per layer])."""
    b, t = tokens.shape
    x = p["embed_weight"][tokens]                               # (B, T, D)
    auxes, counts = [], []
    for l in range(m["num_layers"]):
        pre = "l%d_" % l
        x = x + attention(p, pre, rms_norm(x, p[pre + "attn_norm_gamma"],
                                           m["rms_eps"]), m)
        h = rms_norm(x, p[pre + "ffn_norm_gamma"], m["rms_eps"])
        y, aux, cnt = moe(p, pre, h.reshape(b * t, -1), m)
        x = x + y.reshape(b, t, -1)
        auxes.append(aux)
        counts.append(cnt)
    x = rms_norm(x, p["final_norm_gamma"], m["rms_eps"])
    return x.reshape(b * t, -1) @ p["lm_head_weight"].T, auxes, counts


def objective(p, tokens, labels, m):
    """-> (mean CE + aux_coef * sum(aux), (mean CE, logits, auxes, counts))"""
    import jax
    import jax.numpy as jnp
    logits, auxes, counts = forward(p, tokens, m)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels.reshape(-1)[:, None], 1).mean()
    return ce + m["aux_coef"] * sum(auxes), (ce, logits, auxes, counts)


def loss_and_grads(config, params, tokens, labels, names=None):
    """float32, highest precision.  -> dict: ``loss`` (mean CE, what the
    program's metric reads), ``objective``, ``logits`` (B*T, V), ``aux``
    and ``counts`` per layer, ``grads`` of ``names`` (every parameter
    where None: a gradient is as large as its weight)."""
    import jax
    import jax.numpy as jnp
    m = _model(config)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    wanted = {k: p.pop(k) for k in (list(p) if names is None else names)}
    tk = jnp.asarray(tokens).astype(jnp.int32)
    lb = jnp.asarray(labels).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        # data and weights are arguments, not constants of the program
        (obj, (ce, logits, auxes, counts)), grads = jax.jit(
            jax.value_and_grad(
                lambda w, rest, a, b: objective({**rest, **w}, a, b, m),
                has_aux=True))(wanted, p, tk, lb)
    return {"loss": float(ce), "objective": float(obj), "logits": logits,
            "aux": [float(a) for a in auxes], "counts": counts,
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
    """Mean cross-entropy per position and the first Adam step's change
    of ``names``.  The heads scale their own gradients (1 / positions,
    ``aux_coef``) and the optimizer's ``rescale_grad`` is 1."""
    import jax
    if optimizer.get("wd", 0.0) or optimizer.get("rescale_grad", 1.0) != 1.0:
        raise ValueError("the reference's Adam step has no weight decay "
                         "and no gradient rescale: %r" % (optimizer,))
    out = loss_and_grads(config, params, data["data"],
                         labels["softmax_label"], names)
    return {"loss": out["loss"], "objective": out["objective"],
            "aux": out["aux"],
            "updates": {n: jax.device_get(adam_first_step(out["grads"][n],
                                                          optimizer))
                        for n in names}}
