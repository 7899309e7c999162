"""Plain reference of the ``ouro-2.6b`` configuration: Ouro (``model_type``
``ouro``, ByteDance; arXiv:2510.25741), a looped language model: forward,
the expected-exit objective, its gradients and one Adam step, in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, with no
program code, no ``scan``, no ``checkpoint`` and no kernel: Python ``for``
over the passes, the layers and the heads.

``x = Emb(tokens)``.  For pass ``t = 1 .. R`` (``total_ut_steps``), with
the SAME weights in every pass:

    for layer l:  x = x + N2(Attn_l(N1(x)));   x = x + N4(MLP_l(N3(x)))
    h_t = N(x);   x = h_t           (the final norm closes every pass)
    ce_t = CE(h_t W_head^T, label);   g_t = h_t w_g^T + b_g

Every ``N*`` is an RMSNorm with a gain (eps ``rms_eps``).  ``Attn_l(h)``:
``q = h Wq``, ``k = h Wk``, ``v = h Wv`` as heads of Dh, no bias, no head
norm; q and k rotated at positions ``0..T-1``, lane ``i`` with lane ``i +
Dh / 2``, angle ``pos * theta ** (-2 i / Dh)``; query head ``n`` reads
key/value head ``n // (H / Hkv)``; scores times ``Dh ** -0.5``, causal,
softmax; ``concat_heads(P v) Wo``.  ``MLP_l(h) = (silu(h Wg) * (h Wu))
Wd``.

The objective of a row: ``lambda_t = sigmoid(g_t)``, ``p_1 = lambda_1``,
``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, ``p_R = prod_{j<R} (1 -
lambda_j)``; ``sum_t p_t ce_t - beta * H(p)`` with ``H(p) = -sum_t p_t
log p_t`` (``0 log 0 = 0``).  The loss is its mean over the rows.

Weight names and layouts are the program's (``mxnet_tpu.models.ouro``):
projections ``(out, in)``, the gate ``exit_gate_weight`` ``(1, D)`` and
``exit_gate_bias`` ``(1,)``.

``objective`` is the whole model as one function, for ``jax.grad`` at
sizes that fit.  ``loss_and_grads`` computes the same STAGE BY STAGE (a
layer's projections, one head's attention, the rest of the layer, the
head over ``ROWS`` rows: one stage's weights and activations on the
device at a time, the backward pass by ``jax.vjp`` of the same stage
function from the stage's kept input): the harness calls it while its
checking module still holds the chip's state, and the whole model's
float32 activations beside that do not fit.
"""
from __future__ import annotations

ROWS = 1024      # rows the head's stage sees at a time


def _model(config):
    return dict(config["model"]["kwargs"])


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, matrix products only (2mnk).  Every pass
    counts: ``total_ut_steps`` x [L layers (q, k, v, o and the three
    SwiGLU projections: 3 x forward; causal attention over the exact
    ``T (T + 1) / 2`` pairs, 4 Dh H a pair forward and 2.5 x that
    backward, the scores being formed again) + the head and the gate (3 x
    forward)].  What the backward pass forms again of a pass (the loop's
    recomputation) is not work and is not counted; nor are norms, the
    rotation, the objective, the embedding lookup and the optimizer.  At
    one pass this is a plain dense decoder's count."""
    m = _model(config)
    D, T, H, Hkv, dh = (m["hidden_size"], m["seq_len"], m["num_heads"],
                        m["num_kv_heads"], m["head_dim"])
    proj = 2 * D * dh * (2 * H + 2 * Hkv) + 3 * 2 * D * m["mlp_width"]
    scores = 4 * dh * H * (T + 1) / 2
    head = 2 * D * (m["vocab_size"] + 1)
    return float(m.get("total_ut_steps", 4)) * (
        m["num_layers"] * (3.0 * proj + 3.5 * scores) + 3.0 * head)


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def rotate(x, theta):
    """x (B, T, H, Dh) at positions 0..T-1, lane i with lane i + Dh/2."""
    import jax.numpy as jnp
    t, dh = x.shape[1], x.shape[3]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -- the stages of a layer: weights first, as tuples -------------------------

QKV = ("attn_norm_gamma", "q_proj_weight", "k_proj_weight", "v_proj_weight")
REST = ("o_proj_weight", "attn_post_norm_gamma", "ffn_norm_gamma",
        "gate_proj_weight", "up_proj_weight", "down_proj_weight",
        "ffn_post_norm_gamma")
CLOSE = ("final_norm_gamma", "lm_head_weight", "exit_gate_weight",
         "exit_gate_bias")


def qkv(w, x, m):
    """x (B, T, D) -> q (B, T, H, Dh), k and v (B, T, Hkv, Dh), q and k
    rotated."""
    gamma, wq, wk, wv = w
    b, t, _ = x.shape
    dh = m["head_dim"]
    h = rms_norm(x, gamma, m["rms_eps"])
    q, k, v = ((h @ wx.T).reshape(b, t, -1, dh) for wx in (wq, wk, wv))
    return rotate(q, m["rope_theta"]), rotate(k, m["rope_theta"]), v


def attend(q, k, v):
    """One head: q, k, v (B, T, Dh) -> (B, T, Dh), causal."""
    import jax
    import jax.numpy as jnp
    t, dh = q.shape[1], q.shape[2]
    s = jnp.einsum("bqd,bkd->bqk", q, k) * dh ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)


def rest(w, x, a, m):
    """x (B, T, D) the layer's input, a (B, T, H Dh) the heads' outputs
    side by side -> the layer's output."""
    import jax
    wo, post_attn, pre_mlp, wg, wu, wd, post_mlp = w
    eps = m["rms_eps"]
    x = x + rms_norm(a @ wo.T, post_attn, eps)
    h = rms_norm(x, pre_mlp, eps)
    y = (jax.nn.silu(h @ wg.T) * (h @ wu.T)) @ wd.T
    return x + rms_norm(y, post_mlp, eps)


def close(w, x, label, m):
    """What ends a pass, over rows: x (N, D), label (N,) -> (h the next
    pass's rows, ce (N,), g (N,))."""
    import jax
    import jax.numpy as jnp
    gamma, head, gate_w, gate_b = w
    h = rms_norm(x, gamma, m["rms_eps"])
    logp = jax.nn.log_softmax(h @ head.T, axis=-1)
    ce = -jnp.take_along_axis(logp, label[:, None], 1)[:, 0]
    return h, ce, (h @ gate_w.T)[:, 0] + gate_b[0]


def exit_distribution(g):
    """g (R, N) gate logits -> p (R, N): a row's distribution over the
    depth it leaves at; the last pass takes what is left."""
    import jax
    import jax.numpy as jnp
    lam = jax.nn.sigmoid(g)
    p, left = [], jnp.ones_like(g[0])
    for t in range(g.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def exit_loss(ce, g, beta):
    """ce, g (R, N) -> the rows' mean ``sum_t p_t ce_t - beta H(p)``."""
    import jax.numpy as jnp
    p = exit_distribution(g)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    return jnp.mean(jnp.sum(p * ce, axis=0) + beta * jnp.sum(plogp, axis=0))


def _of(p, pre, names):
    return tuple(p[pre + n] for n in names)


def attention(q, k, v):
    """Every head in turn: (B, T, H, Dh) against (B, T, Hkv, Dh) ->
    (B, T, H Dh)."""
    import jax.numpy as jnp
    b, t, H, dh = q.shape
    group = H // k.shape[2]
    return jnp.concatenate([attend(q[:, :, n], k[:, :, n // group],
                                   v[:, :, n // group]) for n in range(H)],
                           axis=-1)


def objective(config, p, tokens, labels):
    """The whole model as one function of its weights: the loss."""
    import jax.numpy as jnp
    m = _model(config)
    x = p["embed_weight"][tokens]                      # (B, T, D)
    b, t, d = x.shape
    ces, gs = [], []
    for _ in range(m.get("total_ut_steps", 4)):
        for l in range(m["num_layers"]):
            pre = "l%d_" % l
            x = rest(_of(p, pre, REST), x,
                     attention(*qkv(_of(p, pre, QKV), x, m)), m)
        h, ce, g = close(_of(p, "", CLOSE), x.reshape(b * t, d),
                         labels.reshape(-1), m)
        x = h.reshape(b, t, d)
        ces.append(ce)
        gs.append(g)
    return exit_loss(jnp.stack(ces), jnp.stack(gs), m.get("exit_beta", 0.1))


def loss_and_grads(config, params, tokens, labels, names=None):
    """float32, highest precision, STAGE BY STAGE: the weights stay on
    the host and one stage's are on the device at a time, with each
    layer application's input; the backward pass walks the passes and
    the layers from the last with ``jax.vjp`` of the same stage
    functions, which form the stage again, and ADDS a weight's gradients
    over the passes.

    -> dict: ``loss`` (the rows' mean objective, what the program's
    metric reads), ``p`` and ``ce`` (the rows' mean exit distribution and
    cross entropy a pass), ``grads`` of ``names`` (every parameter where
    None)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    R, L = m.get("total_ut_steps", 4), m["num_layers"]
    beta = m.get("exit_beta", 0.1)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(host) if names is None else set(names)
    tk = jnp.asarray(np.asarray(tokens)).astype(jnp.int32)
    lb = jnp.asarray(np.asarray(labels)).astype(jnp.int32).reshape(-1)
    grads = {}

    def on_device(pre, which):
        return tuple(jnp.asarray(host[pre + n]) for n in which)

    def keep(pre, which, got):
        for n, g in zip(which, got):
            if pre + n in wanted:
                grads[pre + n] = grads[pre + n] + g if pre + n in grads else g

    def bwd(fn):
        """fn(w, *xs) -> jitted (w, *xs, cotangent) -> fn's vjp."""
        return jax.jit(lambda *a: jax.vjp(fn, *a[:-1])[1](a[-1]))

    qkv_f = jax.jit(lambda w, x: qkv(w, x, m))
    qkv_b = bwd(lambda w, x: qkv(w, x, m))
    attend_f, attend_b = jax.jit(attend), bwd(attend)
    rest_f = jax.jit(lambda w, x, a: rest(w, x, a, m))
    rest_b = bwd(lambda w, x, a: rest(w, x, a, m))
    close_f = jax.jit(lambda w, x, lab: close(w, x, lab, m))
    close_b = jax.jit(lambda w, x, lab, ct: jax.vjp(
        lambda w, x: close(w, x, lab, m), w, x)[1](ct))

    def heads(q, k, v):
        group = q.shape[2] // k.shape[2]
        return [(n, n // group) for n in range(q.shape[2])]

    def blocks(n):
        size = ROWS if n > ROWS and n % ROWS == 0 else n
        return [slice(i, i + size) for i in range(0, n, size)]

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(host["embed_weight"])[tk]             # (B, T, D)
        b, t, d = x.shape
        entered, closed, ces, gs = [], [], [], []
        w_close = on_device("", CLOSE)
        for _ in range(R):
            entered.append([])
            for l in range(L):
                pre = "l%d_" % l
                entered[-1].append(x)
                q, k, v = qkv_f(on_device(pre, QKV), x)
                a = jnp.concatenate([attend_f(q[:, :, n], k[:, :, j],
                                              v[:, :, j])
                                     for n, j in heads(q, k, v)], axis=-1)
                x = rest_f(on_device(pre, REST), x, a)
            closed.append(x.reshape(b * t, d))
            out = [close_f(w_close, closed[-1][rows], lb[rows])
                   for rows in blocks(b * t)]
            h, ce, g = (jnp.concatenate(part) for part in zip(*out))
            x = h.reshape(b, t, d)
            ces.append(ce)
            gs.append(g)
        ce, g = jnp.stack(ces), jnp.stack(gs)
        loss, (d_ce, d_g) = jax.value_and_grad(
            lambda ce, g: exit_loss(ce, g, beta), argnums=(0, 1))(ce, g)
        p_mean = jnp.mean(exit_distribution(g), axis=1)

        dx = jnp.zeros((b * t, d), jnp.float32)   # from the pass behind
        for r in reversed(range(R)):
            parts = []
            for rows in blocks(b * t):
                d_w, d_rows = close_b(w_close, closed[r][rows], lb[rows],
                                      (dx[rows], d_ce[r][rows],
                                       d_g[r][rows]))
                keep("", CLOSE, d_w)
                parts.append(d_rows)
            dx = jnp.concatenate(parts).reshape(b, t, d)
            for l in reversed(range(L)):
                pre = "l%d_" % l
                x = entered[r][l]
                w_qkv, w_rest = on_device(pre, QKV), on_device(pre, REST)
                q, k, v = qkv_f(w_qkv, x)
                pairs = heads(q, k, v)
                a = jnp.concatenate([attend_f(q[:, :, n], k[:, :, j],
                                              v[:, :, j])
                                     for n, j in pairs], axis=-1)
                d_w, dx_rest, da = rest_b(w_rest, x, a, dx)
                keep(pre, REST, d_w)
                da = da.reshape(q.shape)
                dq, dk, dv = [], jnp.zeros_like(k), jnp.zeros_like(v)
                for n, j in pairs:
                    one = attend_b(q[:, :, n], k[:, :, j], v[:, :, j],
                                   da[:, :, n])
                    dq.append(one[0])
                    dk = dk.at[:, :, j].add(one[1])
                    dv = dv.at[:, :, j].add(one[2])
                d_w, dx_qkv = qkv_b(w_qkv, x, (jnp.stack(dq, axis=2), dk,
                                               dv))
                keep(pre, QKV, d_w)
                dx = dx_rest + dx_qkv
                del w_qkv, w_rest, d_w
            dx = dx.reshape(b * t, d)
        if "embed_weight" in wanted:
            grads["embed_weight"] = jnp.zeros(
                host["embed_weight"].shape, jnp.float32).at[
                    tk.reshape(-1)].add(dx)
    return {"loss": float(loss), "p": np.asarray(p_mean),
            "ce": np.asarray(jnp.mean(ce, axis=1)), "grads": grads}


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
    """The rows' mean objective (``loss``), the first Adam step's change
    of ``names``, and the rows' mean exit distribution and cross entropy
    a pass (``exit``).  The loss head scales its own gradient and the
    optimizer's ``rescale_grad`` is 1."""
    import jax
    if optimizer.get("wd", 0.0) or optimizer.get("rescale_grad", 1.0) != 1.0:
        raise ValueError("the reference's Adam step has no weight decay "
                         "and no gradient rescale: %r" % (optimizer,))
    out = loss_and_grads(config, params, data["data"],
                         labels["softmax_label"], names)
    return {"loss": out["loss"],
            "updates": {n: jax.device_get(adam_first_step(out["grads"][n],
                                                          optimizer))
                        for n in names},
            "exit": {"p": out["p"].tolist(), "ce": out["ce"].tolist()}}
