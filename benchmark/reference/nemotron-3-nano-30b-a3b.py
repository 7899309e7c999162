"""Plain reference of the ``nemotron-3-nano-30b-a3b`` configuration:
NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``) forward,
loss, gradients, one Adam step and the selection bias's first move, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
with no program code.

Rows ``h = E[tokens]`` (no multiplier).  The layers are ONE-branch blocks
by ``layer_types`` (the published ``hybrid_override_pattern``, ``M`` /
``E`` / ``*`` spelled out for the layers built):

    h = h + Mixer_l(RMSNorm(h))            eps 1e-5

and nothing else in a layer; then ``logits = RMSNorm(h) W_head`` (untied,
no divisor) and the mean next-token cross entropy.  No projection has a
bias.

``mamba`` (Mamba-2, arXiv:2405.21060): ``[z | xBC | dt] = u W_in`` (``H P``
| ``H P + 2 G N`` | ``H`` wide: 4096 | 6144 | 64; ``H P`` is heads x head
lanes, NOT ``expand`` x the hidden size); ``xBC = silu(conv(xBC) + b)``,
``conv`` depthwise and causal, ``c_t = sum_j w[:, j] x_{t - (W - 1) + j}``,
zeros before the sequence; ``[x | B | C] = xBC`` (x as H heads of P lanes,
B and C as G groups of N, head ``j`` reading group ``j // (H / G)``); ``dt
= softplus(dt + dt_bias)``, ``A = -exp(A_log)``, and TOKEN BY TOKEN, a head
(``lax.scan``: no chunk)

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T        S_0 = 0
    y_t = C_t^T S_t + D x_t

``y = y * silu(z)`` (the gate BEFORE the norm); an RMSNorm over EACH
GROUP's ``H P / G`` lanes times ONE gain over all ``H P``; ``y W_out``.

``moe``: ``s = sigmoid(u W_r)`` over all ``num_experts``; chosen = the
``experts_per_tok`` largest of ``s + b`` (``n_group`` 1, ``topk_group`` 1:
a plain top-k; ``b``, ``*_select_bias``, enters the choice only); ``w_e =
route_scale * s_e / (sum_chosen(s) + 1e-20)`` (the published form; the
program divides by ``max(sum, 1e-9)``: six sigmoids sum to about 3 and the
two agree to the last bit); an expert is PLAIN, two matrices, ``relu(u
W_up)^2 W_down``; ``y = sum over the chosen experts HELD HERE of w_e
Expert_e(u) + Shared(u)``, the shared expert of the same form and its own
width: a loop over the ``experts_held`` experts from ``first_expert`` on.
What the absent experts would have added is left out, here as in the
program; the weights are normalized over all chosen experts, held or not.
``b``'s move after a step is ``bias_rate * sign(mean load - load)``.  There
is no load-balance loss.

``attention``: q as ``num_heads`` heads of ``head_dim``, k and v as
``num_kv_heads``; no bias, no head norm, NO rotation; query head ``n``
reads key/value head ``n // (H / Hkv)`` under the causal mask; scores
times ``head_dim ** -0.5``, softmax; ``o Wo``.

Weight names and layouts are the program's
(``mxnet_tpu.models.nemotron_h``): projections ``(out, in)``, the taps
``(C, W)`` and their bias ``(C,)``, stacked experts ``(held, D, W)`` and
``(held, W, D)``; ``A_log`` is ``*_ssm_a_log_bias``, ``dt_bias``
``*_ssm_dt_bias``, ``D`` ``*_ssm_d_gamma``, one number a head each.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds the
chip's state, and the whole model's float32 weights beside that do not
fit.  ONE block program a KIND of layer, compiled once for all layers of
the kind, and one head program.  The scan's backward pass keeps one state
a segment of ``SEGMENT`` tokens and forms a segment's again.
"""
from __future__ import annotations


def _model(config):
    return dict(config["model"]["kwargs"])


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs a head computes over one sequence under the
    causal mask: ``T (T + 1) / 2``."""
    return seq_len * (seq_len + 1) // 2


def forward_flops_per_token(config) -> dict:
    """Forward FLOPs a token by part, matrix products only (2mnk).  A
    Mamba layer: its two projections (``D -> 2 H P + 2 G N + H`` and ``H P
    -> D``) and the recurrence priced ONCE, as the rule states it: a
    token's write to the state and its read of it, ``2 N P`` each a head.
    An expert layer: the router over all experts, the shared expert's two
    matrices, and the HELD share of the k chosen experts' two (``k x held
    / experts x 2 x 2 D W``).  An attention layer: the four projections
    and attention over the causal pairs (2 x 2 Dh H a pair).  The head
    over the vocabulary rows held.  Norms, the convolution, the gates, the
    squares, the embedding lookup, the sort and the optimizer are not
    counted."""
    m = _model(config)
    D, T = m["hidden_size"], m["seq_len"]
    H, P, N, G = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
                  m["ssm_groups"])
    Ha, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E = m["num_experts"]
    held = m.get("experts_held") or E
    n = {kind: sum(1 for k in m["layer_types"] if k == kind)
         for kind in ("mamba", "moe", "attention")}
    return {
        "ssm_proj": n["mamba"] * (2 * D * (2 * H * P + 2 * G * N + H)
                                  + 2 * H * P * D),
        "ssm_scan": n["mamba"] * 4 * N * P * H,
        "moe_route": n["moe"] * 2 * D * E,
        "moe_shared": n["moe"] * 2 * 2 * D * m["shared_width"],
        "moe_experts": n["moe"] * m["experts_per_tok"] * held / E
        * 2 * 2 * D * m["expert_width"],
        "attn_proj": n["attention"] * 2 * D * dh * (2 * Ha + 2 * Hkv),
        "attn": n["attention"] * 4 * dh * Ha * causal_pairs(T) / T,
        "head": 2 * D * m["vocab_size"],
    }


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward: 3 x
    ``forward_flops_per_token``."""
    return 3.0 * sum(forward_flops_per_token(config).values())


def rms_norm(x, gamma, eps, groups=1):
    """The statistic over each of ``groups`` equal parts of the last axis,
    ONE gain over all of it."""
    import jax.numpy as jnp
    parts = x.reshape(x.shape[:-1] + (groups, -1))
    parts = parts / jnp.sqrt(jnp.mean(parts * parts, axis=-1, keepdims=True)
                             + eps)
    return parts.reshape(x.shape) * gamma


SEGMENT = 64     # tokens a checkpointed segment of the scan


def ssm_scan(x, bm, cm, dt, a, d):
    """The recurrence token by token: x (B, T, H, P), bm and cm (B, T, G,
    N), dt (B, T, H) after the softplus, a (H,) negative, d (H,) ->
    (B, T, H, P).  ``B`` and ``C`` are indexed BY GROUP: the state is
    ``(B, G, K, P, N)``, ``K = H / G`` heads under each group's ``B_t`` and
    ``C_t``.  Two nested ``lax.scan``s over tokens, the inner one
    checkpointed a segment (eight tokens a trip of its loop)."""
    import jax
    import jax.numpy as jnp
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    k = h // g
    seg = SEGMENT if t % SEGMENT == 0 else 1
    a = a.reshape(g, k)

    def token(S, c):
        xt, bt, ct, dtt = c                  # (B,G,K,P) (B,G,N) (B,G,N) (B,G,K)
        S = jnp.exp(dtt * a)[..., None, None] * S \
            + dtt[..., None, None] * xt[..., None] * bt[:, :, None, None, :]
        return S, jnp.sum(ct[:, :, None, None, :] * S, axis=4)

    @jax.checkpoint
    def segment(S, c):
        return jax.lax.scan(token, S, c, unroll=min(seg, 8))

    def by_token(v, tail):                   # (B, T, ..) -> (T/seg, seg, B, ..)
        return jnp.moveaxis(v.reshape((b, t) + tail), 1, 0).reshape(
            (t // seg, seg, b) + tail)

    _, y = jax.lax.scan(segment, jnp.zeros((b, g, k, p, n), x.dtype),
                        (by_token(x, (g, k, p)), by_token(bm, (g, n)),
                         by_token(cm, (g, n)), by_token(dt, (g, k))))
    y = jnp.moveaxis(y.reshape(t, b, h, p), 0, 1)
    return y + d[:, None] * x


def causal_conv(x, w, bias):
    """x (B, T, C), w (C, W), bias (C,): the causal depthwise
    convolution plus the bias."""
    import jax.numpy as jnp
    t, taps = x.shape[1], w.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(taps)) + bias


def mamba(p, pre, u, m):
    """The Mamba-2 mixer, u (B, T, D) -> (B, T, D)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = u.shape
    H, P, N, G = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
                  m["ssm_groups"])
    inner, bc = H * P, G * N
    zxbcdt = u @ p[pre + "in_proj_weight"].T
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(causal_conv(xbc, p[pre + "conv_weight"],
                                  p[pre + "conv_bias"]))
    x = xbc[..., :inner].reshape(b, t, H, P)
    bm = xbc[..., inner:inner + bc].reshape(b, t, G, N)
    cm = xbc[..., inner + bc:].reshape(b, t, G, N)
    dt = jax.nn.softplus(dt + p[pre + "ssm_dt_bias"])
    y = ssm_scan(x, bm, cm, dt, -jnp.exp(p[pre + "ssm_a_log_bias"]),
                 p[pre + "ssm_d_gamma"]).reshape(b, t, inner)
    y = rms_norm(y * jax.nn.silu(z), p[pre + "ssm_norm_gamma"], m["rms_eps"],
                 groups=G)
    return y @ p[pre + "out_proj_weight"].T


def attention(p, pre, x, m):
    """x (B, T, D) -> (B, T, D): no positions, no head norms."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = (x @ p[pre + "q_proj_weight"].T).reshape(b, t, H, dh)
    k = (x @ p[pre + "k_proj_weight"].T).reshape(b, t, Hkv, dh)
    v = (x @ p[pre + "v_proj_weight"].T).reshape(b, t, Hkv, dh)
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
    return a @ p[pre + "o_proj_weight"].T


def relu2(x):
    import jax
    return jax.nn.relu(x) ** 2


def moe(p, pre, x, m):
    """x (N, D) -> ((N, D), choices per expert (E,)): the held experts'
    part plus the shared expert.  ``first_expert`` and ``experts_held``
    say which experts the stacked weights hold (default: all)."""
    import jax
    import jax.numpy as jnp
    E, k = m["num_experts"], m["experts_per_tok"]
    held = m.get("experts_held") or E
    first = m.get("first_expert", 0)
    s = jax.nn.sigmoid(x @ p[pre + "moe_gate_weight"].T)        # (N, E)
    bias = p.get(pre + "moe_dispatch_select_bias", jnp.zeros((E,)))
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    chosen = (top_e[..., None] == jnp.arange(E)).any(axis=1)    # (N, E)
    w = m["route_scale"] * s * chosen \
        / (jnp.sum(s * chosen, axis=-1, keepdims=True) + 1e-20)
    y = relu2(x @ p[pre + "moe_shared_i2h_weight"].T) \
        @ p[pre + "moe_shared_h2o_weight"].T

    @jax.checkpoint          # one expert's hidden activations at a time
    def expert(x, w_e, w_up, w_down):
        return w_e[:, None] * (relu2(x @ w_up) @ w_down)

    for e in range(held):
        y = y + expert(x, w[:, first + e],
                       p[pre + "moe_experts_i2h_weight"][e],
                       p[pre + "moe_experts_h2o_weight"][e])
    return y, chosen.sum(axis=0).astype(jnp.float32)


def block(p, pre, x, m, kind):
    """One one-branch block: x (B, T, D) -> (x, choices per expert or
    None)."""
    b, t, _ = x.shape
    h = rms_norm(x, p[pre + "norm_gamma"], m["rms_eps"])
    counts = None
    if kind == "mamba":
        y = mamba(p, pre, h, m)
    elif kind == "attention":
        y = attention(p, pre, h, m)
    else:
        y, counts = moe(p, pre, h.reshape(b * t, -1), m)
        y = y.reshape(b, t, -1)
    return x + y, counts


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
    reads), ``counts`` (choices per expert, per expert block), ``grads``
    of ``names`` (every parameter where None).  ``params`` may hold the
    blocks' ``*_select_bias`` states; a block without one has a zero
    bias."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(k for k in host if not k.endswith("select_bias")) \
        if names is None else set(names)
    tk = jnp.asarray(np.asarray(tokens)).astype(jnp.int32)
    lb = jnp.asarray(np.asarray(labels)).astype(jnp.int32)
    grads, counts = {}, {}

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

    def block_programs(kind):
        def fwd(p, rest, x):
            return block({**rest, **p}, "", x, m, kind)

        def bwd(p, rest, x, g):
            return jax.vjp(lambda p, x: fwd(p, rest, x)[0], p, x)[1](g)
        return jax.jit(fwd), jax.jit(bwd)

    head_grad = jax.jit(jax.value_and_grad(
        lambda w, x, t: head_loss(w, x, t, m), argnums=(0, 1)))
    blocks = [("l%d_" % l, kind) for l, kind in enumerate(m["layer_types"])]
    programs = {kind: block_programs(kind)
                for kind in sorted(set(k for _, k in blocks))}
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [embed[tk]]                       # x before each block
        for pre, kind in blocks:
            x, c = programs[kind][0](*part(pre), states[-1])
            states.append(x)
            if c is not None:
                counts[pre + "moe_dispatch"] = c
        loss, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]),
             jnp.asarray(host["lm_head_weight"])), states[-1], lb)
        keep("", {"final_norm_gamma": d_gain, "lm_head_weight": d_head})
        del d_head
        for (pre, kind), x in zip(reversed(blocks), reversed(states[:-1])):
            p_block = part(pre)
            d_block, dx = programs[kind][1](*p_block, x, dx)
            keep(pre, d_block)
            del p_block, d_block
        if "embed_weight" in wanted:
            keep("", {"embed_weight": jnp.zeros_like(embed).at[tk].add(dx)})
    return {"loss": float(loss), "counts": counts, "grads": grads}


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
    """The mean cross-entropy (``loss``), the first Adam step's change
    of ``names`` and each expert block's first selection-bias move.  The
    loss head scales its own gradient and the optimizer's
    ``rescale_grad`` is 1."""
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
