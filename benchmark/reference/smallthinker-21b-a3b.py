"""Plain reference of the ``smallthinker-21b-a3b`` configuration:
SmallThinker-21BA3B (PowerInfer) forward, loss, gradients and one Adam
step, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, with no program code.

Input: ``x = Emb(tokens)`` (no scale).  Layer ``l``, with two RMSNorms
with gains and no bias anywhere:

    h   = N1(x)
    x'  = x + Attn_l(h)
    z   = h Wr                      the router reads the rows ATTENTION reads
    x'' = x' + MoE(N2(x'), z)

then a final RMSNorm, an untied head and next-token cross-entropy.

``Attn_l(h)``: ``q = h Wq`` as H heads of Dh, ``k = h Wk`` and ``v = h
Wv`` as Hkv heads, no norm over a head's lanes.  ``layer_types[l]`` is
the layer's kind.  A ``sliding`` layer rotates q and k at positions
``0..T-1``, lane ``i`` with lane ``i + Dh / 2``, angle ``pos * theta **
(-2 i / Dh)``, and query ``i`` reads keys ``j`` with ``0 <= i - j < W``
(``window_mask``: its own position and the ``W - 1`` before it).  A
``full`` layer rotates NOTHING and reads every ``j <= i``.  Query head
``n`` reads key/value head ``n // (H / Hkv)``; scores times ``Dh **
-0.5``, softmax; ``y = concat_heads(P v) Wo``.  The scores are formed a
head and a block of ``QUERY_BLOCK`` queries at a time, each against its
own block of the dense boolean mask.

``MoE(g, z)``: the top ``experts_per_tok`` of the ``num_experts`` logits
``z`` are chosen; ``w`` = softmax over the chosen logits alone; ``y = sum
over the chosen experts HELD HERE of w_e (relu(g Wg_e) * (g Wu_e))
Wd_e`` (ReGLU): a loop over the ``experts_held`` experts from
``first_expert`` on.  What the absent experts would have added is left
out, here as in the program; the weights are normalized over all chosen
experts, held or not.  No shared expert, no selection bias, no
load-balance loss.

Weight names and layouts are the program's
(``mxnet_tpu.models.smallthinker``): projections ``(out, in)``, stacked
experts ``(held, D, W)``, ``(held, D, W)``, ``(held, W, D)``.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's state, and the whole model's float32 weights beside that do
not fit.  One block program a kind of layer and one head program.
"""
from __future__ import annotations

QUERY_BLOCK = 1024     # queries a block of one head's scores


def _model(config):
    return dict(config["model"]["kwargs"])


def allowed_pairs(seq_len: int, window: int = 0) -> int:
    """(query, key) pairs a head computes over one sequence: query ``i``
    reads ``min(i + 1, W)`` keys under a window of ``W`` (``W (W + 1) / 2
    + (T - W) W``), ``i + 1`` under the causal mask (``T (T + 1) / 2``;
    ``window`` 0, or a window of ``T`` or more)."""
    t, w = seq_len, window if 0 < window < seq_len else seq_len
    return w * (w + 1) // 2 + (t - w) * w


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward (3 x forward), matrix
    products only (2mnk).  An attention block: the four projections (q,
    k, v, o) and attention over the pairs its kind of mask ALLOWS (2 x 2
    Dh H a pair).  An expert layer: the router over all experts and the
    HELD share of the k chosen experts (k x held / experts x 3 x 2 D W:
    0.75 expert a token at 8 of 64 under top-6).  The head over the
    vocabulary rows held.  Norms, the rotation, the gate's product, the
    embedding lookup, the sort and the optimizer are not counted."""
    m = _model(config)
    D, T, H, Hkv, dh = (m["hidden_size"], m["seq_len"], m["num_heads"],
                        m["num_kv_heads"], m["head_dim"])
    proj = 2 * D * dh * (2 * H + 2 * Hkv)
    pairs = {"sliding": allowed_pairs(T, m["window"]),
             "full": allowed_pairs(T)}
    scores = sum(4 * dh * H * pairs[kind] / T for kind in m["layer_types"])
    E = m["num_experts"]
    held = m.get("experts_held") or E
    sparse = 2 * D * E + m["experts_per_tok"] * held / E \
        * 3 * 2 * D * m["expert_width"]
    head = 2 * D * m["vocab_size"]
    return 3.0 * (m["num_layers"] * (proj + sparse) + scores + head)


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


def window_mask(seq_len: int, window: int = 0):
    """(T, T) numpy bool, True where query ``i`` (row) may read key ``j``
    (column): ``i >= j`` and, under a window, ``i - j < W``."""
    import numpy as np
    i, j = np.arange(seq_len)[:, None], np.arange(seq_len)[None, :]
    mask = i >= j
    return mask & (i - j < window) if window else mask


def attention(p, pre, h, m, kind):
    """h (B, T, D), the block's normed rows -> (B, T, D); ``kind``
    ``sliding`` or ``full``."""
    import jax
    import jax.numpy as jnp
    b, t, _ = h.shape
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = (h @ p[pre + "q_proj_weight"].T).reshape(b, t, H, dh)
    k = (h @ p[pre + "k_proj_weight"].T).reshape(b, t, Hkv, dh)
    v = (h @ p[pre + "v_proj_weight"].T).reshape(b, t, Hkv, dh)
    if kind == "sliding":
        q, k = rotate(q, m["rope_theta"]), rotate(k, m["rope_theta"])
        mask = window_mask(t, m["window"])
    else:                                   # no positions at all
        mask = window_mask(t)
    blocks = t // QUERY_BLOCK if t > QUERY_BLOCK and t % QUERY_BLOCK == 0 \
        else 1
    tq = t // blocks
    mask = jnp.asarray(mask.reshape(blocks, tq, t))
    # one (head, block of queries) a step: (H * blocks, B, tq, Dh)
    qs = q.transpose(2, 0, 1, 3).reshape(H, b, blocks, tq, dh) \
        .transpose(0, 2, 1, 3, 4).reshape(H * blocks, b, tq, dh)
    # query head n reads key/value head n // group
    kv_of = jnp.repeat(jnp.arange(H) // (H // Hkv), blocks)
    block_of = jnp.tile(jnp.arange(blocks), H)

    @jax.checkpoint          # one block of one head's scores at a time
    def one(args):
        qb, n, j = args
        s = jnp.einsum("bqd,bkd->bqk", qb, k[:, :, n]) * dh ** -0.5
        s = jnp.where(mask[j][None], s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1),
                          v[:, :, n])

    a = jax.lax.map(one, (qs, kv_of, block_of))
    a = a.reshape(H, blocks, b, tq, dh).transpose(2, 1, 3, 0, 4) \
        .reshape(b, t, H * dh)
    return a @ p[pre + "o_proj_weight"].T


def reglu(x, wg, wu, wd):
    """One expert, its matrices as the stacked tensors keep them, (in,
    out): ``(relu(x Wg) * (x Wu)) Wd``."""
    import jax
    return (jax.nn.relu(x @ wg) * (x @ wu)) @ wd


def route(z, k):
    """Logits (N, E) -> (weights (N, E), 0 where an expert is not among a
    row's top ``k``; chosen (N, E) bool): softmax over the chosen logits
    alone."""
    import jax
    import jax.numpy as jnp
    E = z.shape[-1]
    _, top_e = jax.lax.top_k(z, k)
    chosen = (top_e[..., None] == jnp.arange(E)).any(axis=1)
    return jax.nn.softmax(jnp.where(chosen, z, -jnp.inf), axis=-1), chosen


def moe(p, pre, g, z, m):
    """g (N, D) the rows the experts read, z (N, E) the router's logits
    -> ((N, D) the held experts' part, choices per expert (E,), (zeros,
    lanes): of the gate lanes ``relu(g Wg_e)`` of the rows that chose a
    held expert ``e``, how many are exactly 0, and how many there
    are)."""
    import jax
    import jax.numpy as jnp
    E, k = m["num_experts"], m["experts_per_tok"]
    held = m.get("experts_held") or E
    first = m.get("first_expert", 0)
    w, chosen = route(z, k)

    @jax.checkpoint          # one expert's hidden activations at a time
    def expert(g, w_e, wg, wu, wd):
        return w_e[:, None] * reglu(g, wg, wu, wd)

    y = jnp.zeros_like(g)
    zeros = jnp.zeros((), jnp.int32)
    for e in range(held):
        wg, wu, wd = (p[pre + "moe_experts_%s_weight" % n][e]
                      for n in ("i2h_gate", "i2h", "h2o"))
        y = y + expert(g, w[:, first + e], wg, wu, wd)
        lanes = jax.lax.stop_gradient(jax.nn.relu(g @ wg))
        zeros = zeros + jnp.sum((lanes == 0) & chosen[:, first + e, None])
    counts = chosen.sum(axis=0)
    seen = jnp.stack([zeros, counts[first:first + held].sum()
                      * m["expert_width"]])
    return y, counts.astype(jnp.float32), seen.astype(jnp.float32)


def block(p, pre, x, m, kind):
    """One decoder block: x (B, T, D) -> (x, choices per expert, (zeros,
    lanes)).  The mixer is checkpointed by itself, so that a backward
    pass holds its activations or the experts', not both."""
    import jax
    b, t, _ = x.shape
    eps = m["rms_eps"]
    h = rms_norm(x, p[pre + "attn_norm_gamma"], eps)
    x = x + jax.checkpoint(lambda h: attention(p, pre, h, m, kind))(h)
    z = h.reshape(b * t, -1) @ p[pre + "moe_gate_weight"].T
    g = rms_norm(x, p[pre + "ffn_norm_gamma"], eps).reshape(b * t, -1)
    y, counts, seen = moe(p, pre, g, z, m)
    return x + y.reshape(b, t, -1), counts, seen


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
    reads), ``counts`` (choices per expert, per expert block),
    ``act_zeros`` (``(zeros, lanes)`` per expert block, ``moe``),
    ``grads`` of ``names`` (every parameter where None)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(host) if names is None else set(names)
    tk = jnp.asarray(np.asarray(tokens)).astype(jnp.int32)
    lb = jnp.asarray(np.asarray(labels)).astype(jnp.int32)
    grads, counts, act_zeros = {}, {}, {}

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
                for kind in sorted(set(kind for _, kind in blocks))}
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [embed[tk]]                       # x before each block
        for pre, kind in blocks:
            x, c, seen = programs[kind][0](*part(pre), states[-1])
            states.append(x)
            counts[pre + "moe_dispatch"] = c
            act_zeros[pre + "moe_share"] = np.asarray(seen)
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
    return {"loss": float(loss), "counts": counts, "act_zeros": act_zeros,
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
    of ``names``.  The loss head scales its own gradient and the
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
                        for n in names}}
