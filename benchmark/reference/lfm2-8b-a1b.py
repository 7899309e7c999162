"""Plain reference of the ``lfm2-8b-a1b`` configuration: LFM2-8B-A1B
(``model_type`` ``lfm2_moe``, LiquidAI) forward, loss, gradients, one
Adam step and the selection bias's first move, in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, with no program code.

Rows ``x = E[tokens]``; layer ``l`` is pre-norm,

    x = x + Mixer_l(N1(x));   x = x + MLP_l(N2(x))

then a final RMSNorm, the head ``x E^T`` with ``E`` THE EMBEDDING (one
weight, two uses: its gradient is the sum of the lookup's and the
head's) and next-token cross-entropy.  No projection has a bias.

``layer_types[l]`` is the mixer's kind.  ``conv``: ``[B | C | u] = h
W_in`` (D -> 3 D, the thirds in that order), ``z = B * u``, ``c_t =
sum_j w[:, j] z_{t - (W - 1) + j}`` a channel (``w`` ``(D, W)``, zeros
before the sequence), ``y = (C * c) W_out``: no activation, no norm.
``full_attention``: ``q = Nq(h Wq)`` as H heads of Dh, ``k = Nk(h Wk)``
and ``v = h Wv`` as Hkv heads; ``Nq`` / ``Nk`` norm each head's Dh lanes
(one gain vector each) BEFORE the rotation; q and k rotated at positions
``0..T-1``, lane ``i`` with lane ``i + Dh / 2``, angle ``pos * theta **
(-2 i / Dh)``; query head ``n`` reads key/value head ``n // (H / Hkv)``
under the causal mask; scores times ``Dh ** -0.5``, softmax; ``o Wo``.

``MLP_l``: a SwiGLU of ``dense_width`` for the first ``dense_layers``
layers; after them ``s = sigmoid(h Wr)`` over all ``num_experts``;
chosen = top ``experts_per_tok`` of ``s + b``; ``w_e = route_scale * s_e
/ (sum_chosen(s) + 1e-6)`` (the published form; the program divides by
``max(sum, 1e-9)``: four sigmoids sum to about 2 and the two differ by
5e-7 of a weight); ``y = sum over the chosen experts HELD HERE of w_e
Expert_e(h)``: a loop over the ``experts_held`` experts from
``first_expert`` on.  What the absent experts would have added is left
out, here as in the program; the weights are normalized over all chosen
experts, held or not.  ``b`` (``*_select_bias``) enters the choice only;
its move after a step is ``bias_rate * sign(mean load - load)``.  There
is no shared expert and no load-balance loss.

Weight names and layouts are the program's
(``mxnet_tpu.models.lfm2_moe``): projections ``(out, in)``, the taps
``(D, W)``, stacked experts ``(held, D, W)``, ``(held, D, W)``,
``(held, W, D)``.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's state, and the whole model's float32 weights beside that do
not fit.  One block program a (kind, dense or expert) pair the layers
use and one head program.
"""
from __future__ import annotations


def _model(config):
    return dict(config["model"]["kwargs"])


def causal_pairs(seq_len: int) -> int:
    """(query, key) pairs a head computes over one sequence under the
    causal mask: ``T (T + 1) / 2``."""
    return seq_len * (seq_len + 1) // 2


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward (3 x forward), matrix
    products only (2mnk).  A convolution layer: its two projections, ``D
    -> 3 D`` and ``D -> D`` (the taps and the gates are no matrix
    products).  An attention layer: the four projections and attention
    over the causal pairs (2 x 2 Dh H a pair).  The dense MLP 3 x 2 D F.
    An expert layer: the router over all experts and the HELD share of
    the k chosen experts (k x held / experts x 3 x 2 D W: 1 expert a
    token at 8 of 32 under top-4).  The head over the vocabulary rows
    held.  Norms, the rotation, the convolution, the embedding lookup,
    the sort and the optimizer are not counted."""
    m = _model(config)
    D, T, H, Hkv, dh = (m["hidden_size"], m["seq_len"], m["num_heads"],
                        m["num_kv_heads"], m["head_dim"])
    mixer = {"conv": 2 * D * 4 * D,
             "full_attention": 2 * D * dh * (2 * H + 2 * Hkv)
             + 4 * dh * H * causal_pairs(T) / T}
    mixers = sum(mixer[kind] for kind in m["layer_types"])
    dense = 3 * 2 * D * m["dense_width"]
    E = m["num_experts"]
    held = m.get("experts_held") or E
    sparse = (2 * D * E + m["experts_per_tok"] * held / E
              * 3 * 2 * D * m["expert_width"])
    L, first = m["num_layers"], min(m["dense_layers"], m["num_layers"])
    head = 2 * D * m["vocab_size"]
    return 3.0 * (mixers + first * dense + (L - first) * sparse + head)


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


def short_conv(p, pre, x):
    """The double-gated short convolution, x (B, T, D) -> (B, T, D)."""
    import jax.numpy as jnp
    t = x.shape[1]
    gate_in, gate_out, u = jnp.split(x @ p[pre + "in_proj_weight"].T, 3,
                                     axis=-1)
    w = p[pre + "conv_weight"]                               # (D, W)
    taps = w.shape[1]
    z = jnp.pad(gate_in * u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(z[:, j:j + t] * w[:, j] for j in range(taps))
    return (gate_out * c) @ p[pre + "out_proj_weight"].T


def attention(p, pre, x, m):
    """x (B, T, D) -> (B, T, D)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta = m["rms_eps"], m["rope_theta"]
    q = rotate(rms_norm((x @ p[pre + "q_proj_weight"].T).reshape(b, t, H, dh),
                        p[pre + "q_norm_gamma"], eps), theta)
    k = rotate(rms_norm((x @ p[pre + "k_proj_weight"].T)
                        .reshape(b, t, Hkv, dh),
                        p[pre + "k_norm_gamma"], eps), theta)
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
    part."""
    import jax
    import jax.numpy as jnp
    E, k = m["num_experts"], m["experts_per_tok"]
    held = m.get("experts_held") or E
    first = m.get("first_expert", 0)
    s = jax.nn.sigmoid(x @ p[pre + "moe_gate_weight"].T)        # (N, E)
    bias = p.get(pre + "moe_dispatch_select_bias", jnp.zeros((E,)))
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    chosen = (top_e[..., None] == jnp.arange(E)).any(axis=1)    # (N, E)
    w = m.get("route_scale", 1.0) * s * chosen \
        / (jnp.sum(s * chosen, axis=-1, keepdims=True) + 1e-6)
    y = jnp.zeros_like(x)

    @jax.checkpoint          # one expert's hidden activations at a time
    def expert(x, w_e, wg, wu, wd):
        return w_e[:, None] * ((jax.nn.silu(x @ wg) * (x @ wu)) @ wd)

    for e in range(held):
        y = y + expert(x, w[:, first + e], *(
            p[pre + "moe_experts_%s_weight" % n][e]
            for n in ("i2h_gate", "i2h", "h2o")))
    return y, chosen.sum(axis=0).astype(jnp.float32)


def block(p, pre, x, m, kind, dense):
    """One decoder block: x (B, T, D) -> (x, choices per expert or
    None).  The mixer is checkpointed by itself, so that a backward pass
    holds its activations or the MLP's, not both."""
    import jax
    b, t, _ = x.shape
    eps = m["rms_eps"]

    def mixer(x):
        h = rms_norm(x, p[pre + "operator_norm_gamma"], eps)
        return short_conv(p, pre, h) if kind == "conv" \
            else attention(p, pre, h, m)

    x = x + jax.checkpoint(mixer)(x)
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], eps).reshape(b * t, -1)
    if dense:
        y, counts = by_rows(lambda rows: swiglu(
            rows, p[pre + "gate_proj_weight"], p[pre + "up_proj_weight"],
            p[pre + "down_proj_weight"]), h), None
    else:
        y, counts = moe(p, pre, h, m)
    return x + y.reshape(b, t, -1), counts


def head_loss(w, x, target, m):
    """w = (final gain, the embedding); x (B, T, D) the last residual
    state -> the mean next-token cross-entropy of ``x E^T``."""
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
    forms the block again.  ``embed_weight``'s gradient is the head's
    plus the lookup's.

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

    def block_programs(kind, dense):
        def fwd(p, rest, x):
            return block({**rest, **p}, "", x, m, kind, dense)

        def bwd(p, rest, x, g):
            return jax.vjp(lambda p, x: fwd(p, rest, x)[0], p, x)[1](g)
        return jax.jit(fwd), jax.jit(bwd)

    head_grad = jax.jit(jax.value_and_grad(
        lambda w, x, t: head_loss(w, x, t, m), argnums=(0, 1)))
    blocks = [("l%d_" % l, (kind, l < m["dense_layers"]))
              for l, kind in enumerate(m["layer_types"])]
    programs = {which: block_programs(*which)
                for which in sorted(set(w for _, w in blocks))}
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [embed[tk]]                       # x before each block
        for pre, which in blocks:
            x, c = programs[which][0](*part(pre), states[-1])
            states.append(x)
            if c is not None:
                counts[pre + "moe_dispatch"] = c
        loss, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]), embed), states[-1], lb)
        keep("", {"final_norm_gamma": d_gain})
        for (pre, which), x in zip(reversed(blocks), reversed(states[:-1])):
            p_block = part(pre)
            d_block, dx = programs[which][1](*p_block, x, dx)
            keep(pre, d_block)
            del p_block, d_block
        if "embed_weight" in wanted:
            keep("", {"embed_weight": d_head.at[tk].add(dx)})
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
