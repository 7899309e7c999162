"""Plain reference of the ``sdar-30b-a3b`` configuration: SDAR-30B-A3B-Chat
(``model_type`` ``sdar_moe``: a Qwen3-MoE-shaped decoder trained by
diffusion over blocks, arXiv:2503.09573 in its vectorised form) forward,
the weighted masked-diffusion loss, gradients and one Adam step, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
with no program code.

A row of the batch is ``N = 2 T`` ids, ``[x_t ; x_0]``: the noised
sequence then the clean one.  Row ``n`` is at position ``n mod T`` and in
block ``b(n) = (n mod T) // beta``.  Layer ``l``: ``x + Attn(RMSNorm(x))``,
``x + MoE(RMSNorm(x))``; a final RMSNorm and an untied head over the
noised half.  No projection has a bias.

Attention: ``Q = h Wq`` as H heads of Dh, ``K = h Wk`` and ``V = h Wv`` as
Hkv heads; Q and K normed over each head's Dh lanes (one gain vector
each); both rotated at ``n mod T``, lane ``i`` with lane ``i + Dh / 2``,
angle ``pos * theta ** (-2 i / Dh)``; query head ``j`` reads key/value
head ``j // (H / Hkv)``; ``softmax(Q K^T / sqrt(Dh) + M) V``; ``Wo``.
``M`` allows (``block_mask``, a plain boolean array written from this
sentence): a noised query ``n < T`` the noised keys of its own block
(``m < T``, ``b(m) = b(n)``) and the clean keys of earlier blocks (``m >=
T``, ``b(m) < b(n)``); a clean query ``n >= T`` the clean keys with
``b(m) <= b(n)``; nothing else.

MoE: ``p = softmax(h Wr)`` over all ``num_experts``; the
``experts_per_tok`` largest; weights ``p_e / sum of the chosen``; ``y =
sum over the chosen experts HELD HERE of w_e Wd^e (silu(Wg^e h) * Wu^e
h)``: a loop over the ``experts_held`` experts from ``first_expert`` on.
What the absent experts would have added is left out, here as in the
program.  Load-balance score a block: ``E sum_e mean_rows(p_e) *
share_e`` with ``share_e`` the fraction of the rows' choices that fell
on ``e`` (no gradient through the counts), over all ``num_experts``.

Loss: ``(1 / (B T)) sum_i [target_i >= 0] weight_i CE(logits_i,
target_i)`` over the noised rows, row ``i`` against the clean token of
its own position (no shift); the labels carry ``target`` (-1 where the
position is not masked) and ``weight`` (``1 / t`` of the row's block).
The objective adds ``aux_coef`` x the sum of the blocks' load-balance
scores.

Weight names and layouts are the program's
(``mxnet_tpu.models.sdar_moe``): projections ``(out, in)``, stacked
experts ``(held, D, W)``, ``(held, D, W)``, ``(held, W, D)``.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's state, and the whole model's float32 weights beside that do
not fit.  One block program (all layers are the same block) and one head
program.
"""
from __future__ import annotations


def _model(config):
    return dict(config["model"]["kwargs"])


def allowed_pairs(seq_len: int, block_len: int) -> int:
    """(query, key) pairs the block mask allows over the ``2 T`` rows of
    one sequence: a noised row its block's ``beta`` and ``beta b`` clean
    keys, a clean row ``beta (b + 1)``: ``T beta + T^2`` in all."""
    return seq_len * block_len + seq_len * seq_len


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token (a CLEAN position: ``T`` a sequence),
    forward + backward (3 x forward), matrix products only (2mnk), as the
    vectorised form runs it: both halves, ``2 T`` rows, go through every
    layer, and the head reads the noised half.  A layer and row: the
    four projections, attention over the pairs the mask allows (2 x 2 Dh
    H a pair), the router over all experts and the HELD share of the k
    chosen experts (k x held / experts x 3 x 2 D W).  Norms, the
    rotation, the embedding lookup, the sort and the optimizer are not
    counted."""
    m = _model(config)
    D, T, H, Hkv, dh = (m["hidden_size"], m["seq_len"], m["num_heads"],
                        m["num_kv_heads"], m["head_dim"])
    E = m["num_experts"]
    held = m.get("experts_held") or E
    rows = 2 * T
    proj = 2 * D * dh * (2 * H + 2 * Hkv)
    attention = 4 * dh * H * allowed_pairs(T, m["block_len"]) / rows
    sparse = 2 * D * E + m["experts_per_tok"] * held / E \
        * 3 * 2 * D * m["expert_width"]
    layer_row = proj + attention + sparse
    head = 2 * D * m["vocab_size"]
    return 3.0 * (2 * m["num_layers"] * layer_row + head)


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def rotate(x, theta, period):
    """x (B, N, H, Dh), row n at position n mod period, lane i with lane
    i + Dh / 2."""
    import jax.numpy as jnp
    n, dh = x.shape[1], x.shape[3]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    pos = (jnp.arange(n) % period).astype(jnp.float32)
    ang = pos[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def block_mask(seq_len: int, block_len: int):
    """(2 T, 2 T) numpy bool, True where the query (row) may read the key
    (column); the four quadrants written out."""
    import numpy as np
    T = seq_len
    blk = np.arange(T) // block_len
    same = blk[:, None] == blk[None, :]
    earlier = blk[None, :] < blk[:, None]          # key's block before
    mask = np.zeros((2 * T, 2 * T), bool)
    mask[:T, :T] = same                            # noised sees noised
    mask[:T, T:] = earlier                         # noised sees clean
    mask[T:, T:] = same | earlier                  # clean sees clean
    return mask                                    # clean never sees noised


def attention(p, pre, x, m, mask):
    """x (B, N, D) -> (B, N, D); ``mask`` (N, N) bool."""
    import jax
    import jax.numpy as jnp
    b, n, _ = x.shape
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta, T = m["rms_eps"], m["rope_theta"], m["seq_len"]
    q = (x @ p[pre + "q_proj_weight"].T).reshape(b, n, H, dh)
    k = (x @ p[pre + "k_proj_weight"].T).reshape(b, n, Hkv, dh)
    v = (x @ p[pre + "v_proj_weight"].T).reshape(b, n, Hkv, dh)
    q = rotate(rms_norm(q, p[pre + "q_norm_gamma"], eps), theta, T)
    k = rotate(rms_norm(k, p[pre + "k_norm_gamma"], eps), theta, T)
    group = H // Hkv
    kv_of = jnp.arange(H) // group            # query head j reads j // group

    @jax.checkpoint          # one head's (N, N) scores at a time
    def one_head(args):
        qh, j = args
        kh, vh = k[:, :, j], v[:, :, j]
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * dh ** -0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh)

    a = jax.lax.map(one_head, (q.transpose(2, 0, 1, 3), kv_of))
    return a.transpose(1, 2, 0, 3).reshape(b, n, H * dh) \
        @ p[pre + "o_proj_weight"].T


def moe(p, pre, x, m):
    """x (N, D) -> ((N, D) the held experts' part, the block's
    load-balance score, choices per expert (E,))."""
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

    @jax.checkpoint          # one expert's hidden activations at a time
    def expert(x, w_e, wg, wu, wd):
        return w_e[:, None] * ((jax.nn.silu(x @ wg) * (x @ wu)) @ wd)

    y = jnp.zeros_like(x)
    for e in range(held):
        y = y + expert(x, w[:, first + e], *(
            p[pre + "moe_experts_%s_weight" % s][e]
            for s in ("i2h_gate", "i2h", "h2o")))
    return y, aux, counts


def block(p, pre, x, m, mask):
    """One decoder block: x (B, N, D) -> (x, load-balance score, choices
    per expert).  The mixer is checkpointed by itself, so that a
    backward pass holds its activations or the MLP's, not both."""
    import jax
    b, n, _ = x.shape
    eps = m["rms_eps"]
    x = x + jax.checkpoint(lambda x: attention(
        p, pre, rms_norm(x, p[pre + "attn_norm_gamma"], eps), m, mask))(x)
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    y, aux, counts = moe(p, pre, h.reshape(b * n, -1), m)
    return x + y.reshape(b, n, -1), aux, counts


def head_loss(w, x, target, weight, m):
    """w = (final gain, head); x (B, N, D) the last residual state.  ->
    the weighted masked loss over the noised half, ``1 / (B T)`` a row."""
    import jax
    import jax.numpy as jnp
    b, T = x.shape[0], m["seq_len"]
    noised = rms_norm(x[:, :T], w[0], m["rms_eps"]).reshape(b * T, -1)
    logp = jax.nn.log_softmax(noised @ w[1].T, axis=-1)
    has = target >= 0
    ce = -jnp.take_along_axis(logp, jnp.where(has, target, 0)[:, None],
                              1)[:, 0]
    return jnp.sum(jnp.where(has, weight * ce, 0.0)) / (b * T)


def loss_and_grads(config, params, data, labels, names=None):
    """float32, highest precision, BLOCK BY BLOCK: the weights stay on
    the host and one block's are on the device at a time, with the
    residual states between blocks; the backward pass walks the blocks
    from the last with ``jax.vjp`` of the same block function, which
    forms the block again.

    ``data`` (B, 2 T) ids, ``labels`` (B, 2, T): targets and weights.
    -> dict: ``loss`` (the weighted masked loss, what the program's
    metric reads), ``aux`` (each block's load-balance score), ``counts``
    (choices per expert, per block), ``grads`` of ``names`` (every
    parameter where None) of ``loss + aux_coef * sum(aux)``."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(host) if names is None else set(names)
    tk = jnp.asarray(np.asarray(data)).astype(jnp.int32)
    lb = np.asarray(labels, np.float32)
    target = jnp.asarray(lb[:, 0].reshape(-1)).astype(jnp.int32)
    weight = jnp.asarray(lb[:, 1].reshape(-1))
    coef = float(m.get("aux_coef", 0.0))
    mask = jnp.asarray(block_mask(m["seq_len"], m["block_len"]))
    grads, counts, auxes = {}, {}, []

    def part(pre):
        """One block's weights on the device, the prefix taken off (all
        blocks then share one program), as (those whose gradient is
        wanted, the rest)."""
        mine = {k[len(pre):]: (k, jnp.asarray(v)) for k, v in host.items()
                if k.startswith(pre)}
        return ({k: v for k, (name, v) in mine.items() if name in wanted},
                {k: v for k, (name, v) in mine.items()
                 if name not in wanted})

    def keep(pre, block_grads):
        for k, g in block_grads.items():
            if pre + k in wanted:
                grads[pre + k] = np.asarray(g)

    def fwd(p, rest, x, mask):
        return block({**rest, **p}, "", x, m, mask)

    def bwd(p, rest, x, mask, g):
        """The cotangents of (x out, the block's score) are (g, coef)."""
        out, vjp = jax.vjp(lambda p, x: fwd(p, rest, x, mask)[:2], p, x)
        return vjp((g, jnp.asarray(coef, out[1].dtype)))

    fwd, bwd = jax.jit(fwd), jax.jit(bwd)
    head_grad = jax.jit(jax.value_and_grad(
        lambda w, x, t, wt: head_loss(w, x, t, wt, m), argnums=(0, 1)))
    blocks = ["l%d_" % l for l in range(m["num_layers"])]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [embed[tk]]                       # x before each block
        for pre in blocks:
            x, aux, c = fwd(*part(pre), states[-1], mask)
            states.append(x)
            auxes.append(float(aux))
            counts[pre + "moe_dispatch"] = c
        loss, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]),
             jnp.asarray(host["lm_head_weight"])), states[-1], target, weight)
        keep("", {"final_norm_gamma": d_gain, "lm_head_weight": d_head})
        del d_head
        for pre, x in zip(reversed(blocks), reversed(states[:-1])):
            p_block = part(pre)
            d_block, dx = bwd(*p_block, x, mask, dx)
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
    """The weighted masked loss (``loss``) and the first Adam step's
    change of ``names`` under ``loss + aux_coef * sum(load balance)``.
    The loss head scales its own gradient and the optimizer's
    ``rescale_grad`` is 1."""
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
