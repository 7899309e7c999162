"""Plain reference of the ``keye-vl-2.0-30b-a3b`` configuration:
Keye-VL-2.0-30B-A3B's language model (``model_type`` ``KeyeVL2``: a
Qwen3-MoE-shaped decoder whose attention selects its keys by a learned
indexer, DeepSeek Sparse Attention) forward, both losses, gradients and
one Adam step, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, with no program code.

Layer ``l``, rows ``x`` ``(B, T, D)``, ``h = RMSNorm(x)``; no projection
has a bias.

Main projections: ``q = h Wq`` as H heads of Dh, ``k = h Wk`` and ``v = h
Wv`` as Hkv heads; q and k normed over each head's Dh lanes (one gain
vector each); rotated at theta, lane ``i`` with lane ``i + Dh / 2``, by
``mrope_sections`` ``(n_0, n_1, n_2)``: of a head's Dh / 2 frequencies
``theta ** (-2 i / Dh)`` the first ``n_0`` turn by the temporal position,
the next ``n_1`` by the height's, the last ``n_2`` by the width's.  Text
has all three equal to the row's index in its sequence.

Indexer, on ``u = stop_gradient(h)``: ``qI = u WqI`` as Hi heads of Di,
``kI = LayerNorm(u WkI)`` one head of Di (gain and bias), ``w = u Ww`` (Hi
numbers a row); qI and kI rotated over all Di lanes by the temporal
position.  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) Di**-0.5
Hi**-0.5`` for ``s <= t``.

Selection (no gradient): ``S_t`` = the ``min(t + 1, topk)`` keys ``s <=
t`` with the largest ``I[t, s]``, a tie going to the earlier key
(``lax.top_k``'s order), a block of query rows at a time, as a dense
boolean array.

Attention: query head n reads key/value head ``n // (H / Hkv)``; ``A[n,
t, .] = softmax over S_t of q[n, t] . k[s] / sqrt(Dh)``; ``o = A v``;
``Wo``.  Index loss: ``p[t, s] = (1 / H) sum_n A[n, t, s]`` (no
gradient), ``L_I = (1 / (B T)) sum_t KL(p[t, .] || softmax over S_t of
I[t, .])``.

Then ``x + y`` and ``x + MoE(RMSNorm(x))``: ``p = softmax(h Wr)`` over
all ``num_experts``, the ``experts_per_tok`` largest, weights ``p_e / sum
of the chosen``, ``y = sum over the chosen experts HELD HERE of w_e Wd^e
(silu(Wg^e h) * Wu^e h)`` (a ``lax.scan`` over the ``experts_held``
experts from ``first_expert`` on; what the absent experts would have
added is left out, here as in the program); load-balance score a block
``E sum_e mean_rows(p_e) share_e`` over all ``num_experts``.  A final
RMSNorm, an untied head, the mean next-token cross entropy.

Objective: ``CE + aux_coef * sum(load balance) + sum_l L_I``.  CE and
the balance reach every weight but the indexer's; ``L_I`` reaches
``WqI``, ``WkI``, ``Ww`` and the LayerNorm's two vectors only: here that
is what the two ``stop_gradient``s leave.

FLOPs a trained token at the cell's sizes (``train_flops_per_sample``;
D 2048, H 32, Hkv 4, Dh 128, Hi 16, Di 64, T 8192, topk 2048, E 128, k
8, held 16, W 768, V 18992, 4 layers), forward, a layer: projections 2 D
Dh (2 H + 2 Hkv) = 37.75 M; indexer projections 2 D (Hi Di + Di + Hi) =
4.52 M; scores 2 Hi Di x (T + 1) / 2 causal pairs a row = 8.39 M;
attention 4 Dh H x 14 681 088 / 8192 selected pairs a row = 29.36 M;
router 2 D E = 0.52 M; held experts k held / E x 6 D W = 9.44 M: 89.98
M.  Four layers 359.9 M + head 2 D V = 77.79 M: 437.7 M forward.
Training 3 x that, less 1 x the indexer's projections (their input
takes no gradient: 2 x): 3 x 437.7 M - 4 x 4.52 M = 1.295 G.

Weight names and layouts are the program's (``mxnet_tpu.models.keye_vl``):
projections ``(out, in)``, stacked experts ``(held, D, W)``, ``(held, D,
W)``, ``(held, W, D)``.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's state.  One block program, run on both walks, and one head
program.
"""
from __future__ import annotations

SELECT_BLOCK = 512          # query rows a ``lax.top_k``
INDEX_EPS = 1e-6            # the LayerNorm on the indexer's key


def _model(config):
    return dict(config["model"]["kwargs"])


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs the selection keeps in one sequence: row ``t``
    keeps ``min(t + 1, topk)``."""
    return sum(min(t + 1, topk) for t in range(seq_len))


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, matrix products only (2mnk): 3 x the
    forward pass, except the indexer's three projections (2 x: their
    input takes no gradient).  A layer and row: the four projections, the
    indexer's three, the scores over the causal pairs (2 Hi Di a pair),
    attention over the pairs the selection KEEPS (2 x 2 Dh H a pair: the
    target's second q k^T is the same products formed again, not
    counted), the router over all experts and the HELD share of the k
    chosen experts.  Norms, rotations, the k-th value, the embedding
    lookup, the sort and the optimizer are not counted."""
    m = _model(config)
    D, T, H, Hkv, dh = (m["hidden_size"], m["seq_len"], m["num_heads"],
                        m["num_kv_heads"], m["head_dim"])
    Hi, Di, E = m["index_heads"], m["index_dim"], m["num_experts"]
    held = m.get("experts_held") or E
    proj = 2 * D * dh * (2 * H + 2 * Hkv)
    index_proj = 2 * D * (Hi * Di + Di + Hi)
    scores = 2 * Hi * Di * (T * (T + 1) // 2) / T
    attention = 4 * dh * H * selected_pairs(T, m["topk"]) / T
    sparse = 2 * D * E + m["experts_per_tok"] * held / E \
        * 3 * 2 * D * m["expert_width"]
    layer = proj + index_proj + scores + attention + sparse
    head = 2 * D * m["vocab_size"]
    return 3.0 * (m["num_layers"] * layer + head) \
        - m["num_layers"] * index_proj


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def layer_norm(x, gamma, beta, eps):
    import jax.numpy as jnp
    c = x - jnp.mean(x, axis=-1, keepdims=True)
    return c / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) \
        * gamma + beta


def text_positions(batch: int, seq_len: int, axes: int = 3):
    """(B, axes, T): every axis the row's index in its sequence."""
    import jax.numpy as jnp
    return jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.float32),
                            (batch, axes, seq_len))


def rotate(x, theta, positions, sections):
    """x (B, T, heads, Dh), lane i with lane i + Dh / 2; ``positions``
    (B, len(sections), T); frequency ``i`` turns by the axis whose
    section holds it."""
    import numpy as np
    import jax.numpy as jnp
    dh = x.shape[3]
    half = dh // 2
    assert sum(sections) == half
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    axis = np.concatenate([np.full(n, a) for a, n in enumerate(sections)])
    ang = jnp.transpose(positions[:, axis, :], (0, 2, 1)) * freq  # (B, T, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def indexer_scores(q_i, k_i, w):
    """q_i (T, Hi, Di), k_i (T, Di), w (T, Hi) -> I (T, T), every pair;
    ``SELECT_BLOCK`` query rows' (rows, Hi, T) products at a time."""
    import jax
    import jax.numpy as jnp
    t, hi, di = q_i.shape

    @jax.checkpoint
    def rows(args):
        q_b, w_b = args
        z = jnp.einsum("tjd,sd->tjs", q_b, k_i)
        return jnp.einsum("tjs,tj->ts", jax.nn.relu(z), w_b) \
            * di ** -0.5 * hi ** -0.5

    n = SELECT_BLOCK if t % SELECT_BLOCK == 0 else t
    return jax.lax.map(rows, (q_i.reshape(t // n, n, hi, di),
                              w.reshape(t // n, n, hi))).reshape(t, t)


def selection(scores, topk):
    """scores (T, T) -> bool (T, T): row t's ``min(t + 1, topk)`` best
    causal keys, ties to the earlier key, ``SELECT_BLOCK`` rows a
    ``lax.top_k``; a key is selected where some place of the row's
    ``topk`` names it."""
    import jax
    import jax.numpy as jnp
    t = scores.shape[0]
    n = SELECT_BLOCK if t % SELECT_BLOCK == 0 else t
    causal = jnp.tril(jnp.ones((t, t), bool))

    def rows(part):
        _, best = jax.lax.top_k(part, min(topk, t))
        return (best[:, :, None] == jnp.arange(t)).any(axis=1)

    ranked = jnp.where(causal, scores, -jnp.inf)
    return jax.lax.map(rows, ranked.reshape(t // n, n, t)).reshape(t, t) \
        & causal


def attention(p, pre, h, m, positions):
    """h (B, T, D) the normed rows -> ((B, T, D), the block's index loss,
    selected pairs)."""
    import jax
    import jax.numpy as jnp
    b, t, _ = h.shape
    H, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    Hi, Di = m["index_heads"], m["index_dim"]
    eps, theta = m["rms_eps"], m["rope_theta"]
    sections = tuple(m.get("mrope_sections", (16, 24, 24)))
    q = (h @ p[pre + "q_proj_weight"].T).reshape(b, t, H, dh)
    k = (h @ p[pre + "k_proj_weight"].T).reshape(b, t, Hkv, dh)
    v = (h @ p[pre + "v_proj_weight"].T).reshape(b, t, Hkv, dh)
    q = rotate(rms_norm(q, p[pre + "q_norm_gamma"], eps), theta, positions,
               sections)
    k = rotate(rms_norm(k, p[pre + "k_norm_gamma"], eps), theta, positions,
               sections)
    u = jax.lax.stop_gradient(h)
    temporal = positions[:, :1]
    q_i = rotate((u @ p[pre + "index_q_proj_weight"].T).reshape(
        b, t, Hi, Di), theta, temporal, (Di // 2,))
    k_i = layer_norm(u @ p[pre + "index_k_proj_weight"].T,
                     p[pre + "index_k_norm_gamma"],
                     p[pre + "index_k_norm_beta"],
                     INDEX_EPS)
    k_i = rotate(k_i.reshape(b, t, 1, Di), theta, temporal,
                 (Di // 2,))[:, :, 0]
    w = u @ p[pre + "index_w_proj_weight"].T
    group = H // Hkv
    kv_of = jnp.arange(H) // group            # query head n reads n // group

    def one_sequence(q, k, v, q_i, k_i, w):
        scores = indexer_scores(q_i, k_i, w)
        chosen = jax.lax.stop_gradient(selection(scores, m["topk"]))

        def probabilities(qh, j):
            s = (qh @ k[:, j].T) * dh ** -0.5
            return jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)

        @jax.checkpoint          # one head's (T, T) scores at a time
        def one_head(args):
            qh, j = args
            return probabilities(qh, j) @ v[:, j]

        heads = q.transpose(1, 0, 2)
        out = jax.lax.map(one_head, (heads, kv_of))

        def add_head(total, args):
            return total + probabilities(*args), None

        target, _ = jax.lax.scan(
            add_head, jnp.zeros((t, t), jnp.float32),
            (jax.lax.stop_gradient(heads), kv_of))
        target = jax.lax.stop_gradient(target) / H
        log_index = jax.nn.log_softmax(
            jnp.where(chosen, scores, -jnp.inf), axis=-1)
        kl = jnp.sum(jnp.where(
            chosen, jax.scipy.special.xlogy(target, target)
            - target * jnp.where(chosen, log_index, 0.0), 0.0), axis=-1)
        return out.transpose(1, 0, 2).reshape(t, H * dh), jnp.sum(kl), \
            jnp.sum(chosen)

    outs, kls, kept = [], 0.0, 0
    for i in range(b):
        o, kl, n = one_sequence(q[i], k[i], v[i], q_i[i], k_i[i], w[i])
        outs.append(o)
        kls, kept = kls + kl, kept + n
    return jnp.stack(outs) @ p[pre + "o_proj_weight"].T, kls / (b * t), kept


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
    def expert(y, args):
        w_e, wg, wu, wd = args
        return y + w_e[:, None] * ((jax.nn.silu(x @ wg) * (x @ wu)) @ wd), \
            None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        w[:, first:first + held].T,) + tuple(
        p[pre + "moe_experts_%s_weight" % s]
        for s in ("i2h_gate", "i2h", "h2o")))
    return y, aux, counts


def block(p, pre, x, m, positions):
    """One decoder block: x (B, T, D) -> (x, load-balance score, index
    loss, choices per expert, selected pairs).  The mixer is checkpointed
    by itself, so that a backward pass holds its activations or the
    MLP's, not both."""
    import jax
    b, t, _ = x.shape
    eps = m["rms_eps"]
    y, index_loss, kept = jax.checkpoint(lambda x: attention(
        p, pre, rms_norm(x, p[pre + "attn_norm_gamma"], eps), m,
        positions))(x)
    x = x + y
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    y, aux, counts = moe(p, pre, h.reshape(b * t, -1), m)
    return x + y.reshape(b, t, -1), aux, index_loss, counts, kept


def head_loss(w, x, target, m):
    """w = (final gain, head); x (B, T, D) the last residual state ->
    the mean next-token cross entropy."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    logits = rms_norm(x, w[0], m["rms_eps"]).reshape(b * t, -1) @ w[1].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, target.reshape(-1)[:, None],
                                         1)[:, 0])


def loss_and_grads(config, params, tokens, labels, names=None,
                   positions=None):
    """float32, highest precision, BLOCK BY BLOCK: the weights stay on
    the host and one block's are on the device at a time, with the
    residual states between blocks; the backward pass walks the blocks
    from the last with the same program (``jax.vjp`` of the block
    function), which forms the block again.

    ``tokens``, ``labels`` (B, T) ids; ``positions`` (B, 3, T) or None
    (text).  -> dict: ``loss`` (the mean cross entropy, what the
    program's metric reads), ``aux`` (each block's load-balance score),
    ``index_loss`` (each block's ``L_I``), ``counts`` (choices per
    expert, per block), ``selected`` (pairs kept, per block), ``grads``
    of ``names`` (every parameter where None) of ``loss + aux_coef *
    sum(aux) + sum(index_loss)``."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(host) if names is None else set(names)
    tk = jnp.asarray(np.asarray(tokens)).astype(jnp.int32)
    target = jnp.asarray(np.asarray(labels)).astype(jnp.int32)
    coef = float(m.get("aux_coef", 0.001))
    where = text_positions(*tk.shape) if positions is None \
        else jnp.asarray(positions, jnp.float32)
    grads, counts, auxes, index_losses, kept = {}, {}, [], [], []

    blocks = ["l%d_" % l for l in range(m["num_layers"])]
    # a block's weights whose gradient some block is asked for, the prefix
    # taken off: every block differentiates by these, so all share ONE
    # program
    wanted_here = {k[len(pre):] for pre in blocks for k in wanted
                   if k.startswith(pre)}

    def part(pre):
        """One block's weights on the device, the prefix taken off."""
        return {k[len(pre):]: jnp.asarray(v) for k, v in host.items()
                if k.startswith(pre)}

    def keep(pre, block_grads):
        for k, g in block_grads.items():
            if pre + k in wanted:
                grads[pre + k] = np.asarray(g)

    @jax.jit
    def block_and_grads(p, x, where, g):
        """The ONE block program, run on both walks (a second one for the
        walk forward alone costs more to compile at ``highest`` than its
        four runs save): ``block``'s outputs and, for the cotangents (g,
        coef, 1) of (x out, the block's balance score, its index loss),
        the gradients by the block's wanted weights and by x."""
        def run(mine, x):
            out = block({**p, **mine}, "", x, m, where)
            return out[:3], out[3:]

        out, vjp, rest = jax.vjp(
            run, {k: p[k] for k in wanted_here}, x, has_aux=True)
        return out + rest, vjp((g, jnp.asarray(coef, out[1].dtype),
                                jnp.ones_like(out[2])))

    head_grad = jax.jit(jax.value_and_grad(
        lambda w, x, t: head_loss(w, x, t, m), argnums=(0, 1)))
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [embed[tk]]                       # x before each block
        for pre in blocks:
            # the walk forward: no cotangent yet, the gradients are dropped
            x, aux, index_loss, c, n = block_and_grads(
                part(pre), states[-1], where, jnp.zeros_like(states[-1]))[0]
            states.append(x)
            auxes.append(float(aux))
            index_losses.append(float(index_loss))
            counts[pre + "moe_dispatch"] = c
            kept.append(int(n))
        loss, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]),
             jnp.asarray(host["lm_head_weight"])), states[-1], target)
        keep("", {"final_norm_gamma": d_gain, "lm_head_weight": d_head})
        del d_head
        for pre, x in zip(reversed(blocks), reversed(states[:-1])):
            d_block, dx = block_and_grads(part(pre), x, where, dx)[1]
            keep(pre, d_block)
            del d_block
        if "embed_weight" in wanted:
            keep("", {"embed_weight": jnp.zeros_like(embed).at[tk].add(dx)})
    return {"loss": float(loss), "aux": auxes, "index_loss": index_losses,
            "counts": counts, "selected": kept, "grads": grads}


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
    """The mean cross entropy (``loss``) and the first Adam step's change
    of ``names`` under ``loss + aux_coef * sum(load balance) + sum(index
    loss)``.  Every head scales its own gradient and the
    optimizer's ``rescale_grad`` is 1."""
    import jax
    if optimizer.get("wd", 0.0) or optimizer.get("rescale_grad", 1.0) != 1.0:
        raise ValueError("the reference's Adam step has no weight decay "
                         "and no gradient rescale: %r" % (optimizer,))
    out = loss_and_grads(config, params, data["data"],
                         labels["softmax_label"], names,
                         positions=data.get("positions"))
    return {"loss": out["loss"], "aux": out["aux"],
            "index_loss": out["index_loss"],
            "updates": {n: jax.device_get(adam_first_step(out["grads"][n],
                                                          optimizer))
                        for n in names}}
