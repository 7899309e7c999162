"""Plain reference of the ``granite-4.0-h-micro`` configuration: Granite
4.0-H Micro (``model_type`` ``granitemoehybrid``, IBM) forward, loss,
gradients and one Adam step, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, with no program code.

Rows ``h = embedding_multiplier * E[tokens]``; layer ``l`` is pre-norm
with scaled residual adds,

    h = h + residual_multiplier * Mixer_l(N1(h))
    h = h + residual_multiplier * (silu(g) * u) W_out,  [g | u] = N2(h) W_in

then a final RMSNorm, the head ``h E^T / logits_scaling`` with ``E`` THE
EMBEDDING (one weight, two uses: its gradient is the sum of the lookup's
and the head's) and next-token cross-entropy.  No projection has a bias.

``layer_types[l]`` is the mixer's kind.  ``mamba`` (Mamba-2,
arXiv:2405.21060): ``[z | xBC | dt] = u W_in`` (``H P`` | ``H P + 2 G N``
| ``H`` wide); ``xBC = silu(conv(xBC) + b)``, ``conv`` depthwise and
causal, ``c_t = sum_j w[:, j] x_{t - (W - 1) + j}``, zeros before the
sequence; ``[x | B | C] = xBC`` (x as H heads of P lanes, B and C as G
groups of N); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, and
TOKEN BY TOKEN, a head (``lax.scan``: no chunk)

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T        S_0 = 0
    y_t = C_t^T S_t + D x_t

head ``j`` reading group ``j // (H / G)``; ``y = RMSNorm(y * silu(z))``
over all ``H P`` lanes, with a gain; ``y W_out``.  ``attention``: q as
``num_heads`` heads of ``head_dim``, k and v as ``num_kv_heads``; no
bias, no head norm, no rotation; query head ``n`` reads key/value head
``n // (H / Hkv)`` under the causal mask; scores times
``attention_multiplier``, softmax; ``o Wo``.

Weight names and layouts are the program's
(``mxnet_tpu.models.granite_hybrid``): projections ``(out, in)``, the
taps ``(C, W)`` and their bias ``(C,)``; ``A_log`` is ``*_ssm_a_log_bias``,
``dt_bias`` ``*_ssm_dt_bias``, ``D`` ``*_ssm_d_gamma``, one number a
head each.

``loss_and_grads`` computes block by block (one block's weights on the
device at a time, the backward pass by ``jax.vjp`` of the same block
function): the harness calls it while its checking module still holds
the chip's state (12 B + 4 B a parameter, 11.5 GiB), and the whole
model's float32 weights beside that do not fit.  One block program a
mixer kind and one head program.  The scan's backward pass keeps one
state a segment of ``SEGMENT`` tokens and forms a segment's again.
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
    Mamba layer: its two projections (``D -> 2 H P + 2 G N + H`` and ``H
    P -> D``) and the recurrence priced ONCE, as the rule states it: a
    token's write to the state and its read of it, ``2 N P`` each a head
    (whatever chunks an implementation forms).  An attention layer: the
    four projections and attention over the causal pairs (2 x 2 Dh H a
    pair).  Every layer's SwiGLU ``3 x 2 D F``.  The head over the
    vocabulary rows held.  Norms, the convolution, the gates, the
    embedding lookup and the optimizer are not counted."""
    m = _model(config)
    D, T = m["hidden_size"], m["seq_len"]
    H, P, N, G = (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
                  m["ssm_groups"])
    Ha, Hkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    kinds = m["layer_types"]
    mamba = sum(1 for k in kinds if k == "mamba")
    return {
        "ssm_proj": mamba * (2 * D * (2 * H * P + 2 * G * N + H)
                             + 2 * H * P * D),
        "ssm_scan": mamba * 4 * N * P * H,
        "attn_proj": (len(kinds) - mamba) * 2 * D * dh * (2 * Ha + 2 * Hkv),
        "attn": (len(kinds) - mamba) * 4 * dh * Ha * causal_pairs(T) / T,
        "mlp": len(kinds) * 3 * 2 * D * m["mlp_width"],
        "head": 2 * D * m["vocab_size"],
    }


def train_flops_per_sample(config) -> float:
    """FLOPs per trained token, forward + backward: 3 x
    ``forward_flops_per_token``."""
    return 3.0 * sum(forward_flops_per_token(config).values())


def rms_norm(x, gamma, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


SEGMENT = 64     # tokens a checkpointed segment of the scan


def ssm_scan(x, bm, cm, dt, a, d):
    """The recurrence token by token: x (B, T, H, P), bm and cm (B, T, G,
    N), dt (B, T, H) after the softplus, a (H,) negative, d (H,) ->
    (B, T, H, P).  Two nested ``lax.scan``s over tokens, the inner one
    checkpointed a segment (eight tokens a trip of its loop), over states
    ``(B, G, K, P, N)``."""
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
    y = rms_norm(y * jax.nn.silu(z), p[pre + "ssm_norm_gamma"], m["rms_eps"])
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
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * m["attention_multiplier"]
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), vh)

    a = jax.lax.map(one_head, (q.transpose(2, 0, 1, 3), kv_of))
    a = a.transpose(1, 2, 0, 3).reshape(b, t, H * dh)
    return a @ p[pre + "o_proj_weight"].T


def swiglu(x, w_in, w_out):
    """``[g | u] = x W_in``, ``(silu(g) * u) W_out``; projections as
    FullyConnected keeps them, (out, in)."""
    import jax
    import jax.numpy as jnp
    g, u = jnp.split(x @ w_in.T, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out.T


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


def block(p, pre, x, m, kind):
    """One decoder block: x (B, T, D) -> (B, T, D).  The mixer is
    checkpointed by itself, so that a backward pass holds its
    activations or the MLP's, not both."""
    import jax
    b, t, _ = x.shape
    eps, res = m["rms_eps"], m["residual_multiplier"]

    def mixer(x):
        h = rms_norm(x, p[pre + "mixer_norm_gamma"], eps)
        return mamba(p, pre, h, m) if kind == "mamba" \
            else attention(p, pre, h, m)

    x = x + res * jax.checkpoint(mixer)(x)
    h = rms_norm(x, p[pre + "ffn_norm_gamma"], eps).reshape(b * t, -1)
    y = by_rows(lambda rows: swiglu(rows, p[pre + "input_linear_weight"],
                                    p[pre + "output_linear_weight"]), h)
    return x + res * y.reshape(b, t, -1)


def head_loss(w, x, target, m):
    """w = (final gain, the embedding); x (B, T, D) the last residual
    state -> the mean next-token cross-entropy of ``x E^T /
    logits_scaling``."""
    import jax
    import jax.numpy as jnp
    b, t, _ = x.shape
    logits = rms_norm(x, w[0], m["rms_eps"]).reshape(b * t, -1) @ w[1].T \
        / m["logits_scaling"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, target.reshape(-1)[:, None],
                                         1)[:, 0])


def loss_and_grads(config, params, tokens, labels, names=None):
    """float32, highest precision, BLOCK BY BLOCK: the weights stay on
    the host and one block's are on the device at a time, with the
    residual states between blocks; the backward pass walks the blocks
    from the last with ``jax.vjp`` of the same block function, which
    forms the block again.  ``embed_weight``'s gradient is the head's
    plus the lookup's (times ``embedding_multiplier``).

    -> dict: ``loss`` (the mean cross-entropy, what the program's metric
    reads) and ``grads`` of ``names`` (every parameter where None)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    m = _model(config)
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    wanted = set(host) if names is None else set(names)
    tk = jnp.asarray(np.asarray(tokens)).astype(jnp.int32)
    lb = jnp.asarray(np.asarray(labels)).astype(jnp.int32)
    grads = {}

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
            return jax.vjp(lambda p, x: fwd(p, rest, x), p, x)[1](g)
        return jax.jit(fwd), jax.jit(bwd)

    head_grad = jax.jit(jax.value_and_grad(
        lambda w, x, t: head_loss(w, x, t, m), argnums=(0, 1)))
    blocks = [("l%d_" % l, kind) for l, kind in enumerate(m["layer_types"])]
    programs = {kind: block_programs(kind)
                for kind in sorted(set(k for _, k in blocks))}
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(host["embed_weight"])
        states = [m["embedding_multiplier"] * embed[tk]]   # x before a block
        for pre, kind in blocks:
            states.append(programs[kind][0](*part(pre), states[-1]))
        loss, ((d_gain, d_head), dx) = head_grad(
            (jnp.asarray(host["final_norm_gamma"]), embed), states[-1], lb)
        keep("", {"final_norm_gamma": d_gain})
        for (pre, kind), x in zip(reversed(blocks), reversed(states[:-1])):
            p_block = part(pre)
            d_block, dx = programs[kind][1](*p_block, x, dx)
            keep(pre, d_block)
            del p_block, d_block
        if "embed_weight" in wanted:
            keep("", {"embed_weight": d_head.at[tk].add(
                m["embedding_multiplier"] * dx)})
    return {"loss": float(loss), "grads": grads}


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
