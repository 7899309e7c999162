"""Plain reference of the ``ptb-lstm`` configuration: the unrolled LSTM
language model of Zaremba et al. 2014 (arXiv:1409.2329, the "small"
model's shape) forward, loss and gradients in float32 ``jax.numpy``,
with no program code.

It follows ``mxnet_tpu.models.lstm.lstm_unroll``: one (4H, in) weight
per layer and input kind, gates in the order input, candidate, forget,
output; the hidden states of all time steps stacked time-major into the
vocabulary projection.  Departure from the paper, the program's: no
dropout, and the loss runs over every position, padding included.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import flops  # noqa: E402


def train_flops_per_sample(config) -> float:
    m = config["model"]["kwargs"]
    return flops.lstm_lm_train_flops(m["num_lstm_layer"], m["num_hidden"],
                                     m["num_embed"], m["num_label"])


def summed_loss(p, tokens, labels, init, layers):
    """Cross-entropy summed over every (time, sentence) position."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hp = lax.Precision.HIGHEST
    seq_len = tokens.shape[1]
    emb = p["embed_weight"][tokens]                       # (B, T, E)
    c = [init["l%d_init_c" % l] for l in range(layers)]
    h = [init["l%d_init_h" % l] for l in range(layers)]
    outs = []
    for t in range(seq_len):
        x = emb[:, t]
        for l in range(layers):
            gates = (jnp.dot(x, p["l%d_i2h_weight" % l].T, precision=hp)
                     + p["l%d_i2h_bias" % l]
                     + jnp.dot(h[l], p["l%d_h2h_weight" % l].T, precision=hp)
                     + p["l%d_h2h_bias" % l])
            i, g, f, o = jnp.split(gates, 4, axis=1)
            c[l] = jax.nn.sigmoid(f) * c[l] + jax.nn.sigmoid(i) * jnp.tanh(g)
            h[l] = jax.nn.sigmoid(o) * jnp.tanh(c[l])
            x = h[l]
        outs.append(x)
    hidden = jnp.concatenate(outs, axis=0)                # (T*B, H)
    logits = jnp.dot(hidden, p["cls_weight"].T, precision=hp) + p["cls_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    flat = labels.T.reshape(-1)                           # time-major
    return -jnp.take_along_axis(logp, flat[:, None], axis=1).sum()


def reference_step(config, params, data, labels, optimizer, names):
    """Mean loss per position and the first SGD step's change of
    ``names``.  The program's SoftmaxOutput sums ``p - onehot`` over all
    positions and the module rescales by 1/batch (sentences, not
    positions): the step is ``-lr * (grad_of_sum / batch + wd * w)``."""
    import jax
    import jax.numpy as jnp
    layers = int(config["model"]["kwargs"]["num_lstm_layer"])
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    tokens = jnp.asarray(data["data"]).astype(jnp.int32)
    init = {k: jnp.asarray(v, jnp.float32) for k, v in data.items()
            if k != "data"}
    y = jnp.asarray(labels["softmax_label"]).astype(jnp.int32)
    batch = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        # data and weights are arguments, not constants of the program
        # (one compiled reference for every seed)
        total, grads = jax.jit(jax.value_and_grad(
            lambda q, tk, ys, st: summed_loss(q, tk, ys, st, layers)))(
                p, tokens, y, init)
    lr, wd = optimizer["learning_rate"], optimizer.get("wd", 0.0)
    return {"loss": float(total) / y.size,
            "updates": {n: -lr * (jax.device_get(grads[n]) / batch
                                  + wd * jax.device_get(p[n]))
                        for n in names}}
