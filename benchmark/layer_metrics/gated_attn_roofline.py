"""Per-layer metric ``gated_attn_roofline``: the attention kernels' share
of their roofline in a model whose full-attention layers are every
``full_attention_interval``-th: the least time the chip needs for a
step's causal attention of those layers (``interval_attention_work``,
below: forward and backward, ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``) over the device
time of the operations whose name begins ``splash_mha`` (the forward and
the fused backward kernel of JAX's splash attention, which
``causal_attention`` lowers to on a TPU).  The output gate's product and
the rotation of part of the lanes are not the kernel's work.  Nothing
where the trace holds no such operation."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def full_layers(config) -> int:
    """The layers BUILT (``num_hidden_layers``, from 0) that attend in
    full: every ``full_attention_interval``-th."""
    every = int(config["full_attention_interval"])
    return sum(1 for layer in range(int(config["num_hidden_layers"]))
               if (layer + 1) % every == 0)


def interval_attention_work(config, traffic):
    """(operations, bytes) of a training step's attention.

    A pair and query head: ``Q K^T`` and ``P V`` forward (2 x 2 Dh) and
    five such products backward (the scores again, dV, dP, dQ, dK): 14
    Dh, over the exact ``T (T + 1) / 2`` causal pairs of a sequence.
    Bytes: q, o, dq, do at H heads and k, v, dk, dv at the key/value
    heads, once each."""
    import kernel_rooflines
    b, t, _, item = kernel_rooflines._sizes(config, traffic)
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    dh = int(config["head_dim"])
    layers = full_layers(config)
    ops = layers * 14 * dh * b * h * (t * (t + 1) // 2)
    nbytes = layers * item * b * t * dh * (4 * h + 4 * kv)
    return float(ops), float(nbytes)


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX, interval_attention_work)
