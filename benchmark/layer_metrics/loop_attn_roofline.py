"""Per-layer metric ``loop_attn_roofline``: the attention kernels' share
of their roofline in a model whose stack of layers is applied
``total_ut_steps`` times a step (a loop node of the graph): the least
time the chip needs for a step's causal attention of every layer in
every pass (``looped_attention_work``, below: forward and backward,
``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``) over the device time of the operations
whose name begins ``splash_mha`` (the forward and the fused backward
kernel of JAX's splash attention, which ``causal_attention`` lowers to on
a TPU).  The loop's backward pass forms each pass again from its carry,
so the forward kernel runs twice a layer and pass: that second run is
time and no work, and lowers the share by about the forward kernel's part
of the three calls' time.  ``kernel_rooflines.causal_attention_work``
reads ``num_hidden_layers`` once and would read a quarter.  Nothing where
the trace holds no such operation."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def looped_attention_work(config, traffic):
    """(operations, bytes) of a training step's attention.

    A pair and query head: ``Q K^T`` and ``P V`` forward (2 x 2 Dh) and
    five such products backward (the scores again, dV, dP, dQ, dK): 14
    Dh, over the exact ``T (T + 1) / 2`` causal pairs of a sequence, in
    each of the ``num_hidden_layers`` layers built and each of the
    ``total_ut_steps`` passes.  Bytes: q, o, dq, do at H heads and k, v,
    dk, dv at the key/value heads, once each a layer and pass.  The
    forward that the backward pass forms again is not counted."""
    import kernel_rooflines
    b, t, layers, item = kernel_rooflines._sizes(config, traffic)
    calls = layers * int(config["total_ut_steps"])
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    dh = int(config["head_dim"])
    ops = calls * 14 * dh * b * h * (t * (t + 1) // 2)
    nbytes = calls * item * b * t * dh * (4 * h + 4 * kv)
    return float(ops), float(nbytes)


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX, looped_attention_work)
