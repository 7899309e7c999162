"""Per-layer metric ``swa_attn_roofline``: the attention kernels' share of
their roofline in a model that mixes sliding-window and full layers with
grouped query heads: the least time the chip needs for a step's
attention over the pairs each built layer's mask allows
(``mixed_window_attention_work``, below: forward and backward,
``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``) over the device time of the operations
whose name begins ``splash_mha`` (the forward and the fused backward
kernel of JAX's splash attention, which ``causal_attention`` lowers to on
a TPU under the window as under the causal mask: the two kinds' kernels
share the name and are summed).  What the kernel's tiling visits beyond
the allowed pairs (the other half of a diagonal tile, the part of a tile
that has left the window) is not work and lowers the share.  Nothing
where the trace holds no such operation."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def allowed_pairs(seq_len: int, window: int = 0) -> int:
    """(query, key) pairs a head computes over one sequence of ``T``:
    query ``i`` reads ``min(i + 1, W)`` keys under a window of ``W``, ``W
    (W + 1) / 2 + (T - W) W``; under the causal mask (``window`` 0, or a
    window the sequence fits in) ``i + 1``, ``T (T + 1) / 2``: the exact
    count with the diagonal, not the accepted causal cells' ``T^2 / 2``
    (0.02 % apart at 4096)."""
    t, w = seq_len, window if 0 < window < seq_len else seq_len
    return w * (w + 1) // 2 + (t - w) * w


def mixed_window_attention_work(config, traffic):
    """(operations, bytes) of a training step's attention, every layer
    BUILT (``model.kwargs.layer_types``: ``sliding`` under
    ``sliding_window``, ``full`` under the causal mask).

    A pair and query head: ``Q K^T`` and ``P V`` forward (2 x 2 Dh) and
    five such products backward (the scores again, dV, dP, dQ, dK): 14
    Dh, as ``kernel_rooflines.causal_attention_work`` counts the causal
    half.  Bytes: q, o, dq, do at H heads and k, v, dk, dv at the
    key/value heads, once each, whatever the layer's kind."""
    import kernel_rooflines
    b, t, _, item = kernel_rooflines._sizes(config, traffic)
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    dh = int(config["head_dim"])
    window = int(config["sliding_window"])
    kinds = list(config["model"]["kwargs"]["layer_types"])
    pairs = sum(allowed_pairs(t, window if kind == "sliding" else 0)
                for kind in kinds)
    ops = 14 * dh * b * h * pairs
    nbytes = len(kinds) * item * b * t * dh * (4 * h + 4 * kv)
    return float(ops), float(nbytes)


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX,
                                       mixed_window_attention_work)
