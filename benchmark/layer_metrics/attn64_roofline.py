"""Per-layer metric ``attn64_roofline``: the attention kernels' share of
the roofline of the USEFUL work at heads of 64 lanes: the least time the
chip needs for the causal attention of the attention layers built, 14 x
64 operations a (query, key) pair and head over the causal pairs
(``attn64_work``, below), over the device time of the operations whose
name begins ``splash_mha`` (the forward and the fused backward kernel
``causal_attention`` lowers to on a TPU).  Where the wrapper pads a
64-lane head with zeros to a whole 128-lane row, the kernels compute
twice the useful products: such a form reads under 50 BY CONSTRUCTION,
however well it runs, and two heads a lane tile would not.  Nothing
where the trace holds no such operation."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def attention_layers(config) -> int:
    """The layers BUILT (``built_layers`` of the published
    ``layer_types``) whose mixer is attention."""
    kinds = config["layer_types"]
    return sum(1 for layer in config["built_layers"]
               if kinds[layer] == "full_attention")


def attn64_work(config, traffic):
    """(operations, bytes) of a training step's causal attention at the
    heads' own width ``Dh = hidden_size / num_attention_heads``: forward
    ``Q K^T`` and ``P V`` over the ``T (T + 1) / 2`` causal pairs (2 x 2
    Dh a pair and head), backward five such products for the forward's
    two (the scores again, dV, dP, dQ, dK): 14 Dh a pair and head.
    Bytes: q, o, dq, do at H heads and k, v, dk, dv at the key/value
    heads, once each, unpadded."""
    import kernel_rooflines
    b, t, _, item = kernel_rooflines._sizes(config, traffic)
    h = int(config["num_attention_heads"])
    kv = int(config.get("num_key_value_heads") or h)
    dh = int(config.get("head_dim") or config["hidden_size"] // h)
    layers = attention_layers(config)
    pairs = t * (t + 1) // 2
    return (float(layers * 14 * dh * b * h * pairs),
            float(layers * item * b * t * dh * (4 * h + 4 * kv)))


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX, attn64_work)
