"""Per-layer metric ``bd_attn_roofline``: the attention kernel's share of
its roofline under the block-diffusion mask with grouped query heads: the
least time the chip needs for a step's attention over the pairs the mask
allows (``block_diffusion_attention_work``, below: forward and backward,
``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``) over the device time of the operations
whose name begins ``splash_mha`` (the forward and the fused backward
kernel of JAX's splash attention, which ``causal_attention`` lowers to on
a TPU for this mask too; its multi-head kernel serves a group of query
heads from one key/value head in place, so the name stays ``mha``).
What the kernel's tiling visits beyond the allowed pairs (the other half
of a diagonal tile, a 1024 x 1024 tile for the noised rows' band of
``block_len``) is not work and lowers the share.  Nothing where the trace
holds no such operation."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def allowed_pairs(seq_len: int, block_len: int) -> int:
    """(query, key) pairs a head computes over the ``2 T`` rows ``[noised ;
    clean]`` of one sequence of ``T`` clean tokens in blocks of ``beta``:
    a noised row of block ``b`` reads its block's ``beta`` noised keys and
    the ``beta b`` clean keys before it, a clean row ``beta (b + 1)``
    clean keys.  Over the ``T / beta`` blocks: ``T beta + beta^2 (nb (nb
    - 1) / 2 + nb (nb + 1) / 2) = T beta + T^2``."""
    return seq_len * block_len + seq_len * seq_len


def block_diffusion_attention_work(config, traffic):
    """(operations, bytes) of a training step's attention, every layer.

    A pair and query head: ``Q K^T`` and ``P V`` forward (2 x 2 Dh) and
    five such products backward (the scores again, dV, dP, dQ, dK): 14
    Dh, as ``kernel_rooflines.causal_attention_work`` counts the causal
    half.  Bytes: q, o, dq, do at H heads and k, v, dk, dv at the
    key/value heads over the ``2 T`` rows, once each."""
    import kernel_rooflines
    b, t, layers, item = kernel_rooflines._sizes(config, traffic)
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    dh = int(config["head_dim"])
    block = int(config["model"]["kwargs"]["block_len"])
    ops = 14 * dh * b * h * allowed_pairs(t, block)
    nbytes = item * b * 2 * t * dh * (4 * h + 4 * kv)
    return float(layers * ops), float(layers * nbytes)


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX,
                                       block_diffusion_attention_work)
