"""Per-layer metric ``dsa_attn_roofline``: the attention kernels' share of
their roofline where the keys are SELECTED: the least time the chip needs
for a step's softmax attention over the pairs a learned top-k selection
keeps (``selected_attention_work``, below: forward and backward,
``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``) over the device time of the operations
whose name begins ``splash_mha`` (the forward and the fused backward
kernel of JAX's splash attention, which ``IndexedSelfAttention`` lowers
its attend pass to on a TPU, with the selection as a dynamic mask).  The
kernels visit every causal tile that holds a selected pair and count
every pair of it, selected or not: what the selection leaves out is time
and no work, so the share is at most ``dsa_kept_pairs_share`` of what the
causal mask's would be while every causal tile is hit.  The indexer's
scores are formed by other operations (the scopes ``dsa_score`` and
``dsa_kl``: ``scope_dsa_ms``) and are not counted here.  The work
function lives here until a ``benchmark`` PR moves it to
``kernel_rooflines.py`` (``benchmark/README.md``).  Nothing where the
trace holds no such operation or the configuration has no ``sa_config``."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs a head computes over one sequence: row ``t``
    reads ``min(t + 1, topk)`` keys: ``topk (topk + 1) / 2`` over the
    first ``topk`` rows and ``topk`` a row behind them."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def selected_attention_work(config, traffic):
    """(operations, bytes) of a training step's attention, every layer.

    A SELECTED pair and query head: ``Q K^T`` and ``P V`` forward (2 x 2
    Dh) and five such products backward (the scores again, dV, dP, dQ,
    dK): 14 Dh, as ``kernel_rooflines.causal_attention_work`` counts a
    causal pair; no pair a tile merely visits.  Bytes: q, o, dq, do at H
    heads and k, v, dk, dv at the key/value heads, once each."""
    import kernel_rooflines
    b, t, layers, item = kernel_rooflines._sizes(config, traffic)
    h = int(config["num_attention_heads"])
    kv = int(config["num_key_value_heads"])
    dh = int(config["head_dim"])
    topk = int(config["sa_config"]["topk"])
    ops = 14 * dh * b * h * selected_pairs(t, topk)
    nbytes = item * b * t * dh * (4 * h + 4 * kv)
    return float(layers * ops), float(layers * nbytes)


def read(obs):
    import kernel_rooflines
    if "sa_config" not in obs.get("config", {}):
        return None
    return kernel_rooflines.read_share(obs, PREFIX, selected_attention_work)
