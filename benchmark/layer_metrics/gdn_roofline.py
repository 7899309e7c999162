"""Per-layer metric ``gdn_roofline``: the chunked gated delta rule's share
of its roofline where the decay is ONE number a head and token (Gated
DeltaNet): the least time the chip needs for a step's chunks of every
Gated DeltaNet layer built (``gdn_chunk_work``, below: each product of
the chunked rule once, forward and backward, and what the ALGORITHM must
move once, with a ``(B, T, Hv)`` decay) over the device time of the
operations whose name begins ``kda_chunk`` (the Pallas kernels
``kda_chunk_fwd`` and ``kda_chunk_bwd``, which both front ends of
``ops/linear_attention.py`` lower to on a TPU).  The work is the same
whatever implements the rule: kernels that take the head's decay over all
key lanes move more than this counts, and the share says so.  Nothing
where the trace holds no such operation."""
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "kda_chunk"


def linear_layers(config) -> int:
    """The layers BUILT (``num_hidden_layers``, from 0) that mix by the
    rule: all but every ``full_attention_interval``-th."""
    every = int(config["full_attention_interval"])
    return sum(1 for layer in range(int(config["num_hidden_layers"]))
               if (layer + 1) % every)


def gdn_chunk_work(config, traffic):
    """(operations, bytes) of a training step's chunked rule: Hv =
    ``linear_num_value_heads`` heads of D = ``linear_value_head_dim``
    lanes (the key heads are as wide), a ``(D, D)`` float32 state a head,
    chunks of C = ``kernel_rooflines.KDA_CHUNK`` tokens.

    Operations as ``kernel_rooflines.kda_chunk_work`` counts a chunk and
    head, ``full = 2 C D D`` and ``half = C C D``: forward 3 full + 4
    half, backward 7 full + 11 half.  Bytes, a layer, each tensor once:
    either pass reads q, k, v at Hv heads and o (forward: writes it;
    backward: its gradient) in the compute dtype, the decay and beta
    ``(B, T, Hv)`` in float32 and the float32 entry states ``(B, Hv, T /
    C, D, D)`` (out, then in); the backward pass then writes the
    gradients of q, k, v and of the decay and beta: ``2 (4 item S + 2 c +
    states) + (3 item S + 2 c)`` with ``S = B T Hv D`` and ``c = 4 B T
    Hv``."""
    import kernel_rooflines
    b, t, _, item = kernel_rooflines._sizes(config, traffic)
    h = int(config["linear_num_value_heads"])
    d = int(config["linear_value_head_dim"])
    c = min(kernel_rooflines.KDA_CHUNK, t)
    chunks = b * h * -(-t // c)
    full, half = 2 * c * d * d, c * c * d
    ops = chunks * ((3 * full + 4 * half) + (7 * full + 11 * half))
    seq, col, states = b * t * h * d, 4 * b * t * h, 4 * chunks * d * d
    a_pass = 4 * item * seq + 2 * col + states
    gradients = 3 * item * seq + 2 * col
    layers = linear_layers(config)
    return float(layers * ops), float(layers * (2 * a_pass + gradients))


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX, gdn_chunk_work)
