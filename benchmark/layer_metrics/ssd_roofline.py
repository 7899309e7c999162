"""Per-layer metric ``ssd_roofline``: the chunked state-space rule's share
of its roofline: the least time the chip needs for a step's chunks of
every Mamba-2 layer built (``ssd_chunk_work``, below: each product of the
chunked rule once, forward and backward, the group's ``C B^T`` once a
GROUP and not once a head, and what the ALGORITHM must move once) over
the device time of the operations whose name begins ``ssd_chunk`` (the
Pallas kernels ``ssd_chunk_fwd`` and ``ssd_chunk_bwd`` of
``ops/ssd.py``).  The work is the same whatever implements the rule: a
kernel that forms ``C B^T`` once a grid step, pads a 64-lane head's
products to the MXU's 128 columns or reads ``B`` and ``C`` once a block of
heads does more than this counts, and the share says so.  Nothing where
the trace holds no such operation."""
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "ssd_chunk"
# tokens a chunk: this program's; a chunk size does not enter the
# mathematics (the published mamba_chunk_size, 256, is a tile size too),
# only the split between the pairs inside a chunk and the states between
SSD_CHUNK = 128


def mamba_layers(config) -> int:
    """The layers BUILT (the first ``num_hidden_layers`` of
    ``layer_types``) that mix by the rule."""
    built = config["layer_types"][:int(config["num_hidden_layers"])]
    return sum(1 for kind in built if kind == "mamba")


def ssd_chunk_work(config, traffic):
    """(operations, bytes) of a training step's chunked rule: H =
    ``mamba_n_heads`` heads of P = ``mamba_d_head`` lanes over G =
    ``mamba_n_groups`` groups of N = ``mamba_d_state``, an ``(N, P)``
    float32 state a head, chunks of C = ``SSD_CHUNK`` tokens.

    A chunk and head, each product once, a triangle as half its square:
    ``full = 2 C N P`` is a chunk against a state, ``half = C C P`` the
    lower triangle of a ``(C, C)`` matrix against ``(C, P)``.  Forward,
    2 full + 1 half: the pairs inside the chunk (half), the read of the
    entry state, the write to the state.  Backward, 5 full + 2 half: the
    read formed again (the algorithm keeps entry states, not chunk
    products), the transposes of the read (2 full: C's and the state's
    cotangent), of the write (2 full: x's and B's) and of the pairs (2
    half: the matrix's cotangent and x's).  A chunk and GROUP, ``C C N``
    (the triangle of ``C B^T``): once forward, and backward once again
    and twice transposed (C's and B's cotangents), 4 in all.

    Bytes, a layer, each tensor once: either pass reads x and moves y
    (forward: writes it; backward: reads its gradient) in the compute
    dtype, reads B and C ``(B, T, G N)`` in the compute dtype and the
    step ``(B, T, H)`` in float32, and moves the float32 entry states
    ``(B, H, T / C, N, P)`` (out, then in); the backward pass then writes
    the gradients of x, B, C and of the step: ``2 (2 item S + 2 item g +
    c + states) + (item S + 2 item g + c)`` with ``S = B T H P``, ``g =
    B T G N`` and ``c = 4 B T H``."""
    import kernel_rooflines
    b, t, _, item = kernel_rooflines._sizes(config, traffic)
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    g, n = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    c = min(SSD_CHUNK, t)
    chunks = b * -(-t // c)
    full, half = 2 * c * n * p, c * c * p
    ops = chunks * (h * (7 * full + 3 * half) + g * 4 * c * c * n)
    seq, grp, col = b * t * h * p, b * t * g * n, 4 * b * t * h
    states = 4 * chunks * h * n * p
    a_pass = 2 * item * seq + 2 * item * grp + col + states
    gradients = item * seq + 2 * item * grp + col
    layers = mamba_layers(config)
    return float(layers * ops), float(layers * (2 * a_pass + gradients))


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX, ssd_chunk_work)
