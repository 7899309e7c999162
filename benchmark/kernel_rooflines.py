"""What a kernel's algorithm needs for one training step on one chip, and
the kernel's share of its roofline.

Operations and bytes come from the configuration's and the traffic's
sizes alone (never from a configuration's name), in the 2mnk convention
of ``flops.py`` and of the published peaks.  They count the useful work,
not what a tiling visits or pads, and every tensor once: a share above
100 % means a count here is too high or the summed time leaves out part
of the kernel's work, never a fast kernel.

Roofline time is the larger of operations over the peak FLOP/s and bytes
over the peak HBM bytes/s (``peaks.json``; the bf16 peak, which is also
what one MXU pass of a float32 matmul at the default precision runs at).
The share is roofline time over the time the trace measured for the
operations whose printed name begins with the kernel's prefix, on the
first device (as ``device_ops`` is), a traced step.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import trace_reduce

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
Work = Tuple[float, float]                  # operations, bytes


def _sizes(config: Dict, traffic: Dict) -> Tuple[int, int, int, int]:
    """(sequences a step and chip, their length, layers, bytes an
    element of the compute dtype)."""
    return (int(traffic["batch_per_chip"]), int(config["input"]["seq_len"]),
            int(config["num_hidden_layers"]),
            ITEMSIZE[config["compute_dtype"]])


def causal_attention_work(config: Dict, traffic: Dict) -> Work:
    """Causal self-attention, forward and backward, every layer.

    Forward: the causal half of ``Q K^T`` and of ``P V``, 2 x (2 B H T^2
    Dh) / 2 = ``2 B H T^2 Dh``.  Backward: five such products (the
    scores again, dV, dP, dQ, dK) for the forward's two: 2.5 x.  Bytes:
    q, o, dq, do at H heads and k, v, dk, dv at the key/value heads,
    once each."""
    b, t, layers, item = _sizes(config, traffic)
    h = int(config["num_attention_heads"])
    kv = int(config.get("num_key_value_heads") or h)
    dh = int(config.get("head_dim") or config["hidden_size"] // h)
    forward = 2 * b * h * t * t * dh
    nbytes = item * b * t * dh * (4 * h + 4 * kv)
    return layers * 3.5 * forward, float(layers * nbytes)


def grouped_matmul_work(config: Dict, traffic: Dict) -> Work:
    """The routed experts' grouped matmuls, every layer routed.

    ``rows = B T k`` token-choices go through gate, up and down
    projections of D x W: three matmuls forward, and for each a
    backward-data and a backward-weight one, nine of ``2 rows D W``.
    Bytes, a matmul: its rows in and out (``rows (D + W)`` whichever
    way it runs) and the stacked ``(E, D, W)`` weight, or its gradient,
    once."""
    b, t, layers, item = _sizes(config, traffic)
    rows = b * t * int(config["num_experts_per_tok"])
    d = int(config["hidden_size"])
    w = int(config.get("moe_intermediate_size")
            or config["intermediate_size"])
    e = int(config["num_experts"])
    ops = 2 * rows * d * w
    nbytes = item * (rows * (d + w) + e * d * w)
    return float(layers * 9 * ops), float(layers * 9 * nbytes)


def _built_layers_in(config: Dict, listed) -> int:
    """How many of the layers built (1 .. ``num_hidden_layers``) are in
    ``listed``, the published list of a kind of mixer's layers."""
    built = int(config["num_hidden_layers"])
    return sum(1 for layer in listed if 1 <= int(layer) <= built)


def latent_attention_work(config: Dict, traffic: Dict) -> Work:
    """Causal attention whose query and key heads (``qk_nope_head_dim +
    qk_rope_head_dim`` = Dqk) are another size than its value heads
    (``v_head_dim`` = Dv), forward and backward, every layer built that
    mixes by it: those of ``linear_attn_config.full_attn_layers`` where
    the configuration has the list, else every layer, and one more for
    each of ``num_nextn_predict_layers``.

    Every product over the causal half.  Forward: ``Q K^T`` at Dqk and
    ``P V`` at Dv, ``B H T^2 (Dqk + Dv)``.  Backward: five products,
    three at Dqk (the scores again, dQ, dK) and two at Dv (dV, dP),
    ``B H T^2 (3 Dqk + 2 Dv)``.  Bytes: q, k and their gradients at
    Dqk, v, o and theirs at Dv, H heads each, once; no lane a kernel
    pads to counts.  With Dqk = Dv and as many key/value heads as query
    heads this is ``causal_attention_work``."""
    b, t, layers, item = _sizes(config, traffic)
    mixers = config.get("linear_attn_config") or {}
    if "full_attn_layers" in mixers:
        layers = _built_layers_in(config, mixers["full_attn_layers"])
    layers += int(config.get("num_nextn_predict_layers") or 0)
    h = int(config["num_attention_heads"])
    dqk = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    dv = int(config["v_head_dim"])
    half = b * h * t * t              # 2 x the T^2 / 2 pairs a head
    ops = half * ((dqk + dv) + (3 * dqk + 2 * dv))
    nbytes = item * b * t * h * 4 * (dqk + dv)
    return float(layers * ops), float(layers * nbytes)


# tokens a chunk of the gated delta rule: the published kernels' and
# this program's; the entry states kept a chunk follow from it
KDA_CHUNK = 64


def kda_chunk_work(config: Dict, traffic: Dict) -> Work:
    """The chunked gated delta rule (KDA), forward and backward, every
    layer built that ``linear_attn_config.kda_layers`` lists: H =
    ``num_heads`` heads of D = ``head_dim`` key and value lanes, a
    ``(D, D)`` float32 state a head, chunks of C = ``KDA_CHUNK`` tokens.

    A chunk and head, each product once, a triangle as half its square
    (as attention's causal half).  ``full = 2 C D D`` is a chunk against
    a state, ``half = C C D`` the lower triangle of a ``(C, C)``
    product with ``(C, D)``.  Forward, 3 full + 4 half: the key scores
    and the query scores (2 half), the decayed keys' read of the entry
    state, the unit-triangular system against that one right-hand side
    (half; solved after the read: one right-hand side, not the two of
    solving first), the queries' read of the state, the intra-chunk
    output (half), the write to the state.  Backward, 7 full + 11 half:
    the chunk formed again from its entry state, since the algorithm
    keeps entry states and not chunk products (both scores, the read,
    the system: 1 full + 3 half; counted as ``causal_attention_work``
    counts the scores again), then the transposes: of the two reads (2
    full), of the write (2 full: the corrections', the keys'), of the
    state's own step (2 full: from the output and from the read), of
    the intra-chunk output (2 half), of the system (2 half: the
    transposed solve and the matrix's cotangent), of the two scores (4
    half).  Not counted: the passes an exact float32 product takes on
    the MXU, a level of a halving scheme, a layout copy.

    Bytes, what the two kernels must move once.  Forward: q, k, v in and
    o out in the compute dtype, the log-decay ``(B, T, H, D)`` and beta
    ``(B, T, H)`` in float32 (the rule's decay is formed and kept in
    float32 whatever the compute dtype), the float32 entry states ``(B,
    H, T / C, D, D)`` out.  Backward: q, k, v, the decay, beta and the
    states in again with o's gradient (o itself is not needed), the
    gradients of q, k, v in the compute dtype and of the decay and beta
    in float32 out."""
    b, t, _, item = _sizes(config, traffic)
    mixers = config["linear_attn_config"]
    layers = _built_layers_in(config, mixers["kda_layers"])
    h, d = int(mixers["num_heads"]), int(mixers["head_dim"])
    c = min(KDA_CHUNK, t)
    chunks = b * h * -(-t // c)
    full, half = 2 * c * d * d, c * c * d
    ops = chunks * ((3 * full + 4 * half) + (7 * full + 11 * half))
    seq, col, states = b * t * h * d, 4 * b * t * h, 4 * chunks * d * d
    # either pass: four tensors in the compute dtype, the decay, beta
    # and the states; the backward pass then writes its five gradients
    a_pass = (4 * item + 4) * seq + col + states
    gradients = (3 * item + 4) * seq + col
    return float(layers * ops), float(layers * (2 * a_pass + gradients))


def roofline_time(work: Work, peaks: Dict) -> Tuple[float, str]:
    """(seconds the chip needs at the least, which peak bounds it)."""
    t_ops = work[0] / peaks["bf16_flops_per_s"]
    t_bytes = work[1] / peaks["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), "compute" if t_ops >= t_bytes else "memory"


def read_share(obs: Dict, prefix: str,
               work_of: Callable[[Dict, Dict], Work]) -> Optional[Tuple]:
    """What a ``<kernel>_roofline`` reader returns: the share in per
    cent with the two times behind it, or None where the run has no
    trace or the trace no operation of that name."""
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    kernel_s = trace_reduce.seconds_of(tr["op_seconds"], prefix) / tr["steps"]
    if not kernel_s:
        return None
    least_s, bound = roofline_time(work_of(obs["config"], obs["traffic"]),
                                   obs["peaks"])
    return 100.0 * least_s / kernel_s, {
        "kernel_ms": 1e3 * kernel_s, "roofline_ms": 1e3 * least_s,
        "bound": bound, "steps": tr["steps"]}
