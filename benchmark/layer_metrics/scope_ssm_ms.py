"""Per-layer metric ``scope_ssm_ms``: device time a traced step in the
state-space mixers: the operations the program made under scopes of the
kinds ``ssm_proj`` (a Mamba-2 layer's in-projection ``D -> 2 H P + 2 G N
+ H`` and out-projection, forward and both gradients, and the cuts XLA
puts round them), ``ssm_conv`` (the biased depthwise convolution with its
SiLU, either lowering), ``ssm_scan`` (the recurrence: the op
``SSDScan``'s gates, its kernels ``ssd_chunk_fwd`` / ``ssd_chunk_bwd``,
which ``ssd_roofline`` reads, or its plain chunks, and the layout
passes round them) and ``ssm_norm`` (the gate ``y * silu(z)`` and the
RMSNorm over all the mixer's lanes), plain symbols and two ops of
``mxnet_tpu/models/granite_hybrid.py``.  ``scope_seconds`` joins the
trace's operations with the program's own table of its step.
``scope_other_ms.tok`` holds these kinds too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for them.  Nothing where the program
gives no table or the step has none of these scopes."""
LAYER = "linear attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_norm")


def read(obs):
    import scope_seconds
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    table = scope_seconds.program_table()
    if table is None:
        return None
    kinds, _ = scope_seconds.split(tr["op_seconds"], table)
    if not any(k in kinds for k in KINDS):
        return None
    by_kind = {k: 1e3 * kinds.get(k, 0.0) / tr["steps"] for k in KINDS}
    return sum(by_kind.values()), {"steps": tr["steps"], "by_kind": by_kind}
