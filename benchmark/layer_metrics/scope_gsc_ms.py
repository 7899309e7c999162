"""Per-layer metric ``scope_gsc_ms``: device time a traced step in the
gated short-convolution mixers: the operations the program made under
scopes of the kinds ``gsc_proj`` (a convolution layer's in-projection ``D
-> 3 D`` and out-projection, forward and both gradients, and what XLA
puts round them) and ``gsc_conv`` (the convolution with its two gates,
either lowering: XLA's forward fusion and the backward kernel
``gsc_roofline`` reads, or the plain form's cuts, padded copy and
shifted products in both passes), plain symbols and one op of
``mxnet_tpu/models/lfm2_moe.py``.  ``scope_seconds`` joins the trace's
operations with the program's own table of its step.
``scope_other_ms.tok`` holds these kinds too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for them.  Nothing where the program
gives no table or the step has none of these scopes."""
LAYER = "linear attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("gsc_proj", "gsc_conv")


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
