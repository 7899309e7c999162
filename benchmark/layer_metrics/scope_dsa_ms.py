"""Per-layer metric ``scope_dsa_ms``: device time a traced step in
attention that selects its keys: the operations the program made under
scopes of the kinds ``dsa_index`` (the indexer's three projections, its
norm and rotation: plain ops of ``mxnet_tpu/models/keye_vl.py``),
``dsa_score`` (the indexer's scores over the causal pairs),
``dsa_select`` (a row's k-th value, the tie rule, the mask),
``dsa_attn`` (softmax attention under the selection: the kernels
``dsa_attn_roofline`` reads, and the mask's tiling) and ``dsa_kl`` (the
heads' probabilities formed again and summed, the index loss and the
indexer's gradient), the last four inside ``IndexedSelfAttention``
(``mxnet_tpu/ops/sparse_attention.py``).  ``scope_seconds`` joins the
trace's operations with the program's own table of its step.
``scope_other_ms.tok`` holds these kinds too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for them.  Nothing where the program
gives no table or the step has none of these scopes."""
LAYER = "Pallas kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("dsa_index", "dsa_score", "dsa_select", "dsa_attn", "dsa_kl")


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
