"""Per-layer metric ``scope_moe_share_ms``: device time a traced step in
what a row bound adds to a rank's share of a routed layer beside its
three parts: the operations the program made under scopes of the kind
``moe_share`` (``mxnet_tpu/ops/moe.py`` ``_share_bounded``: the bound's
test and the sums that add the second pass's outputs to the first's).
The parts keep their own scopes (``moe_route``, ``moe_experts``,
``moe_combine``: ``scope_moe_experts_ms``, ``scope_moe_layout_ms``) in
both passes.  No declared scope may stand around the ``cond`` (the
outermost wins, and would take the parts' operations), so the
``conditional`` itself (a wrapper event), its branch of zeros and the
sums JAX's transpose makes of the two passes' gradients stay under the
node's generic ``_moe_share_ffn.<node>`` (``scope_generic_share.tok``'s
``by_kind``); and XLA:TPU merges the test and the sums into
neighbouring fusions, which keep ONE ``op_name``: the Kimi cell reads
0.0 here beside 1.72 ms of ``_moe_share_ffn`` (PERF.md, PR 69).
``scope_parts`` joins the trace's operations with the program's own
table of its step and leaves out the wrapper events (``while``,
``conditional``, ``call``: ``wrapper_ms`` in the extra).
``scope_other_ms.tok`` holds this kind too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for it.  0.0 where the step has the
table and no such scope (every one-chip LM cell lists the entry: the
cells' membership checks hold their lists equal); nothing where the
program gives no table."""
LAYER = "routed experts"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("moe_share",)


def read(obs):
    import scope_parts
    return scope_parts.read_ms(obs, KINDS)
