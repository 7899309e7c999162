"""Per-layer metric ``scope_loop_head_ms``: device time a traced step in
what ends each pass of a looped model and in the objective behind the
loop: the operations the program made under scopes of the kinds
``loop_head`` (the head's and the exit gate's projections inside the
loop's body, every pass, and the exit distribution and the expected loss
behind it: ``mxnet_tpu/models/ouro.py``) and ``lm_loss`` (the per-token
cross entropy's own scope, every pass: ``scope_lm_loss_ms`` reads it
alone).  ``scope_seconds`` joins the trace's operations with the
program's own table of its step.  ``scope_other_ms.tok`` holds the kind
``loop_head`` too: ``scope_seconds.KINDS`` is the benchmark's and names
no reader for it.  Nothing where the program gives no table or the step
has no ``loop_head`` scope."""
LAYER = "loop node"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("loop_head", "lm_loss")


def read(obs):
    import scope_seconds
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    table = scope_seconds.program_table()
    if table is None:
        return None
    kinds, _ = scope_seconds.split(tr["op_seconds"], table)
    if KINDS[0] not in kinds:
        return None
    by_kind = {k: 1e3 * kinds.get(k, 0.0) / tr["steps"] for k in KINDS}
    return sum(by_kind.values()), {"steps": tr["steps"], "by_kind": by_kind}
