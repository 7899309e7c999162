"""Per-layer metric ``scope_moe_experts_ms``: device time a traced step in the
operations the program made under scopes of the kind
``moe_experts``: the expert layer's body (``moe_experts.l<i>``,
``ops/moe.py``): the grouped matmuls and the row movement inside the op.
``scope_seconds`` joins the trace's operations with the program's own
table of its step; 0 where the step has no such scope, nothing where the
program gives no table."""
LAYER = "routed experts"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_ms(obs, "scope_moe_experts_ms")
