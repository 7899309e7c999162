"""Per-layer metric ``scope_moe_layout_ms``: device time a traced step in the
operations the program made under scopes of the kind
``moe_route`` and ``moe_combine``: the router, the sorted layout it
builds and the weighted sum back (``ops/moe.py``).
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
    return scope_seconds.read_ms(obs, "scope_moe_layout_ms")
