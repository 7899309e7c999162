"""Per-layer metric ``scope_optimizer_ms``: device time a traced step in the
operations the program made under scopes of the kind
``optimizer``: each parameter's update (``optimizer.<parameter>``,
``module/fused.py``), the sparse-embedding tables' included.
``scope_seconds`` joins the trace's operations with the program's own
table of its step; 0 where the step has no such scope, nothing where the
program gives no table."""
LAYER = "train step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_ms(obs, "scope_optimizer_ms")
