"""Per-layer metric ``scope_norm_ms``: device time a traced step in the
operations the program made under scopes of the kind
``batchnorm``: the generic scope of every ``BatchNorm`` node.  XLA
merges a BatchNorm's passes into its neighbours' fusions, and a fusion
has one scope: read it together with ``scope_conv_ms``.
``scope_seconds`` joins the trace's operations with the program's own
table of its step; 0 where the step has no such scope, nothing where the
program gives no table."""
LAYER = "ops"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_ms(obs, "scope_norm_ms")
