"""Per-layer metric ``scope_kda_ms``: device time a traced step in the
operations the program made under scopes of the kind
``kda``: the Kimi delta attention op's body (``kda.l<i>``,
``ops/linear_attention.py``): the chunk kernels and the algebra around
them.
``scope_seconds`` joins the trace's operations with the program's own
table of its step; 0 where the step has no such scope, nothing where the
program gives no table."""
LAYER = "linear attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_ms(obs, "scope_kda_ms")
