"""Per-layer metric ``scope_lm_loss_ms``: device time a traced step in the
operations the program made under scopes of the kind
``lm_loss``: the per-token loss head's body (``SoftmaxCELoss``,
``ops/transformer.py``).
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
    return scope_seconds.read_ms(obs, "scope_lm_loss_ms")
