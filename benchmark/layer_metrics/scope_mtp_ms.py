"""Per-layer metric ``scope_mtp_ms``: device time a traced step in the
operations the program made under scopes of the kind
``mtp``: every node a builder put under the prefix ``mtp.``
(``models/glm_moe_lite.py``): the prediction module's projection,
attention, experts, head and loss.
``scope_seconds`` joins the trace's operations with the program's own
table of its step; 0 where the step has no such scope, nothing where the
program gives no table."""
LAYER = "prediction heads"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_ms(obs, "scope_mtp_ms")
