"""Per-layer metric ``scope_attn_ms``: device time a traced step in the
operations the program made under scopes of the kind
``attn``: the attention op's body (``attn.l<i>``,
``ops/transformer.py``): its kernels or plain blocks and whatever else
the op computes (a ``CausalSelfAttention``'s projections, norms and
rotation).
``scope_seconds`` joins the trace's operations with the program's own
table of its step; 0 where the step has no such scope, nothing where the
program gives no table."""
LAYER = "Pallas kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    import scope_seconds
    return scope_seconds.read_ms(obs, "scope_attn_ms")
