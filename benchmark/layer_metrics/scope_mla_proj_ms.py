"""Per-layer metric ``scope_mla_proj_ms``: device time a traced step in the
operations the program made under scopes of the kind
``mla_q``, ``mla_kv``, ``rope``: latent attention's projections,
norms and rotation around the attention op
(``models/latent_attention.py``).
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
    return scope_seconds.read_ms(obs, "scope_mla_proj_ms")
