"""Per-layer metric ``scope_glue_ms``: device time a traced step in
what holds a block together: the operations the program made under
scopes of the kinds ``block_norm`` (``mxnet_tpu/models/decoder.py``
``block``: the pre-norms and a sandwich block's post-norms),
``residual`` (its two sums where the builder named no scope of its own
for one, and Granite's residual multiplier) and ``cast`` (the fused
step's ``cast.params``: the parameters' copies in the compute type,
``module/fused.py``).
``scope_parts`` joins the trace's operations with the program's own
table of its step and leaves out the wrapper events (``while``,
``conditional``, ``call``: ``wrapper_ms`` in the extra).
``scope_other_ms.tok`` holds the three kinds too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for them.  0.0 where the step has the
table and no such scope (every one-chip LM cell lists the entry: the
cells' membership checks hold their lists equal); nothing where the
program gives no table."""
LAYER = "ops"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("block_norm", "residual", "cast")


def read(obs):
    import scope_parts
    return scope_parts.read_ms(obs, KINDS)
