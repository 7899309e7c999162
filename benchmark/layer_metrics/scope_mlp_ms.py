"""Per-layer metric ``scope_mlp_ms``: device time a traced step in the
dense MLPs: the operations the program made under scopes of the kind
``mlp`` (``mxnet_tpu/models/decoder.py`` ``swiglu``: the three
projections, the activation and the product, forward and both gradients,
wherever a builder calls it: the dense layers of Kimi, GLM, LFM2 and
Trinity, every layer of Ouro inside the loop's body; Granite's own fused
pair; a shared expert's projections, gate and the sum that adds it,
``moe/layer.py``).  A prediction module's shared expert is ``mtp.mlp``,
kind ``mtp``, and reads under ``scope_mtp_ms``.
``scope_parts`` joins the trace's operations with the program's own
table of its step and leaves out the wrapper events (``while``,
``conditional``, ``call``: ``wrapper_ms`` in the extra).
``scope_other_ms.tok`` holds this kind too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for it.  0.0 where the step has the
table and no such scope (every one-chip LM cell lists the entry: the
cells' membership checks hold their lists equal); nothing where the
program gives no table."""
LAYER = "ops"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("mlp",)


def read(obs):
    import scope_parts
    return scope_parts.read_ms(obs, KINDS)
