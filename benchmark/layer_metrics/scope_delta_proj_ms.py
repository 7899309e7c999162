"""Per-layer metric ``scope_delta_proj_ms``: device time a traced step
in what stands round a delta-rule core: the operations the program made
under scopes of the kinds ``kda_proj`` (Kimi Delta Attention's q, k, v
projections and their causal convolutions, the low-rank decay and output
gates, the write gate, ``GatedRMSNorm`` and ``o_proj``:
``mxnet_tpu/models/kimi_linear.py``) and ``gdn_proj`` (Gated DeltaNet's
fused projections, convolution and output stage:
``mxnet_tpu/models/qwen3_next.py``).  The rule and its kernels are
``kda`` (``scope_kda_ms``).
``scope_parts`` joins the trace's operations with the program's own
table of its step and leaves out the wrapper events (``while``,
``conditional``, ``call``: ``wrapper_ms`` in the extra).
``scope_other_ms.tok`` holds both kinds too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for them.  0.0 where the step has the
table and no such scope (every one-chip LM cell lists the entry: the
cells' membership checks hold their lists equal); nothing where the
program gives no table."""
LAYER = "linear attention"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("kda_proj", "gdn_proj")


def read(obs):
    import scope_parts
    return scope_parts.read_ms(obs, KINDS)
