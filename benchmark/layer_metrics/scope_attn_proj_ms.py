"""Per-layer metric ``scope_attn_proj_ms``: device time a traced step in
what stands round an attention core: the operations the program made
under scopes of the kinds ``attn_proj`` (the q, k, v and o projections,
the head norms, the rotation and the reshapes between them:
``mxnet_tpu/models/decoder.py`` ``gqa_attention``, OLMoE's own
``attention``, latent attention's output projection and the cut round
its rotated queries; SDAR's and Keye's first residual sum lies in it, the
builders' own choice) and ``attn_gate`` (an output gate's projection,
sigmoid and product: Trinity, Qwen3-Next).  The cores are ``attn`` /
``dsa_*`` (``scope_attn_ms``, ``scope_dsa_ms``), latent attention's other
projections ``mla_q`` / ``mla_kv`` / ``rope`` (``scope_mla_proj_ms``).
``scope_parts`` joins the trace's operations with the program's own
table of its step and leaves out the wrapper events (``while``,
``conditional``, ``call``: ``wrapper_ms`` in the extra).
``scope_other_ms.tok`` holds both kinds too: ``scope_seconds.KINDS`` is
the benchmark's and names no reader for them.  0.0 where the step has the
table and no such scope (every one-chip LM cell lists the entry: the
cells' membership checks hold their lists equal); nothing where the
program gives no table."""
LAYER = "ops"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
KINDS = ("attn_proj", "attn_gate")


def read(obs):
    import scope_parts
    return scope_parts.read_ms(obs, KINDS)
