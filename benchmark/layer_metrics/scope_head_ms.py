"""Per-layer metric ``scope_head_ms``: device time a traced step at the
two ends of the trunk: the operations the program made under scopes of
the kinds ``lm_head`` (``mxnet_tpu/models/decoder.py`` ``lm_head_loss``:
the final norm, the vocabulary projection, a divisor of the logits;
Ouro's final norm inside the loop; SDAR's cut of the noised half),
``embed`` (``embed``: the lookup, its reshape and a multiplier behind it,
and the table's gradient) and ``embed_sparse`` (the fused step's own
prologue for a table that is not shared, ``module/fused.py``: the ids'
de-duplication and the unique rows' gather, and behind the update the
rows' scatter).  The cross entropy is ``lm_loss``
(``scope_lm_loss_ms``); Ouro's head projection ``loop_head``
(``scope_loop_head_ms``).
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
KINDS = ("lm_head", "embed", "embed_sparse")


def read(obs):
    import scope_parts
    return scope_parts.read_ms(obs, KINDS)
