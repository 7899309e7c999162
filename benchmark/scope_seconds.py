"""Device seconds a step by the scope that made each operation: the join
of a trace's ``op_seconds`` with the program's own table of its step.

``obs["trace"]["op_seconds"]`` holds every operation of the first device
inside the traced window, keyed ``<instruction> <opcode> <largest
array>`` (``trace_reduce.short_name``): the instruction is the first word.
The program that ran ``fit`` in this process says which scope made each
instruction of its ``fused:step`` executable
(``mxnet_tpu.trace.program_scopes``: the graph node, ``attn.l0`` or
``convolution.stage1_unit1_conv1``, or the step part,
``optimizer.<parameter>``), read from the optimized HLO of the executable
that ran; it builds the table at the first request, here, after the
window.  A scope's kind is what is before its first dot.

The split is whole by construction: every operation's seconds go to its
scope's kind or, where the table has no scope for it, to ``unnamed``, so
the ``scope_*_ms`` entries of a cell and its unnamed time sum to the
summed ``op_seconds`` (the first device's busy time where its operations
do not overlap).  What the join cannot see: a fusion has the scope of ONE
of the operations XLA merged into it; an instruction of another program
that ran in the window under the same name as one of the step's
(``fusion.3`` of a metric's reduction) is read as the step's.

A program without such a table (an older commit), a run without a trace,
and a process in which no fused step ran give None, and the readers
nothing.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

PROGRAM = "fused:step"
TABLE_SPAN = "trace:scope_table"
BY_KIND_ENTRIES = 8
LARGEST_UNNAMED = 5

# reader (``layer_metrics/<reader>.py``) -> the scope kinds it sums; every
# other kind is ``scope_other_ms``'s
KINDS = {
    "scope_optimizer_ms": ("optimizer",),
    "scope_attn_ms": ("attn",),
    "scope_mla_proj_ms": ("mla_q", "mla_kv", "rope"),
    "scope_kda_ms": ("kda",),
    "scope_moe_experts_ms": ("moe_experts",),
    "scope_moe_layout_ms": ("moe_route", "moe_combine"),
    "scope_lm_loss_ms": ("lm_loss",),
    "scope_mtp_ms": ("mtp",),
    "scope_conv_ms": ("convolution",),
    "scope_norm_ms": ("batchnorm",),
}
NAMED = frozenset(k for kinds in KINDS.values() for k in kinds)


def kind_of(scope: str) -> str:
    return scope.partition(".")[0]


def program_table() -> Optional[Dict[str, str]]:
    """{instruction: scope} of this process's step program, or None."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return None
    scopes_of = getattr(trace, "program_scopes", None)
    return scopes_of(PROGRAM) if scopes_of is not None else None


def split(op_seconds: Dict[str, float], table: Dict[str, str]
          ) -> Tuple[Dict[str, float], float]:
    """(kind -> seconds, seconds in no scope) of ``op_seconds``."""
    kinds: Dict[str, float] = {}
    unnamed = 0.0
    for key, s in op_seconds.items():
        scope = table.get(key.split(" ", 1)[0])
        if scope is None:
            unnamed += s
        else:
            kind = kind_of(scope)
            kinds[kind] = kinds.get(kind, 0.0) + s
    return kinds, unnamed


def _traced_split(obs):
    """(kinds, unnamed, steps, table) of a traced run, or None."""
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    table = program_table()
    if table is None:
        return None
    return split(tr["op_seconds"], table) + (tr["steps"], table)


def _ms_by_kind(kinds, names, steps):
    return {k: 1e3 * kinds.get(k, 0.0) / steps for k in names}


def read_ms(obs, reader: str):
    """What ``layer_metrics/<reader>.py`` returns: ms a step in the
    kinds ``KINDS[reader]`` (0.0 where the step has no such scope), each
    kind's own where there are several."""
    got = _traced_split(obs)
    if got is None:
        return None
    kinds, _, steps, _ = got
    by_kind = _ms_by_kind(kinds, KINDS[reader], steps)
    extra = {"steps": steps}
    if len(by_kind) > 1:
        extra["by_kind"] = by_kind
    return sum(by_kind.values()), extra


def read_other_ms(obs):
    """ms a step in every scoped operation whose kind no reader of
    ``KINDS`` names, with the BY_KIND_ENTRIES largest kinds."""
    got = _traced_split(obs)
    if got is None:
        return None
    kinds, _, steps, _ = got
    others = sorted((k for k in kinds if k not in NAMED),
                    key=lambda k: (-kinds[k], k))
    by_kind = _ms_by_kind(kinds, others, steps)
    return sum(by_kind.values()), {
        "steps": steps, "kinds": len(others),
        "by_kind": {k: by_kind[k] for k in others[:BY_KIND_ENTRIES]}}


def read_unnamed_share(obs):
    """Per cent of the first device's busy time in operations with no
    scope, with the times the whole split is checked on: ``scoped_ms +
    unnamed_ms = ops_ms`` (every operation, summed), beside ``busy_ms``
    (their union), and the LARGEST_UNNAMED operations with no scope."""
    got = _traced_split(obs)
    if got is None:
        return None
    kinds, unnamed, steps, table = got
    tr = obs["trace"]
    busy_s = tr["per_device"][sorted(tr["per_device"])[0]]["busy_s"]
    if not busy_s:
        return None
    scoped = sum(kinds.values())
    extra = {"steps": steps, "unnamed_ms": 1e3 * unnamed / steps,
             "scoped_ms": 1e3 * scoped / steps,
             "ops_ms": 1e3 * (scoped + unnamed) / steps,
             "busy_ms": 1e3 * busy_s / steps}
    largest = sorted(((s, op) for op, s in tr["op_seconds"].items()
                      if op.split(" ", 1)[0] not in table), reverse=True)
    extra["largest_unnamed"] = [[op, 1e3 * s / steps]
                                for s, op in largest[:LARGEST_UNNAMED]]
    build_ms = _table_build_ms()
    if build_ms is not None:
        extra["table_build_ms"] = build_ms
    return 100.0 * unnamed / busy_s, extra


def _table_build_ms() -> Optional[float]:
    """What the program took to build the table (its span), in ms."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return None
    spans = [e for e in trace.span_events(names=(TABLE_SPAN,))
             if (e.get("args") or {}).get("program") == PROGRAM]
    return spans[-1]["dur"] / 1e3 if spans else None
