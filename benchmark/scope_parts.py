"""What the readers of a decoder block's parts share (PR 69): the join of
a trace's ``op_seconds`` with the program's table of its step, as
``scope_seconds`` makes it, less the WRAPPER events, and with each scope's
sort as the program gives it.

A traced ``while``, ``conditional`` or ``call`` is an event of its own
that spans its body's operations, which the trace holds too: a sum that
takes both counts the body twice (Ouro's ``scope_other_ms.tok`` reads
150 % of its step for that).  An operation's opcode is the second word of
its key (``trace_reduce.short_name``).  The readers here leave such an
event out of every sum and say what they left out as ``wrapper_ms``.

``scope_seconds.KINDS`` is a file the benchmark had and names no reader
for the kinds summed here, so ``scope_other_ms.tok`` holds them too: the
same operations from the other side, wrappers and all.

Which sort a scope is of (``declared`` by a builder or an op, ``generic``:
the executor's name for a node nobody named, ``enclosing``: a loop node
around its body, ``adopted``: a compiler-written kernel's) is the
program's to say: ``mxnet_tpu.trace.scopes.sort_of``.  A program without
it (an older commit) gives None, and ``scope_generic_share`` nothing.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Optional

import scope_seconds

WRAPPERS = frozenset(("while", "conditional", "call"))
BY_KIND_ENTRIES = 8


def is_wrapper(key: str) -> bool:
    """Whether the operation ``<instruction> <opcode> <largest array>``
    is an event that spans its body's operations."""
    words = key.split(" ", 2)
    return len(words) > 1 and words[1] in WRAPPERS


def sort_function() -> Optional[Callable[[str], Optional[str]]]:
    """The program's ``sort_of``, or None where it has none."""
    try:
        from mxnet_tpu.trace import scopes
    except ImportError:
        return None
    return getattr(scopes, "sort_of", None)


def traced(obs):
    """``(plain, wrapped, steps)`` of a traced run whose program gives a
    table, or None: ``{scope, or None where the table has none: seconds}``
    of the operations that are no wrappers, and of the wrappers."""
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    table = scope_seconds.program_table()
    if table is None:
        return None
    plain: Dict[Optional[str], float] = {}
    wrapped: Dict[Optional[str], float] = {}
    for key, s in tr["op_seconds"].items():
        into = wrapped if is_wrapper(key) else plain
        scope = table.get(key.split(" ", 1)[0])
        into[scope] = into.get(scope, 0.0) + s
    return plain, wrapped, tr["steps"]


def _of_kinds(by_scope, kinds):
    """{kind: seconds} of ``by_scope``'s scopes of ``kinds``, in order."""
    out = dict.fromkeys(kinds, 0.0)
    for scope, s in by_scope.items():
        kind = scope_seconds.kind_of(scope) if scope else None
        if kind in out:
            out[kind] += s
    return out


def read_ms(obs, kinds: Iterable[str]):
    """What a ``scope_*_ms`` reader of these kinds returns: ms a step in
    the operations under scopes of ``kinds`` that are no wrappers (0.0
    where the step has the table and none of the kinds), each kind's own
    where there are several, and the wrappers under them as
    ``wrapper_ms``.  None without a trace or a table."""
    got = traced(obs)
    if got is None:
        return None
    plain, wrapped, steps = got
    kinds = tuple(kinds)
    by_kind = _of_kinds(plain, kinds)
    extra = {"steps": steps, "wrapper_ms":
             1e3 * sum(_of_kinds(wrapped, kinds).values()) / steps}
    if len(kinds) > 1:
        extra["by_kind"] = {k: 1e3 * v / steps for k, v in by_kind.items()}
    return 1e3 * sum(by_kind.values()) / steps, extra


def read_generic_share(obs):
    """Per cent of the first device's busy time in operations whose
    scope is a generic one (nobody named the node), wrappers left out;
    with the BY_KIND_ENTRIES largest generic kinds and the whole split by
    sort: ``named_ms`` (declared and adopted) ``+ generic_ms +
    enclosing_ms + unnamed_ms + wrapper_ms = ops_ms``, beside
    ``busy_ms``, and the wrappers by their scopes' kinds
    (``wrapper_by_kind``).  None without a trace, a table or the
    program's ``sort_of``."""
    got = traced(obs)
    sort_of = sort_function()
    if got is None or sort_of is None:
        return None
    plain, wrapped, steps = got
    tr = obs["trace"]
    busy_s = tr["per_device"][sorted(tr["per_device"])[0]]["busy_s"]
    if not busy_s:
        return None
    sums = {"named": 0.0, "generic": 0.0, "enclosing": 0.0, "unnamed": 0.0,
            "wrapper": sum(wrapped.values())}
    generic: Dict[str, float] = {}
    for scope, s in plain.items():
        # the table resolves against what this process entered, so only
        # an operation of no scope has no sort
        sort = sort_of(scope) if scope is not None else None
        if sort == "generic":
            kind = scope_seconds.kind_of(scope)
            generic[kind] = generic.get(kind, 0.0) + s
        sums[sort if sort in ("generic", "enclosing")
             else "named" if sort else "unnamed"] += s
    largest = sorted(generic, key=lambda k: (-generic[k], k))
    extra = {"steps": steps, "kinds": len(generic),
             "by_kind": {k: 1e3 * generic[k] / steps
                         for k in largest[:BY_KIND_ENTRIES]},
             "busy_ms": 1e3 * busy_s / steps,
             "ops_ms": 1e3 * sum(sums.values()) / steps}
    extra.update(("%s_ms" % k, 1e3 * v / steps) for k, v in sums.items())
    # whose wrappers: a reader that sums a kind through ``scope_seconds``
    # holds that kind's wrappers too, beside their bodies
    of = {}
    for scope, s in wrapped.items():
        kind = scope_seconds.kind_of(scope) if scope else "unnamed"
        of[kind] = of.get(kind, 0.0) + s
    extra["wrapper_by_kind"] = {
        k: 1e3 * of[k] / steps for k in sorted(
            of, key=lambda k: (-of[k], k))[:BY_KIND_ENTRIES]}
    return 100.0 * sums["generic"] / busy_s, extra
