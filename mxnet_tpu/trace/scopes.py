"""Device scopes: which graph node or step part made a device operation.

Every scope the program enters while it traces a step is a
``jax.named_scope``, so its name lands in the ``op_name`` of the HLO
operations made under it (``jit(step_s2)/transpose(jvp(attn.l0))/
dot_general``; JAX writes the ``jvp`` / ``transpose`` wrappers of the
backward pass itself).  A name reads ``<kind>.<instance>``: the kind is
what a reader sums by (``kind_of``).  There are two sorts:

* **declared** (``declared``): a block part a model builder or an op
  names (``attn.l0``, ``moe_experts.l2``, ``mla_q.l3``, ``lm_loss``,
  ``mtp.attn``: ``ops/transformer.py`` ``layer_scope`` / ``node_scope``)
  or a part of the fused step (``optimizer.<parameter>``,
  ``cast.params``, ``augment``, ``embed_sparse.<table>``:
  ``module/fused.py``);
* **generic** (``generic``): what the executor enters around every
  other op node, ``<op type, lower case>.<node name>``
  (``convolution.stage1_unit1_conv1``);
* **enclosing** (``enclosing``): what a node that holds a body enters
  around it (``loop``, ``ops/control_flow.py``).  It names only what no
  scope of the body's own names: the loop's ``while``, its counters, the
  sums of the passes' gradients.

Precedence (``resolve``): a declared scope wins over a generic one, and
of nested declared scopes the outermost wins; an enclosing scope loses
to both, wherever it stands on the path.  Which names are scopes,
and of which sort, is what this process entered: JAX's own path
segments (``while``, ``body``, ``checkpoint``, ``jit(_where)``) are
never taken for one.

``program_scopes(name)`` is the program's own table ``{HLO instruction
name: scope}`` of the executable the ``cached_jit`` program ``name``
runs, read from its optimized HLO text.  A fusion carries ONE
``op_name`` (XLA keeps that of one of the operations it merged), so a
fusion that spans two scopes is counted under one of them; an
instruction with no metadata, or none of whose path segments is a scope,
is in no scope and not in the table (but for a kernel the compiler wrote
under a name its caller adopted: ``adopt``).  The table is built when it is
asked for and at no other time: nothing here runs at bind or on the
step's path.

``SCHEME`` is part of the fused programs' module names
(``module_name``).  JAX's persistent cache key strips debug info, and a
scope is debug info: without it a step whose operations did not change
would be served an executable compiled before its scopes existed, with
that executable's names.  The module name is hashed, so a change of the
scheme costs one cold compile a fused program, and a PR that changes no
scheme none.  Raise it when the scopes of an unchanged operation change.
"""
from __future__ import annotations

import re
import time
from typing import Dict, Optional, Set

__all__ = ["SCHEME", "module_name", "declared", "generic", "enclosing", "adopt",
           "resolve", "kind_of", "sort_of", "op_names_of", "table_of",
           "register_program", "program_scopes", "program_op_names"]

# 2 (PR 69): the builders name the rest of a decoder block (``mlp``,
# ``block_norm``, ``residual``, ``lm_head``, ``embed``, ``kda_proj``, ...)
SCHEME = 2
TABLE_SPAN = "trace:scope_table"

_declared: Set[str] = set()
_generic: Set[str] = set()
_enclosing: Set[str] = set()
# op_name prefix of a kernel the compiler writes itself -> its scope
_adopted: Dict[str, str] = {}
# cached_jit name -> [the latest program built under it, its table]
_programs: Dict[str, list] = {}
# cached_jit name -> (that program, its instructions' op_names)
_op_names: Dict[str, tuple] = {}


def module_name(base: str) -> str:
    """``step`` -> ``step_s2``: the name a scoped program's function
    takes, and with it its HLO module (``jit_step_s2``)."""
    return "%s_s%d" % (base, SCHEME)


def declared(name: str):
    """``jax.named_scope`` of a block or step part that names itself."""
    import jax
    _declared.add(name)
    return jax.named_scope(name)


def generic(name: str):
    """``jax.named_scope`` of a node nobody named, by op type and node."""
    import jax
    _generic.add(name)
    return jax.named_scope(name)


def enclosing(name: str):
    """``jax.named_scope`` of a node around the body it holds: the scope
    of the operations under it that no declared or generic scope names."""
    import jax
    _enclosing.add(name)
    return jax.named_scope(name)


def adopt(op_name_prefix: str, scope: str) -> None:
    """A kernel that the compiler writes itself loses the ``op_name`` of
    the operation it came from: XLA:TPU turns ``lax.ragged_dot`` into a
    Mosaic custom call whose whole ``op_name`` is ``ragged-dot-none``.
    The one caller of such an operation says here which scope its
    kernels belong to (no instance: the layer is not known any more)."""
    _adopted[op_name_prefix] = scope


def kind_of(scope: str) -> str:
    """What is before the first dot: ``attn.l0`` -> ``attn``,
    ``mtp.attn`` -> ``mtp``, ``lm_loss`` -> ``lm_loss``."""
    return scope.partition(".")[0]


def sort_of(scope: str) -> Optional[str]:
    """Which sort of scope this process entered ``scope`` as:
    ``"declared"``, ``"generic"``, ``"enclosing"``, ``"adopted"`` (a
    scope some compiler-written kernel was given to and nothing entered)
    or None (no scope of this process).  In ``resolve``'s order, for a
    name entered as more than one sort.  What lets a reader of a table
    say how much of a step nobody has named without a list of kinds."""
    if scope in _declared:
        return "declared"
    if scope in _generic:
        return "generic"
    if scope in _enclosing:
        return "enclosing"
    if scope in _adopted.values():
        return "adopted"
    return None


_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def _segments(op_name: str):
    """The path's segments, outermost first, each out of the transforms
    JAX wrapped it in (``transpose(jvp(attn.l0))`` -> ``attn.l0``).  A
    ``jit(...)`` segment names a function, never a scope."""
    depth, start = 0, 0
    for i, c in enumerate(op_name + "/"):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth <= 0:
            seg, start = op_name[start:i], i + 1
            while True:
                m = _WRAPPED.match(seg)
                if m is None:
                    yield seg
                    break
                if m.group(1) in ("jit", "pjit"):
                    break
                seg = m.group(2)


def resolve(op_name: str) -> Optional[str]:
    """The scope an HLO ``op_name`` lies in, or None: the outermost
    declared scope on its path, else the outermost generic one, else the
    outermost enclosing one, else the scope that adopted a
    compiler-written kernel of this name."""
    first_generic = around = None
    for seg in _segments(op_name):
        if seg in _declared:
            return seg
        if first_generic is None and seg in _generic:
            first_generic = seg
        if around is None and seg in _enclosing:
            around = seg
    if first_generic is None:
        first_generic = around
    if first_generic is None:
        for prefix, scope in _adopted.items():
            if op_name.startswith(prefix):
                return scope
    return first_generic


_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=(){}"]+) = ', re.M)
_OP_NAME = re.compile(r'\bmetadata=\{op_name="([^"]*)"')


def op_names_of(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} of one optimized HLO module's text:
    every instruction of every computation that carries one.  An
    instruction may span lines (a Pallas kernel's custom call holds a
    JSON ``kernel_metadata`` before its ``metadata``): what lies between
    one instruction's start and the next is its own."""
    out = {}
    text = hlo_text or ""
    starts = list(_INSTRUCTION.finditer(text))
    for m, following in zip(starts, starts[1:] + [None]):
        found = _OP_NAME.search(
            text, m.end(), following.start() if following else len(text))
        if found is not None:
            out[m.group(1)] = found.group(1)
    return out


def table_of(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope} of one optimized HLO module's text:
    every instruction whose ``op_name`` (``op_names_of``) resolves."""
    return _table(op_names_of(hlo_text))


def _table(op_names: Dict[str, str]) -> Dict[str, str]:
    out = {}
    memo: Dict[str, Optional[str]] = {}
    for instruction, op_name in op_names.items():
        if op_name not in memo:
            memo[op_name] = resolve(op_name)
        if memo[op_name] is not None:
            out[instruction] = memo[op_name]
    return out


def register_program(name: str, program) -> None:
    """``program`` (a ``compile_cache.CachedFunction``) is now what runs
    under ``name``; the latest one built is the one kept, and with it
    whatever its function closes over, until the next takes its place."""
    _programs[name] = [program, None]


def program_scopes(name: str = "fused:step") -> Optional[Dict[str, str]]:
    """The table of the latest program built under ``name``, or None
    where there is none or it has not run.  Built at the first request
    (and kept): with an executable at hand from its text, else by
    lowering and compiling again for the avals of the program's first
    dispatch, which JAX's persistent cache answers where it is on.  The
    time that took is the span ``trace:scope_table``."""
    held = _programs.get(name)
    if held is None:
        return None
    if held[1] is None:
        t0 = time.perf_counter()
        names = program_op_names(name)
        if names is None:
            return None
        held[1] = _table(names)
        from . import complete
        complete(TABLE_SPAN, t0, time.perf_counter() - t0, cat="compile",
                 program=name, instructions=len(held[1]))
    return held[1]


def program_op_names(name: str = "fused:step") -> Optional[Dict[str, str]]:
    """{HLO instruction: op_name} of the latest program built under
    ``name``: JAX's whole path of each instruction, its own segments
    too (``while/body``, ``checkpoint/rematted_computation``: what a
    backward pass forms again), for a reader that asks what no scope
    says.  None where ``program_scopes`` gives None; built with it, at
    the first request of either."""
    held = _programs.get(name)
    if held is None:
        return None
    kept = _op_names.get(name)
    if kept is None or kept[0] is not held[0]:
        text = held[0].optimized_hlo()
        if text is None:
            return None
        kept = _op_names[name] = (held[0], op_names_of(text))
    return kept[1]
