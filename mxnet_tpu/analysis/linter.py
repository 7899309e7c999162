"""Project-specific static analysis: the bug classes this repo has
actually paid for, turned into mechanical checks.

Every rule here descends from a named incident in CHANGES.md (see
docs/analysis.md for the catalog).  The framework is deliberately
self-contained — stdlib ``ast`` only, no imports from the rest of
``mxnet_tpu`` — so ``tools/lint.py`` can load it by file path and run
in milliseconds without pulling in jax.

Rules
-----
donated-aliasing   ``jax.device_put`` of a host buffer flowing into
                   donated state without ``jnp.copy`` (PR 2 / PR 7r2:
                   nondeterministic result corruption on CPU zero-copy)
raw-jit            ``jax.jit`` outside ``compile_cache`` — a program with
                   no name, no AOT handle and no compile counters
raw-dist-init      ``jax.distributed.initialize`` outside
                   ``mxnet_tpu/dist/`` — the process-group boot is
                   single-owner (gloo selection, pre-backend ordering,
                   idempotent re-entry; ISSUE 18)
raw-env            ``os.environ`` reads bypassing ``base.get_env``
raw-time           ``time.time()`` in rate/duration arithmetic (PR 3's
                   Speedometer NTP-step bug class)
unseeded-fork-rng  global ``np.random.*`` draws — decorrelation hazard
                   in forked reader workers (PR 6)
raw-future-settle  ``set_result``/``set_exception`` outside the
                   InvalidStateError-tolerant helpers (PR 4's
                   engine-wedging class)
raw-retry          a loop that both sleeps and swallows exceptions —
                   a bare retry loop outside ``mxnet_tpu.faults``
                   (PR 15: unbudgeted instant reforks let a
                   crash-looping decode bug hot-spin the reader fork
                   path; retries ride faults.Backoff/retry_call)
decode-host-sync   ``np.asarray``/``.item()``/``float(x)`` inside a
                   per-token decode loop (a For/While whose body calls
                   a ``*step*``/``forward`` callee) — each one is a
                   device→host sync serialized against the step stream,
                   turning a per-STEP sync budget into per-token * N
                   (PR 16: the paged engine's contract is ONE host sync
                   per compiled step; hoist the pull out of the loop or
                   batch it into the step's single asarray)
unsealed-replay    ``np.load``/``np.fromfile`` in a capture-shard
                   reader with no SEALED-marker gate — a torn or
                   in-progress shard tail silently becomes training
                   data (PR 17: replay readers must check
                   ``is_sealed``/``sealed_shards`` first, mirroring
                   the checkpoint COMMIT discipline)
moe-raw-scatter    ``.at[].add``/``segment_sum`` scatter-accumulates
                   outside ``mxnet_tpu/moe/`` and the embed choke
                   files — a raw scatter-add wraps or clamps
                   out-of-range indices onto LIVE expert/embedding
                   rows (ISSUE 19; the PR 12 pad-bug class); writes
                   ride ``moe.dispatch`` / ``embed.sparse``, which
                   fold overflow to a dropped sentinel
raw-pallas-call    ``pl.pallas_call`` outside ``ops/pallas_kernels`` —
                   shipped kernels live in ONE module so the kernel
                   search's bitwise parity gate covers every tiling
                   the repo runs (ISSUE 20); a stray pallas_call is
                   an unsearched, ungated kernel (the rtc user-kernel
                   passthrough carries inline suppressions)

Suppressions
------------
Inline, same line or the line above, WITH a written reason::

    x = time.time()  # lint: allow(raw-time) — absolute ts for humans

File-level (first 10 lines), for files where a rule is wholesale
inapplicable::

    # lint: allow-file(raw-env) — DMLC protocol vars, reference semantics

A suppression without a reason (the ``— why`` part) is itself an error:
the whole value of the mechanism is that every exception is explained.

Baseline
--------
A checked-in JSON baseline (``tools/lint_baseline.json`` by default,
``MXNET_LINT_BASELINE`` to override) lets the tree start green: known
findings are fingerprinted by (rule, path, source line text) — not line
number, so unrelated edits don't churn it — and only NEW findings fail.
Regenerate with ``tools/lint.py --write-baseline``.
"""
from __future__ import annotations

import ast
import hashlib
import json
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Set, Tuple

__all__ = ["Finding", "RULES", "lint_file", "lint_source", "lint_paths",
           "Baseline", "load_baseline", "fingerprint"]

# ---------------------------------------------------------------------------
# findings + suppressions

_ALLOW_RE = re.compile(
    r"#\s*lint:\s*allow\(([a-z0-9_,\- ]+)\)\s*(?:[—–-]+\s*(.*\S))?")
_ALLOW_FILE_RE = re.compile(
    r"#\s*lint:\s*allow-file\(([a-z0-9_,\- ]+)\)\s*(?:[—–-]+\s*(.*\S))?")


class Finding:
    """One lint hit: rule id, location, message."""

    def __init__(self, rule: str, path: str, line: int, col: int,
                 msg: str, src_line: str = ""):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.msg = msg
        self.src_line = src_line

    def __repr__(self):
        return "%s:%d:%d: [%s] %s" % (self.path, self.line, self.col,
                                      self.rule, self.msg)

    def fingerprint(self) -> str:
        return fingerprint(self.rule, self.path, self.src_line)


def fingerprint(rule: str, path: str, src_line: str) -> str:
    """Line-number-free identity of a finding: stable across edits that
    merely move the offending line."""
    h = hashlib.sha256()
    h.update(("%s\0%s\0%s" % (rule, path, src_line.strip())).encode())
    return h.hexdigest()[:16]


class _Suppressions:
    """Per-file suppression table parsed from comments."""

    def __init__(self, source: str, path: str):
        self.by_line: Dict[int, Set[str]] = {}
        self.file_wide: Set[str] = set()
        self.errors: List[Finding] = []
        lines = source.splitlines()
        try:
            import io
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                self._parse(tok.string, tok.start[0], path,
                            lines[tok.start[0] - 1]
                            if tok.start[0] <= len(lines) else "")
        except (tokenize.TokenError, IndentationError, SyntaxError):
            pass
        # a COMMENT-ONLY allow line extends through the rest of its
        # comment block to the first code line after it, so a multi-line
        # reason can sit above the statement it blesses; an INLINE allow
        # (trailing a code line) covers that statement only — extending
        # it would silently bless the next statement too
        for lineno in sorted(self.by_line):
            if not lines[lineno - 1].lstrip().startswith("#"):
                continue
            rules = self.by_line[lineno]
            nxt = lineno + 1
            while nxt <= len(lines):
                stripped = lines[nxt - 1].strip()
                self.by_line.setdefault(nxt, set()).update(rules)
                if stripped and not stripped.startswith("#"):
                    break  # reached the code line the allow targets
                nxt += 1

    def _parse(self, comment: str, lineno: int, path: str, src_line: str):
        m = _ALLOW_FILE_RE.search(comment)
        if m:
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            if not m.group(2):
                self.errors.append(Finding(
                    "lint-meta", path, lineno, 0,
                    "allow-file(%s) carries no reason — write one after "
                    "an em dash" % ",".join(sorted(rules)), src_line))
            elif lineno > 10:
                self.errors.append(Finding(
                    "lint-meta", path, lineno, 0,
                    "allow-file must appear in the first 10 lines",
                    src_line))
            else:
                self.file_wide |= rules
            return
        m = _ALLOW_RE.search(comment)
        if m:
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            if not m.group(2):
                self.errors.append(Finding(
                    "lint-meta", path, lineno, 0,
                    "allow(%s) carries no reason — write one after an "
                    "em dash" % ",".join(sorted(rules)), src_line))
                return
            self.by_line.setdefault(lineno, set()).update(rules)

    def allows(self, rule: str, line: int) -> bool:
        if rule in self.file_wide:
            return True
        return rule in self.by_line.get(line, set())


# ---------------------------------------------------------------------------
# AST helpers

def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute(Name('jax'),'jit'); None when not a plain
    dotted name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _attach_parents(tree: ast.AST) -> None:
    for parent in ast.walk(tree):
        for child in ast.iter_child_nodes(parent):
            child._lint_parent = parent  # type: ignore[attr-defined]


def _parent(node: ast.AST) -> Optional[ast.AST]:
    return getattr(node, "_lint_parent", None)


def _enclosing_funcs(node: ast.AST) -> List[str]:
    """Names of enclosing function defs, innermost first."""
    names = []
    cur = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            names.append(cur.name)
        cur = _parent(cur)
    return names


class _Ctx:
    def __init__(self, path: str, rel: str, tree: ast.AST, source: str):
        self.path = path
        self.rel = rel          # repo-relative, forward slashes
        self.tree = tree
        self.source = source
        self.lines = source.splitlines()

    def src(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def finding(self, rule: str, node: ast.AST, msg: str) -> Finding:
        f = Finding(rule, self.rel, node.lineno, node.col_offset, msg,
                    self.src(node.lineno))
        f._node = node  # statement-span suppression check
        return f


# ---------------------------------------------------------------------------
# rules

def _rule_raw_jit(ctx: _Ctx) -> Iterable[Finding]:
    """jax.jit outside compile_cache: a program the compile counters,
    the scope table and the AOT warm-up cannot see."""
    if ctx.rel.startswith("mxnet_tpu/compile_cache/"):
        return
    for node in ast.walk(ctx.tree):
        if _dotted(node) == "jax.jit" and isinstance(node, ast.Attribute):
            # flag the reference itself: call sites, partial(jax.jit,..),
            # and decorator usage all contain this Attribute node
            yield ctx.finding(
                "raw-jit", node,
                "jax.jit bypasses compile_cache.cached_jit — route through "
                "the one wrapper (name, AOT handle, counters), or suppress "
                "with the reason (donation layout / pallas)")


_PALLAS_CALLS = ("pl.pallas_call", "pallas.pallas_call",
                 "jax.experimental.pallas.pallas_call")


def _rule_raw_pallas_call(ctx: _Ctx) -> Iterable[Finding]:
    """pallas_call outside ops/pallas_kernels: the kernel search's
    parity gate (ISSUE 20) only covers kernels it can enumerate — every
    shipped tiling lives in the one module whose candidates are
    bitwise-checked against jnp twins before a winner persists.  A
    pallas_call elsewhere is an unsearched, ungated kernel."""
    if ctx.rel.startswith("mxnet_tpu/ops/pallas_kernels"):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and _dotted(node) in _PALLAS_CALLS:
            yield ctx.finding(
                "raw-pallas-call", node,
                "pallas_call outside ops/pallas_kernels — shipped kernels "
                "live there so the kernel search's parity gate covers "
                "them; add the kernel to ops/pallas_kernels (plus a "
                "kernelsearch candidate space), or suppress with the "
                "reason it cannot ride the gated module")


def _rule_raw_dist_init(ctx: _Ctx) -> Iterable[Finding]:
    """jax.distributed.initialize outside mxnet_tpu/dist/: the boot is
    single-owner (dist.boot) — it must run before any backend init,
    select the CPU collectives implementation, and tolerate re-entry.
    A second raw call either crashes ("already initialized") or, worse,
    races the backend into a coordinator-less state (ISSUE 18)."""
    if ctx.rel.startswith("mxnet_tpu/dist/"):
        return
    for node in ast.walk(ctx.tree):
        if _dotted(node) == "jax.distributed.initialize" \
                and isinstance(node, ast.Attribute):
            yield ctx.finding(
                "raw-dist-init", node,
                "raw jax.distributed.initialize — the process-group "
                "lifecycle is owned by mxnet_tpu.dist.boot (gloo "
                "selection, pre-backend ordering, idempotent re-entry); "
                "call dist.boot.initialize / ensure_from_env instead")


_ENV_READS = ("os.environ.get", "os.getenv", "environ.get")


def _rule_raw_env(ctx: _Ctx) -> Iterable[Finding]:
    """os.environ reads outside base.get_env: the PR 6 convention — one
    typed, defaulted accessor, not N ad-hoc parses."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in _ENV_READS:
            yield ctx.finding(
                "raw-env", node,
                "raw environment read — use base.get_env(name, default, "
                "typ) (typed parse, one convention)")
        elif (isinstance(node, ast.Subscript)
              and _dotted(node.value) in ("os.environ", "environ")
              and isinstance(getattr(node, "ctx", None), ast.Load)):
            yield ctx.finding(
                "raw-env", node,
                "raw os.environ[...] read — use base.get_env")


def _rule_raw_time(ctx: _Ctx) -> Iterable[Finding]:
    """time.time() feeding duration/rate arithmetic: wall clock steps
    under NTP/DST and corrupts the window (PR 3's Speedometer bug).
    A bare timestamp recorded for humans (dict value, logged) is fine;
    arithmetic must ride time.perf_counter()."""
    # names assigned from time.time() per enclosing function
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _dotted(node.func) in ("time.time",)):
            continue
        parent = _parent(node)
        # direct arithmetic: time.time() - start, start - time.time()...
        if isinstance(parent, ast.BinOp):
            yield ctx.finding(
                "raw-time", node,
                "time.time() in duration arithmetic — wall clock steps "
                "under NTP; use time.perf_counter()")
            continue
        if isinstance(parent, ast.Compare):
            yield ctx.finding(
                "raw-time", node,
                "time.time() compared against a deadline — use "
                "time.perf_counter() or time.monotonic()")
            continue
        # assigned to a name that later appears in a BinOp in the same
        # function: start = time.time(); ...; time.time() - start
        if isinstance(parent, ast.Assign) and len(parent.targets) == 1 \
                and isinstance(parent.targets[0], ast.Name):
            name = parent.targets[0].id
            scope = _enclosing_scope(node)
            if scope is not None and _name_in_arith(scope, name):
                yield ctx.finding(
                    "raw-time", node,
                    "time.time() stored in %r which feeds arithmetic — "
                    "wall clock steps under NTP; use time.perf_counter()"
                    % name)


def _enclosing_scope(node: ast.AST) -> Optional[ast.AST]:
    cur = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Module)):
            return cur
        cur = _parent(cur)
    return None


def _name_in_arith(scope: ast.AST, name: str) -> bool:
    for n in ast.walk(scope):
        if isinstance(n, (ast.BinOp, ast.Compare, ast.AugAssign)):
            for sub in ast.walk(n):
                if isinstance(sub, ast.Name) and sub.id == name:
                    return True
    return False


_NPR_SAFE = {"seed", "default_rng", "Generator", "RandomState",
             "SeedSequence", "PCG64", "get_state", "set_state"}


def _rule_unseeded_fork_rng(ctx: _Ctx) -> Iterable[Finding]:
    """Draws from numpy's GLOBAL generator: forked reader workers
    inherit one identical state, so every worker produces the SAME
    'random' crops/flips (PR 6's decorrelation bug).  Use an explicit
    np.random.default_rng(seed) or reseed per (seed, shard, epoch, seq)
    before drawing."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if not dotted:
            continue
        for prefix in ("np.random.", "numpy.random."):
            if dotted.startswith(prefix):
                fn = dotted[len(prefix):]
                if "." not in fn and fn not in _NPR_SAFE:
                    yield ctx.finding(
                        "unseeded-fork-rng", node,
                        "np.random.%s draws from the process-global "
                        "generator — forked workers inherit identical "
                        "state; use an explicit default_rng(seed) or "
                        "reseed per (seed, shard, epoch, seq)" % fn)
                break


def _rule_raw_future_settle(ctx: _Ctx) -> Iterable[Finding]:
    """fut.set_result/set_exception outside the InvalidStateError-
    tolerant helpers: a routine client cancel made the raw call raise,
    killing the worker thread and wedging the serve engine (PR 4 review
    round 2).  Settle futures only through serve.batcher._set_result /
    _set_exception."""
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("set_result", "set_exception")):
            continue
        funcs = _enclosing_funcs(node)
        if funcs and funcs[0] in ("_set_result", "_set_exception"):
            continue  # the tolerant helpers themselves
        yield ctx.finding(
            "raw-future-settle", node,
            "raw Future.%s — a cancelled future raises "
            "InvalidStateError and kills the calling thread; use the "
            "tolerant _set_result/_set_exception helpers"
            % node.func.attr)


def _rule_raw_retry(ctx: _Ctx) -> Iterable[Finding]:
    """A loop whose body both sleeps AND swallows an exception is a
    hand-rolled retry loop: unbounded, unjittered, invisible to the
    fault plane's counters (the PR 15 reader-refork hot-loop class).
    Retries belong to faults.Backoff / faults.retry_call — bounded,
    jittered, deterministic, traced.  Poll loops (sleep, no swallowed
    exception) and fail-fast loops (except that raises/breaks/returns)
    are not flagged; faults/ itself implements the primitive."""
    if ctx.rel.startswith("mxnet_tpu/faults/"):
        return
    flagged: Set[int] = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.While, ast.For)):
            continue
        sleeps = [n for n in ast.walk(node)
                  if isinstance(n, ast.Call)
                  and _dotted(n.func) == "time.sleep"]
        if not sleeps:
            continue
        swallowing = [
            h for h in ast.walk(node)
            if isinstance(h, ast.ExceptHandler)
            and not any(isinstance(x, (ast.Raise, ast.Break, ast.Return))
                        for x in ast.walk(h))]
        if not swallowing:
            continue
        for s in sleeps:
            if id(s) in flagged:    # inner loop already reported it
                continue
            flagged.add(id(s))
            yield ctx.finding(
                "raw-retry", s,
                "sleep inside a loop that swallows exceptions — a bare "
                "retry loop: unbounded and unjittered; use "
                "faults.retry_call / faults.Backoff (bounded budget, "
                "deterministic jitter, traced waits)")


_HOST_SYNC_DOTTED = {"np.asarray", "numpy.asarray", "np.array",
                     "numpy.array", "jax.device_get"}


def _rule_decode_host_sync(ctx: _Ctx) -> Iterable[Finding]:
    """A device->host materialization inside a per-token decode loop: a
    For/While whose body drives a ``*step*``/``forward`` callee is the
    serving hot loop, and every ``np.asarray``/``.item()``/``float(x)``
    in it blocks on the device stream once per token.  The paged decode
    engine's budget is ONE host sync per compiled step (PR 16); extra
    pulls belong outside the loop, or batched into that one asarray.
    ``int(...)`` on an already-host numpy scalar is not flagged — the
    sync already happened at the step's asarray."""
    flagged: Set[int] = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.While, ast.For)):
            continue
        steppy = False
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                name = n.func.attr if isinstance(n.func, ast.Attribute) \
                    else (n.func.id if isinstance(n.func, ast.Name)
                          else None)
                if name and ("step" in name or name == "forward"):
                    steppy = True
                    break
        if not steppy:
            continue
        for n in ast.walk(node):
            if not isinstance(n, ast.Call) or id(n) in flagged:
                continue
            d = _dotted(n.func)
            what = None
            if d in _HOST_SYNC_DOTTED:
                what = d
            elif isinstance(n.func, ast.Attribute) \
                    and n.func.attr == "item" and not n.args:
                what = ".item()"
            elif isinstance(n.func, ast.Name) and n.func.id == "float" \
                    and n.args and not isinstance(n.args[0], ast.Constant):
                what = "float(...)"
            if what is None:
                continue
            flagged.add(id(n))
            yield ctx.finding(
                "decode-host-sync", n,
                "%s inside a per-token decode loop — a device->host "
                "sync serialized against the step stream once per "
                "token; hoist it out of the loop or batch it into the "
                "step's single asarray (one host sync per compiled "
                "step)" % what)


_JNP_FRESH = {"zeros", "ones", "full", "zeros_like", "ones_like",
              "full_like", "arange", "eye", "copy", "empty"}


def _rule_donated_aliasing(ctx: _Ctx) -> Iterable[Finding]:
    """jax.device_put inside an init*/restore* function without
    jnp.copy: on CPU device_put can zero-copy ALIAS the host buffer, and
    state built in init/restore paths is donated every step — XLA then
    scribbles over memory numpy still owns (PR 2's nondeterministic
    resume corruption; bit again in PR 7 review round 2 in two train
    steps' init).  Freshly-created jnp.* arrays are exempt (nothing on
    host aliases them)."""
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _dotted(node.func) == "jax.device_put"):
            continue
        funcs = _enclosing_funcs(node)
        if not any(("init" in f or "restore" in f) for f in funcs):
            continue
        # exempt: wrapped in jnp.copy(...)
        parent = _parent(node)
        if isinstance(parent, ast.Call) \
                and _dotted(parent.func) in ("jnp.copy", "jax.numpy.copy"):
            continue
        # exempt: placing a freshly-created device array
        if node.args:
            arg = node.args[0]
            d = _dotted(arg.func) if isinstance(arg, ast.Call) else None
            if d and (d.startswith("jnp.") or d.startswith("jax.numpy.")) \
                    and d.split(".")[-1] in _JNP_FRESH:
                continue
        yield ctx.finding(
            "donated-aliasing", node,
            "device_put in an init/restore path without jnp.copy — on "
            "CPU it may zero-copy alias the host buffer, and donated "
            "state scribbles over memory the host still owns; wrap in "
            "jnp.copy(...) (or suppress with why the result is never "
            "donated)")


_SHARD_LOADERS = {"np.load", "numpy.load", "np.fromfile",
                  "numpy.fromfile"}


def _rule_unsealed_replay(ctx: _Ctx) -> Iterable[Finding]:
    """A function that reads capture-shard files (``np.load`` /
    ``np.fromfile`` in shard-touching code) without any reference to
    the SEALED discipline: capture shards publish in two atomic steps
    (shard file, then SEALED marker — mirroring the checkpoint COMMIT
    protocol), so a reader that skips the marker check replays torn or
    in-progress tails as training data (PR 17).  The gate is any
    seal-named reference (``is_sealed`` / ``sealed_shards`` / a SEALED
    constant) in the same function; shard-ness is a ``shard-`` string
    (the capture file prefix) or a shard-named identifier."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        sharded = "shard" in node.name.lower()
        sealed = "seal" in node.name.lower()
        loads = []
        for n in ast.walk(node):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                low = n.value.lower()
                if "shard-" in low:
                    sharded = True
                if "seal" in low:
                    sealed = True
            elif isinstance(n, ast.Name):
                low = n.id.lower()
                if "shard" in low:
                    sharded = True
                if "seal" in low:
                    sealed = True
            elif isinstance(n, ast.Attribute):
                low = n.attr.lower()
                if "shard" in low:
                    sharded = True
                if "seal" in low:
                    sealed = True
            elif isinstance(n, ast.Call) \
                    and _dotted(n.func) in _SHARD_LOADERS:
                loads.append(n)
        if not (sharded and loads) or sealed:
            continue
        for n in loads:
            yield ctx.finding(
                "unsealed-replay", n,
                "capture-shard read with no SEALED-marker gate — a "
                "torn or in-progress shard tail becomes training "
                "data; check online.capture.is_sealed(path) (or "
                "iterate sealed_shards()) before loading, like the "
                "checkpoint COMMIT discipline")


_SEGMENT_SUMS = {"jax.ops.segment_sum", "ops.segment_sum",
                 "jops.segment_sum"}
# the scatter choke points: capacity-bucketed dispatch (sentinel-fold,
# mode="drop") and the sparse-embed grad path (capped-unique dedup)
_SCATTER_CHOKE = ("mxnet_tpu/moe/", "mxnet_tpu/embed/sparse.py",
                  "mxnet_tpu/embed/table.py")


def _rule_moe_raw_scatter(ctx: _Ctx) -> Iterable[Finding]:
    """``.at[...].add(...)`` / ``segment_sum`` scatter-accumulates
    outside the dispatch/embed choke points: a raw scatter-add onto an
    expert or row buffer bypasses the sentinel-fold discipline (ISSUE
    19 / the PR 12 pad-bug class) — an out-of-range or dropped index
    wraps (negatives) or clamps onto a LIVE row and silently corrupts
    it with traffic the row never accepted.  In-place ``.at[].set``
    writes (paged KV cache, slot zeroing) are not accumulates and stay
    legal."""
    if ctx.rel.startswith(_SCATTER_CHOKE):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "add" \
                and isinstance(f.value, ast.Subscript) \
                and isinstance(f.value.value, ast.Attribute) \
                and f.value.value.attr == "at":
            yield ctx.finding(
                "moe-raw-scatter", node,
                "raw .at[].add scatter-accumulate — expert/row buffers "
                "are written only through the choke points "
                "(moe.dispatch.dispatch, embed.sparse grad fold) where "
                "sentinel-fold + mode=\"drop\" keep dropped traffic out "
                "of live rows; route through them or suppress with why "
                "this buffer has no out-of-range indices")
        elif isinstance(f, ast.Attribute) and _dotted(f) in _SEGMENT_SUMS:
            yield ctx.finding(
                "moe-raw-scatter", node,
                "raw segment_sum scatter-accumulate outside the "
                "moe.dispatch / embed.sparse choke points — same "
                "wrapped-index corruption class as .at[].add (see "
                "moe-raw-scatter)")


RULES = {
    "donated-aliasing": _rule_donated_aliasing,
    "raw-jit": _rule_raw_jit,
    "raw-dist-init": _rule_raw_dist_init,
    "raw-env": _rule_raw_env,
    "raw-time": _rule_raw_time,
    "unseeded-fork-rng": _rule_unseeded_fork_rng,
    "raw-future-settle": _rule_raw_future_settle,
    "raw-retry": _rule_raw_retry,
    "decode-host-sync": _rule_decode_host_sync,
    "unsealed-replay": _rule_unsealed_replay,
    "moe-raw-scatter": _rule_moe_raw_scatter,
    "raw-pallas-call": _rule_raw_pallas_call,
}


# ---------------------------------------------------------------------------
# driver

def lint_source(source: str, rel: str, path: Optional[str] = None,
                rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint one source string; ``rel`` is the repo-relative path used in
    findings and path-scoped rules (forward slashes)."""
    rel = rel.replace(os.sep, "/")
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError as e:
        return [Finding("syntax", rel, e.lineno or 0, 0,
                        "syntax error: %s" % e.msg)]
    _attach_parents(tree)
    ctx = _Ctx(path or rel, rel, tree, source)
    sup = _Suppressions(source, rel)
    findings: List[Finding] = list(sup.errors)
    selected = set(rules) if rules is not None else set(RULES)
    for rule_name, rule in RULES.items():
        if rule_name not in selected:
            continue
        for f in rule(ctx):
            # an allow anywhere on the enclosing STATEMENT's lines (or
            # the comment block above it) suppresses — a flagged call
            # may sit on a continuation line of a multi-line statement
            lines = {f.line}
            node = getattr(f, "_node", None)
            stmt = node
            while stmt is not None and not isinstance(stmt, ast.stmt):
                stmt = _parent(stmt)
            if stmt is not None:
                lines.update(range(stmt.lineno,
                                   (getattr(stmt, "end_lineno", None)
                                    or stmt.lineno) + 1))
            if not any(sup.allows(rule_name, ln) for ln in lines):
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_file(path: str, root: str,
              rules: Optional[Iterable[str]] = None) -> List[Finding]:
    rel = os.path.relpath(os.path.abspath(path), os.path.abspath(root))
    with open(path, encoding="utf-8", errors="replace") as f:
        return lint_source(f.read(), rel, path, rules)


def lint_paths(paths: Iterable[str], root: str,
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Lint every .py under the given files/directories."""
    out: List[Finding] = []
    for p in paths:
        if os.path.isdir(p):
            for base, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        out.extend(lint_file(os.path.join(base, fn), root,
                                             rules))
        elif p.endswith(".py"):
            out.extend(lint_file(p, root, rules))
    return out


# ---------------------------------------------------------------------------
# baseline

class Baseline:
    """Known-findings set: only NEW findings fail (the tree starts green,
    drift is caught)."""

    def __init__(self, fingerprints: Set[str], path: Optional[str] = None):
        self.fingerprints = fingerprints
        self.path = path

    def new_findings(self, findings: List[Finding]) -> List[Finding]:
        return [f for f in findings
                if f.fingerprint() not in self.fingerprints]

    @staticmethod
    def from_findings(findings: List[Finding],
                      path: Optional[str] = None) -> "Baseline":
        return Baseline({f.fingerprint() for f in findings}, path)

    def save(self, path: str, findings: List[Finding]) -> None:
        entries = [{"rule": f.rule, "path": f.path,
                    "line": f.src_line.strip(),
                    "fingerprint": f.fingerprint()}
                   for f in sorted(findings,
                                   key=lambda x: (x.path, x.line))]
        with open(path, "w") as fp:
            json.dump({"version": 1, "entries": entries}, fp, indent=1)
            fp.write("\n")


def load_baseline(path: str) -> Baseline:
    """Missing file -> empty baseline (a fresh tree has nothing
    grandfathered); malformed -> error, a torn baseline must not
    silently whitelist everything new."""
    if not os.path.exists(path):
        return Baseline(set(), path)
    with open(path) as fp:
        data = json.load(fp)
    return Baseline({e["fingerprint"] for e in data.get("entries", [])},
                    path)
