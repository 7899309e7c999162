"""cached_jit: the one wrapper of ``jax.jit``.

``cached_jit(fn, name=...)`` behaves exactly like ``jax.jit(fn)`` until
something warms it; every program the training and serving entry points
build goes through it.  What it adds:

* a **name**: the key of the compile counters
  (``mx.profiler.compile_report()``) and of the scope table
  (``trace.scopes.register_program``);
* an **AOT handle**: ``warm(*args)`` / ``compile_for(*args)`` lower and
  compile the program for these arguments without running it, which is
  what ``Module.prepare``, ``BucketingModule.precompile``,
  ``FusedTrainStep.aot_compile`` and the serve warm-up grid ride;
* a **dispatch** for what was warmed: the compiled executable is wrapped
  in a ``_CachedExecutable`` that replays it through
  ``LoadedExecutable.execute`` with the recorded input pruning (jit drops
  unused args from the executable), device placement, and output pytree.
  The first call of a wrapped executable is validated (arity, avals,
  placement); one that refuses it is replaced by a fresh ``Compiled``.

Nothing is persisted here: what survives a restart is JAX's persistent
compilation cache, which ``place_jax_cache`` (`jaxcache.py`) places.
"""
from __future__ import annotations

import logging
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from ..base import make_lock
from .stats import get_stats

__all__ = ["CachedFunction", "cached_jit"]

logger = logging.getLogger(__name__)


class _CacheEntryInvalid(Exception):
    """Raised when a wrapped executable cannot serve the call; always
    handled by falling back to a fresh compile."""


# -- leaf plumbing -----------------------------------------------------------

def _canon_leaf(x):
    """Physical form of one argument leaf: typed PRNG keys lower to
    their uint32 key data (raw ``execute`` takes physical buffers)."""
    import jax
    dt = getattr(x, "dtype", None)
    if dt is not None and jax.dtypes.issubdtype(dt, jax.dtypes.extended):
        try:
            return jax.random.key_data(x)
        except Exception:
            return x
    return x


def _leaf_aval(x) -> Tuple[Tuple[int, ...], str]:
    dt = getattr(x, "dtype", None)
    if dt is None:
        dt = np.result_type(x)
    return (tuple(np.shape(x)), str(dt))


def _sig_leaf(x):
    """Dispatch-signature form of one leaf.  jax arrays contribute their
    cached ShapedArray aval (hashable, eq-comparable, ~8x cheaper than
    building (shape, str(dtype)) tuples — this runs per call on the hot
    path); everything else falls back to the tuple form."""
    import jax
    if isinstance(x, jax.Array):
        return x.aval
    return _leaf_aval(x)


# -- the raw-execute callable -------------------------------------------------

class _CachedExecutable:
    """Callable over the original args pytree, backed by a compiled
    PJRT executable (``_wrap_live`` builds it).

    Input shardings are the EXECUTABLE's (``Compiled.input_shardings``),
    not the call args': jit repositions uncommitted arguments (an
    unpinned RNG key becomes mesh-replicated) and the raw execute path
    must do the same.  Single-device programs replay through
    ``execute`` (first call fully validated, steady calls pay only
    flatten + prune).  Multi-device programs replay through
    ``execute_sharded`` with per-call placement checks and reassemble
    each output from its shards under the recorded output sharding —
    plain ``execute`` would silently return shard 0 of a partitioned
    output."""

    def __init__(self, loaded, out_tree, kept: Sequence[int],
                 avals: Sequence[Tuple[Tuple[int, ...], str]],
                 shardings: Sequence[Any],
                 out_avals: Sequence[Tuple[Tuple[int, ...], str]],
                 out_shardings: Sequence[Any], name: str):
        self._loaded = loaded
        self._out_tree = out_tree
        self._kept = tuple(kept)
        self._avals = tuple(avals)          # kept leaves only
        self._shardings = tuple(shardings)  # kept leaves only
        self._out_avals = tuple(out_avals)
        self._out_shardings = tuple(out_shardings)
        self._multi = any(s is not None and len(s.device_set) > 1
                          for s in tuple(shardings) + tuple(out_shardings))
        self.name = name
        self._validated = False

    def _place(self, i: int, x):
        """Validate/canonicalize kept leaf i (first call only)."""
        import jax
        shape, dtype = self._avals[i]
        sh = self._shardings[i]
        if not isinstance(x, jax.Array):
            if sh is None:
                raise _CacheEntryInvalid("host leaf without a sharding")
            x = jax.device_put(np.asarray(x, dtype=np.dtype(dtype)), sh)
        if tuple(x.shape) != shape or str(x.dtype) != dtype:
            raise _CacheEntryInvalid(
                "aval mismatch: got %s%s, executable wants %s%s"
                % (x.dtype, tuple(x.shape), dtype, shape))
        # full sharding comparison, not device_set: a mesh over the same
        # devices in a different ORDER assigns replicas differently
        if sh is not None and x.sharding != sh:
            x = jax.device_put(x, sh)
        return x

    def __call__(self, *args):
        import jax
        flat = jax.tree_util.tree_flatten(args)[0]
        kept = [_canon_leaf(flat[i]) for i in self._kept]
        if not self._validated:
            if max(self._kept, default=-1) >= len(flat) or \
                    len(kept) != len(self._avals):
                raise _CacheEntryInvalid(
                    "arity mismatch: %d args vs %d recorded"
                    % (len(flat), len(self._avals)))
            kept = [self._place(i, x) for i, x in enumerate(kept)]
        if self._multi:
            # every call: an argument the caller keeps on one device
            # (base RNG key, lr scalar) must land in the executable's
            # sharding each step — exactly what jit dispatch does
            kept = [x if getattr(x, "sharding", None) == sh
                    else jax.device_put(x, sh)
                    for x, sh in zip(kept, self._shardings)]
            parts = self._loaded.execute_sharded(kept) \
                .disassemble_into_single_device_arrays()
            outs = [jax.make_array_from_single_device_arrays(
                        av[0], sh, shards)
                    for av, sh, shards in zip(self._out_avals,
                                              self._out_shardings, parts)]
        else:
            outs = self._loaded.execute(kept)
        res = jax.tree_util.tree_unflatten(self._out_tree, outs)
        self._validated = True
        return res

    def cost_analysis(self):
        return self._loaded.cost_analysis()


def _wrap_live(compiled, lowered, args, name: str):
    """Wrap a compiled executable in the raw-execute path, or None when
    it cannot be expressed.

    This is a steady-state dispatch optimization: per call on a
    150-leaf train state a CPU host measured raw ``execute`` at 1.8ms
    vs 2.2ms through jit dispatch and 3.4ms through
    ``Compiled.__call__`` — without it, every warmed program (serve
    construction warms ALL buckets by default) would pay the slowest
    path forever."""
    import jax
    if jax.process_count() > 1:
        return None
    try:
        flat = [_canon_leaf(x)
                for x in jax.tree_util.tree_flatten(args)[0]]
        kept = sorted(compiled._executable._kept_var_idx)
        if kept and kept[-1] >= len(flat):
            return None

        def is_sharding(x):
            return hasattr(x, "device_set")

        in_sh = jax.tree_util.tree_leaves(compiled.input_shardings[0],
                                          is_leaf=is_sharding)
        out_sh = jax.tree_util.tree_leaves(compiled.output_shardings,
                                           is_leaf=is_sharding)
        out_info = jax.tree_util.tree_leaves(lowered.out_info)
        if len(in_sh) != len(kept) or len(out_sh) != len(out_info):
            return None
        return _CachedExecutable(
            compiled.runtime_executable(), lowered.out_tree, kept,
            [_leaf_aval(flat[i]) for i in kept], in_sh,
            [(tuple(i.shape), str(i.dtype)) for i in out_info], out_sh,
            name)
    except Exception:
        return None


def optimized_hlo_text(executable) -> Optional[str]:
    """The optimized (post-partitioner) HLO text of an entry a
    ``CachedFunction`` dispatches to: a ``Compiled`` or a
    ``_CachedExecutable``.  None where the backend gives none."""
    try:
        if hasattr(executable, "as_text"):
            return executable.as_text()
        return executable._loaded.hlo_modules()[0].to_string()
    except Exception:
        return None


def _arg_specs(args):
    """``args`` as ``jax.ShapeDtypeStruct`` leaves: what a later
    ``jit.lower`` needs to make the same program again, holding no
    buffer (a donated argument is dead after the call it was given to).
    A committed array keeps its sharding; an uncommitted one, which jit
    places itself, none."""
    import jax

    def spec(x):
        if not isinstance(x, jax.Array):
            return x
        # a typed PRNG key array has no weak_type; a tracer (the
        # function called under an outer transform) no placement
        placed = not isinstance(x, jax.core.Tracer) and x.committed
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
            sharding=x.sharding if placed else None)
    return jax.tree_util.tree_map(spec, args)


# -- the jit wrapper ---------------------------------------------------------

def _signature(args) -> Tuple:
    import jax
    flat, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_sig_leaf(x) for x in flat))



class CachedFunction:
    """Drop-in jax.jit wrapper with AOT dispatch.

    With no ``warm()`` call, ``__call__`` delegates straight to the
    wrapped ``jax.jit`` function.  Otherwise calls dispatch on the args'
    aval signature to a per-signature entry: the AOT-compiled program as
    a ``_CachedExecutable`` (or the ``Compiled`` itself where raw
    execute cannot express it), which is what ``warm()`` installs so a
    pre-compiled program is found by the later identical call instead
    of recompiling inside jit's own cache."""

    def __init__(self, fn, name: Optional[str] = None,
                 donate_argnums=None, **jit_kwargs):
        import jax
        if "static_argnums" in jit_kwargs:
            raise ValueError("cached_jit supports dynamic args only; "
                             "close over static values instead")
        self._fn = fn
        self.name = name or getattr(fn, "__name__", "<fn>")
        if donate_argnums is not None:
            jit_kwargs["donate_argnums"] = donate_argnums
        self._jit = jax.jit(fn, **jit_kwargs)
        self._entries: Dict[Tuple, Any] = {}
        self._last: Optional[Tuple[Tuple, Any]] = None
        self._called = False
        # the first dispatch's avals: what ``optimized_hlo`` needs
        # afterwards
        self._specs = None
        self._lock = make_lock("compile_cache.cached_fn")

    @property
    def has_compiled(self) -> bool:
        """Whether any program exists yet (compiled or warmed)."""
        return self._called or bool(self._entries)

    # -- public ------------------------------------------------------------
    def __call__(self, *args):
        if not self._entries:
            # cold default path: plain jit, zero added machinery
            if not self._called:
                self._specs = _arg_specs(args)
                self._called = True
            return self._jit(*args)
        sig = _signature(args)
        last = self._last
        if last is not None and last[0] == sig:
            entry = last[1]
        else:
            entry = self._entries.get(sig)
            if entry is None:
                entry = self._acquire(sig, args)
            self._last = (sig, entry)
        self._called = True
        if isinstance(entry, _CachedExecutable) and not entry._validated:
            return self._first_call(sig, entry, args)
        return entry(*args)

    def warm(self, *args) -> str:
        """Compile the program for these args WITHOUT running it — no
        outputs materialize, no donation happens, no aux state moves.
        Returns 'present' | 'compiled'."""
        sig = _signature(args)
        if sig in self._entries:
            return "present"
        self._acquire(sig, args)
        return "compiled"

    def compile_for(self, *args):
        """The entry (Compiled or _CachedExecutable) for these args,
        compiling if needed — the AOT handle bench and
        ``FusedTrainStep.aot_compile`` install directly."""
        sig = _signature(args)
        entry = self._entries.get(sig)
        if entry is None:
            entry = self._acquire(sig, args)
        return entry

    def optimized_hlo(self) -> Optional[str]:
        """The optimized HLO text of the executable that runs: of the
        entry last dispatched to (or warmed) where there is one.  The
        plain ``jax.jit`` path holds no executable: there the function
        is lowered and compiled again for the avals of its first
        dispatch, which gives the same program and, where JAX's
        persistent cache is on, reads it from there.  None before the
        first dispatch.  Never called on the step's path: it is what
        ``trace.scopes.program_scopes`` builds its table from, on
        request."""
        if self._last is not None:
            entry = self._last[1]
        else:
            entry = next(reversed(self._entries.values()), None)
        if entry is None and self._specs is not None:
            entry = self._jit.lower(*self._specs).compile()
        return optimized_hlo_text(entry) if entry is not None else None

    # -- internals ---------------------------------------------------------
    def _first_call(self, sig, entry, args):
        """Validated first execution of a wrapped executable; any
        failure drops the entry and compiles fresh."""
        try:
            out = entry(*args)
        except Exception as e:
            logger.warning(
                "wrapped executable for %s failed on first use (%s: %s); "
                "recompiling", self.name, type(e).__name__, e)
            fresh = self._compile(args)[1]
            with self._lock:
                self._entries[sig] = fresh
                self._last = (sig, fresh)
            return fresh(*args)
        return out

    def _acquire(self, sig, args):
        with self._lock:
            entry = self._entries.get(sig)
            if entry is not None:
                return entry
            if self._specs is None:
                self._specs = _arg_specs(args)
            # a second signature on an already-compiled program is a
            # RETRACE — in a steady loop that's the silent-10x bug the
            # recompile guard exists to catch
            lowered, compiled = self._compile(args,
                                              retrace=self.has_compiled)
            # dispatch future calls through the raw-execute path
            # (measured faster than both jit and Compiled.__call__);
            # anything it can't express keeps the Compiled handle
            entry = _wrap_live(compiled, lowered, args,
                               self.name) or compiled
            self._entries[sig] = entry
            return entry

    def _compile(self, args, retrace: bool = False):
        """Plain AOT lower + compile, counted -> (lowered, compiled)."""
        stats = get_stats()
        t0 = time.perf_counter()
        lowered = self._jit.lower(*args)
        stats.note_trace_lower(self.name, time.perf_counter() - t0)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        stats.note_compile(self.name, time.perf_counter() - t1,
                           retrace=retrace)
        return lowered, compiled


def cached_jit(fn, name: Optional[str] = None, donate_argnums=None,
               **jit_kwargs) -> CachedFunction:
    """jax.jit under a name, with an AOT handle (see CachedFunction)."""
    return CachedFunction(fn, name=name, donate_argnums=donate_argnums,
                          **jit_kwargs)
