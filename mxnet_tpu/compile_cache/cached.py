"""cached_jit: jax.jit with a persistent, cross-process executable cache.

``cached_jit(fn)`` behaves exactly like ``jax.jit(fn)`` until a cache is
active (``MXNET_COMPILE_CACHE=<dir>`` or ``configure()``); the serving
and training entry points route every program through it.  With a cache:

* first call lowers the function (``jit(...).lower(args)``), keys the
  lowered StableHLO text + environment (fingerprint.py), and looks the
  key up on disk;
* a **hit** deserializes the PJRT executable — milliseconds instead of
  the XLA optimization pipeline — and wraps it in a
  ``_CachedExecutable`` that replays it through
  ``LoadedExecutable.execute`` with the recorded input pruning
  (jit drops unused args from the executable), device placement, and
  output pytree;
* a **miss** compiles via the AOT path (``lowered.compile()``),
  serializes the executable, and publishes it atomically;
* anything the fast path cannot express — multi-process meshes, input
  shardings without a recipe, a backend whose PJRT client cannot
  serialize — **bypasses**: the program compiles exactly as before (and
  a serialize-incapable backend flips the cache to JAX's built-in
  persistent compilation cache so later compiles still persist).

A cache entry can only ever fail toward a recompile: checksums are
verified before PJRT sees the blob, the first call of a deserialized
executable is validated (arity, avals, placement) and any failure drops
the entry, warns once, and compiles fresh.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from .. import trace as _trace
from ..base import get_env, make_lock
from .fingerprint import (environment_fingerprint,
                          fast_key as _fast_key_of, program_key)
from .stats import get_stats
from .store import CacheStore, warn_once

__all__ = ["CachedFunction", "CompileCache", "cached_jit", "get_cache",
           "configure", "reset"]

DEFAULT_SIZE_MB = 2048.0


class _CacheEntryInvalid(Exception):
    """Raised when a deserialized entry cannot serve the call; always
    handled by falling back to a fresh compile."""


_nocache_lock = make_lock("compile_cache.nocache")
_nocache_depth = 0
_nocache_prev = True


@contextlib.contextmanager
def _fresh_compile_ctx():
    """Compile OUTSIDE jax's builtin persistent compilation cache.

    An executable that jax served from ITS disk cache re-serializes into
    a blob missing its jitted kernel symbols — deserializing that later
    fails with "Symbols not found" (measured on CPU PJRT), so every
    executable WE intend to serialize must come from a fresh backend
    compile.  The thread-local ``enable_compilation_cache(False)``
    context is NOT enough: ``compilation_cache.is_cache_used`` memoizes
    its verdict once per process, so after any ordinary compile the
    flag is ignored.  Instead the cache is disabled process-wide for
    the duration (refcounted — overlapping warmup-pool compiles share
    one window) with ``reset_cache()`` dropping the memo on the way in
    AND out; an unrelated compile racing the window merely skips the
    jax cache once."""
    global _nocache_depth, _nocache_prev
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jax_cc
    with _nocache_lock:
        if _nocache_depth == 0:
            _nocache_prev = bool(jax.config.jax_enable_compilation_cache)
            jax_cc.reset_cache()
            jax.config.update("jax_enable_compilation_cache", False)
        _nocache_depth += 1
    try:
        yield
    finally:
        with _nocache_lock:
            _nocache_depth -= 1
            if _nocache_depth == 0:
                jax.config.update("jax_enable_compilation_cache",
                                  _nocache_prev)
                jax_cc.reset_cache()


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


def _sharding_recipe(s):
    """Reconstructable description of an input sharding, or None when it
    has no recipe (such a program is compiled but not cached)."""
    from jax.sharding import NamedSharding, SingleDeviceSharding
    if isinstance(s, SingleDeviceSharding):
        (dev,) = tuple(s.device_set)
        return ("dev", int(dev.id))
    if isinstance(s, NamedSharding):
        mesh = s.mesh
        spec = tuple(tuple(e) if isinstance(e, (list, tuple)) else e
                     for e in tuple(s.spec))
        return ("named", tuple(int(n) for n in mesh.devices.shape),
                tuple(mesh.axis_names), spec,
                tuple(int(d.id) for d in mesh.devices.ravel()))
    return None


def _placement_extras(args) -> str:
    """Ordered device placement of every argument leaf — the part of a
    program's identity its HLO text does not carry.  The platform rides
    along: device ids repeat across platforms, and the same StableHLO
    placed on cpu(0) and on tpu(0) of one process are two programs."""
    import jax
    parts = []
    for x in jax.tree_util.tree_flatten(args)[0]:
        sh = getattr(x, "sharding", None)
        parts.append(None if sh is None else
                     (next(iter(sh.device_set)).platform,
                      _sharding_recipe(sh)))
    return repr(parts)


def _recipe_to_sharding(r, by_id):
    """``by_id``: the executable's OWN client's devices.  Device ids
    repeat across platforms (TFRT_CPU_0 and TPU_0 are both id 0), so a
    cpu-context program in a TPU process must not resolve its recipe
    against the default backend."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    if r[0] == "dev":
        return SingleDeviceSharding(by_id[r[1]])
    if r[0] == "named":
        _tag, shape, axes, spec, ids = r
        devs = np.array([by_id[i] for i in ids]).reshape(shape)
        return NamedSharding(Mesh(devs, tuple(axes)),
                             PartitionSpec(*spec))
    raise ValueError("unknown sharding recipe %r" % (r[0],))


# -- the deserialized-executable callable ------------------------------------

class _CachedExecutable:
    """Callable over the original args pytree, backed by a deserialized
    PJRT executable.

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
                 out_shardings: Sequence[Any], name: str, key: str):
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
        self.key = key
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
    """Wrap a FRESHLY compiled executable in the same raw-execute path
    deserialized entries use, or None when it cannot be expressed.

    This is a steady-state dispatch optimization, not just a cache
    concern: per call on a 150-leaf train state this host measured raw
    ``execute`` at 1.8ms vs 2.2ms through jit dispatch and 3.4ms through
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
            name, key=None)
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


# -- the disk-backed cache ---------------------------------------------------

class CompileCache:
    """Persistent executable cache over one directory (see module
    docstring).  Thread-safe; shared by every CachedFunction in the
    process via ``get_cache()``."""

    def __init__(self, directory: str, size_mb: Optional[float] = None):
        if size_mb is None:
            size_mb = get_env("MXNET_COMPILE_CACHE_SIZE_MB",
                              DEFAULT_SIZE_MB, float)
        self.store = CacheStore(directory, size_mb)
        self.mode = "serialize"

    # -- keying ------------------------------------------------------------
    def key_for(self, lowered, args) -> str:
        """HLO text alone is NOT the whole program: the device
        assignment is a compile parameter that never appears in it (the
        same step lowered for a mesh over devices (1,2) vs (2,3) — or
        (1,2) vs (2,1) — is textually identical but placed differently),
        so the args' ordered placement recipes join the key."""
        return program_key(lowered.as_text(),
                           extras=(_placement_extras(args),),
                           env_fp=environment_fingerprint())

    def bypass_reason(self) -> Optional[str]:
        if self.mode != "serialize":
            return "builtin-fallback"
        import jax
        if jax.process_count() > 1:
            return "multi-process"
        return None

    # -- load / store ------------------------------------------------------
    def load_entry(self, key: str, name: str):
        """-> validated-on-first-call _CachedExecutable, or None.  Fully
        self-contained: the sidecar carries the output pytree, input
        pruning, avals and placement, so a hit needs NO lowering."""
        res = self.store.load(key)
        if res is None:
            return None
        blob, meta = res
        import jax
        t0 = time.perf_counter()
        try:
            platform = meta.get("platform")
            if platform:
                client = jax.local_devices(backend=platform)[0].client
            else:
                client = jax.devices()[0].client
            by_id = {d.id: d for d in client.devices()}
            loaded = client.deserialize_executable(
                blob, [by_id[i] for i in meta["devices"]])
            shardings = [_recipe_to_sharding(r, by_id)
                         for r in meta["shardings"]]
            out_shardings = [_recipe_to_sharding(r, by_id)
                             for r in meta["out_shardings"]]
            entry = _CachedExecutable(
                loaded, meta["out_tree"], meta["kept"], meta["avals"],
                shardings, meta["out_avals"], out_shardings, name, key)
        except Exception as e:
            warn_once(
                "deserialize",
                "compile cache entry %s would not deserialize on this "
                "backend (%s: %s); recompiling"
                % (key[:12], type(e).__name__, e))
            self.store.invalidate(key)
            return None
        dt = time.perf_counter() - t0
        get_stats().note_hit(name, dt)
        _trace.complete("compile:deserialize", t0, dt, cat="compile",
                        program=name)
        return entry

    def load_fast(self, fkey: str, name: str):
        """Trace-free lookup: fast key -> index -> entry.  A dangling
        index (its target evicted or corrupt) is dropped and reads as a
        miss — the HLO-keyed path then takes over after one lowering."""
        key = self.store.load_index(fkey)
        if key is None:
            return None
        entry = self.load_entry(key, name)
        if entry is None:
            self.store.drop_index(fkey)
        return entry

    def store_entry(self, key: str, compiled, lowered, args, name: str,
                    fkey: Optional[str] = None) -> None:
        """Serialize + publish one freshly compiled executable; every
        failure degrades to running uncached."""
        import jax
        stats = get_stats()
        out_tree = lowered.out_tree
        flat = jax.tree_util.tree_flatten(args)[0]
        flat = [_canon_leaf(x) for x in flat]
        try:
            kept = sorted(compiled._executable._kept_var_idx)
        except Exception:
            kept = list(range(len(flat)))
        if kept and kept[-1] >= len(flat):
            stats.note_bypass(name, "arg-pruning-opaque")
            return

        def is_sharding(x):
            return hasattr(x, "device_set")

        # placement from the EXECUTABLE, not the args: jit repositions
        # uncommitted inputs (e.g. an unpinned RNG key lands replicated
        # on the mesh) and replay must reproduce that
        try:
            in_sh = jax.tree_util.tree_leaves(compiled.input_shardings[0],
                                              is_leaf=is_sharding)
            out_sh = jax.tree_util.tree_leaves(compiled.output_shardings,
                                               is_leaf=is_sharding)
            out_info = jax.tree_util.tree_leaves(lowered.out_info)
        except Exception:
            stats.note_bypass(name, "shardings-opaque")
            return
        if len(in_sh) != len(kept) or len(out_sh) != len(out_info):
            stats.note_bypass(name, "shardings-opaque")
            return
        avals, recipes = [], []
        for i, sh in zip(kept, in_sh):
            r = _sharding_recipe(sh)
            if r is None:
                stats.note_bypass(name, "unserializable-sharding")
                return
            avals.append(_leaf_aval(flat[i]))
            recipes.append(r)
        out_avals, out_recipes = [], []
        for info, sh in zip(out_info, out_sh):
            r = _sharding_recipe(sh)
            if r is None:
                stats.note_bypass(name, "unserializable-sharding")
                return
            out_avals.append((tuple(info.shape), str(info.dtype)))
            out_recipes.append(r)
        try:
            rex = compiled.runtime_executable()
            # the executable's OWN client (a cpu-ctx program in a process
            # whose default backend is the TPU must not serialize
            # through the TPU client)
            client = getattr(rex, "client", None) or jax.devices()[0].client
            platform = client.platform
            devices = list(rex.local_devices())
            blob = client.serialize_executable(rex)
        except Exception as e:
            self._serialize_unavailable(e)
            stats.note_bypass(name, "serialize-unavailable")
            return
        # verify before publishing: CPU PJRT has produced blobs that
        # reference unexported kernel symbols (executables served from
        # jax's own cache, among others) — a blob that cannot load NOW
        # will never load, and publishing it would cost every later
        # process a failed deserialize
        try:
            client.deserialize_executable(blob, devices)
        except Exception as e:
            warn_once(
                "blob-verify",
                "freshly serialized executable for %s would not "
                "deserialize (%s: %s); not caching this program"
                % (name, type(e).__name__, e))
            stats.note_bypass(name, "unserializable-blob")
            return
        import jaxlib
        meta = {"name": name, "kept": kept, "avals": avals,
                "shardings": recipes, "platform": platform,
                "devices": [int(d.id) for d in devices],
                "out_tree": out_tree, "out_avals": out_avals,
                "out_shardings": out_recipes,
                "jax": (jax.__version__, jaxlib.__version__)}
        nbytes = self.store.save(key, blob, meta)
        stats.note_store(nbytes)
        # index only a PUBLISHED entry: a failed save already invalidated
        # the key, and a dangling index would defeat the trace-free path
        # with one wasted lookup per warm start until it self-healed
        if fkey is not None and nbytes > 0:
            self.store.save_index(fkey, key)

    # -- builtin-cache fallback --------------------------------------------
    def _serialize_unavailable(self, exc) -> None:
        """PJRT executable serialization missing on this backend: this
        cache stands down (every program bypasses from here on) and
        JAX's own persistent compilation cache, wherever the entry point
        placed it (``place_jax_cache``), is what persists compiles."""
        if self.mode != "serialize":
            return
        self.mode = "builtin"
        import jax
        warn_once("serialize-unavailable",
                  "PJRT executable serialization unavailable on this "
                  "backend (%s: %s); JAX's persistent compilation cache "
                  "at %r stays in charge"
                  % (type(exc).__name__, exc,
                     jax.config.jax_compilation_cache_dir))

    def describe(self) -> dict:
        return {"directory": self.store.directory, "mode": self.mode,
                "entries": self.store.entry_count(),
                "disk_bytes": self.store.disk_bytes(),
                "size_mb": self.store.size_bytes / 2 ** 20}


# -- process-global cache handle ---------------------------------------------

_cache: Optional[CompileCache] = None
_cache_resolved = False
_cache_lock = make_lock("compile_cache.configure")


def get_cache() -> Optional[CompileCache]:
    """The active cache, or None (default: ``MXNET_COMPILE_CACHE`` env
    var names the directory; empty/unset = off)."""
    global _cache, _cache_resolved
    if _cache_resolved:
        return _cache
    with _cache_lock:
        if _cache_resolved:
            return _cache
        d = (get_env("MXNET_COMPILE_CACHE") or "").strip()
        cache = None
        if d:
            try:
                cache = CompileCache(d)
            except Exception as e:
                warn_once("cache-init",
                          "MXNET_COMPILE_CACHE=%r unusable (%s: %s); "
                          "running uncached" % (d, type(e).__name__, e))
        _cache = cache
        _cache_resolved = True
    return _cache


def configure(directory: Optional[str],
              size_mb: Optional[float] = None) -> Optional[CompileCache]:
    """Programmatic cache setup (None disables).  Re-reads the
    environment fingerprint so a test that monkeypatched flags keys
    correctly."""
    global _cache, _cache_resolved
    with _cache_lock:
        environment_fingerprint(refresh=True)
        _cache = CompileCache(directory, size_mb) if directory else None
        _cache_resolved = True
    return _cache


def reset() -> None:
    """Forget the configured cache (next get_cache() re-reads the env)."""
    global _cache, _cache_resolved
    with _cache_lock:
        _cache = None
        _cache_resolved = False
        environment_fingerprint(refresh=True)


# -- the jit wrapper ---------------------------------------------------------

def _signature(args) -> Tuple:
    import jax
    flat, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_sig_leaf(x) for x in flat))


def _sig_string(sig: Tuple) -> str:
    """Deterministic text form of a signature (treedef and ShapedArray
    reprs are stable for a given structure) — the aval half of a fast
    key."""
    treedef, avals = sig
    return "%s|%s" % (treedef, avals)


class CachedFunction:
    """Drop-in jax.jit wrapper with cache-aware AOT dispatch.

    With no cache configured and no ``warm()`` call, ``__call__``
    delegates straight to the wrapped ``jax.jit`` function — the default
    path is byte-for-byte the old behavior.  Otherwise calls dispatch on
    the args' aval signature to a per-signature entry: a deserialized
    ``_CachedExecutable`` (cache hit) or the AOT-compiled ``Compiled``
    (miss/bypass — also what ``warm()`` installs so a pre-compiled
    program is found by the later identical call instead of recompiling
    inside jit's own cache)."""

    def __init__(self, fn, name: Optional[str] = None,
                 donate_argnums=None, fast_key: Optional[str] = None,
                 **jit_kwargs):
        import jax
        if "static_argnums" in jit_kwargs:
            raise ValueError("cached_jit supports dynamic args only; "
                             "close over static values instead")
        self._fn = fn
        self.name = name or getattr(fn, "__name__", "<fn>")
        if donate_argnums is not None:
            jit_kwargs["donate_argnums"] = donate_argnums
        self._jit = jax.jit(fn, **jit_kwargs)
        # fast_key: caller-supplied description of everything the traced
        # program depends on beyond the input avals (symbol-graph hash,
        # optimizer hparams, flags).  Lets a warm start skip tracing
        # entirely: fast_key + aval signature + env/code fingerprints
        # index straight into the disk entry.  The HLO-text key stays
        # the ground truth — a fast-key miss (or any code change, via
        # code_fingerprint) falls back to lower-then-lookup.
        self._fast_desc = fast_key
        self._entries: Dict[Tuple, Any] = {}
        self._last: Optional[Tuple[Tuple, Any]] = None
        self._called = False
        # the first dispatch's avals, and whether this process traced
        # the function: what ``optimized_hlo`` needs afterwards
        self._specs = None
        self._traced = False
        self._lock = make_lock("compile_cache.cached_fn")

    @property
    def has_compiled(self) -> bool:
        """Whether any program exists yet (compiled, warmed, or loaded)."""
        return self._called or bool(self._entries)

    # -- public ------------------------------------------------------------
    def __call__(self, *args):
        if not self._entries and get_cache() is None:
            # cold default path: plain jit, zero added machinery
            if not self._called:
                self._specs = _arg_specs(args)
                self._called = self._traced = True
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
        """Compile (or load) the program for these args WITHOUT running
        it — no outputs materialize, no donation happens, no aux state
        moves.  Returns 'present' | 'hit' | 'compiled'."""
        sig = _signature(args)
        if sig in self._entries:
            return "present"
        entry = self._acquire(sig, args)
        # disk-backed entries carry their store key; a live wrapper
        # (fresh compile re-dispatched through raw execute) does not
        return "hit" if isinstance(entry, _CachedExecutable) \
            and entry.key is not None else "compiled"

    def compile_for(self, *args):
        """The entry (Compiled or _CachedExecutable) for these args,
        compiling/loading if needed — the AOT handle bench and
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
        request.  A program that the fast key served without tracing is
        traced once here, for the scopes it enters."""
        if self._last is not None:
            entry = self._last[1]
        else:
            entry = next(reversed(self._entries.values()), None)
        if self._specs is not None and (entry is None or not self._traced):
            lowered = self._jit.lower(*self._specs)
            self._traced = True
            if entry is None:
                entry = lowered.compile()
        return optimized_hlo_text(entry) if entry is not None else None

    # -- internals ---------------------------------------------------------
    def _first_call(self, sig, entry, args):
        """Validated first execution of a deserialized entry; any
        failure drops the entry and compiles fresh (the corruption /
        stale-entry tolerance contract)."""
        try:
            out = entry(*args)
        except Exception as e:
            warn_once(
                "entry-exec",
                "cached executable for %s failed on first use (%s: %s); "
                "recompiling" % (self.name, type(e).__name__, e))
            cache = get_cache()
            if cache is not None and entry.key is not None:
                cache.store.invalidate(entry.key)
            # republish: the bad entry was invalidated above, so the
            # fresh executable takes its slot for the next process
            fresh = self._compile(args, store=True)
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
            retrace = self.has_compiled
            stats = get_stats()
            cache = get_cache()
            reason = cache.bypass_reason() if cache is not None else None
            fkey = None
            if cache is not None and reason is None and \
                    self._fast_desc is not None:
                # trace-free path: no jit.lower, no graph walk — the
                # whole warm start is one deserialize
                fkey = _fast_key_of(self._fast_desc, _sig_string(sig))
                entry = cache.load_fast(fkey, self.name)
                if entry is not None:
                    self._entries[sig] = entry
                    return entry
            t0 = time.perf_counter()
            lowered = self._jit.lower(*args)
            self._traced = True
            dt0 = time.perf_counter() - t0
            stats.note_trace_lower(self.name, dt0)
            entry = None
            key = None
            if cache is not None:
                if reason is None:
                    key = cache.key_for(lowered, args)
                    entry = cache.load_entry(key, self.name)
                    if entry is None:
                        stats.note_miss(self.name)
                    elif fkey is not None:
                        # heal the index: the entry existed but the fast
                        # key didn't point at it yet
                        cache.store.save_index(fkey, key)
                else:
                    stats.note_bypass(self.name, reason)
            if entry is None:
                t1 = time.perf_counter()
                if key is not None:
                    with _fresh_compile_ctx():
                        compiled = lowered.compile()
                else:
                    compiled = lowered.compile()
                dt1 = time.perf_counter() - t1
                stats.note_compile(self.name, dt1, retrace=retrace)
                if key is not None:
                    cache.store_entry(key, compiled, lowered, args,
                                      self.name, fkey=fkey)
                # dispatch future calls through the raw-execute path
                # (measured faster than both jit and Compiled.__call__);
                # anything it can't express keeps the Compiled handle
                entry = _wrap_live(compiled, lowered, args,
                                   self.name) or compiled
            self._entries[sig] = entry
            return entry

    def _compile(self, args, store: bool = True):
        """Plain AOT compile (no lookup) — the bad-entry fallback."""
        stats = get_stats()
        cache = get_cache()
        will_store = (store and cache is not None
                      and cache.bypass_reason() is None)
        t0 = time.perf_counter()
        lowered = self._jit.lower(*args)
        self._traced = True
        dt0 = time.perf_counter() - t0
        stats.note_trace_lower(self.name, dt0)
        t1 = time.perf_counter()
        if will_store:
            with _fresh_compile_ctx():
                compiled = lowered.compile()
        else:
            compiled = lowered.compile()
        dt1 = time.perf_counter() - t1
        stats.note_compile(self.name, dt1)
        if will_store:
            fkey = None
            if self._fast_desc is not None:
                fkey = _fast_key_of(self._fast_desc,
                                    _sig_string(_signature(args)))
            cache.store_entry(cache.key_for(lowered, args), compiled,
                              lowered, args, self.name, fkey=fkey)
        return compiled


def cached_jit(fn, name: Optional[str] = None, donate_argnums=None,
               fast_key: Optional[str] = None, **jit_kwargs) -> CachedFunction:
    """jax.jit through the persistent executable cache (see
    CachedFunction)."""
    return CachedFunction(fn, name=name, donate_argnums=donate_argnums,
                          fast_key=fast_key, **jit_kwargs)
