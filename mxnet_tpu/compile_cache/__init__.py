"""mxnet_tpu.compile_cache — persistent executable cache + AOT warmup.

Compilation is a first-class cost for a stack that restarts, autoscales
and hot-reloads: every process start used to pay the full XLA compile
for every train step, eval program, serve bucket and sequence bucket.
This subsystem kills that cold start on three legs:

1. **Persistent on-disk executable cache** (`cached.py`, `store.py`,
   `fingerprint.py`): ``cached_jit`` routes ``jax.jit`` programs through
   an AOT lower->lookup->(deserialize | compile+serialize) path keyed on
   the lowered program + jax/jaxlib versions + backend + topology +
   compile flags.  Atomic publish, checksum-verified reads, LRU size
   bound, warn-and-recompile on any malformed entry, and a fallback to
   JAX's builtin persistent cache (placed by ``place_jax_cache``,
   `jaxcache.py`) on backends without PJRT executable serialization.
   Enable with ``MXNET_COMPILE_CACHE=<dir>`` (size bound
   ``MXNET_COMPILE_CACHE_SIZE_MB``, default 2048).

2. **Parallel AOT warmup** (`warmup.py`): ``parallel_warm`` compiles a
   program grid through a bounded thread pool (XLA releases the GIL);
   ``ServeEngine._warmup``, ``BucketingModule.precompile`` and
   ``Module.prepare`` ride it.

3. **Observability** (`stats.py`): per-program trace/lower/compile
   seconds, hits/misses/bypasses, bytes on disk and a steady-state
   retrace counter via ``mx.profiler.compile_report()/_str()``.
"""
from .cached import (CachedFunction, CompileCache, cached_jit, configure,
                     get_cache, reset)
from .jaxcache import (CompileCounter, count_backend_compiles, jax_cache_dir,
                       place_jax_cache, record_compile_spans)
from .stats import CompileStats, get_stats
from .warmup import WarmupError, default_warmup_threads, parallel_warm

__all__ = ["CachedFunction", "CompileCache", "CompileCounter", "CompileStats",
           "WarmupError", "cached_jit", "configure",
           "count_backend_compiles", "default_warmup_threads", "get_cache",
           "get_stats", "jax_cache_dir", "parallel_warm", "place_jax_cache",
           "record_compile_spans", "reset"]
