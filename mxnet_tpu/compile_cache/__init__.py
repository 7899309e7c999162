"""mxnet_tpu.compile_cache — one compile cache, AOT warmup, counters.

Compilation is a first-class cost for a stack that restarts, autoscales
and hot-reloads.  What a restart keeps is JAX's persistent compilation
cache and nothing else; this package places it and builds on it:

1. **Where the cache lives** (`jaxcache.py`): ``place_jax_cache`` puts
   JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
   ``<checkout>/.jax_cache``, and keeps every program;
   ``count_backend_compiles`` tells compile requests from real backend
   compiles, ``record_compile_spans`` puts each program's trace / lower /
   backend seconds on the trace ring.

2. **The one jit wrapper** (`cached.py`): ``cached_jit`` gives a program
   its name (compile counters, the scope table of ``trace.scopes``), an
   AOT handle (``warm`` / ``compile_for``) and the raw-execute dispatch
   of what was warmed.

3. **Parallel AOT warmup** (`warmup.py`): ``parallel_warm`` compiles a
   program grid through a bounded thread pool (XLA releases the GIL);
   ``ServeEngine._warmup``, ``BucketingModule.precompile`` and
   ``Module.prepare`` ride it.

4. **Observability** (`stats.py`): per-program trace/lower/compile
   seconds and a steady-state retrace counter via
   ``mx.profiler.compile_report()/_str()``.
"""
from .cached import CachedFunction, cached_jit
from .jaxcache import (CompileCounter, count_backend_compiles, jax_cache_dir,
                       place_jax_cache, record_compile_spans)
from .stats import CompileStats, get_stats
from .warmup import WarmupError, default_warmup_threads, parallel_warm

__all__ = ["CachedFunction", "CompileCounter", "CompileStats",
           "WarmupError", "cached_jit", "count_backend_compiles",
           "default_warmup_threads", "get_stats", "jax_cache_dir",
           "parallel_warm", "place_jax_cache", "record_compile_spans"]
