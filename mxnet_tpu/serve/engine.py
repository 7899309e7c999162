"""ServeEngine: pre-compiled shape buckets + dynamic batching + hot reload.

The inference-side counterpart of the training stack: where ``fit`` owns
one donated XLA program per megabatch, the engine owns one pre-compiled
inference executable per BATCH BUCKET (the BucketingModule idea applied
to the request axis) and a micro-batcher that coalesces concurrent
``submit()`` calls into the smallest bucket that fits, padding the tail
rows.  All buckets are compiled and warmed at construction — the serving
loop never sees a compile stall.

Weights live in ONE set of parameter buffers shared by every bucket's
executor (Predictor's executor cache + ``shared_exec``), so
``reload(...)`` — from a newer legacy pair or a ``mxnet_tpu.checkpoint``
step — swaps every bucket at once.  The swap holds the same lock the
dispatcher holds while running a batch, so each batch executes entirely
under one weights version: in-flight requests are neither dropped nor
served a mix of old and new layers.

::

    eng = mx.serve.ServeEngine.from_checkpoint(
        "model", epoch=3, input_shapes={"data": (1, 6),
                                        "softmax_label": (1,)})
    fut = eng.submit(x)                  # x: one item, shape (6,)
    probs = fut.result(timeout=1.0)
    eng.reload_from_checkpoint("model", epoch=7)   # hot swap
    print(mx.profiler.serve_report_str())
    eng.close()                          # graceful: drains the queue
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace as _trace
from ..base import MXNetError, get_env, make_rlock
from ..context import Context
from ..faults import point as _fault_point
from ..predictor import Predictor, load_checkpoint_pair
from .batcher import MicroBatcher
from .errors import ServeError, ServeRequestError
from .stats import ServeStats

__all__ = ["ServeEngine", "default_buckets"]


def default_buckets(max_batch_size: int) -> Tuple[int, ...]:
    """Power-of-two batch buckets up to (and including) max_batch_size:
    few compiled programs, worst-case pad waste < 50%."""
    if max_batch_size < 1:
        raise ServeError("max_batch_size must be >= 1, got %d"
                         % max_batch_size)
    buckets = []
    b = 1
    while b < max_batch_size:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch_size)
    return tuple(buckets)


class ServeEngine:
    """Dynamic-batching inference server over a Predictor (see module
    docstring).

    Parameters
    ----------
    symbol : Symbol | str
        Network: a Symbol, a symbol-JSON string, or a path to one.
    params : dict
        Parameter blob (``arg:``/``aux:`` prefixes accepted).
    input_shapes : dict name -> shape
        Per-input shapes INCLUDING a leading batch dim (its value is a
        template — the engine rebinds dim 0 to each bucket size).  The
        request payload is one item of ``input_shapes[data_name][1:]``;
        non-data inputs (labels) are zero-filled.
    batch_buckets : sequence of int, optional
        Compiled batch sizes; default power-of-two grid up to
        ``MXNET_SERVE_MAX_BATCH`` (8).
    max_delay_ms / queue_depth / deadline_ms :
        Batching knobs; default from ``MXNET_SERVE_MAX_DELAY_MS`` (2),
        ``MXNET_SERVE_QUEUE_DEPTH`` (4x max batch),
        ``MXNET_SERVE_DEADLINE_MS`` (1000; 0 disables).
    mesh / param_specs :
        Multichip serving: a named mesh (``parallel.make_mesh``, an
        axes list, or ``"tp=2"``) plus per-param PartitionSpecs.  Every
        bucket executor is placed on the mesh — weights sharded per
        spec (a model too big for one chip serves from N), padded
        batches ``device_put`` with a ``P("dp", ...)`` input sharding
        when the mesh has a dp axis that divides the bucket (replicated
        otherwise), GSPMD inserts the collectives, outputs reassemble
        on gather.  Composes with hot reload (a swapped weight lands
        back in its shard sharding) and the compile cache (mesh axes
        join the program keys).
    fuse :
        Operator fusion on the serving graph (``passes.fuse``): None =
        the ``MXNET_FUSE`` default when a pipeline is built (on), False
        = off, True/dict = fusion passes even without quantization.
        Fusion is exact (bitwise in f32).
    embed_dedup :
        Rec-serve embedding lookups: None = the ``MXNET_EMBED_DEDUP``
        default (off), True/int = rewrite ``Embedding`` nodes to the
        deduped ``_sparse_embedding`` lookup (``passes.embed``) — each
        distinct id in a request batch gathers its row once, and
        padded/out-of-range ids read as zero vectors.  For id-list
        models pass ``type_dict={"<ids input>": np.int32}`` so request
        payloads ship as ints.
    autotune :
        ``True`` (or ``MXNET_AUTOTUNE=1`` with ``autotune=None``) picks
        the pass-pipeline variant by measurement — candidates are timed
        through ``compile_cache``-warmed predictors, the winner is
        persisted per (model, topology) fingerprint
        (``MXNET_AUTOTUNE_DIR``) and reloaded with zero measurements on
        the next construction.  ``"joint"`` (or ``MXNET_AUTOTUNE=joint``)
        searches the JOINT space — fusion x bucket grid x quantize op
        subset — ranked by the learned cost model with only a shortlist
        measured (``MXNET_AUTOTUNE_SHORTLIST``); an explicit
        ``batch_buckets=`` pins the grid axis.  See docs/autotune.md and
        ``mx.profiler.autotune_report()``.
    quantize / calib_data / u8_wire / pipeline :
        Graph-optimized serving (``mxnet_tpu.passes``).  ``quantize=``
        takes ``"int8"`` (needs ``calib_data``: a sample of requests in
        WIRE format, item-stacked — the engine calibrates activation
        ranges on it), ``"float16"``/``"bfloat16"`` (pure precision
        rewrite, no calibration), or a dict of QuantizePass kwargs.
        ``u8_wire=`` (True or ``{"mean":, "scale":, "hwc":}``) moves the
        cast/normalize prologue into the graph and retypes the data
        input to uint8, so each request ships 4x fewer bytes.
        ``pipeline=`` overrides with a pre-built PassPipeline.  The
        bucket grid is compiled FROM the transformed graph (AOT-warmed
        through compile_cache.parallel_warm), the pipeline fingerprint
        keys the compiled programs apart from their f32 twins, and hot
        reload re-quantizes fresh f32 weights automatically.
    """

    def __init__(self, symbol, params: Dict,
                 input_shapes: Dict[str, Tuple[int, ...]], *,
                 data_name: Optional[str] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 max_delay_ms: Optional[float] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 output_index: int = 0,
                 dev_type: str = "cpu", dev_id: int = 0,
                 type_dict: Optional[Dict] = None,
                 name: str = "serve", warmup: bool = True,
                 mesh=None, param_specs: Optional[Dict] = None,
                 quantize=None, calib_data=None, u8_wire=None,
                 fuse=None, pipeline=None, autotune=None,
                 embed_dedup=None):
        if not input_shapes:
            raise ServeError("input_shapes must name at least one input")
        sym_json = symbol.tojson() if hasattr(symbol, "tojson") else symbol
        explicit_buckets = batch_buckets is not None
        if batch_buckets is None:
            batch_buckets = default_buckets(
                get_env("MXNET_SERVE_MAX_BATCH", 8, int))
        self._buckets = tuple(sorted(set(int(b) for b in batch_buckets)))
        if not self._buckets or self._buckets[0] < 1:
            raise ServeError("batch_buckets must be positive ints, got %r"
                             % (batch_buckets,))
        self.max_batch_size = self._buckets[-1]
        if max_delay_ms is None:
            max_delay_ms = get_env("MXNET_SERVE_MAX_DELAY_MS", 2.0, float)
        if queue_depth is None:
            queue_depth = get_env("MXNET_SERVE_QUEUE_DEPTH",
                                  4 * self.max_batch_size, int)
        if deadline_ms is None:
            deadline_ms = get_env("MXNET_SERVE_DEADLINE_MS", 1000.0, float)
        self.max_delay_ms = float(max_delay_ms)
        self.queue_depth = int(queue_depth)
        self.deadline_ms = float(deadline_ms) or None
        self._shapes_tpl = {k: tuple(v) for k, v in input_shapes.items()}
        if data_name is None:
            data_name = "data" if "data" in self._shapes_tpl \
                else next(iter(self._shapes_tpl))
        if data_name not in self._shapes_tpl:
            raise ServeError("data_name %r not in input_shapes %s"
                             % (data_name, sorted(self._shapes_tpl)))
        self.data_name = data_name
        self.item_shape = self._shapes_tpl[data_name][1:]
        self._output_index = int(output_index)
        self.name = name
        self.weights_version = 0
        # serializes batch execution against weight swaps: a batch runs
        # entirely under one version, a reload waits out the in-flight
        # batch instead of tearing it.  RLock so reload()/pause() nest
        # on one thread; _pause_owner guards the close-inside-pause
        # deadlock (close joins the dispatcher, which needs this lock).
        self._swap_lock = make_rlock("serve.engine_swap")
        self._pause_owner: Optional[int] = None
        # serializes close(): every closer returns only after shutdown
        # actually finished, not merely after some other thread STARTED
        # it.  RLock: a drop-on-close done-callback runs inline on the
        # closer's own thread and may close() again (see close()).
        self._close_lock = make_rlock("serve.engine_close")
        # per-bucket shape dicts, built once: _run_batch is the hot loop
        self._shapes_by_bucket = {b: self._bucket_shapes(b)
                                  for b in self._buckets}
        if mesh is not None:
            from jax.sharding import Mesh
            from ..parallel import make_mesh
            if not isinstance(mesh, Mesh):
                mesh = make_mesh(mesh)
        self._mesh = mesh
        self._param_specs = dict(param_specs or {})
        if self._param_specs and mesh is None:
            raise ServeError("param_specs without mesh=: specs are "
                             "PartitionSpecs over a named mesh")
        from ..autotune import mode as _autotune_mode
        autotuned = False
        amode = _autotune_mode(autotune) \
            if pipeline is None and fuse is None else None
        if amode == "joint":
            # cost-model-ranked joint search over fusion x bucket grid x
            # quantize op subset (autotune.tune_serve_joint): the model
            # ranks the whole space, only a shortlist is measured, the
            # winner persists per (symbol, shapes, quantize, topology).
            # The winning grid replaces the default bucket chain (an
            # explicit batch_buckets= argument pins the grid — only the
            # other axes are searched then)
            from ..autotune import tune_serve_joint
            fuse, win_buckets, quantize, pipeline = tune_serve_joint(
                sym_json, params, self._shapes_tpl, self._buckets,
                data_name=data_name, quantize=quantize,
                calib_data=calib_data, u8_wire=u8_wire,
                dev=(dev_type, dev_id), name=name,
                explicit_buckets=explicit_buckets)
            if win_buckets != self._buckets:
                self._buckets = win_buckets
                self.max_batch_size = self._buckets[-1]
                self._shapes_by_bucket = {b: self._bucket_shapes(b)
                                          for b in self._buckets}
            autotuned = True
        elif amode is not None:
            # measurement-driven pipeline-variant choice (fusion on/off
            # around the same fold/CSE/DCE[/quantize] spine); the winner
            # is persisted per (symbol, shapes, quantize, topology) and
            # a fresh process loads it without measuring.  An explicit
            # fuse= argument always wins — tuning only decides where the
            # call site did not (the documented MXNET_AUTOTUNE contract)
            from ..autotune import tune_serve_pipeline
            fuse, pipeline = tune_serve_pipeline(
                sym_json, params,
                self._shapes_by_bucket[self.max_batch_size],
                data_name=data_name, quantize=quantize,
                calib_data=calib_data, u8_wire=u8_wire,
                dev=(dev_type, dev_id), name=name)
            autotuned = True
        if embed_dedup is None and pipeline is None:
            # resolve the env default HERE, not only inside
            # build_serving_pipeline: with no other pipeline feature on,
            # MXNET_EMBED_DEDUP=1 alone must still build a pipeline
            from ..passes import default_embed_dedup
            embed_dedup = default_embed_dedup() or None
        if pipeline is None and (quantize or u8_wire or fuse or autotuned
                                 or embed_dedup):
            from ..passes import build_serving_pipeline
            pipeline = build_serving_pipeline(
                quantize=quantize, calib_data=calib_data,
                calib_shapes=self._shapes_by_bucket[self.max_batch_size],
                data_name=data_name, u8_wire=u8_wire, fuse=fuse,
                name=name, ctx=Context(dev_type, dev_id),
                embed_dedup=embed_dedup)
        self.pipeline = pipeline
        self._predictor = Predictor(
            sym_json, params, self._shapes_by_bucket[self.max_batch_size],
            dev_type, dev_id, type_dict=type_dict, pipeline=pipeline)
        self._data_dtype = np.dtype(
            self._predictor._exec.arg_dict[data_name].dtype)
        self.stats = ServeStats(name, self.max_batch_size)
        from .. import profiler
        profiler.register_serve_stats(self.stats)
        if warmup:
            self._warmup()
        elif self._mesh is not None:
            # the dispatcher's reshape() must never bind a bucket the
            # mesh placement missed (mixed single-device/mesh operands
            # crash the jit): place the whole grid even without warmup
            self._bind_grid()
        self._batcher = MicroBatcher(
            self._run_batch, self._finish,
            max_batch_size=self.max_batch_size,
            max_delay_ms=self.max_delay_ms, queue_depth=self.queue_depth,
            default_deadline_ms=self.deadline_ms, validate=self._validate,
            stats=self.stats, name=name)
        self._closed = False

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, prefix: str, epoch: int,
                        input_shapes: Dict[str, Tuple[int, ...]],
                        **kwargs) -> "ServeEngine":
        """Serve a legacy ``save_checkpoint`` pair (missing vs corrupt
        artifacts fail with candidates listed, like load_checkpoint)."""
        sym_json, params = load_checkpoint_pair(prefix, epoch)
        return cls(sym_json, params, input_shapes, **kwargs)

    @classmethod
    def from_checkpoint_dir(cls, directory: str, symbol,
                            input_shapes: Dict[str, Tuple[int, ...]],
                            step: Optional[int] = None,
                            **kwargs) -> "ServeEngine":
        """Serve a ``mxnet_tpu.checkpoint`` store (full train state saved
        by CheckpointManager / ``Module.fit(checkpoint=...)``): loads the
        newest committed step (or ``step``), keeping params + aux and
        dropping the optimizer state.  ``symbol`` is required — the store
        holds arrays, not the graph."""
        params, _meta = _load_checkpoint_dir_params(directory, step)
        return cls(symbol, params, input_shapes, **kwargs)

    # -- shape / dtype plumbing -------------------------------------------
    def _bucket_shapes(self, b: int) -> Dict[str, Tuple[int, ...]]:
        return {k: (b,) + v[1:] for k, v in self._shapes_tpl.items()}

    def _input_specs(self, bucket: int) -> Dict:
        """Mesh input shardings for one bucket's non-param inputs: the
        batch dim over ``dp`` when the mesh has one that divides the
        bucket, replicated otherwise (small buckets on a dp mesh pad
        up through replication — correctness first)."""
        from jax.sharding import PartitionSpec as P
        dp = dict(self._mesh.shape).get("dp", 1)
        specs = {}
        for name, shape in self._shapes_by_bucket[bucket].items():
            if dp > 1 and shape and shape[0] % dp == 0:
                specs[name] = P(*(["dp"] + [None] * (len(shape) - 1)))
            else:
                specs[name] = P()
        return specs

    def _grid_fail(self, bucket, phase, exc):
        """One error-message shape for every grid construction phase
        (bind / mesh placement / compile / first run) — the bind and
        placement phases also run with warmup=False, so the message
        names the grid, not a warmup that may not have run."""
        raise ServeError(
            "serve bucket-grid construction failed at bucket %d (input "
            "shapes %s, %s phase): %s: %s"
            % (bucket, sorted(self._shapes_by_bucket[bucket].items()),
               phase, type(exc).__name__, exc)) from exc

    def _bind_grid(self) -> Dict:
        """Bind every bucket executor (they share one set of parameter
        buffers) and, with a mesh, place each on it — params at their
        specs, inputs per ``_input_specs``.  Shared param NDArrays are
        placed once; re-placing to the same sharding is a no-op."""
        p = self._predictor
        execs = {}
        for b in self._buckets:
            try:
                execs[b] = p.ensure_bound(self._shapes_by_bucket[b])
            except Exception as e:
                self._grid_fail(b, "bind", e)
            if self._mesh is not None:
                try:
                    execs[b].set_mesh(self._mesh,
                                      param_specs=self._param_specs,
                                      input_specs=self._input_specs(b))
                except Exception as e:
                    self._grid_fail(b, "mesh placement", e)
        return execs

    def _warmup(self) -> None:
        """Compile + run every bucket once so serving never compiles.

        Three phases: (1) bind every bucket executor sequentially
        (cheap; they share one set of parameter buffers), (2) compile
        the bucket programs through a bounded thread pool — XLA
        compilation releases the GIL, so the grid warms in max(compile)
        instead of sum; ``MXNET_SERVE_WARMUP_THREADS`` bounds the pool
        (default: one thread per bucket up to the host's cores) — and
        (3) run each bucket once, serially (cheap after compilation:
        buffers allocate, the executable loads).  On a restart phase 2
        traces and lowers the grid again and reads the executables from
        JAX's persistent cache, where the entry point placed one.

        Any failure is re-raised as a ServeError naming the offending
        bucket and its shapes — a mid-grid compile error must not
        surface as a bare jax traceback with no bucket context."""
        from ..compile_cache import WarmupError, default_warmup_threads, \
            parallel_warm
        p = self._predictor
        self._warmup_threads = max(1, get_env(
            "MXNET_SERVE_WARMUP_THREADS",
            default_warmup_threads(len(self._buckets)), int))

        fail = self._grid_fail
        execs = self._bind_grid()
        try:
            parallel_warm(
                [("bucket %d" % b,
                  lambda e=execs[b]: e.precompile(("fwd_eval",)))
                 for b in self._buckets],
                threads=self._warmup_threads)
        except WarmupError as e:
            bucket = int(str(e.label).split()[1])
            fail(bucket, "compile", e.__cause__ or e)
        for b in self._buckets:
            try:
                p.reshape(self._shapes_by_bucket[b])
                p.set_input(self.data_name,
                            np.zeros((b,) + self.item_shape,
                                     self._data_dtype))
                p.forward()
                p.get_output(self._output_index)   # sync: executable is hot
            except Exception as e:
                fail(b, "first run", e)

    def _validate(self, data) -> np.ndarray:
        """Admission-time request validation (caller's thread): shape and
        dtype are checked BEFORE the queue, so one malformed request can
        never take a batch of good ones down with it."""
        arr = np.asarray(data)
        if arr.dtype.kind not in "biuf":
            raise ServeRequestError(
                "request dtype %s is not numeric (expected castable to %s)"
                % (arr.dtype, self._data_dtype))
        if tuple(arr.shape) != tuple(self.item_shape):
            raise ServeRequestError(
                "request shape %s != item shape %s (submit ONE item; the "
                "server owns the batch dim)"
                % (tuple(arr.shape), tuple(self.item_shape)))
        return np.ascontiguousarray(arr, dtype=self._data_dtype)

    def _pick_bucket(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self.max_batch_size       # n <= max_batch_size by contract

    # -- batch execution (dispatcher thread) ------------------------------
    def _run_batch(self, reqs) -> Tuple:
        n = len(reqs)
        bucket = self._pick_bucket(n)
        # replica-failure seam: an injected `error` fails this batch
        # (every future gets the exception — exactly what a broken
        # replica looks like to the router), a `crash` kills the whole
        # engine process
        _fault_point("serve.dispatch", n=n, bucket=bucket)
        with _trace.span("serve:run_batch", cat="serve", n=n,
                         bucket=bucket):
            data = np.stack([r.data for r in reqs])
            if bucket > n:
                pad = np.zeros((bucket - n,) + self.item_shape,
                               self._data_dtype)
                data = np.concatenate([data, pad], axis=0)
            with self._swap_lock:
                p = self._predictor
                # cache hit: no compile
                p.reshape(self._shapes_by_bucket[bucket])
                p.set_input(self.data_name, data)
                p.forward()
                out = p._exec.outputs[self._output_index]._get()
            # start the D2H copy and return: the completion thread blocks
            # on it while THIS thread dispatches the next batch (score()
            # pattern)
            start = getattr(out, "copy_to_host_async", None)
            if callable(start):
                try:
                    start()
                except Exception:
                    pass
        self.stats.on_batch(n, bucket)
        return out, n

    def _finish(self, handoff) -> List[np.ndarray]:
        """Completion thread: block on the D2H copy, slice per request."""
        out, n = handoff
        with _trace.span("serve:d2h_finish", cat="serve", n=n):
            host = np.asarray(out)
            return [np.array(host[i]) for i in range(n)]

    # -- client API --------------------------------------------------------
    def submit(self, data, deadline_ms: Optional[float] = None):
        """Enqueue one item (shape ``item_shape``); returns a
        concurrent.futures.Future of the output row.  Raises
        ServeRequestError / ServeOverloadError / ServeClosedError
        immediately (see serve.errors)."""
        return self._batcher.submit(data, deadline_ms=deadline_ms)

    def submit_many(self, items, deadline_ms: Optional[float] = None):
        """Convenience fan-out: one future per item."""
        return [self.submit(x, deadline_ms=deadline_ms) for x in items]

    def predict(self, data, timeout: Optional[float] = None) -> np.ndarray:
        """Blocking one-shot: submit + result."""
        return self.submit(data).result(timeout=timeout)

    # -- hot weight reload -------------------------------------------------
    def reload(self, arg_params: Dict,
               aux_params: Optional[Dict] = None) -> int:
        """Atomically swap weights between batches.  In-flight requests
        finish under the old version; everything dispatched after this
        returns sees the new one.  Returns the new weights version."""
        with self._swap_lock:
            self._predictor.set_params(arg_params, aux_params)
            self.weights_version += 1
            version = self.weights_version
        self.stats.on_reload()
        return version

    def reload_from_checkpoint(self, prefix: str, epoch: int) -> int:
        """Hot-swap to a legacy pair's params (symbol must match the
        serving graph — only weights move)."""
        _sym_json, params = load_checkpoint_pair(prefix, epoch)
        return self.reload(params)

    def reload_from_checkpoint_dir(self, directory: str,
                                   step: Optional[int] = None) -> int:
        """Hot-swap to a ``mxnet_tpu.checkpoint`` step (default newest
        committed)."""
        params, _meta = _load_checkpoint_dir_params(directory, step)
        return self.reload(params)

    @contextlib.contextmanager
    def pause(self):
        """Hold batch execution between batches (the weights-swap lock):
        queued requests wait, admissions keep their overload semantics.
        For maintenance windows and deterministic tests.  reload() and
        nested pause() are fine inside; close() is not (it would join a
        dispatcher blocked on this lock) and raises instead of hanging.
        A close() from another thread blocks until the pause exits."""
        with self._swap_lock:
            prev = self._pause_owner
            self._pause_owner = threading.get_ident()
            try:
                yield
            finally:
                self._pause_owner = prev

    # -- introspection -----------------------------------------------------
    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    def pending_requests(self) -> int:
        """Requests currently waiting in the bounded queue (the
        ``queue_depth`` attribute is the configured bound)."""
        return self._batcher.queue_depth()

    def outstanding(self) -> int:
        """Admitted requests not yet terminally resolved (queued or in
        flight) — what a router or multiplexer must wait out before it
        may drain or evict this engine."""
        return self.stats.outstanding()

    def device_bytes(self) -> int:
        """Approximate device-memory footprint of this engine: every
        distinct PERSISTENT buffer bound by the bucket-grid executors —
        parameters (shared across buckets, counted once) and per-bucket
        input staging buffers.  Transient forward outputs are not
        counted, so the real peak runs somewhat above this; size
        ``MXNET_SERVE_MUX_BYTES`` with headroom.  The multiplexer's
        admission budget is checked against this."""
        return exec_device_bytes(self._predictor._exec_cache.values())

    # -- lifecycle ---------------------------------------------------------
    def close(self, drain: bool = True) -> None:
        """Graceful shutdown: stop admissions, drain queued requests
        (partial batches flush immediately), join the worker threads.
        ``drain=False`` fails queued requests with ServeClosedError.

        Thread-safe and idempotent: concurrent closers serialize, and
        every one of them returns only after shutdown completed.  A
        close() from the thread that holds ``pause()`` raises (guaranteed
        deadlock); a close() from ANOTHER thread while a pause is held
        simply blocks until the pause exits — the dispatcher needs the
        paused lock to finish its in-flight batch before it can be
        joined (see ``test_close_without_drain_fails_pending``)."""
        if self._pause_owner == threading.get_ident():
            raise ServeError(
                "close() inside pause() would deadlock: the dispatcher "
                "needs the paused lock to finish its in-flight batch — "
                "exit pause() first (or close from another thread)")
        if self._batcher.is_worker_thread():
            # reentrant close from a future done-callback (run inline on
            # the completion thread): request shutdown without joining or
            # taking the close lock — an outer closer may hold it while
            # joining this very thread
            self._batcher.request_close(drain=drain)
            return
        with self._close_lock:
            # _closed is flipped BEFORE the batcher shutdown: close(
            # drain=False) fails dropped futures whose done-callbacks run
            # inline on THIS thread and may close() again — the RLock
            # re-enters and this guard returns.  For a concurrent closer
            # the guard is race-free: it acquires the lock only after the
            # first closer finished the joins, so returning early here
            # still means shutdown completed.
            if self._closed:
                return
            self._closed = True
            self._batcher.close(drain=drain)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def exec_device_bytes(execs) -> int:
    """Distinct PERSISTENT device bytes bound by an iterable of
    executors (arg + aux buffers), deduped by owning buffer (shared
    param NDArrays count once across bucket executors); transient
    forward outputs are excluded.  The one accounting the multiplexer
    budgets against — ServeEngine and DecodeEngine must agree on it, so
    there is exactly one implementation."""
    seen = set()
    total = 0
    for ex in execs:
        for d in (ex.arg_dict, ex.aux_dict):
            for arr in d.values():
                root = arr._root()
                if id(root) in seen:
                    continue
                seen.add(id(root))
                a = root._get()
                if a is not None:
                    total += int(getattr(a, "nbytes", 0) or
                                 a.size * np.dtype(a.dtype).itemsize)
    return total


def _load_checkpoint_dir_params(directory: str,
                                step: Optional[int] = None) -> Tuple[Dict, Dict]:
    """Read serving weights out of a mxnet_tpu.checkpoint store: params +
    fixed (both are executor arguments) and aux; optimizer slots and RNG
    stay behind.  -> (params dict, meta)."""
    from ..checkpoint import CheckpointManager
    mgr = CheckpointManager(directory, async_save=False,
                            name="serve-restore")
    try:
        tree, meta = mgr.restore(step=step)
    finally:
        mgr.close()
    if not isinstance(tree, dict) or "params" not in tree:
        raise MXNetError(
            "checkpoint under %r is not a module train state (expected a "
            "{'params', ...} tree, got %s); serve needs a state saved by "
            "save_module / Module.fit(checkpoint=...)"
            % (directory, type(tree).__name__))
    params: Dict = {}
    for group in ("params", "fixed", "aux"):
        params.update(tree.get(group) or {})
    return params, meta
