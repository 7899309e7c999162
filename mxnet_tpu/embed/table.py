"""EmbeddingTable: a giant ``(vocab, dim)`` table as a first-class, fast
device object.

The TPU-native rebuild of the reference parameter server's raison
d'être (PAPER.md layer 7): where ps-lite striped big arrays across
server PROCESSES (``kvstore_dist.h`` GetServerKeyRanges) and shipped
(row_ids, values) over ZeroMQ, this shards table ROWS across a mesh
axis via GSPMD and lets XLA collectives do the routing — lookups gather
from whichever chip owns the row, updates scatter back, and the "server
side" optimizer state shards along the very same axis (the
cross-replica weight-update-sharding recipe applied to rows).

Three traced programs per table, all through the compile cache:

* ``lookup(ids)``        — deduped gather (embed/sparse.py), optional
                           sum/mean pooling with padded-id masking
* ``update(ids, grads)`` — deduped scatter-add + lazy per-row optimizer
                           (slots sharded like the table, donated)
* ``accumulate(ids, g)`` — optimizer-free deduped scatter-add (the
                           kvstore "server accumulates pushes" default)

The table also trains INSIDE ``Module.fit``'s fused step without this
class (module/fused.py detects Embedding layers structurally); this
object is the serving/kvstore-facing surface: ``kvstore.create(
"device_embed")`` wraps one per sparse key, ``ServeEngine`` rec models
look up through the same traced path.
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError, get_env
from .sparse import (dedup_ids, dedup_scatter_add, resolve_cap,
                     slot_leaves_row_shaped, sparse_apply_rows)
from .stats import EmbedStats

__all__ = ["EmbeddingTable"]


class EmbeddingTable:
    """Device-resident, optionally row-sharded embedding table.

    Parameters
    ----------
    vocab, dim : int
        Table geometry.  Row ids outside ``[0, vocab)`` read as zero
        vectors (the padded-batch sentinel contract) and their updates
        drop.
    mesh / spec :
        Row sharding: a named mesh (``parallel.make_mesh`` result) plus
        the axis to shard rows over — an axis name string (``"dp"``), a
        PartitionSpec, or None for the mesh's first axis.  ``vocab``
        must divide evenly (same rule as every sharded param).  Without
        a mesh the table lives on the default device.
    dtype :
        Row dtype (f32 default).
    unique_cap : int, optional
        Traced dedup output size per lookup/update batch, counted in
        distinct REAL ids (a sentinel slot for padded ids is reserved
        on top); 0/None = the safe worst case,
        ``min(batch size, vocab + 1)``.
        Must be >= the distinct ids any batch can contain — too small
        truncates ``jnp.unique`` and corrupts results, which the
        host-side ``MXNET_EMBED_CHECK_CAP`` guard (default on) turns
        into a clear error.  ``MXNET_EMBED_UNIQUE_CAP`` is the env
        spelling.
    optimizer :
        An ``mxnet_tpu.optimizer.Optimizer`` with a fused functional
        form and row-shaped state (SGD/NAG/Adagrad/Adam); arms
        ``update``.  Settable later via :meth:`set_optimizer`.
    """

    def __init__(self, vocab: int, dim: int, mesh=None, spec=None,
                 dtype=jnp.float32, unique_cap: Optional[int] = None,
                 optimizer=None, initializer=None, name: str = "embed"):
        if vocab < 1 or dim < 1:
            raise MXNetError("EmbeddingTable needs vocab, dim >= 1 "
                             "(got %d, %d)" % (vocab, dim))
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.name = name
        self.dtype = np.dtype(dtype)
        if unique_cap is None:
            unique_cap = get_env("MXNET_EMBED_UNIQUE_CAP", 0, int)
        self.unique_cap = int(unique_cap) or None
        self._check_cap = get_env("MXNET_EMBED_CHECK_CAP", True, bool)
        self.mesh = mesh
        self._sharding = self._row_sharding(mesh, spec)
        self.stats = EmbedStats(name)
        from .. import profiler
        profiler.register_embed_stats(self.stats)
        self._t = 0
        self._progs = {}
        self.optimizer = None
        self._opt_update = None
        self._opt_init = None
        self.slots = None
        rows = self._init_rows(initializer)
        # jnp.copy: the table is DONATED by the update/accumulate
        # programs; a zero-copy device_put alias of the host init buffer
        # would be scribbled over (the PR 2 corruption class)
        self.rows = jnp.copy(jax.device_put(rows, self._sharding)) \
            if self._sharding is not None else jnp.array(rows, copy=True)
        if optimizer is not None:
            self.set_optimizer(optimizer)

    # -- construction -------------------------------------------------------
    def _init_rows(self, initializer):
        if initializer is None:
            return np.zeros((self.vocab, self.dim), self.dtype)
        if callable(initializer):
            out = np.zeros((self.vocab, self.dim), np.float32)
            initializer("%s_weight" % self.name, _HostArr(out))
            return out.astype(self.dtype)
        arr = np.asarray(
            initializer._get() if hasattr(initializer, "_get")
            else initializer)
        if tuple(arr.shape) != (self.vocab, self.dim):
            raise MXNetError(
                "EmbeddingTable %r init value shape %s != (%d, %d)"
                % (self.name, tuple(arr.shape), self.vocab, self.dim))
        return arr.astype(self.dtype)

    def _row_sharding(self, mesh, spec):
        if mesh is None:
            if spec is not None:
                raise MXNetError("EmbeddingTable spec= without mesh=")
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import normalize_spec, validate_spec
        if spec is None:
            spec = P(mesh.axis_names[0], None)
        elif isinstance(spec, str) and "," not in spec:
            spec = P(spec, None)
        else:
            spec = normalize_spec(spec)
        validate_spec("%s_weight" % self.name, spec, mesh,
                      shape=(self.vocab, self.dim))
        self.row_spec = spec
        return NamedSharding(mesh, spec)

    def set_optimizer(self, optimizer) -> None:
        """Arm the sparse update path.  The optimizer's fused form is
        snapshotted NOW (hyperparameters bake into the traced program;
        re-call after mutating them) and its state must be row-shaped —
        the lazy per-row update condition (embed/sparse.py)."""
        fused = optimizer.fused_update_fn()
        if fused is None:
            raise MXNetError(
                "optimizer %s has no fused functional form; the sparse "
                "embedding update is a traced program"
                % type(optimizer).__name__)
        opt_init, opt_update = fused
        if not slot_leaves_row_shaped(opt_init, self.vocab, self.dim,
                                      self.dtype):
            raise MXNetError(
                "optimizer %s state for a (%d, %d) table is not row-"
                "shaped; the lazy per-row sparse update cannot express "
                "it — use SGD/NAG/Adagrad/Adam or the dense path"
                % (type(optimizer).__name__, self.vocab, self.dim))
        self.optimizer = optimizer
        self._opt_update = opt_update
        self._opt_init = opt_init
        self.slots = self._fresh_slots()
        # the step counter resets WITH the slots (same rule as a
        # slot-less restore): a stale t against zeroed Adam moments
        # would skew bias correction on every post-re-arm step
        self._t = 0
        # drop every traced update program (keys are ("update", cap)):
        # the new optimizer's hyperparameters/closures must re-bake
        self._progs = {k: v for k, v in self._progs.items()
                       if k[0] != "update"}

    def _fresh_slots(self):
        slots = self._opt_init(self.rows)
        if self._sharding is not None:
            slots = jax.tree_util.tree_map(
                lambda s: jax.device_put(s, self._sharding), slots,
                is_leaf=lambda x: x is None)
        return slots

    # -- traced programs ----------------------------------------------------
    def _distinct(self, ids_h: np.ndarray) -> int:
        """Distinct dedup-buffer values in one host id batch: in-range
        ids each count once, every out-of-range id shares the one
        sentinel (the ``dedup_ids`` fold) — exactly the slots the
        traced ``jnp.unique`` needs.  Computed ONCE per call and fed
        to both the stats counters and the cap guard."""
        flat = ids_h.reshape(-1)
        return int(np.unique(
            np.where((flat < 0) | (flat >= self.vocab), self.vocab,
                     flat)).size)

    def _cap(self, ids_h: np.ndarray, n_distinct: int) -> int:
        cap = resolve_cap(self.unique_cap, ids_h.size, self.vocab)
        if self._check_cap and self.unique_cap is not None \
                and n_distinct > cap:
            # a user cap below the batch's distinct count makes
            # jnp.unique truncate — NaN lookups, silently dropped grads
            raise MXNetError(
                "EmbeddingTable %r: batch holds %d distinct ids "
                "(out-of-range ids count as one) but unique_cap=%d "
                "admits only %d dedup slots; jnp.unique would truncate "
                "and corrupt the result.  Raise unique_cap / "
                "MXNET_EMBED_UNIQUE_CAP (0 = safe worst case), or "
                "set MXNET_EMBED_CHECK_CAP=0 to run unchecked."
                % (self.name, n_distinct, self.unique_cap, cap))
        return cap

    def _lookup_prog(self, cap: int, combiner: Optional[str]):
        key = ("lookup", cap, combiner)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        vocab = self.vocab
        from .sparse import dedup_lookup

        def fn(table, ids):
            # ONE implementation of the lookup contract (sparse.py):
            # the table, fused-step and _sparse_embedding paths must
            # never drift on dedup/pad semantics
            out, _uniq, _inv = dedup_lookup(table, ids, cap=cap)
            if combiner is None:
                return out
            pooled = jnp.sum(out, axis=-2)
            if combiner == "sum":
                return pooled
            # mean over REAL (in-range) ids; all-pad rows divide by 1
            n = jnp.sum(((ids >= 0) & (ids < vocab)),
                        axis=-1).astype(out.dtype)
            return pooled / jnp.maximum(n, 1)[..., None]

        from ..compile_cache import cached_jit
        prog = cached_jit(fn, name="embed:lookup")
        self._progs[key] = prog
        return prog

    def _update_prog(self, cap: int):
        if self._opt_update is None:
            raise MXNetError(
                "EmbeddingTable %r has no optimizer; call set_optimizer "
                "(or use accumulate for optimizer-free scatter-add)"
                % self.name)
        key = ("update", cap)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        vocab, dim = self.vocab, self.dim
        opt_update = self._opt_update
        wd = float(self.optimizer.wd)

        def fn(table, slots, ids, grads, lr, t):
            flat = ids.reshape(-1)
            uniq, inv = dedup_ids(flat, cap, sentinel=vocab)
            grows = dedup_scatter_add(
                grads.reshape(-1, dim).astype(table.dtype), inv, cap)
            return sparse_apply_rows(table, slots, uniq, grows,
                                     opt_update, lr, wd, t)

        from ..compile_cache import cached_jit
        prog = cached_jit(fn, name="embed:update", donate_argnums=(0, 1))
        self._progs[key] = prog
        return prog

    def _accumulate_prog(self, cap: int):
        key = ("acc", cap)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        vocab, dim = self.vocab, self.dim

        def fn(table, ids, values):
            flat = ids.reshape(-1)
            uniq, inv = dedup_ids(flat, cap, sentinel=vocab)
            vrows = dedup_scatter_add(
                values.reshape(-1, dim).astype(table.dtype), inv, cap)
            return table.at[uniq].add(vrows, mode="drop")

        from ..compile_cache import cached_jit
        prog = cached_jit(fn, name="embed:accumulate", donate_argnums=(0,))
        self._progs[key] = prog
        return prog

    # -- public surface -----------------------------------------------------
    def lookup(self, ids, combiner: Optional[str] = None):
        """Deduped lookup: ``ids (...,) -> (..., dim)`` (or pooled
        ``(..., dim)`` over the last ids axis with ``combiner=
        "sum"|"mean"``, padded ids masked).  Returns a jnp array."""
        if combiner not in (None, "sum", "mean"):
            raise MXNetError("combiner must be None|'sum'|'mean', got %r"
                             % (combiner,))
        ids_h = np.asarray(ids._get() if hasattr(ids, "_get") else ids)
        n_uniq = self._distinct(ids_h)
        # guard BEFORE stats: a rejected lookup must not inflate the
        # dedup counters (update/accumulate order likewise)
        cap = self._cap(ids_h, n_uniq)
        self.stats.note_ids("%s_weight" % self.name, ids_h, n_uniq=n_uniq)
        prog = self._lookup_prog(cap, combiner)
        out = prog(self.rows, jnp.asarray(ids_h.astype(np.int32)))
        return out

    def update(self, ids, grads, lr: Optional[float] = None):
        """Deduped sparse train step: apply the optimizer to the rows
        named by ``ids`` with per-occurrence output grads ``grads``
        (``ids.shape + (dim,)``).  Donates and replaces the table and
        slot buffers."""
        ids_h = np.asarray(ids._get() if hasattr(ids, "_get") else ids)
        g = grads._get() if hasattr(grads, "_get") else grads
        n_uniq = self._distinct(ids_h)
        cap = self._cap(ids_h, n_uniq)
        prog = self._update_prog(cap)
        self.stats.note_ids("%s_weight" % self.name, ids_h, n_uniq=n_uniq)
        self.stats.note_update("%s_weight" % self.name, cap)
        if lr is None:
            lr = self.optimizer.base_lr()
        # commit the step counter only AFTER the program returns: a
        # raise mid-call (bad grads shape, trace error) must not skew
        # Adam-style bias correction on the retry
        t_next = self._t + 1
        self.rows, self.slots = prog(
            self.rows, self.slots, jnp.asarray(ids_h.astype(np.int32)),
            jnp.asarray(g), jnp.asarray(lr, jnp.float32),
            jnp.asarray(t_next, jnp.int32))
        self._t = t_next
        return self.rows

    def accumulate(self, ids, values):
        """Optimizer-free deduped scatter-add (the kvstore "server
        accumulates pushes" default merge).  Donates the table."""
        ids_h = np.asarray(ids._get() if hasattr(ids, "_get") else ids)
        v = values._get() if hasattr(values, "_get") else values
        n_uniq = self._distinct(ids_h)
        cap = self._cap(ids_h, n_uniq)
        self.stats.note_ids("%s_weight" % self.name, ids_h, n_uniq=n_uniq)
        self.rows = self._accumulate_prog(cap)(
            self.rows, jnp.asarray(ids_h.astype(np.int32)),
            jnp.asarray(v))
        return self.rows

    def set_rows(self, value) -> None:
        """Replace the whole table (dense init/push), re-placed into the
        row sharding."""
        arr = np.asarray(value._get() if hasattr(value, "_get")
                         else value)
        if tuple(arr.shape) != (self.vocab, self.dim):
            raise MXNetError(
                "EmbeddingTable %r set_rows shape %s != (%d, %d)"
                % (self.name, tuple(arr.shape), self.vocab, self.dim))
        arr = arr.astype(self.dtype)
        # jnp.copy: donated table must own fresh storage (see __init__)
        self.rows = jnp.copy(jax.device_put(arr, self._sharding)) \
            if self._sharding is not None else jnp.array(arr, copy=True)

    def as_numpy(self) -> np.ndarray:
        """The full table on host (gathers a sharded table)."""
        return np.asarray(jax.device_get(self.rows))

    # -- checkpoint ---------------------------------------------------------
    def state(self) -> dict:
        """Pytree for mxnet_tpu.checkpoint's sharded save (leaves keep
        their live sharding: each process writes only its own rows)."""
        return {"rows": self.rows, "slots": self.slots,
                "t": jnp.asarray(self._t, jnp.int32)}

    def restore(self, tree: dict) -> None:
        """Restore from :meth:`state` output (host or device leaves);
        rows land back in this table's row sharding — a table saved on
        one mesh restores onto another (cross-mesh restore).  A tree
        without slots restored into an optimizer-armed table re-arms
        fresh slots AND a fresh step counter (t = 0)."""
        def put(x):
            if x is None:
                return None
            a = np.asarray(x)
            # jnp.copy: donated table/slots must own fresh storage
            # (see __init__)
            return jnp.copy(jax.device_put(a, self._sharding)) \
                if self._sharding is not None else jnp.array(a, copy=True)
        self.rows = put(tree["rows"])
        slots = tree.get("slots")
        if slots is not None and self.optimizer is None:
            raise MXNetError(
                "EmbeddingTable %r restore carries optimizer slots but "
                "no optimizer is set; call set_optimizer first"
                % self.name)
        self._t = int(np.asarray(tree.get("t", 0)))
        if self.optimizer is not None:
            if slots is None:
                # checkpoint saved without slots (optimizer-free table,
                # or an older tree): re-arm fresh state rather than let
                # the next update trace None into sparse_apply_rows.
                # The step counter resets WITH the slots — carrying the
                # tree's t against zeroed Adam moments would shrink the
                # bias-correction denominators to ~1 and skew every
                # post-restore step
                self.slots = self._fresh_slots()
                self._t = 0
            else:
                self.slots = jax.tree_util.tree_map(
                    put, slots, is_leaf=lambda x: x is None)


class _HostArr:
    """Minimal NDArray-alike handed to reference initializers (they call
    ``arr[:] = value``)."""

    def __init__(self, arr):
        self._a = arr
        self.shape = arr.shape

    def __setitem__(self, key, value):
        self._a[key] = np.asarray(
            value._get() if hasattr(value, "_get") else value)
