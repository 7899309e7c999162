"""CheckpointManager: policy + async orchestration over the layout/shard
primitives.

::

    mgr = checkpoint.CheckpointManager("/ckpt/run7", keep_last_n=3,
                                       keep_every_k=1000,
                                       save_every_steps=100)
    mgr.save(step, state_tree, meta)         # async: ~one step of stall
    ...
    tree, meta = mgr.restore(like=template)  # newest committed step
    print(mx.profiler.checkpoint_report_str())

``save`` snapshots on the calling (train) thread — on-device copies plus
async D2H start — and hands serialization + the atomic commit to the
background writer.  ``restore`` reads the newest committed step (torn
saves are skipped by construction, see layout.py) and device_puts each
shard straight to its target device when a ``like`` template supplies
shardings.  Retention (keep-last-N / keep-every-K) runs after every
commit.  ``install_preemption_handler`` arms a SIGTERM hook for the
snapshot-then-exit path (Module.fit polls ``preempted`` each batch).
"""
from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from .. import trace as _trace
from ..base import MXNetError, make_lock
from . import layout
from .sharded import flatten_state, merge_indexes, read_leaf, write_leaf
from .snapshot import AsyncWriter, snapshot_tree

__all__ = ["CheckpointManager", "CheckpointStats"]

_FORMAT = 1


class CheckpointStats:
    """Save/restore counters for one manager; surfaced through
    ``mx.profiler.checkpoint_report()``."""

    def __init__(self, name: str):
        self.name = name
        self._lock = make_lock("checkpoint.manager")
        self._c: Dict[str, float] = {
            "saves_started": 0, "saves_committed": 0, "save_failures": 0,
            "restores": 0, "last_step": -1,
            "save_s": 0.0, "last_save_s": 0.0,
            "bytes": 0, "last_bytes": 0, "last_bytes_per_s": 0.0,
            "overhead_s": 0.0, "last_overhead_s": 0.0,
            "restore_s": 0.0, "last_restore_s": 0.0,
        }

    def add(self, **kwargs) -> None:
        with self._lock:
            for k, v in kwargs.items():
                if k.startswith("last_") or k == "last_step":
                    self._c[k] = v
                else:
                    self._c[k] += v

    def report(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self._c)
        for k in ("save_s", "last_save_s", "overhead_s", "last_overhead_s",
                  "restore_s", "last_restore_s", "last_bytes_per_s"):
            out[k] = round(out[k], 4)
        return out

    def report_str(self) -> str:
        r = self.report()
        return ("checkpoint manager %r\n"
                "  saves: %d committed / %d started (%d failed), "
                "last step %d\n"
                "  save wall:   %.3fs last, %.3fs total, %.1f MB/s last\n"
                "  train-thread overhead: %.4fs last, %.4fs total\n"
                "  restores: %d, %.3fs last" % (
                    self.name, r["saves_committed"], r["saves_started"],
                    r["save_failures"], r["last_step"], r["last_save_s"],
                    r["save_s"], r["last_bytes_per_s"] / 1e6,
                    r["last_overhead_s"], r["overhead_s"], r["restores"],
                    r["last_restore_s"]))


def _write_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
        f.flush()
        os.fsync(f.fileno())


def _multiprocess() -> Tuple[int, int]:
    """(process_index, process_count) — (0, 1) before jax is importable."""
    try:
        import jax
        return jax.process_index(), jax.process_count()
    except Exception:
        return 0, 1


def _barrier(name: str, timeout_ms: int = 120000) -> None:
    """Cross-process rendezvous that is safe OFF the main thread.

    The save path runs on the async writer thread, concurrently with the
    train thread's dispatches.  ``mhu.sync_global_devices`` is a device
    collective (a jitted psum): issued from a second thread it interleaves
    with the train step's collectives in a different order on each rank
    and wedges the whole collective runtime ("Gloo ... connection reset
    by peer", then the coordination service takes the job down).  The
    coordination-service barrier is a plain gRPC rendezvous — no device
    programs — so the writer thread can block on it freely."""
    # jax 0.9.0 has no public handle on the coordination client
    from jax._src import distributed
    client = distributed.global_state.client
    if client is not None:
        client.wait_at_barrier(name, timeout_in_ms=int(timeout_ms))
        return
    # no coordination service (jax.distributed not initialized)
    from jax.experimental import multihost_utils as mhu
    mhu.sync_global_devices(name)


class CheckpointManager:
    """Async, sharded, crash-safe checkpoint store rooted at one
    directory (see module docstring)."""

    def __init__(self, directory: str, keep_last_n: Optional[int] = 3,
                 keep_every_k: Optional[int] = None,
                 save_every_steps: Optional[int] = None,
                 async_save: bool = True, max_pending: int = 2,
                 name: Optional[str] = None):
        self.directory = str(directory)
        self.keep_last_n = keep_last_n
        self.keep_every_k = keep_every_k
        self.save_every_steps = save_every_steps
        self.async_save = async_save
        self.name = name or os.path.basename(os.path.normpath(self.directory))
        self.stats = CheckpointStats(self.name)
        from .. import profiler
        profiler.register_checkpoint_stats(self.stats)
        self._writer = AsyncWriter(name="ckpt-writer-%s" % self.name,
                                   max_pending=max_pending) \
            if async_save else None
        self._closed = False
        self.preempted = False
        self._prev_handlers: Dict[int, Any] = {}
        proc, _ = _multiprocess()
        if proc == 0:
            # wreckage from a previous crashed writer; no save can be in
            # flight for this root before the manager exists
            layout.clean_stale_tmp(self.directory)

    # -- discovery --------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        """Newest committed step (the documented discovery API: torn and
        uncommitted saves are never visible here)."""
        return layout.latest_step(self.directory)

    def all_steps(self):
        return layout.all_steps(self.directory)

    def should_save(self, step: int) -> bool:
        return bool(self.save_every_steps) and step > 0 \
            and step % self.save_every_steps == 0

    # -- save -------------------------------------------------------------
    def save(self, step: int, tree, meta: Optional[Dict] = None,
             blocking: Optional[bool] = None) -> None:
        """Checkpoint ``tree`` (a pytree of arrays) + JSON-able ``meta``
        as ``step``.  Async by default: the call costs one on-device copy
        of the state; serialization and the atomic commit happen on the
        writer thread.  ``blocking=True`` (or ``async_save=False``)
        commits before returning."""
        if self._closed:
            raise MXNetError("CheckpointManager %r is closed" % self.name)
        step = int(step)
        blocking = (not self.async_save) if blocking is None else blocking
        t0 = time.perf_counter()
        snap = snapshot_tree(tree)
        meta = dict(meta or {})
        meta.setdefault("step", step)
        self.stats.add(saves_started=1)
        if self._writer is None or blocking:
            if self._writer is not None:
                self._writer.wait()     # keep commits ordered by step
            self._write_state(step, snap, meta)
            dt = time.perf_counter() - t0
            self.stats.add(last_overhead_s=dt, overhead_s=dt)
            return
        self._writer.submit(lambda: self._write_state(step, snap, meta))
        dt = time.perf_counter() - t0
        self.stats.add(last_overhead_s=dt, overhead_s=dt)
        # the train-thread stall a save cost: snapshot + async submit
        _trace.complete("ckpt:snapshot_overhead", t0, dt, cat="ckpt",
                        step=step)

    def _write_state(self, step: int, snap, meta: Dict) -> None:
        t0 = time.perf_counter()
        proc, nproc = _multiprocess()
        try:
            if nproc > 1:
                final = self._write_state_multiprocess(step, snap, meta,
                                                       proc, nproc)
            else:
                tmp = layout.begin_step(self.directory, step)
                try:
                    self._write_shards(tmp, step, snap, meta, 0, 1)
                    layout.commit_step(self.directory, step, tmp)
                except BaseException:
                    layout.abort_step(tmp)
                    raise
        except BaseException:
            self.stats.add(save_failures=1)
            raise
        dt = max(time.perf_counter() - t0, 1e-9)
        # runs on the writer thread: its own lane in the dumped trace,
        # visibly overlapping the train-thread dispatch spans
        _trace.complete("ckpt:write_commit", t0, dt, cat="ckpt", step=step)
        nbytes = self._dir_bytes(step)
        self.stats.add(saves_committed=1, last_step=step,
                       save_s=dt, last_save_s=dt, bytes=nbytes,
                       last_bytes=nbytes, last_bytes_per_s=nbytes / dt)
        if proc == 0:
            layout.apply_retention(self.directory, self.keep_last_n,
                                   self.keep_every_k)

    def _write_shards(self, tmp: str, step: int, snap, meta: Dict,
                      proc: int, nproc: int) -> int:
        """Write this process's shard files + index (+ meta on rank 0)
        into ``tmp``; returns bytes written."""
        leaves, spec = flatten_state(snap)
        entries: Dict[str, Dict] = {}
        nbytes = 0
        for leaf_id, arr in leaves.items():
            entry = write_leaf(tmp, leaf_id, arr, process_index=proc)
            nbytes += sum(s.get("bytes", 0) for s in entry["shards"])
            entries[leaf_id] = entry
        index = {"format": _FORMAT, "step": step, "process_count": nproc,
                 "spec": spec, "leaves": entries}
        if nproc > 1:
            _write_json(os.path.join(tmp, "index.p%d.json" % proc), index)
        else:
            _write_json(os.path.join(tmp, layout.INDEX_FILE), index)
            _write_json(os.path.join(tmp, layout.META_FILE), meta)
        return nbytes

    def _write_state_multiprocess(self, step: int, snap, meta: Dict,
                                  proc: int, nproc: int) -> str:
        """Multi-process protocol on a shared filesystem: every process
        writes its own shards into ONE deterministic tmp dir, rank 0
        merges the per-process indexes and runs the commit.  Barriers
        ride the coordination service (NOT device collectives — this
        runs on the writer thread, see :func:`_barrier`)."""
        tmp = os.path.join(self.directory,
                           layout.step_dir_name(step) + ".tmp-shared")
        if proc == 0:
            os.makedirs(self.directory, exist_ok=True)
            if os.path.exists(tmp):
                import shutil
                shutil.rmtree(tmp)
            os.makedirs(tmp)
        _barrier("ckpt-begin-%d" % step)
        self._write_shards(tmp, step, snap, meta, proc, nproc)
        _barrier("ckpt-shards-%d" % step)
        if proc == 0:
            per_proc = []
            spec = None
            for p in range(nproc):
                with open(os.path.join(tmp, "index.p%d.json" % p)) as f:
                    idx = json.load(f)
                spec = idx["spec"]
                per_proc.append(idx["leaves"])
            merged = {"format": _FORMAT, "step": step,
                      "process_count": nproc, "spec": spec,
                      "leaves": merge_indexes(per_proc)}
            _write_json(os.path.join(tmp, layout.INDEX_FILE), merged)
            _write_json(os.path.join(tmp, layout.META_FILE), meta)
            final = layout.commit_step(self.directory, step, tmp)
        else:
            final = os.path.join(self.directory, layout.step_dir_name(step))
        _barrier("ckpt-commit-%d" % step)
        return final

    def _dir_bytes(self, step: int) -> int:
        d = os.path.join(self.directory, layout.step_dir_name(step))
        try:
            return sum(os.path.getsize(os.path.join(d, f))
                       for f in os.listdir(d))
        except OSError:
            return 0

    # -- restore ----------------------------------------------------------
    def restore(self, step: Optional[int] = None, like=None):
        """-> (tree, meta) for ``step`` (default: newest committed).

        ``like``: an optional template pytree with the same structure;
        each saved leaf is restored with the template leaf's sharding
        (shards device_put directly to their target devices) and cast to
        its dtype.  Without a template, leaves come back as host numpy
        arrays."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise MXNetError(
                    "no committed checkpoint under %r (torn/uncommitted "
                    "saves are skipped; see latest_step())" % self.directory)
        if not layout.is_committed(self.directory, step):
            raise MXNetError(
                "checkpoint step %d under %r is missing or uncommitted "
                "(committed steps: %s)"
                % (step, self.directory, self.all_steps()))
        t0 = time.perf_counter()
        d = os.path.join(self.directory, layout.step_dir_name(step))
        with open(os.path.join(d, layout.INDEX_FILE)) as f:
            index = json.load(f)
        meta: Dict = {}
        try:
            with open(os.path.join(d, layout.META_FILE)) as f:
                meta = json.load(f)
        except OSError:
            pass
        tree = self._read_tree(d, index["spec"], index["leaves"], like)
        dt = time.perf_counter() - t0
        self.stats.add(restores=1, restore_s=dt, last_restore_s=dt)
        _trace.complete("ckpt:restore", t0, dt, cat="ckpt", step=step)
        return tree, meta

    def _read_tree(self, d: str, spec, entries, like):
        import jax
        kind = spec["kind"]
        if kind == "none":
            return None
        if kind == "dict":
            tpl = like if isinstance(like, dict) else {}
            return {k: self._read_tree(d, v, entries, tpl.get(k))
                    for k, v in spec["items"].items()}
        if kind in ("tuple", "list"):
            tpl = like if isinstance(like, (tuple, list)) \
                and len(like) == len(spec["items"]) \
                else [None] * len(spec["items"])
            vals = [self._read_tree(d, v, entries, t)
                    for v, t in zip(spec["items"], tpl)]
            return tuple(vals) if kind == "tuple" else vals
        entry = entries[spec["id"]]
        sharding = getattr(like, "sharding", None) \
            if isinstance(like, jax.Array) else None
        dtype = getattr(like, "dtype", None) if like is not None else None
        return read_leaf(d, entry, sharding=sharding, target_dtype=dtype)

    # -- lifecycle --------------------------------------------------------
    def wait(self) -> None:
        """Block until every queued async save has committed; re-raises a
        writer failure."""
        if self._writer is not None:
            self._writer.wait()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._writer is not None:
            self._writer.close()
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        self._prev_handlers = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # -- preemption -------------------------------------------------------
    def install_preemption_handler(
            self, state_fn: Optional[Callable[[], Tuple[int, Any, Dict]]]
            = None, exit_after: bool = True,
            signals=(signal.SIGTERM,)) -> None:
        """Arm SIGTERM (by default) for preemption.

        Without ``state_fn`` the handler only sets ``self.preempted`` —
        a training loop polling it (Module.fit does, every batch) then
        snapshots at a safe step boundary and exits.  With ``state_fn``
        (-> ``(step, tree, meta)``) the handler itself runs a BLOCKING
        save and, when ``exit_after``, exits with the conventional
        128+signum code."""
        def _handler(signum, frame):
            self.preempted = True
            if state_fn is not None:
                step, tree, meta = state_fn()
                meta = dict(meta or {})
                meta["preempted"] = True
                self.save(step, tree, meta, blocking=True)
                if exit_after:
                    sys.exit(128 + signum)

        for sig in signals:
            try:
                self._prev_handlers.setdefault(sig, signal.getsignal(sig))
                signal.signal(sig, _handler)
            except ValueError as e:     # not the main thread
                raise MXNetError(
                    "preemption handler must be installed from the main "
                    "thread: %s" % e) from e
