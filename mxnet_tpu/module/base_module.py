"""BaseModule: the abstract intermediate-level interface + fit loop.

Reference: python/mxnet/module/base_module.py (fit at lines 273-393).
"""
from __future__ import annotations

import functools
import itertools
import logging
import time
from typing import List, Optional

import numpy as np

from ..base import MXNetError, get_env
from .. import metric as metric_mod
from .. import io as mx_io
from .. import trace as _trace
from ..model import BatchEndParam
from ..initializer import Uniform

__all__ = ["BaseModule"]


def _fire_callbacks(callbacks, param):
    """Invoke a single callback or a list of them (the reference's
    list-or-single dispatch, shared by fit and score)."""
    if callbacks is None:
        return
    for cb in (callbacks if isinstance(callbacks, list) else [callbacks]):
        cb(param)


def _hold_labels(labels):
    """Labels of a step whose metric runs after the iterator's next
    ``next()``: an iterator may refill its label buffers in place (the
    DataIter contract allows it), and that must not shift the deferred
    step's labels.  A device array is immutable, so it is held as it is,
    with no read back to the host; a host array is copied (labels are
    small; the outputs stay in flight)."""
    from ..ndarray import NDArray

    def hold(x):
        if x is None:
            return None
        if isinstance(x, NDArray):
            a = x._get()
            return NDArray(a.copy() if isinstance(a, np.ndarray) else a)
        return np.array(x, copy=True)
    return [hold(x) for x in (labels or [])]


def _inspects_outputs(callbacks):
    """Whether a batch-end callback declares ``inspects_outputs = True``:
    it reads the module's outputs or parameters and must run while its
    own batch's are current, so nothing may be dispatched before it."""
    cbs = callbacks if isinstance(callbacks, list) \
        else ([callbacks] if callbacks else [])
    return any(getattr(cb, "inspects_outputs", False) for cb in cbs)


_module_numbers = itertools.count(1)


def _recorded(name, *attrs):
    """Decorator for a module's set-up methods and ``fit``: one span
    ``name`` (cat ``train``) a call, from its entry to its return or
    raise, whoever calls.  Its ``module`` is the object's number
    (``BaseModule._trace_module``); each of ``attrs`` is read from the
    object when the call ends (``for_training``: what the module is
    bound for, also where the call found it bound and did nothing)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(self, *args, **kwargs):
            if not _trace.enabled():
                return fn(self, *args, **kwargs)
            t0 = time.perf_counter()
            try:
                return fn(self, *args, **kwargs)
            finally:
                _trace.complete(name, t0, time.perf_counter() - t0,
                                cat="train", module=self._trace_module,
                                **{a: getattr(self, a) for a in attrs})
        return wrapped
    return deco


class BaseModule:
    """Abstract module (reference base_module.py:41)."""

    def __init__(self, logger=logging):
        self.logger = logger
        # what the module's spans call it: unique in the process, fixed
        # for the object's life; a module made by another (a bucket's)
        # takes its maker's
        self._trace_module = next(_module_numbers)
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high level ---------------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def _outputs_in_flight(self):
        """Hook for the dispatch/metric overlap of fit() and score():
        the outputs of the step (or eval forward) just dispatched, as
        device arrays still in flight with their D2H copies started, or
        None to keep the synchronous per-batch order (the default —
        Module overrides on the one-process fused path, off the host)."""
        return None

    def _wire_eval_augment(self, eval_data):
        """A device-augment pipeline (uint8 wire, feed.AugmentSpec on
        the iterator) used for standalone score/predict must install
        its prologue on the fused step — or fail with the actionable
        message — BEFORE its batches reach the trace; fit() does the
        same for train_data."""
        spec = getattr(eval_data, "augment_spec", None)
        if spec is None:
            return
        applier = getattr(self, "apply_augment_spec", None)
        if applier is None or not applier(spec):
            raise MXNetError(
                "eval_data ships uint8 device-augment batches but this "
                "module has no fused step to run the on-device "
                "prologue; rebuild the pipeline with "
                "device_augment=False (or MXNET_FEED_DEVICE_AUGMENT=0)")

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, reset=True, epoch=0):
        """Evaluate (reference base_module.py score).

        When the module can start its device->host output copies
        asynchronously (Module's fused path), the metric update for
        batch N is deferred until after batch N+1's forward has been
        dispatched, so eval compute overlaps the transfer + host metric
        instead of blocking on every batch.  Metric totals and the
        per-batch callback order are unchanged."""
        assert self.binded and self.params_initialized
        self._wire_eval_augment(eval_data)
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()

        def fire_callback(nb, loc):
            # merge the loop's locals in so reference-style callbacks
            # reading param.locals['eval_batch'] keep working
            loc = dict(loc or {})
            loc.setdefault("self", self)
            loc.setdefault("eval_metric", eval_metric)
            _fire_callbacks(batch_end_callback,
                            BatchEndParam(epoch=epoch, nbatch=nb,
                                          eval_metric=eval_metric,
                                          locals=loc))

        pending = None   # (held labels, outputs in flight, nbatch, locals)

        def drain(p):
            labels, outs, nb, loc = p
            eval_metric.update(labels, outs)
            fire_callback(nb, loc)

        # a callback that reads module outputs (inspects_outputs=True,
        # the same contract fit() honors) must run while ITS batch's
        # outputs are still current — deferral would hand it the next
        # batch's forward
        defer_ok = not _inspects_outputs(batch_end_callback)

        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            outs = self._outputs_in_flight() if defer_ok else None
            if outs is None:
                # synchronous path (classic exec group, worker-local
                # multi-process eval): drain any deferred batch first so
                # callback order stays monotone
                if pending is not None:
                    drain(pending)
                    pending = None
                self.update_metric(eval_metric, eval_batch.label)
                fire_callback(nbatch, locals())
            else:
                if pending is not None:
                    drain(pending)
                # drop the 'pending' binding from the captured locals:
                # it still references the PREVIOUS deferred tuple, and
                # keeping it would chain every batch's outputs/inputs
                # alive until score() returns (O(batches) device memory)
                loc = dict(locals())
                loc.pop("pending", None)
                pending = (_hold_labels(eval_batch.label), outs, nbatch,
                           loc)
        if pending is not None:
            drain(pending)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        self._wire_eval_augment(eval_data)
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Predict (reference base_module.py predict)."""
        assert self.binded and self.params_initialized
        self._wire_eval_augment(eval_data)
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " \
                    "in mini-batches. Maybe bucketing is used?"
            from ..ndarray import concatenate
            output_list2 = [concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    @_recorded("fit:call")
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=None,
            eval_batch_end_callback=None, initializer=Uniform(0.01),
            arg_params=None, aux_params=None, allow_missing=False,
            force_rebind=False, force_init=False, begin_epoch=0,
            num_epoch=None, validation_metric=None, monitor=None,
            work_load_list=None, prefetch_to_device=False,
            checkpoint=None, checkpoint_every=None, resume=False,
            superstep=None, mesh=None, sharding=None, autotune=None):
        """Train (reference base_module.py:273-393).

        **When the metric and the callbacks of a step run.**  Where the
        step's outputs are device arrays still in flight (Module's fused
        step in one process on an accelerator; the CPU backend's arrays
        are the host's own memory), the per-batch loop is pipelined by
        one step: step N+1 is enqueued first, then ``eval_metric`` is
        updated with step N's outputs and labels (held meanwhile) and
        the ``batch_end_callback``s fire for step N, so the host's share
        runs beside the device instead of after it.  Programs,
        arithmetic, metric totals, callback order and arguments
        (``nbatch``, ``eval_metric``, ``locals['data_batch']``) are
        those of the serial loop.  What differs: at step N's callback
        the module's parameters and ``get_outputs()`` are already step
        N+1's, and a change the callback makes to the optimizer reaches
        step N+2.  A callback that reads outputs or parameters, or
        changes training state for the very next step, declares
        ``inspects_outputs = True``, and the loop stays serial, as it
        does for the classic executor path (``BucketingModule``, fusion
        off), multi-process training, a ``monitor`` and ``mx.cpu()``.
        A step that a checkpoint or a preemption follows, and the
        epoch's last, are finished before anything else is enqueued: a
        checkpoint at global step S holds the parameters after S
        updates and the metric through S.  If the next pull or dispatch
        raises, the outstanding step's callbacks fire before the
        exception leaves.

        ``mesh``/``sharding``: first-class multichip training.  ``mesh``
        is a named device mesh (``parallel.make_mesh([("dp", 4),
        ("tp", 2)])``, the axes list itself, or ``"dp=4,tp=2"``); the
        batch shards over the ``dp`` axis, ``sharding`` maps param
        names to PartitionSpecs (``{"fc1_weight": P(None, "tp")}``, or
        ``"None,tp"`` strings / ``__sharding__`` symbol attributes)
        applied as GSPMD constraints inside the fused step — XLA
        inserts the collectives.  Defaults to the ``MXNET_MESH`` env
        knob.  Composes unchanged with ``superstep``,
        ``prefetch_to_device``, on-device augmentation and
        ``checkpoint`` (shards land on the live mesh at restore).  See
        docs/multichip.md.

        ``prefetch_to_device``: wrap ``train_data`` with the feed
        subsystem's device prefetcher (mxnet_tpu.feed) so batch N+1's
        H2D transfer is issued while batch N trains; pass an int to set
        the lookahead depth (True = 2).

        ``superstep``: run K training batches per XLA dispatch (the
        fused step body under ``lax.scan``), with metric accumulation on
        device and ONE scalar drain per K steps — the dispatch-bound
        regime's biggest lever.  Defaults to the ``MXNET_SUPERSTEP`` env
        var (1 = off).  Semantics are preserved exactly (superstep K is
        bitwise-identical to K sequential fused steps); anything needing
        per-step host visibility — a monitor, a metric without a device
        form, ``checkpoint_every`` not a multiple of K, a batch-end
        callback marked ``inspects_outputs=True`` — falls back to K=1
        automatically (logged), as does a partial final megabatch.
        Batch-end callbacks fire once per superstep, with ``nbatch``
        pointing at the last batch of the K and ``param.locals``
        carrying the megabatch ``group`` rather than a per-batch
        ``data_batch``; a callback that needs per-batch locals or
        outputs should declare ``inspects_outputs = True``.

        ``autotune``: measurement-driven knob tuning
        (``mxnet_tpu.autotune``).  When True (or ``MXNET_AUTOTUNE=1``
        with ``autotune=None``) and neither ``superstep=`` nor
        ``MXNET_SUPERSTEP`` chose a K, the superstep is picked by
        dispatching candidate programs on a COPY of the train state —
        training never advances during measurement — with cost read
        from trace spans, and the winner persisted per (model, shapes,
        optimizer, topology) fingerprint under ``MXNET_AUTOTUNE_DIR``;
        the next fit of the same model loads it without measuring.
        Candidates that a superstep blocker rules out are never
        measured.  ``mx.profiler.autotune_report()`` shows the decision.

        ``checkpoint``: a ``mx.checkpoint.CheckpointManager`` (or a
        directory path, wrapped in one with defaults) for crash-safe
        fault tolerance: async saves every ``checkpoint_every`` batches
        (overrides the manager's ``save_every_steps``) and at every
        epoch end, full train state (params, optimizer slots, lr
        schedule, RNG, batch cursor).  ``resume=True`` restores the
        newest committed step and continues from the exact next batch —
        natively when ``train_data`` implements the feed subsystem's
        ``state()``/``restore()`` cursor, otherwise by skipping the
        already-trained batches.  If SIGTERM arrives (the manager's
        ``install_preemption_handler``), the loop snapshots at the next
        batch boundary and returns."""
        import os
        assert num_epoch is not None, "please specify number of epochs"
        if optimizer_params is None:
            optimizer_params = (("learning_rate", 0.01),)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if mesh is not None or sharding is not None:
            setter = getattr(self, "set_mesh", None)
            if setter is None:
                raise MXNetError(
                    "fit(mesh=...) needs a module with multichip support "
                    "(Module); %s has no set_mesh" % type(self).__name__)
            setter(mesh, sharding)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        # compact-feed pipelines (record_pipeline(device_augment=True))
        # ship uint8 HWC batches and carry the augmentation spec the
        # fused step must trace in (cast/crop/flip/normalize on device)
        aug_spec = getattr(train_data, "augment_spec", None)
        eval_spec = getattr(eval_data, "augment_spec", None) \
            if eval_data is not None else None
        if aug_spec is not None and eval_spec is not None and \
                aug_spec.signature() != eval_spec.signature():
            # one fused program family carries ONE prologue; two specs
            # would silently augment eval with the train parameters
            raise MXNetError(
                "train_data and eval_data carry different device-augment "
                "specs (%r vs %r); build both pipelines with the same "
                "augmentation parameters" % (aug_spec, eval_spec))
        aug_spec = aug_spec or eval_spec
        applier = getattr(self, "apply_augment_spec", None)
        if aug_spec is not None:
            if applier is None or not applier(aug_spec):
                raise MXNetError(
                    "the training/eval feed ships uint8 device-augment "
                    "batches but this module has no fused train step to "
                    "run the on-device prologue; rebuild the pipeline "
                    "with device_augment=False (or MXNET_FEED_DEVICE_"
                    "AUGMENT=0) for the host-augmented f32 path")
        elif callable(applier):
            # clear a spec left by a PREVIOUS fit on this module: a
            # stale prologue would block the classic-path fallback and
            # key the compiled step differently for this f32 feed
            applier(None)

        ckpt_mgr = None
        if checkpoint is None and resume:
            raise MXNetError(
                "fit(resume=True) needs checkpoint=<manager or directory>; "
                "without a store to restore from, training would silently "
                "restart from scratch")
        if checkpoint is not None:
            from ..checkpoint import CheckpointManager, save_module, \
                restore_module
            ckpt_mgr = checkpoint if isinstance(checkpoint, CheckpointManager) \
                else CheckpointManager(str(checkpoint))
            if checkpoint_every is not None:
                ckpt_mgr.save_every_steps = int(checkpoint_every)
            # a handled preemption from a PREVIOUS fit must not make this
            # run save-and-return after one batch; re-entering fit is the
            # caller's decision to train again
            ckpt_mgr.preempted = False

        # a manager fit constructed from a bare path is fit's to close:
        # its async-writer thread must not outlive this call (the tier-1
        # leak guard flags exactly that); a caller-supplied manager stays
        # the caller's resource
        _owns_ckpt_mgr = ckpt_mgr is not None and \
            not isinstance(checkpoint, CheckpointManager)
        try:
            if validation_metric is None:
                validation_metric = eval_metric
            if not isinstance(eval_metric, metric_mod.EvalMetric):
                eval_metric = metric_mod.create(eval_metric)

            # superstep resolution: K from the argument, the env knob,
            # or (neither set + autotune on) the measured winner; then
            # every semantic blocker gets a logged fallback to K=1
            k_env = get_env("MXNET_SUPERSTEP", None, int)
            if superstep is not None:
                k_super = int(superstep)
            elif k_env is not None:
                k_super = k_env
            else:
                k_super = 1
                from ..autotune import mode as _autotune_mode
                amode = _autotune_mode(autotune)
                if amode is not None and \
                        callable(getattr(self, "superstep_train", None)) \
                        and getattr(self, "_fused", None) is not None:

                    def _viable(k):
                        return self._superstep_blockers(
                            eval_metric, k, monitor=monitor,
                            batch_end_callback=batch_end_callback,
                            checkpoint_every=(ckpt_mgr.save_every_steps
                                              if ckpt_mgr is not None
                                              else None))
                    if amode == "joint" and \
                            callable(getattr(self, "apply_joint_config",
                                             None)):
                        from ..autotune import tune_fit_joint
                        jcfg = tune_fit_joint(self, viable=_viable)
                        k_super = int(jcfg["superstep"])
                        self.apply_joint_config(jcfg)
                        self.logger.info(
                            "autotune(joint): superstep K=%d unroll=%d "
                            "remat=%s", k_super, jcfg["unroll"],
                            jcfg["remat"])
                    else:
                        from ..autotune import tune_superstep
                        k_super = tune_superstep(self, viable=_viable)
                        self.logger.info("autotune: superstep K=%d",
                                         k_super)
            k_super = max(1, k_super)
            use_super = k_super > 1 and callable(
                getattr(self, "superstep_train", None))
            if k_super > 1 and not use_super:
                self.logger.info("superstep disabled (K=%d -> 1): module has "
                                 "no fused superstep support", k_super)
            if use_super:
                blocker = self._superstep_blockers(
                    eval_metric, k_super, monitor=monitor,
                    batch_end_callback=batch_end_callback,
                    checkpoint_every=(ckpt_mgr.save_every_steps
                                      if ckpt_mgr is not None else None))
                if blocker is not None:
                    self.logger.info("superstep disabled (K=%d -> 1): %s",
                                     k_super, blocker)
                    use_super = False

            # the K=1 loop runs a step's metric and callbacks one step
            # late only where nothing needs them between two dispatches
            pipeline_ok = monitor is None and \
                not _inspects_outputs(batch_end_callback)

            if prefetch_to_device and hasattr(self, "prefetch_to_device"):
                # wrap AFTER init_optimizer so the fused step's batch sharding
                # exists and staged batches land directly in its input layout;
                # in superstep mode the prefetcher assembles whole megabatches
                # (stacked K axis) under the running superstep
                depth = 2 if prefetch_to_device is True \
                    else max(1, int(prefetch_to_device))
                train_data = self.prefetch_to_device(
                    train_data, depth=depth,
                    megabatch=k_super if use_super else 1)

            # each fit journals independently: a later fit restarting from
            # step 1 in the same process must not be muted by the previous
            # run's high-water step
            _trace.reset_journal()
            global_step = 0
            start_epoch, start_batch = begin_epoch, 0
            if ckpt_mgr is not None and resume:
                meta = restore_module(ckpt_mgr, self)
                if meta is not None:
                    global_step = int(meta.get("global_step", 0))
                    start_epoch = int(meta.get("epoch", begin_epoch))
                    start_batch = int(meta.get("nbatch", 0))
                    feed_state = meta.get("feed")
                    if feed_state is not None and \
                            callable(getattr(train_data, "restore", None)):
                        train_data.restore(feed_state)
                    elif start_batch:
                        if callable(getattr(train_data, "restore", None)):
                            # a cursor-less checkpoint resumed into a feed
                            # wrapper (e.g. prefetch added after the save):
                            # its restore() skips UNDERLYING batches exactly,
                            # where next() would pop whole megabatches
                            train_data.restore({"batch": start_batch})
                        else:
                            # generic DataIter: fast-forward by discarding
                            # the already-trained batches (counting the
                            # batches a megabatch carries)
                            skipped = 0
                            while skipped < start_batch:
                                try:
                                    b = train_data.next()
                                except StopIteration:
                                    break
                                skipped += getattr(b, "megabatch", 1)
                    self.logger.info(
                        "resumed from checkpoint step %d: epoch %d, batch %d",
                        global_step, start_epoch, start_batch)

            last_saved_step = [-1]

            def ckpt_save(epoch_, nbatch_, blocking=False):
                meta = {"global_step": global_step, "epoch": epoch_,
                        "nbatch": nbatch_}
                if callable(getattr(train_data, "state", None)):
                    meta["feed"] = train_data.state()
                save_module(ckpt_mgr, self, global_step, meta=meta,
                            blocking=blocking)
                last_saved_step[0] = global_step

            for epoch in range(start_epoch, num_epoch):
                tic = time.perf_counter()
                eval_metric.reset()
                nbatch = start_batch if epoch == start_epoch else 0
                preempted = False

                def fire_batch_end(nb, loc=None):
                    # merge the call site's locals: per-batch sites expose
                    # 'data_batch' like the reference loop did; the
                    # superstep site fires once per K and exposes the whole
                    # 'group' instead (a callback needing per-batch locals
                    # should declare inspects_outputs=True, which forces
                    # K=1)
                    loc = dict(loc or {})
                    loc.setdefault("self", self)
                    loc.setdefault("epoch", epoch)
                    loc.setdefault("nbatch", nb)
                    loc.setdefault("eval_metric", eval_metric)
                    _fire_callbacks(batch_end_callback,
                                    BatchEndParam(epoch=epoch, nbatch=nb,
                                                  eval_metric=eval_metric,
                                                  locals=loc))

                def advance(count, allow_ckpt=True, ckpt_from=None):
                    """Bookkeeping after ``count`` trained batches: counters
                    + checkpoint cadence.  True => leave fit (preemption).
                    ``allow_ckpt=False`` suppresses saves at an unsafe point
                    (mid-way through an unstacked megabatch, where the feed
                    cursor already counted the whole group); ``ckpt_from``
                    re-bases the save-crossing check to the group's first
                    step so a suppressed crossing still saves at its end."""
                    nonlocal nbatch, global_step, preempted
                    prev_step = global_step if ckpt_from is None else ckpt_from
                    nbatch += count
                    global_step += count
                    # run-metrics journal (MXNET_TRACE_JOURNAL): one unified-
                    # report JSONL line every N global steps; a no-op (one
                    # env lookup) when the knob is unset
                    _trace.maybe_journal_step(global_step, epoch=epoch,
                                              nbatch=nbatch)
                    if not allow_ckpt:
                        return False
                    if ckpt_mgr is not None:
                        if ckpt_mgr.preempted:
                            # SIGTERM: snapshot at this safe batch boundary,
                            # flush, and leave the loop (snapshot-then-exit)
                            ckpt_save(epoch, nbatch, blocking=True)
                            ckpt_mgr.wait()
                            self.logger.info(
                                "preempted: checkpoint committed at step %d "
                                "(epoch %d, batch %d); exiting fit",
                                global_step, epoch, nbatch)
                            preempted = True
                            return True
                        # save when (prev_step, global_step] crosses a
                        # save_every multiple — for count=1 that is exactly
                        # should_save(); for a K-step jump it keeps the
                        # cadence alive even after a partial tail or a
                        # resume leaves global_step off the K-aligned
                        # residue class (a bare `step % every == 0` would
                        # then never fire again)
                        every = ckpt_mgr.save_every_steps
                        if every and global_step // every > prev_step // every:
                            ckpt_save(epoch, nbatch)
                    return False

                def step_span(count, ahead=0):
                    """``fit:step``: one iteration of the loop, from
                    before its pull to after advance().  Its children
                    (fit:feed_next, fit:forward_backward, fit:update,
                    fit:update_metric, fit:batch_end) lie inside it and
                    do not overlap, so the step minus its children is
                    the loop's own bookkeeping.  ``ahead``: steps
                    dispatched and not yet counted by advance()."""
                    return _trace.span("fit:step", cat="train",
                                       step=global_step + ahead, epoch=epoch,
                                       nbatch=nbatch + ahead, count=count)

                def pull(data_iter):
                    """next(data_iter) under ``fit:feed_next``; None at
                    the epoch's end (that pull records end=True)."""
                    with _trace.span("fit:feed_next", cat="train") as sp:
                        try:
                            return next(data_iter)
                        except StopIteration:
                            sp.args = {"end": True}
                            return None

                def dispatch(data_batch):
                    """Hand one step to the device: forward, backward
                    and update."""
                    if monitor is not None:
                        monitor.tic()
                    with _trace.span("fit:forward_backward", cat="train"):
                        self.forward_backward(data_batch)
                    with _trace.span("fit:update", cat="train"):
                        self.update()

                def finish(data_batch, held=None, lag=0, allow_ckpt=True,
                           ckpt_from=None):
                    """The host's share of the step dispatch() enqueued:
                    its metric, its callbacks, advance().  ``held`` is
                    (labels, outputs) of a step that is no longer the
                    module's latest; ``lag`` the number of steps
                    dispatched after this one before its metric ran."""
                    with _trace.span("fit:update_metric", cat="train",
                                     for_step=global_step, lag=lag):
                        if held is None:
                            self.update_metric(eval_metric, data_batch.label)
                        else:
                            eval_metric.update(*held)
                    self._note_train_outputs(held[1] if held else None)
                    if monitor is not None:
                        monitor.toc_print()
                    with _trace.span("fit:batch_end", cat="train",
                                     for_step=global_step, lag=lag):
                        fire_batch_end(nbatch, {"data_batch": data_batch})
                    return advance(1, allow_ckpt=allow_ckpt,
                                   ckpt_from=ckpt_from)

                def train_one(data_batch, allow_ckpt=True, ckpt_from=None):
                    """The reference per-batch body, serial."""
                    dispatch(data_batch)
                    return finish(data_batch, allow_ckpt=allow_ckpt,
                                  ckpt_from=ckpt_from)

                def state_must_be_exact():
                    """Whether the step just dispatched ends at a point
                    where the module must hold exactly that many steps:
                    a checkpoint's cadence, or a preemption to answer."""
                    if ckpt_mgr is None:
                        return False
                    every = ckpt_mgr.save_every_steps
                    return ckpt_mgr.preempted or bool(
                        every and (global_step + 1) % every == 0)

                data_iter = iter(train_data)
                if use_super:
                    # pull K batches (or one prefetch-assembled megabatch)
                    # per iteration and run them as ONE dispatch; a partial
                    # tail or a mid-training fallback (hparams mutated,
                    # fusion disabled) trains per-batch instead.  One
                    # fit:step spans the whole iteration; its count is the
                    # number of batches it trained.
                    while not preempted:
                        with step_span(k_super) as step:
                            mega, pulled = None, []
                            while len(pulled) < k_super:
                                b = pull(data_iter)
                                if b is None:
                                    break
                                if getattr(b, "megabatch", 0) > 1:
                                    mega = b
                                    break
                                pulled.append(b)
                            if mega is None and not pulled:
                                step.cancel()
                                break
                            step.args["count"] = len(pulled) + (
                                mega.megabatch if mega is not None else 0)
                            if pulled and (mega is not None
                                           or len(pulled) < k_super):
                                # plain batches that cannot form a full K —
                                # the epoch tail, or stragglers ahead of an
                                # arriving megabatch: train them per-batch,
                                # never drop.  They were all pulled from the
                                # iterator up front, so a feed cursor already
                                # counts them — defer saves to the group's
                                # end like the unstacked-fallback below.
                                start_step = global_step
                                for i, b in enumerate(pulled):
                                    last = i == len(pulled) - 1
                                    if train_one(b, allow_ckpt=last,
                                                 ckpt_from=(start_step if last
                                                            else None)):
                                        return
                                pulled = []
                            group = mega if mega is not None else pulled
                            if not group:
                                continue
                            count = mega.megabatch if mega is not None \
                                else len(pulled)
                            if self.superstep_train(group, eval_metric):
                                with _trace.span("fit:batch_end",
                                                 cat="train"):
                                    fire_batch_end(nbatch + count - 1,
                                                   locals())
                                if advance(count):
                                    return
                            else:
                                # superstep refused (fused path gone /
                                # hparams changed): K=1 fallback.  For an
                                # unstacked megabatch the feed cursor already
                                # counted ALL K batches, so a save fired
                                # mid-group would resume past never-trained
                                # data — defer preemption/save checks to the
                                # group's end (an exact boundary again),
                                # re-basing the crossing test so no
                                # save_every multiple is skipped.
                                singles = mega.unstack() if mega is not None \
                                    else pulled
                                start_step = global_step
                                for i, b in enumerate(singles):
                                    last = i == len(singles) - 1
                                    if train_one(b, allow_ckpt=last,
                                                 ckpt_from=(start_step if last
                                                            else None)):
                                        return
                else:
                    # the K=1 loop, software-pipelined by one step where
                    # the step's outputs are device arrays in flight:
                    # step N's metric and callbacks run after step N+1
                    # is enqueued, on N's outputs and labels, held
                    # meanwhile.  Without such outputs (classic executor,
                    # multi-process, the CPU backend, monitor, an
                    # inspects_outputs callback) nothing is held and the
                    # loop is serial.
                    outstanding = None    # (data_batch, (labels, outputs))
                    deferred = drained_early = 0

                    def drain(lag=0):
                        """finish() of the outstanding step.  Never a
                        checkpoint: the next pull or dispatch has been
                        made, so the feed's cursor and the parameters
                        are a step past the one finished here."""
                        nonlocal outstanding, deferred, drained_early
                        prev, outstanding = outstanding, None
                        if lag:
                            deferred += 1
                        else:
                            drained_early += 1
                        finish(*prev, lag=lag, allow_ckpt=False)

                    try:
                        while True:
                            with step_span(1, ahead=int(
                                    outstanding is not None)) as step:
                                data_batch = pull(data_iter)
                                if data_batch is None:
                                    step.cancel()
                                    break
                                bucket_key = getattr(data_batch,
                                                     "bucket_key", None)
                                if bucket_key is not None:
                                    step.args["bucket_key"] = bucket_key
                                dispatch(data_batch)
                                outs = self._outputs_in_flight() \
                                    if pipeline_ok else None
                                if outstanding is not None:
                                    drain(lag=1)
                                if outs is not None and \
                                        not state_must_be_exact():
                                    outstanding = (data_batch, (_hold_labels(
                                        data_batch.label), outs))
                                    continue
                                drained_early += int(outs is not None)
                                if finish(data_batch):
                                    return
                        if outstanding is not None:
                            # the epoch's last step; a preemption is
                            # answered at the next epoch's first step
                            drain()
                    except BaseException:
                        # the pull or the dispatch failed: the step before
                        # it was trained, so its callbacks still fire
                        if outstanding is not None:
                            drain()
                        raise
                    finally:
                        _trace.counter("fit:deferred", cat="train",
                                       steps=deferred,
                                       drained_early=drained_early)
                if preempted:
                    return

                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                toc = time.perf_counter()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))
                _trace.complete("fit:epoch", tic, toc - tic, cat="train",
                                epoch=epoch, batches=nbatch)

                if epoch_end_callback is not None:
                    arg_params_, aux_params_ = self.get_params()
                    for callback in (epoch_end_callback
                                     if isinstance(epoch_end_callback, list)
                                     else [epoch_end_callback]):
                        callback(epoch, self.symbol, arg_params_, aux_params_)

                if eval_data:
                    res = self.score(eval_data, validation_metric,
                                     batch_end_callback=eval_batch_end_callback,
                                     epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)

                train_data.reset()
                if ckpt_mgr is not None and last_saved_step[0] != global_step:
                    # epoch boundary: cursor points at the NEXT epoch's start.
                    # Skipped when the epoch's last batch already saved this
                    # global_step (an end-of-epoch cursor and a full-epoch
                    # cursor resume identically): re-committing the same step
                    # would rewrite the whole state AND briefly uncommit the
                    # newest checkpoint — a crash there loses it.
                    ckpt_save(epoch + 1, 0)
            if ckpt_mgr is not None:
                ckpt_mgr.wait()
        finally:
            if _owns_ckpt_mgr:
                ckpt_mgr.close()

    # -- symbol -------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    # -- abstract interface --------------------------------------------------
    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        """Save params (reference base_module.py:480-513)."""
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        from ..ndarray import save as nd_save
        nd_save(fname, save_dict)

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=True):
        """Checkpoint through the mxnet_tpu.checkpoint subsystem while
        keeping the legacy files as a readable fallback: writes the
        classic ``prefix-symbol.json`` + ``prefix-%04d.params`` pair
        (atomically — a crash can no longer tear them) AND, with
        ``save_optimizer_states``, the FULL train state (optimizer
        slots, lr schedule position, RNG) as a committed step under
        ``prefix-ckpt/``.  ``model.load_checkpoint`` reads the legacy
        pair; ``mx.checkpoint.restore_module`` (or
        ``fit(checkpoint=prefix + "-ckpt", resume=True)``) resumes with
        nothing reset."""
        from ..model import save_checkpoint as legacy_save
        arg_params, aux_params = self.get_params()
        legacy_save(prefix, epoch, self.symbol, arg_params, aux_params)
        if save_optimizer_states and self.optimizer_initialized:
            from ..checkpoint import CheckpointManager, save_module
            with CheckpointManager(prefix + "-ckpt", keep_last_n=None,
                                   async_save=False) as mgr:
                save_module(mgr, self, epoch,
                            meta={"epoch": epoch, "nbatch": 0},
                            blocking=True)

    def load_params(self, fname):
        from ..ndarray import load as nd_load
        save_dict = nd_load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def _note_train_outputs(self, outputs=None):
        """fit()'s hook after a training step's metric update (the
        step's outputs have been waited for), outside
        ``fit:update_metric``: ``outputs`` are that step's (held) outputs,
        None where they are still the module's latest.  A module that
        derives counters from its outputs overrides it, under a span of
        its own."""

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=None, force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()
