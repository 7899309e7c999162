"""Module: intermediate-level API over one symbol.

Reference: python/mxnet/module/module.py (Module at line 18; init_optimizer
with the same _create_kvstore logic at 271-335, update dispatch at 377-394).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence

from .. import trace as _trace
from ..base import MXNetError, get_env
from ..context import Context, cpu, current_context
from ..initializer import Uniform, create as _create_initializer
from ..ndarray import NDArray, _lives_on_host, zeros as nd_zeros
from .. import optimizer as opt_mod
from ..model import (_create_kvstore, _initialize_kvstore, _param_idx2name,
                     _update_params, _update_params_on_kvstore)
from .base_module import BaseModule, _recorded
from .executor_group import DataParallelExecutorGroup
from .fused import FusedTrainStep

__all__ = ["Module"]


class Module(BaseModule):
    """Module over a Symbol (reference module.py:18).

    The weights have one home at a time.  Before the fused train state
    exists it is ``_arg_params`` / ``_aux_params`` (and the executor
    group that is filled from them).  While a fused state exists it is
    that state and nothing else on any device: ``_fused_ensure_state``
    lets go of the dicts' arrays as the state takes its copies, the
    dicts keep the shapes under ``_params_dirty``, and whatever
    ``get_params`` hands out then is a host array (``cpu`` context)
    that the caller owns."""

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None):
        super().__init__(logger=logger)
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names else []
        label_names = list(label_names) if label_names else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names) if fixed_param_names else []
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

        # named device mesh + per-param GSPMD sharding specs (first-class
        # multichip: set_mesh / bind(mesh=...) / fit(mesh=...)); consumed
        # by _setup_fused, which hands them to FusedTrainStep
        self._mesh = None
        self._sharding_specs = None
        # fused fast path (see fused.py): engaged by init_optimizer when
        # the configuration allows one donated XLA program per batch
        self._fused = None
        # superstep (K fused steps per dispatch): compiled programs keyed
        # by (K, unroll, metric signature), plus the profiler counters
        self._superstep_progs = {}
        self._superstep_unroll = 1
        self._superstep_stats = None
        self._fused_state = None
        self._fused_pending = None
        self._fused_outputs = None
        # post-step state stashed by an early commit (get_outputs between
        # forward and update); update() installs it without re-running
        self._fused_next = None
        # multi-process eval ran worker-locally through the exec group:
        # outputs live there, not in _fused_outputs
        self._fused_eval_local = False
        self._fused_t = 0
        self._fused_key = None
        self._monitor_installed = False
        self._borrowed_optimizer = False
        # classic-path backward has run but update() hasn't: the exec
        # group's grad arrays hold live gradients (guards bucketing
        # prepare(), whose shared-exec warmup would clobber them)
        self._grads_pending = False
        # set when this module's exec group is lent to a sibling (bucketing):
        # the shared arrays are then the single source of truth, so the
        # private donated fused state must never engage
        self._lent_exec_group = False

    # -- properties ----------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        """Static shapes from symbol inference (reference module.py
        output_shapes) — must work before any forward has run
        (SequentialModule wires the next module's input from these at
        bind time)."""
        assert self.binded
        shapes = {name: shape for name, shape in self._data_shapes}
        for name, shape in (self._label_shapes or []):
            shapes[name] = shape
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._output_names, [tuple(s) for s in out_shapes]))

    # -- params --------------------------------------------------------------
    def get_params(self):
        """``(arg_params, aux_params)`` at their current values.  Once
        training has begun they are read back from wherever the weights
        live (the fused state, or the executor group) as ``cpu``-context
        arrays that no later step writes or deletes; before that they
        are the arrays ``init_params`` filled."""
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    @_recorded("module:init_params")
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if self.params_initialized and self._params_dirty:
            # the arrays written below are the current weights': a name
            # this call leaves out keeps what training made of it
            self._sync_params_from_devices()

        if self._arg_params is None:
            param_arrays = [nd_zeros(x[0].shape, dtype=x[0].dtype)
                            for x in self._exec_group.param_arrays]
            self._arg_params = {name: arr for name, arr in
                                zip(self._param_names, param_arrays)}
        if self._aux_params is None:
            aux_arrays = [nd_zeros(x[0].shape, dtype=x[0].dtype)
                          for x in self._exec_group.aux_arrays]
            self._aux_params = {name: arr for name, arr in
                                zip(self._aux_names, aux_arrays)}

        own = {name: attrs["__init__"] for name, attrs
               in self._symbol.attr_dict().items() if "__init__" in attrs}

        def fresh(name, arr):
            # a variable's own initializer (``Variable(init=...)``) before
            # the one this call was handed
            if name in own:
                _create_initializer(own[name])(name, arr)
            elif initializer is not None:
                initializer(name, arr)

        def _impl(name, arr, cache):
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError("%s is not presented" % name)
                    fresh(name, arr)
            else:
                fresh(name, arr)

        for name, arr in self._arg_params.items():
            _impl(name, arr, arg_params)
        for name, arr in self._aux_params.items():
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        # host params changed: any fused device state is stale
        self._fused_state = None
        if not self._restore_exec_arrays():
            self._exec_group.set_params(self._arg_params, self._aux_params)
        self._fused_pending = None
        self._fused_outputs = None
        self._discard_speculation()

    def _restore_exec_arrays(self) -> bool:
        """Bind the executor group again where ``_fused_ensure_state``
        released it, and fill it from ``_arg_params`` / ``_aux_params``;
        True where it did."""
        eg = self._exec_group
        if eg is None or eg.execs:
            return False
        eg.bind_exec(eg.data_shapes, eg.label_shapes)
        eg.set_params(self._arg_params, self._aux_params)
        return True

    def _drop_fused_state(self):
        """The fused state goes; ``_arg_params`` / ``_aux_params`` are
        current.  Where that state held the only device copy of the
        weights, the executor group holds them again."""
        self._fused_state = None
        self._restore_exec_arrays()

    def _sync_params_from_devices(self):
        if self._fused is not None and self._fused_state is not None:
            # the fused state, not the exec group, holds the live params
            self._fused.read_params(self._fused_state, self._arg_params,
                                    self._aux_params)
            if self._exec_group.execs:
                self._exec_group.set_params(self._arg_params,
                                            self._aux_params)
        else:
            self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # -- mesh ----------------------------------------------------------------
    def set_mesh(self, mesh, sharding=None):
        """Install a named device mesh + per-param GSPMD sharding specs
        for multichip training (the public multichip surface, also
        reachable as ``bind(mesh=...)`` / ``fit(mesh=...)``).

        ``mesh``: a ``jax.sharding.Mesh`` (``parallel.make_mesh``), an
        axes list like ``[("dp", 4), ("tp", 2)]``, the ``"dp=4,tp=2"``
        string form, or None to clear.  The batch axis shards over
        ``"dp"``; ``sharding`` maps param names to PartitionSpecs (or
        ``"None,tp"``-style strings) applied as constraints on the
        symbol graph — ``__sharding__`` variable attributes compose,
        with this map winning.

        Call before ``init_optimizer`` (fit does); afterwards the fused
        step is rebuilt on the new mesh with the FULL train state
        carried across — params, optimizer slots (momentum, Adam
        moments), step counter and RNG all land re-sharded on the new
        mesh (the same capture/restore machinery a cross-mesh
        checkpoint resume uses)."""
        from jax.sharding import Mesh
        from ..parallel import make_mesh
        if mesh is not None and not isinstance(mesh, Mesh):
            mesh = make_mesh(mesh)
        if isinstance(sharding, str) and sharding.strip() == "auto":
            # automatic GSPMD sharding search: resolved to a concrete
            # per-param spec map at _setup_fused time (store hit or
            # measured search — mxnet_tpu.dist.shardsearch)
            if mesh is None:
                raise MXNetError(
                    "sharding='auto' needs a mesh to search over; pass "
                    "mesh= alongside it")
            specs = "auto"
        else:
            specs = dict(sharding) if sharding else None
        if mesh == self._mesh and specs == self._sharding_specs:
            return       # no-op set keeps the warm compiled programs
        carried = None
        if self.optimizer_initialized and self._fused is not None and \
                self._fused_state is not None:
            # mid-training re-mesh: dropping the fused state would
            # silently zero every optimizer slot; capture the whole
            # train state and restore it into the new mesh's layout
            from ..checkpoint.module_state import (capture_train_state,
                                                   restore_train_state)
            carried = capture_train_state(self)
        self._mesh = mesh
        self._sharding_specs = specs
        if self.optimizer_initialized:
            self._setup_fused()
            if carried is not None and self._fused is not None:
                restore_train_state(self, *carried)

    # -- bind ----------------------------------------------------------------
    @_recorded("module:bind", "for_training")
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write", no_slice_names=None, mesh=None,
             sharding=None):
        """``no_slice_names``: input/label names that must NOT be batch-
        sliced across devices even when their leading dim equals the batch
        size (e.g. rcnn rois with num_rois == batch_size); they are
        replicated whole instead of silently split.

        ``mesh``/``sharding``: multichip placement — see ``set_mesh``."""
        if mesh is not None or sharding is not None:
            self.set_mesh(mesh, sharding)
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        if no_slice_names:
            # a typo here would silently re-enable the batch-slicing the
            # caller asked to prevent — validate before any state changes
            # so a failed bind leaves the module cleanly unbound
            known = {n for n, _ in data_shapes}
            known |= {n for n, _ in (label_shapes or [])}
            unknown = sorted(set(no_slice_names) - known)
            if unknown:
                raise MXNetError("no_slice_names %s match no bound data/"
                                 "label input (have: %s)"
                                 % (unknown, sorted(known)))

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = list(data_shapes)
        self._label_shapes = list(label_shapes) if label_shapes else None
        self._grad_req = grad_req
        self._no_slice_names = tuple(no_slice_names or ())

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            # the shared parent's exec-group arrays become the single
            # source of truth for every sibling (bucketing); its private
            # donated fused state would silently diverge from them.  The
            # flag also keeps a later init_optimizer from re-engaging
            # fusion on the parent (prepare() binds siblings before the
            # optimizer exists, when _disable_fused is still a no-op).
            shared_module._lent_exec_group = True
            shared_module._disable_fused("executor shared with %r"
                                         % getattr(self._symbol, "name", ""))
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, no_slice_names=self._no_slice_names)

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self._fused_state is not None and \
                not self._fused._multiprocess():
            # bound again in mid-training: the weights stay where they live
            self._exec_group.release()
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)

    def _reset_bind(self):
        if self.binded and self._params_dirty and self._fused_state is None:
            # the classic path's weights would go with the executor group
            self._sync_params_from_devices()
        self.binded = False
        self._exec_group = None
        self._lent_exec_group = False

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind to new input shapes (e.g. a different batch size)
        keeping trained parameters and optimizer state (reference
        module.py reshape)."""
        assert self.binded
        if self.params_initialized and self._params_dirty:
            # updated params live only in the old exec group; pull them back
            # before it is dropped or training silently reverts
            self._sync_params_from_devices()
        # batch shapes change: drop any per-batch fused artifacts (the
        # fused state itself is shape-independent and survives)
        self._fused_pending = None
        self._fused_outputs = None
        self._discard_speculation()
        self._data_shapes = list(data_shapes)
        self._label_shapes = list(label_shapes) if label_shapes else None
        # the fused state holds the weights: the new group keeps shapes only
        released = not self._exec_group.execs
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            self.for_training, self.inputs_need_grad, None,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=getattr(self, "_grad_req", "write"),
            no_slice_names=getattr(self, "_no_slice_names", ()))
        if self._fused is not None:
            self._fused.label_shapes = dict(self._label_shapes or [])
        if released:
            self._exec_group.release()
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # -- optimizer ------------------------------------------------------------
    @_recorded("module:init_optimizer")
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=None, force_init=False):
        """reference module.py:271-335."""
        assert self.binded and self.params_initialized
        if optimizer_params is None:
            optimizer_params = (("learning_rate", 0.01),)
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self.params_initialized and self._params_dirty:
            # force_init mid-training: the live params may exist only in the
            # donated fused state (or exec group); pull them back before the
            # kvstore is re-seeded and _setup_fused drops that state, or
            # training silently reverts to the last-synced values
            self._sync_params_from_devices()
        # the kvstore below is seeded from the executor group's arrays
        self._drop_fused_state()

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)

        if isinstance(optimizer, str):
            batch_size = self._exec_group.batch_size
            if kvstore and kvstore.type == "dist_sync":
                batch_size *= kvstore.num_workers
            idx2name = _param_idx2name(self._param_names,
                                       len(self._context), update_on_kvstore)
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = 1.0 / batch_size
            optimizer = opt_mod.create(optimizer,
                                       sym=self.symbol,
                                       param_idx2name=idx2name,
                                       **optimizer_params)
        else:
            assert isinstance(optimizer, opt_mod.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt_mod.get_updater(optimizer)
        self.optimizer_initialized = True
        self._setup_fused()

    def _fusable(self):
        """Whether the batch body can run as one donated XLA program with
        reference semantics. Anything here that says no falls back to the
        classic executor-group + kvstore/updater path."""
        if not get_env("MXNET_FUSED_TRAIN", True, bool):
            return False
        if not self.for_training or self.inputs_need_grad:
            return False
        if getattr(self, "_grad_req", "write") != "write":
            return False
        if self._monitor_installed or self._borrowed_optimizer:
            return False
        # exec group lent to a sibling (bucketing): stay on the classic
        # path — the fused state is private and siblings would train on
        # stale shared arrays
        if self._lent_exec_group:
            return False
        if self._exec_group is None or self._exec_group.shared_group is not None:
            return False
        if self._optimizer.fused_update_fn() is None:
            return False
        kv = self._kvstore
        if kv is not None and "dist" in kv.type and \
                "dist_sync" not in kv.type:
            # dist_async is inherently a host-side service (stale-weight
            # semantics); only the synchronous family fuses
            return False
        # ctx_group placement needs the node-level eager executor
        if any("ctx_group" in a for a in self._symbol.attr_dict().values()):
            return False
        cs = self._context
        if len({(c.device_type, c.device_id) for c in cs}) != len(cs):
            return False
        if len({c.device_type for c in cs}) != 1:
            return False
        return True

    def _setup_fused(self):
        if self._fused is not None and self._fused_state is not None and \
                self._params_dirty:
            # defense in depth (init_optimizer syncs first): never drop a
            # live fused state that holds the only copy of trained params
            self._sync_params_from_devices()
        self._fused = None
        self._drop_fused_state()
        self._fused_pending = None
        self._fused_outputs = None
        self._superstep_progs = {}
        self._superstep_unroll = 1
        self._discard_speculation()
        mesh = self._mesh
        if mesh is None and (get_env("MXNET_MESH", "") or "").strip():
            # MXNET_MESH="dp=4,tp=2": the env-knob spelling of set_mesh
            from ..parallel import mesh_from_env
            mesh = mesh_from_env()
        specs = self._sharding_specs
        if not self._fusable():
            if mesh is not None or specs:
                # a mesh the user asked for must never silently degrade
                # to a single-device classic loop
                raise MXNetError(
                    "Module mesh training needs the fused train step, "
                    "which this configuration disables (monitor / "
                    "grad_req != 'write' / borrowed optimizer / shared "
                    "executors / optimizer without a fused form / "
                    "MXNET_FUSED_TRAIN=0); remove the blocker or drop "
                    "mesh=/sharding=")
            return
        if mesh is not None and "dp" in mesh.axis_names:
            # (a mesh WITHOUT a dp axis is refused by FusedTrainStep
            # below, re-raised loudly because mesh is set)
            bs = self._exec_group.batch_size
            dp = int(mesh.shape["dp"])
            nproc = len({d.process_index for d in mesh.devices.ravel()})
            if nproc > 1:
                # multi-host mesh (mxnet_tpu.dist): the bound batch is
                # PER PROCESS (each worker feeds its slice of the
                # global batch, reference data-partitioned-by-rank),
                # so this process only has to slice over its share of
                # the dp axis — which must come out whole
                if dp % nproc:
                    raise MXNetError(
                        "the mesh's dp axis (%d) does not divide "
                        "evenly across %d processes; size dp as a "
                        "multiple of the process count" % (dp, nproc))
                local_dp = dp // nproc
                if bs % local_dp:
                    raise MXNetError(
                        "per-process batch size %d is not divisible by "
                        "this process's share of the dp axis (%d of "
                        "%d); pick a batch the local devices can slice "
                        "evenly" % (bs, local_dp, dp))
            elif bs % dp:
                raise MXNetError(
                    "bound batch size %d is not divisible by the mesh's "
                    "dp axis (%d); pick a batch the devices can slice "
                    "evenly" % (bs, dp))
        remat = get_env("MXNET_BACKWARD_DO_MIRROR", False, bool)
        # MXNET_COMPUTE_DTYPE=bfloat16: bf16 fwd/bwd on the MXU with f32
        # master weights (the fp16-era capability mapped the TPU way)
        cdt = get_env("MXNET_COMPUTE_DTYPE") or None
        if specs == "auto":
            # automatic GSPMD sharding search (mxnet_tpu.dist.
            # shardsearch): enumerate per-layer spec candidates, score
            # with the XLA-cost + collective-census model, measure the
            # shortlist, persist the winner per (model, topology)
            # fingerprint — a store hit skips the whole search
            from ..dist.shardsearch import resolve_auto
            specs = resolve_auto(self, mesh)
        try:
            gdp = (self._kvstore is not None
                   and "dist_sync" in self._kvstore.type)
            self._fused = FusedTrainStep(
                self._symbol, self._context, self._data_names,
                self._label_names, self._param_names,
                self._fixed_param_names, self._optimizer,
                label_shapes=self._label_shapes, remat=remat,
                compute_dtype=cdt, global_dp=gdp, mesh=mesh,
                sharding=specs)
            self._fused_hsig = self._fused.hparam_signature()
        except MXNetError as e:
            if mesh is not None or specs:
                # same contract as above: a refused mesh must fail loud,
                # not train on one device
                raise
            # _fusable() already vetted the config, so a refusal here is
            # abnormal (e.g. fused_update_fn without a fused_hparams
            # declaration) — surface why the slow path engaged
            self.logger.warning("fused train step disabled: %s", e)
            self._fused = None

    def apply_augment_spec(self, spec):
        """Wire a feed pipeline's on-device augmentation spec
        (feed.AugmentSpec, carried by ``record_pipeline(device_augment=
        True)`` iterators) into the fused train step, which prepends the
        traced cast/crop/flip/normalize prologue.  Returns False when
        the fused path is not engaged — the caller must then rebuild the
        pipeline host-side, because the classic exec-group path binds
        f32 CHW inputs and cannot consume the uint8 HWC wire format."""
        if self._fused is None or not self.optimizer_initialized:
            return False

        def sig(s):
            return s.signature() if s is not None else None
        before = sig(self._fused.device_augment)
        self._fused.set_device_augment(spec)
        if sig(self._fused.device_augment) != before:
            # the prologue is part of the superstep trace too, and the
            # module-level cache keys only (K, metric) — a stale entry
            # would train through the OLD spec's crop/normalize
            self._superstep_progs = {}
        return True

    def apply_joint_config(self, cfg):
        """Install a joint-autotune winner (autotune.tune_fit_joint):
        superstep unroll depth and the rematerialization flag.  Both
        knobs preserve the training semantics bit-for-bit — unroll only
        changes how lax.scan emits the K iterations, remat only recomputes
        activations in backward — so a persisted winner from another
        process is always safe to apply.  The superstep K itself is
        returned to fit(), which owns the batching loop."""
        if self._fused is None:
            return False
        self._superstep_unroll = max(1, int(cfg.get("unroll", 1)))
        remat = bool(cfg.get("remat", False))
        if remat != bool(self._fused._remat):
            # the remat flag is baked into the traced step: drop every
            # compiled program so the next dispatch re-traces with it
            self._fused._remat = remat
            self._fused._step = None
            self._fused._fwd = None
            self._superstep_progs = {}
        return True

    def _disable_fused(self, reason, replay_backward=True):
        """Leave the fused path mid-training with consistent state: pull
        the live params back into arg_params/exec group and re-seed an
        update_on_kvstore kvstore (it still holds the weights from
        init time — a pull would otherwise revert training)."""
        if self._fused is None:
            return
        if getattr(self._fused, "device_augment", None) is not None:
            # the classic path binds f32 CHW inputs; a uint8 HWC feed
            # has no host fallback — fail with the cause instead of a
            # shape-mismatch crash three frames later
            raise MXNetError(
                "cannot leave the fused train step (%s): on-device "
                "augmentation is active and the classic path cannot "
                "consume the uint8 feed; rebuild the pipeline with "
                "device_augment=False to use the fallback" % reason)
        fused = self._fused
        pend = self._fused_pending
        if self._fused_state is not None:
            self._sync_params_from_devices()
            self._restore_exec_arrays()
            if self._update_on_kvstore and self._kvstore is not None:
                _initialize_kvstore(kvstore=self._kvstore,
                                    param_arrays=self._exec_group.param_arrays,
                                    arg_params=self._arg_params,
                                    param_names=self._param_names,
                                    update_on_kvstore=True)
            if self._optimizer is not None and self._fused_t:
                # classic updater counts per index; continue from the fused
                # step count or Adam's bias correction restarts at t=1
                counts = self._optimizer._index_update_count
                for i in range(len(self._param_names) * len(self._context)):
                    counts.setdefault(i, self._fused_t)
            # hand the accumulated moments (SGD momentum, Adam m/v, ...)
            # to the classic updater — its lazy create_state would zero
            # them and the trajectory would diverge from classic parity
            opt_states = self._fused_state.get("opt") or {}
            updater = self._updater
            if updater is None and self._update_on_kvstore and \
                    self._kvstore is not None:
                updater = getattr(self._kvstore, "_updater", None)
            if opt_states and updater is not None and \
                    hasattr(updater, "states"):
                def _to_nd(x):
                    if x is None:
                        return None
                    if isinstance(x, (tuple, list)):
                        return tuple(_to_nd(e) for e in x)
                    return NDArray(x)
                num_dev = len(self._context)
                for i, n in enumerate(self._param_names):
                    st = opt_states.get(n)
                    if st is None:
                        continue
                    if fused.shard_update or fused.param_specs:
                        # sharded-at-rest state must be gathered
                        # before the per-param host updater owns it
                        def _gather(s):
                            if isinstance(s, (tuple, list)):
                                return tuple(_gather(e) for e in s)
                            return fused.gather_update_leaf(s)
                        st = _gather(st)
                    if self._update_on_kvstore:
                        updater.states[i] = _to_nd(st)
                    else:
                        # one independent copy per device replica
                        for dev in range(num_dev):
                            updater.states[i * num_dev + dev] = _to_nd(st)
        self._fused = None
        self._fused_state = None
        self._fused_pending = None
        self._fused_outputs = None
        self._fused_next = None
        self._superstep_progs = {}
        if pend is not None:
            # an uncommitted batch (forward recorded, update not yet run):
            # replay it through the exec group so the caller's next
            # backward()/update() acts on real gradients, not the
            # bind-time zero buffers
            from ..io import DataBatch
            eg = self._exec_group
            if fused._multiprocess():
                # pend holds GLOBAL arrays; the exec group wants this
                # worker's rows back
                def back(n):
                    return fused.host_outputs([pend[n]], pend)[0]
            else:
                def back(n):
                    return NDArray(pend[n])
            batch = DataBatch(
                data=[back(n) for n in eg.data_names],
                label=[back(n) for n in eg.label_names])
            eg.forward(batch, True)
            if replay_backward:
                eg.backward()
        self.logger.info("fused train step disabled: %s", reason)

    def _fused_ensure_state(self):
        """Build the fused state where there is none and move the
        weights into it: the executor group's arrays go before, the
        dicts' own tensor by tensor as the state takes its copies, so no
        device ever holds the weights twice.  An array somebody took
        from ``get_params()`` before lives on in their hands."""
        if self._fused_state is None:
            if self._params_dirty:
                self._sync_params_from_devices()
            if not self._fused._multiprocess():
                # from here on the fused state holds the live weights and
                # the step's own gradients; the executor group's argument
                # and gradient arrays (8 bytes a parameter) would only
                # lie beside them unread, so they go before the state is
                # built and come back when it is dropped
                # (_drop_fused_state).  Multi-process eval runs the
                # executor group every epoch and keeps them.
                self._exec_group.release()
            # what is left in the dicts is the shapes (dist/shardsearch.py
            # reads them), in the state every reader meets after an
            # update(): _sync_params_from_devices fills them again
            self._fused_state = self._fused.init_state(self._arg_params,
                                                       self._aux_params)
            self._params_dirty = True
            self._fused_t = 0
            from .. import random as _random
            key = _random.new_key()
            if self._fused._multiprocess():
                # every worker must hold the SAME key (it is a replicated
                # program input; in-program folds keep dropout etc
                # consistent across the global batch): rank 0 wins.
                # device_put accepts only HOST values for cross-process
                # shardings, so ship the raw key data and re-wrap on the
                # global mesh (all processes in lockstep).
                import numpy as _np
                import jax
                from jax.experimental import multihost_utils as mhu
                kd = _np.asarray(mhu.broadcast_one_to_all(
                    _np.asarray(jax.random.key_data(key))))
                key = jax.random.wrap_key_data(
                    jax.device_put(kd, self._fused._replicated()))
            self._fused_key = key

    def _fused_warmup(self, data_batch):
        """Compile (or cache-load) the fused step program off the hot
        loop without touching training state: compile-only via
        ``FusedTrainStep.warm_step`` — nothing executes, so the donated
        live state needs no throwaway copy and no optimizer update runs.
        The program is cached by shape/dtype, so the first real batch
        dispatches it without compiling."""
        assert self._fused is not None
        self._fused_ensure_state()
        pend = self._fused.make_batch(data_batch)
        self._fused.warm_step(self._fused_state, pend, self._fused_key)

    @_recorded("module:prepare")
    def prepare(self, data_batch=None, threads=None):
        """AOT-compile this module's hot-loop program(s) before the loop
        runs them.  A restarted process traces and lowers them again and
        reads the executables from JAX's persistent cache, where its
        entry point placed one (``place_jax_cache``).  Compile-only:
        nothing executes, no aux state moves, no gradients land.

        With the fused train step engaged this warms the one donated
        step program (``data_batch`` supplies the batch avals; default a
        zero batch of the bound shapes).  On the classic path every
        bound executor precompiles its default program, in parallel when
        there are several (``threads`` bounds the pool)."""
        assert self.binded and self.params_initialized
        from ..compile_cache import parallel_warm
        if self.for_training and not self.optimizer_initialized:
            # the training hot-loop program is CHOSEN by init_optimizer
            # (fused step vs classic exec-group); warming before that
            # would compile classic programs a fused fit never runs
            raise MXNetError(
                "prepare() on a training-bound module needs "
                "init_optimizer first")
        if self._fused is not None and self.optimizer_initialized:
            if data_batch is None:
                from ..io import DataBatch
                from ..ndarray import NDArray, zeros as nd_zeros
                import jax.numpy as _jnp
                spec = getattr(self._fused, "device_augment", None)
                if spec is not None:
                    # the hot loop feeds compact uint8 HWC batches; warm
                    # THAT program, not the f32 variant fit never runs
                    batch = self._data_shapes[0][1][0]
                    data = [NDArray(_jnp.zeros((batch,) + spec.pre_shape,
                                               _jnp.uint8))]
                    data += [nd_zeros(s) for _, s in self._data_shapes[1:]]
                else:
                    data = [nd_zeros(s) for _, s in self._data_shapes]
                data_batch = DataBatch(
                    data=data,
                    label=[nd_zeros(s)
                           for _, s in (self._label_shapes or [])])
            self._fused_warmup(data_batch)
            return
        parallel_warm(
            [("executor %d" % i, ex.precompile)
             for i, ex in enumerate(self._exec_group.execs)],
            threads=threads)

    def _discard_speculation(self):
        """Drop a stashed early-committed step WITHOUT applying it, rolling
        back the optimizer step count _fused_commit_early pre-advanced (an
        lr scheduler keyed on num_update must not run permanently ahead).
        Discard-with-replay sites (_disable_fused) do NOT use this: there
        the batch still commits classically, so the advance stands."""
        if self._fused_next is not None and self._optimizer is not None:
            self._optimizer.num_update = self._fused_prev_num_update
        self._fused_next = None

    def _fused_commit_early(self):
        """Run the pending batch's committed step on a COPY of the live
        state: outputs land in _fused_outputs, the post-step state is
        stashed in _fused_next for update() to install.  The pre-step
        state survives so an hparam mutation between here and update()
        can still take the classic-replay fallback, and a new forward()
        can discard the speculation entirely."""
        import jax
        import jax.numpy as jnp
        # resolve lr exactly as update() will; remember the pre-bump count
        # so a discarded speculation can put it back (an lr scheduler keyed
        # on num_update must not fire a step early)
        self._fused_prev_num_update = self._optimizer.num_update
        self._optimizer.num_update = max(self._optimizer.num_update,
                                         self._fused_t + 1)
        state_copy = jax.tree_util.tree_map(jnp.copy, self._fused_state)
        new_state, outs = self._fused.step(
            state_copy, self._fused_pending, self._fused_key)
        self._fused_outputs = self._fused.host_outputs(
            outs, self._fused_pending)
        self._fused_next = (new_state, self._fused_outputs)

    def prefetch_to_device(self, data_iter, depth=2, megabatch=1):
        """Wrap ``data_iter`` so each batch's H2D transfer is issued
        ``depth`` steps ahead of consumption (mxnet_tpu.feed staging).
        With the fused train step engaged, batches land directly in its
        batch sharding and make_batch passes them through untouched; on
        the classic (or CPU) path this degrades to plain lookahead
        overlap.  ``megabatch=K`` assembles K-batch megabatches (stacked
        leading axis, superstep input layout) instead, double-buffering
        the next megabatch's H2D under the current superstep.  Call
        after init_optimizer; fit(prefetch_to_device=True) does this
        automatically."""
        from .. import feed as _feed
        return _feed.device_feed(data_iter, module=self, depth=depth,
                                 megabatch=megabatch)

    # -- superstep: K fused steps per dispatch -------------------------------
    def _superstep_blockers(self, eval_metric, k, monitor=None,
                            batch_end_callback=None, checkpoint_every=None):
        """Why superstep K must fall back to per-step dispatch, or None
        when K steps per program is semantically safe.  Anything that
        needs per-step host visibility blocks it."""
        if self._fused is None or not self.optimizer_initialized:
            return "fused train step not engaged"
        if monitor is not None or self._monitor_installed:
            return "monitor attached (needs per-step host visibility)"
        if self._fused._multiprocess():
            return "multi-process training keeps per-step dispatch"
        if eval_metric is not None and \
                getattr(eval_metric, "device_reducer", lambda: None)() is None:
            return "metric %r has no device form" % getattr(
                eval_metric, "name", eval_metric)
        if checkpoint_every and checkpoint_every % k != 0:
            return ("checkpoint_every=%d is not a multiple of K=%d"
                    % (checkpoint_every, k))
        cbs = batch_end_callback if isinstance(batch_end_callback, list) \
            else ([batch_end_callback] if batch_end_callback else [])
        for cb in cbs:
            if getattr(cb, "inspects_outputs", False):
                return "batch-end callback %r inspects per-step outputs" % cb
        return None

    def superstep_train(self, batches, eval_metric=None):
        """Advance K training batches in ONE donated XLA dispatch
        (fused.build_superstep): forward+backward+reduce+update K times
        under lax.scan, metric sums accumulated on device and drained as
        one scalar pytree at the end.  ``batches`` is a list of K
        DataBatch or a pre-staged feed.MegaBatch (K is taken from it).

        Returns True when the superstep dispatched; False when the
        caller must fall back to per-batch processing of these batches
        (fused path gone, or optimizer hyperparameters mutated since the
        program was compiled — the per-batch path resolves both)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if self._fused is None:
            return False
        if self._fused_pending is not None:
            # a recorded-but-uncommitted training forward is a real batch,
            # not a stale artifact: silently dropping it would lose its
            # update (every other path commits or replays it)
            raise MXNetError(
                "superstep_train with an uncommitted forward pending; "
                "call update() to commit it first")
        if self._fused.hparam_signature() != self._fused_hsig:
            return False
        import time as _time
        import jax
        import numpy as _np
        self._fused_ensure_state()
        reducer = eval_metric.device_reducer() if eval_metric is not None \
            else None
        if eval_metric is not None and reducer is None:
            return False

        if self._superstep_stats is None:
            from .. import profiler as _prof
            self._superstep_stats = _prof.SuperstepStats()
            _prof.register_superstep_stats(self._superstep_stats)
        stats = self._superstep_stats

        t0 = _time.perf_counter()
        k, mega = self._fused.make_megabatch(batches)
        h2d_s = _time.perf_counter() - t0
        _trace.complete("superstep:h2d_stage", t0, h2d_s, cat="train")

        unroll = max(1, min(int(self._superstep_unroll), int(k)))
        sig = (k, unroll, reducer.signature if reducer is not None else None)
        prog = self._superstep_progs.get(sig)
        if prog is None:
            prog = self._fused.build_superstep(
                k, reducer.update if reducer is not None else None,
                unroll=unroll)
            self._superstep_progs[sig] = prog

        # per-step lr exactly as K sequential update() calls resolve it:
        # bump the step counter, let the scheduler see each position.
        # The counters (and scheduler state) advance BEFORE the program
        # runs — roll them back if the dispatch (first-call trace /
        # compile included) fails, or a caller that catches and falls
        # back per-batch would train K steps ahead of the device state.
        prev_t = self._fused_t
        prev_num_update = self._optimizer.num_update
        sched = getattr(self._optimizer, "lr_scheduler", None)
        sched_state = sched.state_dict() if sched is not None else None
        try:
            lrs = []
            for _ in range(k):
                self._fused_t += 1
                self._optimizer.num_update = max(
                    self._optimizer.num_update, self._fused_t)
                lrs.append(float(self._optimizer.base_lr()))
            rep = self._fused._replicated()
            lrs = jax.device_put(_np.asarray(lrs, _np.float32), rep)
            acc0 = () if reducer is None else jax.tree_util.tree_map(
                lambda a: jax.device_put(a, rep), reducer.init())

            # stale per-batch artifacts cannot survive a K-step jump (no
            # pending forward exists here — guarded at entry)
            self._fused_outputs = None
            self._fused_eval_local = False
            self._discard_speculation()

            t1 = _time.perf_counter()
            self._fused_state, acc = prog(self._fused_state, mega, lrs,
                                          self._fused_key, acc0)
            dispatch_s = _time.perf_counter() - t1
            _trace.complete("superstep:dispatch", t1, dispatch_s,
                            cat="train", k=k)
        except Exception:
            self._fused_t = prev_t
            self._optimizer.num_update = prev_num_update
            if sched is not None:
                sched.load_state_dict(sched_state)
            raise
        self._params_dirty = True

        wait_s = 0.0
        if reducer is not None:
            t2 = _time.perf_counter()
            host_acc = jax.tree_util.tree_map(lambda a: _np.asarray(a), acc)
            wait_s = _time.perf_counter() - t2
            _trace.complete("superstep:metric_drain", t2, wait_s,
                            cat="train", k=k)
            reducer.absorb(host_acc)
        stats.add(k, h2d_s, dispatch_s, wait_s)
        mcs = getattr(self._fused, "multichip_stats", None)
        if mcs is not None:
            mcs.add_superstep(k, dispatch_s, wait_s)
        return True

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._disable_fused("optimizer borrowed")
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
        # a shared optimizer's state must be visible to every borrower;
        # the donated fused state is private, so stay on the classic path
        self._borrowed_optimizer = True
        self._fused = None
        self._fused_state = None

    # -- computation ----------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        if self._fused is not None and self.optimizer_initialized:
            if is_train:
                # defer: the whole batch body runs as one program when
                # update() commits it (fit order: forward_backward,
                # update, update_metric)
                self._fused_ensure_state()
                self._fused_pending = self._fused.make_batch(data_batch)
                self._fused_outputs = None
                self._fused_eval_local = False
                # a stashed early commit belongs to the superseded batch;
                # dropping it leaves params untouched (the speculative
                # step ran on a copy), which is exactly eval semantics —
                # including the optimizer step count it pre-advanced
                self._discard_speculation()
                return
            if self._fused_state is not None:
                if self._fused._multiprocess():
                    # multi-process eval stays WORKER-LOCAL (reference
                    # dist semantics: validation never synchronizes
                    # workers — uneven per-rank shard counts would
                    # deadlock a collective program): sync the live
                    # params once and run the classic exec group
                    if self._params_dirty:
                        self._sync_params_from_devices()
                    self._exec_group.forward(data_batch, False)
                    self._fused_eval_local = True
                    self._fused_outputs = None
                    return
                # eval on the live training params without syncing them
                # back through the exec group; a pending train batch stays
                # pending (the eval must not eat the next update)
                batch = self._fused.make_batch(data_batch)
                outs = self._fused.forward_only(
                    self._fused_state, batch, self._fused_key, False)
                self._fused_outputs = self._fused.host_outputs(outs, batch)
                return
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._fused is not None and self._fused_pending is not None:
            if out_grads is None:
                return
            # explicit head gradients (e.g. SequentialModule chaining)
            # cannot ride the loss-headed fused program: _disable_fused
            # replays the pending batch through the exec group (from the
            # recorded device arrays — the caller's DataBatch may have
            # been mutated since forward), then the caller's heads land
            # via the backward below (no throwaway ones-seeded backward).
            self._disable_fused("explicit head gradients",
                                replay_backward=False)
        self._exec_group.backward(out_grads=out_grads)
        self._grads_pending = True

    def update(self):
        """reference module.py:377-394."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._fused is not None and self._fused_pending is not None:
            if self._fused.hparam_signature() != self._fused_hsig:
                # the program baked the old lr_mult/wd/rescale/clip;
                # honor the mutation like the classic path does (the
                # pending batch is replayed through the exec group).
                # _disable_fused syncs params (clearing the dirty flag);
                # the classic update below makes them dirty again.
                self._disable_fused("optimizer hyperparameters changed")
                self._params_dirty = True
            else:
                self._fused_t += 1
                # scheduler parity: one optimizer step per batch, lr
                # resolved in python and fed in as a scalar (no recompile)
                self._optimizer.num_update = max(self._optimizer.num_update,
                                                 self._fused_t)
                if self._fused._multiprocess():
                    # the fleet chaos seam (mxnet_tpu.dist): a host
                    # dying mid-step is THE multi-host failure mode;
                    # the per-rank stage lets a chaos plan SIGKILL one
                    # specific host (points=dist.host@rank1) while the
                    # rest of the fleet rides the FleetSupervisor's
                    # restart-from-commit path
                    import jax as _jax
                    from .. import faults as _faults
                    _faults.point("dist.host",
                                  stage="rank%d" % _jax.process_index(),
                                  step=self._fused_t)
                if self._fused_next is not None:
                    # the committed step already ran when outputs were
                    # read between forward and update; install its state
                    # AND its outputs (an interleaved eval forward may
                    # have overwritten _fused_outputs) — no second
                    # evaluation
                    self._fused_state, self._fused_outputs = \
                        self._fused_next
                    self._fused_next = None
                else:
                    self._fused_state, outs = self._fused.step(
                        self._fused_state, self._fused_pending,
                        self._fused_key)
                    self._fused_outputs = self._fused.host_outputs(
                        outs, self._fused_pending)
                self._fused_pending = None
                self._fused_eval_local = False
                return
        with _trace.span("optimizer:update_params", cat="train",
                         arrays=len(self._exec_group.param_arrays)):
            if self._update_on_kvstore:
                _update_params_on_kvstore(self._exec_group.param_arrays,
                                          self._exec_group.grad_arrays,
                                          self._kvstore)
            else:
                _update_params(self._exec_group.param_arrays,
                               self._exec_group.grad_arrays,
                               updater=self._updater,
                               num_device=len(self._context),
                               kvstore=self._kvstore)
        self._grads_pending = False

    def _fused_live(self):
        return self._fused is not None and (self._fused_outputs is not None
                                            or self._fused_pending is not None)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused_eval_local:
            # last forward was a worker-local multi-process eval
            return self._exec_group.get_outputs(
                merge_multi_context=merge_multi_context)
        if self._fused_live():
            if self._fused_outputs is None:
                # outputs requested between forward and update: run the
                # COMMITTED step now on a copy of the state and stash the
                # result for update() to install — the user-facing order
                # forward(); update_metric(); update() then costs ONE
                # evaluation, same as fit()'s order
                if self._fused.hparam_signature() == self._fused_hsig:
                    self._fused_commit_early()
                else:
                    # hparams mutated since forward: nothing may commit
                    # with the baked values; evaluate only (update() will
                    # fall back and replay classic), with the SAME rng
                    # fold the step would use
                    import jax as _jax
                    key = _jax.random.fold_in(self._fused_key,
                                              self._fused_t + 1)
                    outs = self._fused.forward_only(
                        self._fused_state, self._fused_pending, key, True)
                    self._fused_outputs = self._fused.host_outputs(
                        outs, self._fused_pending)
            if merge_multi_context:
                return list(self._fused_outputs)
            return [[o] for o in self._fused_outputs]
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        grads = self._exec_group.get_input_grads(
            merge_multi_context=merge_multi_context)
        # grad-only flows (backward with no optimizer to ever call
        # update()) have now consumed the gradients: release the pending
        # flag or bucketing prepare() would stay locked out.  With an
        # optimizer initialized the PARAM gradients are still live until
        # update() runs (GAN-style flows read input grads first), so the
        # flag must hold.
        if not self.optimizer_initialized:
            self._grads_pending = False
        return grads

    def update_metric(self, eval_metric, labels):
        if self._fused_eval_local:
            self._exec_group.update_metric(eval_metric, labels)
            return
        if self._fused_live():
            eval_metric.update(labels, self.get_outputs())
            return
        self._exec_group.update_metric(eval_metric, labels)

    def _note_train_outputs(self, outputs=None):
        """Read the counter heads that the fused step found in its
        symbol (``trace/heads.py``) from the outputs of the step just
        scored, in their order, each under its own span: nothing, and no
        span, where the fused step is off or the symbol carries no head;
        a head that only feeds the trace only while tracing is on."""
        fused = self._fused
        if fused is None or not self._fused_live() or not fused.heads:
            return
        outs = self.get_outputs() if outputs is None else outputs
        traced = _trace.enabled()
        for head, handle in fused.heads:
            if head.always or traced:
                with _trace.span(head.span, cat="train"):
                    head.emit(fused, handle, outs)

    def _outputs_in_flight(self):
        """The overlap hook of fit() and score(): the outputs of the
        fused step (or eval forward) just dispatched, as device arrays
        with their device->host copies STARTED but not awaited, so the
        next dispatch runs under the transfer and the metric update
        (which blocks) happens a batch later.  None where the outputs
        are not in flight — the classic path (fusion off, a monitor),
        worker-local eval, multi-process training (host_outputs reads
        this worker's rows synchronously), arrays of the CPU backend
        (they are the host's own memory: nothing travels) — and the
        caller keeps the synchronous order."""
        if self._fused is None or self._fused_eval_local or \
                self._fused_outputs is None or self._fused._multiprocess():
            return None
        outs = list(self._fused_outputs)
        if _lives_on_host(outs[0]._get()):
            return None
        for o in outs:
            o._start_host_copy()
        return outs

    def install_monitor(self, mon):
        assert self.binded
        self._monitor_installed = True
        self._disable_fused("monitor installed")
        self._exec_group.install_monitor(mon)
