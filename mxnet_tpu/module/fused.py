"""Fused train step for the Module/FeedForward reference API.

The reference's hot loop (model.py:119-310, module/module.py:377-394) has
python push gradients per-parameter through kvstore and run the optimizer
per-parameter on the host. On TPU that python round-trip dominates: the
fwd+bwd pair is one XLA program, but ~2N more dispatches follow it every
batch. This module collapses the whole batch body — forward, backward,
cross-device gradient reduction, and the optimizer — into ONE donated,
jit-compiled XLA program over the device mesh:

* batch slicing across contexts  -> batch-axis NamedSharding over "dp"
* kvstore local/device reduce    -> psum inserted by GSPMD (rides ICI)
* per-param python updater       -> optimizer's fused_update_fn traced in
* buffer reuse                   -> donation of the whole train state

Engaged automatically by ``Module.init_optimizer`` when semantics allow
(see Module._fusable); anything it can't express (monitor, ctx_group,
grad_req!='write', optimizers without a functional form, shared/bucketing
executors, dist_async kvstores) falls back to the reference path
unchanged.  dist_sync kvstores fuse too (``global_dp``): the mesh spans
every process's devices, each worker feeds its batch as its slice of the
global array, and the cross-process gradient reduction is a GSPMD
collective instead of kvstore round trips.  Disable with
MXNET_FUSED_TRAIN=0.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError, get_env
from ..context import cpu
from ..executor import _GraphProgram
from ..ndarray import NDArray, _read_to_host
from ..parallel.mesh import tracing_over
from .. import trace as _trace
from ..trace import heads as _heads, scopes as _scopes

__all__ = ["FusedTrainStep"]


def _hparams_undeclared(cls):
    """True when the class providing this optimizer's fused_update_fn did
    not also declare (or inherit from a more-derived class declaring)
    ``fused_hparams`` — i.e. the baked-scalar snapshot could be blind to
    state the closures capture."""
    def definer(name):
        for c in cls.__mro__:
            if name in c.__dict__:
                return c
        return None
    fu, fh = definer("fused_update_fn"), definer("fused_hparams")
    return fh is None or not issubclass(fh, fu)


class FusedTrainStep:
    """One donated XLA program per (shapes, dtypes): fwd+bwd+reduce+update.

    State layout (a single donated pytree)::

        {"params": {name: w}, "opt": {name: state}, "aux": {name: a},
         "fixed": {name: w}}

    ``step(state, batch, lr, t)`` advances it one batch and returns the
    graph outputs; ``forward_only(state, batch)`` evaluates without
    touching state (used for eval/predict on the live training params).
    """

    def __init__(self, symbol, contexts, data_names: Sequence[str],
                 label_names: Sequence[str], param_names: Sequence[str],
                 fixed_param_names: Sequence[str], optimizer,
                 label_shapes=None, remat: bool = False,
                 compute_dtype=None, global_dp: bool = False,
                 mesh=None, sharding=None):
        self.global_dp = global_dp
        self.named_mesh = mesh is not None
        if mesh is not None:
            # first-class multichip: a user-provided named mesh (e.g.
            # parallel.make_mesh([("dp", 4), ("tp", 2)])).  The batch
            # axis shards over "dp"; per-param GSPMD constraints over
            # the remaining axes come from ``sharding`` below.
            mdevs = list(mesh.devices.ravel())
            if global_dp:
                # dist_sync + named mesh: the mesh axes span the WHOLE
                # process group (mxnet_tpu.dist).  A mesh covering only
                # a subset would leave the other workers' devices out
                # of the collectives — every SPMD program would hang at
                # the first cross-process barrier, so refuse up front
                # with the shapes.
                if set(mdevs) != set(jax.devices()):
                    raise MXNetError(
                        "dist_sync needs the named mesh to span every "
                        "process's devices (%d in mesh, %d global over "
                        "%d processes); build it from jax.devices() — "
                        "parallel.make_mesh does by default"
                        % (len(mdevs), len(jax.devices()),
                           jax.process_count()))
            if len(set(mdevs)) != len(mdevs):
                raise MXNetError("fused step needs distinct devices")
            if "dp" not in mesh.axis_names:
                raise MXNetError(
                    "mesh %s has no 'dp' axis; the batch shards over "
                    "'dp' — use dp=1 for pure tensor parallelism"
                    % (dict(mesh.shape),))
            self.mesh = mesh
        else:
            devices = [c.jax_device() for c in contexts]
            if len(set(devices)) != len(devices):
                raise MXNetError("fused step needs distinct devices")
            if global_dp:
                # multi-host dist_sync: ONE mesh over every process's
                # devices; GSPMD turns the dp gradient mean into
                # cross-process collectives (ICI within a slice, DCN
                # across) — no kvstore round trips in the hot loop
                # (reference kvstore_dist.h:65-98 semantics at "python
                # pushes one pointer" cost)
                if set(devices) != set(jax.local_devices()):
                    raise MXNetError(
                        "dist_sync fused step needs the module bound on "
                        "every local device (%d bound, %d local)"
                        % (len(devices), jax.local_device_count()))
                self.mesh = Mesh(np.array(jax.devices()), ("dp",))
            else:
                self.mesh = Mesh(np.array(devices), ("dp",))
        self.dp_size = int(self.mesh.shape["dp"])
        # how many PROCESSES the mesh spans: >1 engages the multi-host
        # contract everywhere (per-process batch slices, broadcast init,
        # host-local output gathers, collective-safe checkpointing) —
        # for dist_sync's implicit dp mesh AND for a named mesh whose
        # axes cross process boundaries (mxnet_tpu.dist)
        self._mesh_procs = len({d.process_index
                                for d in self.mesh.devices.ravel()})
        self.data_names = tuple(data_names)
        self.label_names = tuple(label_names)
        self.label_shapes = dict(label_shapes or [])
        fixed = set(fixed_param_names or ())
        self.train_names = [n for n in param_names if n not in fixed]
        self.fixed_names = [n for n in param_names if n in fixed]
        self.aux_names = symbol.list_auxiliary_states()
        # per-param GSPMD sharding constraints: the ``sharding=`` map
        # merged over ``__sharding__`` symbol attributes (explicit map
        # wins).  Resolved to NamedShardings and applied with
        # lax.with_sharding_constraint inside the step trace, so the
        # partitioner inserts the tensor-parallel collectives.
        from ..parallel.mesh import (normalize_spec, sharding_attrs,
                                     validate_spec)
        specs = sharding_attrs(symbol)
        specs.update(sharding or {})
        known = set(param_names) | set(self.aux_names)
        unknown = sorted(set(specs) - known)
        if unknown:
            raise MXNetError(
                "sharding specs name no bound parameter: %s (params: %s)"
                % (unknown, sorted(known)))
        self.param_specs = {}
        for n, sp in specs.items():
            sp = normalize_spec(sp)
            validate_spec(n, sp, self.mesh)
            self.param_specs[n] = sp
        self.optimizer = optimizer
        fused = optimizer.fused_update_fn()
        if fused is None:
            raise MXNetError("optimizer has no fused form")
        if _hparams_undeclared(type(optimizer)):
            # a fused form whose baked scalars we cannot snapshot could be
            # mutated mid-training without us noticing; refuse to fuse
            raise MXNetError(
                "optimizer %s overrides fused_update_fn without declaring "
                "fused_hparams at the same (or a more derived) class; "
                "falling back to the per-param update path"
                % type(optimizer).__name__)
        self._opt_init, self._opt_update = fused
        # deduped sparse embedding updates (mxnet_tpu.embed): Embedding
        # layers whose ids input is a data variable and whose table is
        # consumed nowhere else train through the sparse path — the step
        # dedups the batch's ids, gathers each unique row ONCE, takes
        # grads w.r.t. those rows only (the take-VJP then scatters into
        # a cap-row buffer, not the full table), and applies the
        # optimizer lazily to the touched rows.  One donated dispatch
        # still covers dense + sparse params.  MXNET_EMBED_SPARSE=0
        # restores the dense take-VJP everywhere (the bench baseline).
        from ..embed.detect import find_sparse_embeds
        from ..embed.sparse import slot_leaves_row_shaped
        self.sparse_embeds = {}
        for n, sp in find_sparse_embeds(symbol, self.data_names,
                                        self.train_names).items():
            # lazy per-row updates need row-shaped optimizer state
            # (SGD/NAG/Adagrad/Adam); anything else keeps the dense path
            # for that table
            if slot_leaves_row_shaped(self._opt_init, sp.vocab, sp.dim,
                                      jnp.float32):
                self.sparse_embeds[n] = sp
        self.embed_stats = None
        if self.sparse_embeds:
            from ..embed.stats import EmbedStats
            from .. import profiler as _prof
            self.embed_stats = EmbedStats("fused")
            _prof.register_embed_stats(self.embed_stats)
        # routed-MoE blocks: graph-side detection registers the stats
        # consumer and stamps each block's routing geometry into the
        # program descriptor.  Routing is data-dependent: per-expert
        # traffic reaches the stats from the step's own outputs where
        # the symbol carries the blocks' load head, else from
        # bench/serve samplers
        from ..moe.detect import find_moe_blocks
        self.moe_blocks = find_moe_blocks(symbol)
        # the outputs that feed trace counters (trace/heads.py), each
        # with what its reader needs, in the order fit reads them
        self.heads = _heads.find_all(symbol)
        self.moe_stats = None
        if self.moe_blocks:
            from ..moe.stats import MoeStats
            from .. import profiler as _prof
            self.moe_stats = MoeStats("fused")
            _prof.register_moe_stats(self.moe_stats)
        # static per-param schedule factors (reference lr_mult/wd_mult and
        # the bias/gamma/beta wd rule, resolved by NAME not index)
        self._lr_mult = {n: optimizer._name_lr_mult(n) for n in self.train_names}
        self._wd = {n: optimizer._name_wd(n) for n in self.train_names}
        # remat: checkpoint the WHOLE loss (see _build_step) instead of
        # per-node jax.checkpoint — wrapping single primitives saves
        # nothing (their inputs stay live) and measured 3x LARGER HLO
        # temp at b1024 by blocking XLA's buffer reuse
        self._remat = remat
        self._prog = _GraphProgram(symbol, {}, None, do_mirror=False)
        # mixed precision the TPU way (fp16-era capability, SURVEY §7):
        # master weights and optimizer state stay f32, the fwd/bwd compute
        # runs in bf16 on the MXU, grads are cast back before the update
        self.compute_dtype = compute_dtype
        from ..symbol import id_valued_inputs
        self._no_cast = set(self.label_names) | id_valued_inputs(symbol)
        # MXNET_SHARD_WEIGHT_UPDATE=1: cross-replica sharded weight
        # update (Xu et al. 2020, arxiv 2004.13336 — the ZeRO-1 recipe
        # the TPU way): gradients reduce-scatter over dp, each replica
        # updates only its shard of every parameter and keeps only its
        # shard of the optimizer state, updated params all-gather back.
        # Same math, optimizer memory and update flops divided by the
        # dp degree; expressed purely through sharding constraints, the
        # partitioner forms the collectives.  Generalized to arbitrary
        # named meshes: the update shards over the mesh's "dp" AXIS
        # (not the whole device set), composing with per-param tensor-
        # parallel specs — a dp=4 x tp=2 mesh shards each tp shard's
        # update 4 ways.
        self.shard_update = (
            get_env("MXNET_SHARD_WEIGHT_UPDATE", False, bool)
            and self.dp_size > 1)
        # on-device augmentation prologue (feed.AugmentSpec): when set,
        # uint8 HWC data batches are cast/cropped/flipped/normalized
        # INSIDE the compiled step (feed.augment), so the feed ships
        # ~4x fewer H2D bytes and the per-image python augment loop
        # disappears from the hot path
        self.device_augment = None
        self._step = None
        self._fwd = None
        self._lr_cache = None
        # multichip observability: per-step dispatch time (and, from
        # superstep windows, device time), plus XLA cost analysis +
        # collective counts once an AOT compile ran — surfaced via mx.profiler.multichip_report()
        self.multichip_stats = None
        if len(self.mesh.devices.ravel()) > 1:
            from .. import profiler as _prof
            from ..parallel.mesh import mesh_axes as _mesh_axes
            from ..parallel.mesh import spec_axes as _spec_axes
            self.multichip_stats = _prof.MultichipStats(
                "fused", axes=_mesh_axes(self.mesh),
                spec_axes=sorted({a for sp in self.param_specs.values()
                                  for a in _spec_axes(sp)}))
            _prof.register_multichip_stats(self.multichip_stats)

    def _cast_compute(self, args):
        from ..symbol import cast_compute
        return cast_compute(args, self.compute_dtype, self._no_cast)

    # -- on-device augmentation ---------------------------------------------
    def set_device_augment(self, spec) -> None:
        """Install (or clear) the traced augmentation prologue.  Already-
        built programs are dropped on a real change — the prologue is
        part of the trace and of the compile-cache key; a no-op set
        (same spec, or None over None) keeps the warm programs."""
        if spec is None and self.device_augment is None:
            return
        if getattr(self.device_augment, "signature", None) is not None \
                and spec is not None \
                and self.device_augment.signature() == spec.signature():
            return
        self.device_augment = spec
        self._step = None
        self._fwd = None

    def _maybe_augment(self, batch, rng, train: bool):
        """Trace-time dispatch of the prologue: applies ONLY when the
        first data input arrives as a 4-D uint8 array (the compact HWC
        wire format) — an f32 batch from a host-augmented eval iterator
        or a warmup zero-batch passes through untouched, so one compiled
        family serves both wire formats without runtime branching."""
        spec = self.device_augment
        if spec is None or not self.data_names:
            return batch
        name = self.data_names[0]
        x = batch.get(name)
        if x is None or x.dtype != jnp.uint8 or x.ndim != 4:
            return batch
        from ..feed.augment import AUG_FOLD, augment_batch
        out = dict(batch)
        # a dedicated fold keeps augmentation draws out of the model's
        # own RNG stream; both derive from the per-step key, so resume
        # replays identical crops/flips
        out[name] = augment_batch(x, jax.random.fold_in(rng, AUG_FOLD),
                                  spec, train)
        return out

    # -- placement ----------------------------------------------------------
    def _replicated(self):
        return NamedSharding(self.mesh, P())

    def _batched(self):
        return NamedSharding(self.mesh, P("dp"))

    def batched_sharding(self):
        """Public handle for input pipelines (feed.device_feed /
        feed.DevicePutStage): batches staged with this sharding are
        recognized by make_batch and passed through without a second
        transfer — the H2D lands once, async, in the exact layout the
        donated step program compiled for."""
        return self._batched()

    def megabatched_sharding(self):
        """Sharding for a K-step megabatch: leading K axis unsharded
        (the scan iterates it), batch axis sharded over dp — the layout
        the superstep program compiles for.  feed.DevicePrefetchIter's
        megabatch mode stages with this so make_megabatch passes the
        resident arrays through without a second transfer."""
        return NamedSharding(self.mesh, P(None, "dp"))

    def _multiprocess(self):
        return self._mesh_procs > 1

    def _param_sharding(self, name):
        """At-rest sharding for one named param/aux: its declared GSPMD
        spec, replicated when none."""
        return NamedSharding(self.mesh, self.param_specs.get(name, P()))

    def _update_spec(self, x, name=None):
        """Sharding for one update-path leaf (gradient / optimizer
        slot): the param's declared spec, with the leading dim
        additionally sharded over the dp axis when MXNET_SHARD_WEIGHT_
        UPDATE is on and it divides evenly (replicated otherwise — tiny
        params).  Composes: a tp-sharded weight's momentum stays
        tp-sharded AND dp-sharded at rest."""
        from ..parallel.mesh import spec_axes
        nd = getattr(x, "ndim", 0)
        base = tuple(self.param_specs.get(name, P())) if name else ()
        spec = list(base[:nd]) + [None] * (nd - len(base[:nd]))
        if self.shard_update and nd >= 1 and spec and spec[0] is None \
                and "dp" not in spec_axes(spec) \
                and x.shape[0] % self.dp_size == 0:
            # a declared spec may already spend "dp" on another dim
            # (P(None, "dp")) — a second use would be an invalid
            # duplicate-axis PartitionSpec, so the update rides the
            # declared layout alone
            spec[0] = "dp"
        if not any(e is not None for e in spec):
            return self._replicated()
        return NamedSharding(self.mesh, P(*spec))

    def _check_divisible(self, name, shape):
        """A declared spec whose axis does not divide its dim would shard
        unevenly — checkpoint shard indexes and the donated layout both
        want the even case; refuse with the numbers."""
        spec = self.param_specs.get(name)
        if spec is None:
            return
        from ..parallel.mesh import validate_spec
        validate_spec(name, spec, self.mesh, shape=shape)

    def init_state(self, arg_params: Dict[str, NDArray],
                   aux_params: Dict[str, NDArray]):
        """Build the device-resident train state from param dicts.  The
        state owns storage of its own: each leaf is a copy, made on the
        device where the source lies there, and lands directly in its
        declared sharding (tensor-parallel params never materialize
        replicated on the mesh).  The dicts give their arrays up, each
        for its shape (a ``jax.ShapeDtypeStruct``) as the state takes its
        copy, so that no device holds the weights twice (``Module``: one
        home at a time); a caller that keeps its arrays hands over
        shallow copies of its dicts (``dist/shardsearch.py``)."""
        def leaves(source, names):
            out = {}
            for n in names:
                v = source[n]
                out[n] = a = v._get() if isinstance(v, NDArray) else v
                self._check_divisible(n, a.shape)
                source[n] = jax.ShapeDtypeStruct(a.shape, a.dtype)
            return out
        tree = {"params": leaves(arg_params, self.train_names),
                "fixed": leaves(arg_params, self.fixed_names),
                "aux": leaves(aux_params, self.aux_names)}
        if self._multiprocess():
            # dist init semantics: rank 0's value wins everywhere
            # (reference kvstore_dist init); a global device_put needs
            # identical host values on every process anyway.  ONE pytree
            # collective, not one per tensor.
            from jax.experimental import multihost_utils as mhu
            tree = mhu.broadcast_one_to_all(
                jax.tree_util.tree_map(np.asarray, tree))

        def put(group):
            # device_put may alias the buffer it is given; the state is
            # donated every step, so it must own fresh storage or an
            # NDArray that somebody took from get_params() before the
            # first step gets deleted under them.  A source nobody else
            # holds goes as soon as its copy is made.
            return {n: jnp.copy(jax.device_put(group.pop(n),
                                               self._param_sharding(n)))
                    for n in list(group)}
        params, fixed, aux = put(tree["params"]), put(tree["fixed"]), \
            put(tree["aux"])
        if self.shard_update or self.param_specs:
            # optimizer state lives SHARDED at rest: each replica holds
            # only its slice (the paper's memory saving) and the donated
            # state keeps one stable layout across steps.  Allocate each
            # leaf DIRECTLY into its shard (out_shardings) — a
            # replicate-then-reshard would spike peak HBM by exactly the
            # amount this mode exists to save.
            opt = {}
            init_cache = {}   # one compile per (shape, dtype, spec)
            for n, w in params.items():
                key = (tuple(w.shape), str(w.dtype),
                       repr(self.param_specs.get(n)))
                if key not in init_cache:
                    struct = jax.eval_shape(self._opt_init, w)
                    shardings = jax.tree_util.tree_map(
                        lambda x, _n=n: self._update_spec(x, _n), struct)
                    # lint: allow(raw-jit) — one-shot init compile
                    # per (shape, dtype, spec); out_shardings are LIVE
                    # mesh objects, not serializable cache-key material
                    init_cache[key] = jax.jit(self._opt_init,
                                              out_shardings=shardings)
                opt[n] = init_cache[key](w)
        else:
            opt = {n: self._opt_init(w) for n, w in params.items()}
        # the step counter lives on device and increments in-program: a
        # host-built scalar would cost one transfer per step
        t = jax.device_put(jnp.zeros((), jnp.int32), self._replicated())
        return {"params": params, "opt": opt, "aux": aux, "fixed": fixed,
                "t": t}

    def hparam_signature(self):
        """Snapshot of the optimizer hyperparameters baked into the
        compiled step (everything except lr, which rides in as a runtime
        scalar).  Module.update compares this per batch: a mutation
        (set_lr_mult, wd change, momentum/beta change, ...) drops back to
        the classic path, which resolves them per update like the
        reference."""
        opt = self.optimizer
        # each optimizer class declares which of its scalars the
        # fused_update_fn closures capture (optimizer.fused_hparams);
        # FusedTrainStep.__init__ refused any fused form without the
        # declaration, so nothing baked can escape this snapshot
        baked = tuple((k, getattr(opt, k, None))
                      for k in sorted(opt.fused_hparams))
        return (tuple(sorted(opt.lr_mult.items())),
                tuple(sorted(opt.wd_mult.items())),
                opt.wd, opt.rescale_grad, opt.clip_gradient, baked)

    def make_batch(self, data_batch) -> Dict[str, jnp.ndarray]:
        """Shard one DataBatch over the dp axis of the mesh.  In
        multi-process (dist_sync) mode each process contributes its OWN
        batch as its slice of the global array — the reference's
        data-partitioned-by-rank contract, with the global batch being
        num_workers x the bound batch size."""
        sh = self._batched()
        mp = self._multiprocess()
        if self.embed_stats is not None:
            # dedup-ratio instrumentation on the HOST ids (microseconds
            # on an int batch vs a multi-ms step) — the number
            # mx.profiler.embed_report() surfaces
            by_name = dict(zip(self.data_names, data_batch.data))
            from ..embed.sparse import resolve_cap
            for n, sp in self.sparse_embeds.items():
                ids = by_name.get(sp.ids_name)
                if ids is not None:
                    self.embed_stats.note_ids(n, ids.asnumpy())
                    self.embed_stats.note_update(
                        n, resolve_cap(sp.cap, ids.size, sp.vocab))

        def put(arr):
            a = arr._get()
            # already resident with the right sharding (a device-prefetched
            # pipeline): hand it through untouched
            if getattr(a, "sharding", None) == sh:
                return a
            if mp:
                return jax.make_array_from_process_local_data(
                    sh, np.asarray(a))
            return jax.device_put(a, sh)
        out = {}
        for name, arr in zip(self.data_names, data_batch.data):
            out[name] = put(arr)
        labels = data_batch.label or []
        for i, name in enumerate(self.label_names):
            if i < len(labels) and labels[i] is not None:
                out[name] = put(labels[i])
            else:
                # label-free forward (predict): loss layers ignore the
                # label in their forward pass
                shape = self.label_shapes.get(name)
                if shape is None:
                    raise MXNetError("missing label %r" % name)
                if mp:
                    out[name] = jax.make_array_from_process_local_data(
                        sh, np.zeros(shape, np.float32))
                else:
                    out[name] = jax.device_put(
                        jnp.zeros(shape, jnp.float32), sh)
        return out

    def make_megabatch(self, batches):
        """Assemble a K-step megabatch: ``{name: (K, B, ...) array}`` in
        the megabatched sharding.  ``batches`` is either a pre-staged
        object with a ``megabatch`` attribute and stacked ``data``/
        ``label`` lists (feed.MegaBatch — resident arrays already in the
        right sharding pass through untouched) or a list of K DataBatch,
        stacked on host and shipped in ONE device_put per input.
        Returns ``(k, megabatch_dict)``."""
        if self._multiprocess():
            raise MXNetError("superstep megabatches are single-process "
                             "only (dist training keeps per-step dispatch)")
        sh = self.megabatched_sharding()

        def put(arr):
            a = arr._get() if isinstance(arr, NDArray) else arr
            if getattr(a, "sharding", None) == sh:
                return a
            return jax.device_put(np.asarray(a), sh)

        if hasattr(batches, "megabatch"):
            k = int(batches.megabatch)
            out = {}
            for name, arr in zip(self.data_names, batches.data):
                out[name] = put(arr)
            labels = batches.label or []
            for i, name in enumerate(self.label_names):
                if i >= len(labels) or labels[i] is None:
                    raise MXNetError("superstep training needs label %r"
                                     % name)
                out[name] = put(labels[i])
            return k, out

        k = len(batches)
        from ..feed.staging import stack_batch_arrays

        def stack(arrs):
            return stack_batch_arrays(arrs, sh)

        out = {}
        for i, name in enumerate(self.data_names):
            out[name] = stack([b.data[i] for b in batches])
        for i, name in enumerate(self.label_names):
            col = []
            for b in batches:
                lab = b.label[i] if b.label and i < len(b.label) else None
                if lab is None:
                    raise MXNetError("superstep training needs label %r"
                                     % name)
                col.append(lab)
            out[name] = stack(col)
        return k, out

    def host_outputs(self, outs, batch) -> List[NDArray]:
        """Wrap program outputs for host-side consumers (update_metric,
        get_outputs).  Single-process arrays wrap as-is; multi-process
        global arrays come back as THIS worker's rows (batch-major
        outputs) or the full replicated value, matching the reference's
        per-worker metric semantics.  ``batch`` is the program input dict
        the outputs came from — its leading dim is the global row count
        (a stale module-level row count would mis-slice after an
        interleaved eval of a different batch size)."""
        if not self._multiprocess():
            return [NDArray(o) for o in outs]
        from jax.experimental import multihost_utils as mhu
        rows = batch[self.data_names[0]].shape[0] if self.data_names else None
        res = []
        for o in outs:
            local = mhu.global_array_to_host_local_array(
                o, self.mesh, self._host_spec(o, rows))
            res.append(NDArray(np.asarray(local)))
        return res

    @staticmethod
    def _host_spec(o, rows):
        """Batch-major (slice this worker's rows) vs replicated (keep
        whole), decided from the output's ACTUAL sharding: a replicated
        output whose leading dim merely coincides with the global batch
        must not be sliced.  Falls back to the row-count heuristic only
        when the sharding exposes no named spec."""
        spec = getattr(getattr(o, "sharding", None), "spec", None)
        if spec is not None:
            lead = spec[0] if len(spec) else None
            names = lead if isinstance(lead, tuple) else (lead,)
            return P("dp") if "dp" in names else P()
        return P("dp") if (o.ndim >= 1 and o.shape[0] == rows) else P()

    def head(self, name):
        """What the head ``name`` of ``trace.heads`` needs to be read
        from this step's outputs (its output's index, or a tuple that
        starts with one); None where the symbol carries no such head."""
        return next((handle for head, handle in self.heads
                     if head.name == name), None)

    # -- compiled programs ---------------------------------------------------
    def _make_step_fn(self):
        """The ONE batch-body trace: fwd+bwd+reduce+update as a pure
        function of (state, batch, lr, base_key).  _build_step jits it
        directly; build_superstep runs it K times under jax.lax.scan —
        sharing the trace is what makes superstep K bitwise-identical to
        K sequential fused steps."""
        prog = self._prog
        rescale = self.optimizer.rescale_grad
        clip = self.optimizer.clip_gradient
        lr_mult, wd, opt_update = self._lr_mult, self._wd, self._opt_update
        sparse = self.sparse_embeds
        # which params ride GSPMD constraints through the update: every
        # specced (tensor-parallel) param always; every param when the
        # cross-replica sharded weight update is on
        constrained = self.shard_update or bool(self.param_specs)

        def wsc_param(n, w):
            if n in self.param_specs:
                return jax.lax.with_sharding_constraint(
                    w, self._param_sharding(n))
            return w

        def step(state, batch, lr, base_key):
            params, fixed, aux = state["params"], state["fixed"], state["aux"]
            if self.param_specs:
                # pin the declared layouts at the trace root so GSPMD
                # propagates them through the matmuls (inserting the
                # tensor-parallel collectives) instead of re-deriving a
                # layout from scratch
                params = {n: wsc_param(n, w) for n, w in params.items()}
                fixed = {n: wsc_param(n, w) for n, w in fixed.items()}
            t = state["t"] + 1
            # per-step randomness derived in-program from one resident key:
            # creating a fresh host key every batch would cost a transfer
            rng = jax.random.fold_in(base_key, t)
            # what the step adds around the graph carries a declared
            # device scope too (trace/scopes.py): augment, cast.params,
            # embed_sparse.<table>, optimizer.<parameter>
            with _scopes.declared("augment"):
                batch = self._maybe_augment(batch, rng, train=True)

            # sparse embed prologue: dedup each table's id batch, gather
            # the unique rows ONCE (zero-masked for out-of-range / padded
            # ids), and substitute (rows, inverse indices) for (table,
            # ids) — the Embedding op computes take(rows, inv), which is
            # bit-identical to take(table, ids), but its VJP now scatters
            # into a cap-row buffer instead of the full (vocab, dim)
            # table.  full_tables keeps the real tables for the update.
            full_tables = {}
            sparse_ctx = {}
            if sparse:
                from ..embed.sparse import (_mask_oov_rows, dedup_ids,
                                            resolve_cap)
                batch = dict(batch)
                params = dict(params)
                for n, sp in sparse.items():
                    with _scopes.declared("embed_sparse." + n):
                        ids = batch[sp.ids_name]
                        flat = ids.reshape(-1).astype(jnp.int32)
                        cap = resolve_cap(sp.cap, flat.shape[0], sp.vocab)
                        uniq, inv = dedup_ids(flat, cap, sentinel=sp.vocab)
                        full_tables[n] = params[n]
                        raw = jnp.take(params[n], uniq, axis=0, mode="clip")
                        params[n] = _mask_oov_rows(raw, uniq, sp.vocab)
                        batch[sp.ids_name] = inv.reshape(ids.shape)
                        sparse_ctx[n] = (uniq, cap)

            def loss_fn(train_params):
                args = dict(train_params)
                args.update(fixed)
                args.update(batch)
                with _scopes.declared("cast.params"):
                    args = self._cast_compute(args)
                with tracing_over(self.mesh):
                    outs, new_aux = prog.eval(args, aux, rng, True)
                # aux (BN moving stats) must keep its dtype or the donated
                # state changes signature between steps
                new_aux = {k: v.astype(aux[k].dtype) if k in aux else v
                           for k, v in new_aux.items()}
                return outs, new_aux

            if self._remat:
                # MXNET_BACKWARD_DO_MIRROR=1: rematerialize the forward
                # in the backward pass — activations are not stored, the
                # bwd recomputes them (~1/3 extra FLOPs for ~activation-
                # free HBM), the sublinear-memory trade the reference's
                # mirroring implemented graph-side
                loss_fn = jax.checkpoint(loss_fn)
            outs, vjp_fn, new_aux = jax.vjp(loss_fn, params, has_aux=True)
            grads = vjp_fn([jnp.ones_like(o) for o in outs])[0]

            if sparse:
                from ..embed.sparse import sparse_apply_rows
            new_params, new_opt = {}, {}

            def constrain_update(n):
                if constrained:
                    new_params[n] = jax.lax.with_sharding_constraint(
                        new_params[n], self._param_sharding(n))
                    new_opt[n] = jax.tree_util.tree_map(
                        lambda x: jax.lax.with_sharding_constraint(
                            x, self._update_spec(x, n)), new_opt[n])

            for n, w in full_tables.items():
                # grads[n] is ALREADY per-unique-row: the take-over-inv
                # VJP segment-summed the per-occurrence grads into the
                # cap-row buffer.  Lazy per-row optimizer on the touched
                # rows only; sentinel rows drop on the scatter.
                uniq, cap = sparse_ctx[n]
                with _scopes.declared("optimizer." + n):
                    g = grads[n].astype(w.dtype) * rescale
                    if clip is not None:
                        g = jnp.clip(g, -clip, clip)
                    new_params[n], new_opt[n] = sparse_apply_rows(
                        w, state["opt"][n], uniq, g, opt_update,
                        lr * lr_mult[n], wd[n], t)
                    constrain_update(n)
            for n, w in params.items():
                if n in sparse:
                    continue
                with _scopes.declared("optimizer." + n):
                    g = grads[n].astype(w.dtype) * rescale
                    if clip is not None:
                        g = jnp.clip(g, -clip, clip)
                    if constrained:
                        # grads arrive sharded (reduce-scatter over dp,
                        # tensor-parallel shards stay put), the update
                        # runs on the shard, params leave in their
                        # at-rest spec (all-gather over dp when
                        # replicated there) and optimizer state stays
                        # sharded
                        g = jax.lax.with_sharding_constraint(
                            g, self._update_spec(g, n))
                    new_params[n], new_opt[n] = opt_update(
                        w, g, state["opt"][n], lr * lr_mult[n], wd[n], t)
                    constrain_update(n)
            merged_aux = dict(aux)
            merged_aux.update(new_aux)
            return ({"params": new_params, "opt": new_opt,
                     "aux": merged_aux, "fixed": fixed, "t": t}, outs)

        # the scope scheme is part of the module's name, which JAX's
        # persistent cache hashes (its key strips the scopes themselves)
        step.__name__ = _scopes.module_name("step")
        return step

    def _build_step(self):
        from ..compile_cache import cached_jit
        self._step = cached_jit(self._make_step_fn(), name="fused:step",
                                donate_argnums=(0,))
        _scopes.register_program("fused:step", self._step)
        return self._step

    def _build_fwd(self):
        # one program per mode (is_train closed over: cached_jit takes
        # no static argnum)
        from ..compile_cache import cached_jit
        prog = self._prog

        def make(is_train):
            def fwd(state, batch, rng):
                batch = self._maybe_augment(batch, rng, train=is_train)
                args = dict(state["params"])
                args.update(state["fixed"])
                args.update(batch)
                args = self._cast_compute(args)
                with tracing_over(self.mesh):
                    outs, _ = prog.eval(args, state["aux"], rng, is_train)
                return outs
            mode = "train" if is_train else "eval"
            return cached_jit(fwd, name="fused:fwd_%s" % mode)

        self._fwd = {True: make(True), False: make(False)}
        return self._fwd

    def build_superstep(self, k, metric_update=None, unroll=1):
        """ONE donated XLA program executing K fused steps: the step body
        from _make_step_fn traced under ``jax.lax.scan`` over the
        megabatch's leading K axis, with zero host involvement between
        steps.  ``metric_update(acc, labels, preds)`` (a traced reducer
        from EvalMetric.device_reducer) rides in the scan carry, so the
        caller drains one tiny scalar pytree every K steps instead of
        full output arrays every step.  Per-step learning rates arrive
        as a K-vector (the host resolves the scheduler at each step
        position, exactly as K sequential update() calls would).

        Returns ``superstep(state, megabatch, lrs, base_key, acc) ->
        (new_state, acc)``, jitted with the state donated.  Because the
        scan body IS the sequential step's trace (same in-program step
        counter, same per-step RNG fold), superstep K is bitwise-
        identical to K sequential fused steps — and ``unroll`` (the
        ``lax.scan`` unroll factor, an autotune="joint" knob) only
        restructures control flow, so it preserves that bit-identity."""
        step_fn = self._make_step_fn()
        label_names = self.label_names
        unroll = max(1, min(int(unroll), int(k)))

        def superstep(state, megabatch, lrs, base_key, acc):
            def body(carry, xs):
                st, a = carry
                batch, lr = xs
                st, outs = step_fn(st, batch, lr, base_key)
                if metric_update is not None:
                    labels = [batch[n] for n in label_names]
                    a = metric_update(a, labels, list(outs))
                return (st, a), None

            (state, acc), _ = jax.lax.scan(body, (state, acc),
                                           (megabatch, lrs), length=k,
                                           unroll=unroll)
            return state, acc

        superstep.__name__ = _scopes.module_name("superstep")
        from ..compile_cache import cached_jit
        program = cached_jit(superstep, name="fused:superstep:k%d" % k,
                             donate_argnums=(0,))
        _scopes.register_program(program.name, program)
        return program

    def step(self, state, batch, base_key):
        """Advance one batch; returns (new_state, outputs)."""
        if self._step is None:
            self._build_step()
        lr = self.optimizer.base_lr()
        if self._multiprocess():
            # a host scalar is replicated implicitly; an uncommitted
            # device scalar cannot join a multi-process computation
            return self._dispatch(state, batch, np.float32(lr), base_key)
        if self._lr_cache is None or self._lr_cache[0] != lr:
            # lr changes only when the scheduler fires; keep the device
            # scalar resident between changes
            self._lr_cache = (lr, jnp.asarray(lr, jnp.float32))
        return self._dispatch(state, batch, self._lr_cache[1], base_key)

    def _dispatch(self, state, batch, lr, base_key):
        """Enqueue the step program under its span (also a profiler
        annotation, so the launch sits beside the device operations in
        an xplane).  On a mesh the host dispatch time feeds the
        multichip counters; nothing here waits for the device."""
        stats = self.multichip_stats
        first = stats is not None and stats.steps == 0
        # the first mesh dispatch blocks through trace+compile on a cold
        # cache: its own span and counter, not the steady average
        with _trace.span("fused:first_step(compile)" if first
                         else "fused:dispatch", cat="train"):
            t0 = time.perf_counter()
            out = self._step(state, batch, lr, base_key)
            dt = time.perf_counter() - t0
        if first:
            stats.note_first(dt)
        elif stats is not None:
            stats.add_step(dt)
        return out

    def _gathered(self, x):
        """This process's whole copy of a leaf that lies in shards."""
        # lint: allow(raw-jit) — trivial all-gather reshard with live
        # out_shardings, built on the rare classic-fallback path; never a
        # steady-state dispatch worth a name or a warm-up
        gathered = jax.jit(lambda a: a,
                           out_shardings=self._replicated())(x)
        return gathered.addressable_data(0)

    def gather_update_leaf(self, x):
        """One sharded-at-rest optimizer-state leaf -> replicated (and,
        multi-process, host-materializable).  The classic-updater
        fallback consumes replicated per-param state; handing it raw
        dp shards would crash (non-addressable) or silently feed it a
        layout it cannot use."""
        if x is None:
            return None
        # materialize through host: the classic path mixes this with
        # per-device arrays, and a mesh-committed array would poison
        # every eager op it meets with a device mismatch
        return jnp.asarray(np.asarray(self._gathered(x)))

    def warm_step(self, state, batch, base_key) -> str:
        """Compile (or cache-load) the step program for these avals
        WITHOUT executing it: nothing is donated, no optimizer update
        runs, no state copy is needed.  The warmup entry point for
        Module.prepare / BucketingModule.precompile; safe from a warmup
        thread pool."""
        if self._step is None:
            self._build_step()
        # the lr operand must match step()'s form exactly: a host scalar
        # in multi-process mode (an uncommitted device scalar cannot
        # join a multi-process computation), a device scalar otherwise
        if self._multiprocess():
            lr = np.float32(self.optimizer.base_lr())
        else:
            lr = jnp.asarray(self.optimizer.base_lr(), jnp.float32)
        if hasattr(self._step, "warm"):
            return self._step.warm(state, batch, lr, base_key)
        return "present"     # already an installed AOT executable

    def aot_compile(self, state, batch, base_key):
        """Ahead-of-time compile the step for exactly these avals,
        install the executable as the step program, and return its
        executed-FLOP count from XLA cost analysis (0.0 when the backend
        cannot report one).  Keeps the (state, batch, lr, key) calling
        contract in one place; bench.py uses this so its utilization
        numerator is the very program its loop runs.  A warm process
        start reads the executable from JAX's persistent cache."""
        if self._step is None:
            self._build_step()
        lr = jnp.asarray(self.optimizer.base_lr(), jnp.float32)
        if hasattr(self._step, "compile_for"):
            compiled = self._step.compile_for(state, batch, lr, base_key)
        else:
            compiled = self._step.lower(state, batch, lr, base_key).compile()
        flops = 0.0
        bytes_accessed = 0.0
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            if ca:
                flops = float(ca.get("flops", 0.0))
                bytes_accessed = float(ca.get("bytes accessed", 0.0))
        except Exception:
            pass
        census = None
        if self.multichip_stats is not None:
            # the optimized (post-SPMD-partitioner) HLO names the REAL
            # collectives; parse counts + payload bytes for the
            # collective-vs-compute split in multichip_report()
            from ..compile_cache.cached import optimized_hlo_text
            txt = optimized_hlo_text(compiled)
            from .. import profiler as _prof
            census = _prof.parse_hlo_collectives(txt) if txt else None
            self.multichip_stats.set_cost(
                flops=flops, bytes_accessed=bytes_accessed,
                collectives=census)
        # the cost-model featurizer reads this regardless of topology
        # (multichip_stats only exists past one device)
        self.cost_summary = {"flops": flops,
                             "bytes_accessed": bytes_accessed,
                             "collectives": census}
        self._step = compiled
        self._lr_cache = None
        return flops

    def forward_only(self, state, batch, rng, is_train=False):
        if self._fwd is None:
            self._build_fwd()
        return self._fwd[bool(is_train)](state, batch, rng)

    # -- host sync -----------------------------------------------------------
    def read_params(self, state, arg_params: Dict[str, NDArray],
                    aux_params: Dict[str, NDArray]):
        """Pull the live state back into host-side NDArray dicts: every
        array handed out has the ``cpu`` context and is the caller's own.
        It is a copy (the state buffers are donated to the next step,
        which would delete the arrays under anything that aliased them)
        and costs one device->host transfer; no device holds it."""
        host_device = cpu().jax_device()

        def host(x):
            # a replicated leaf is read from one device; a leaf that lies
            # in shards (a tensor-parallel weight) is whole only once
            # assembled: by jax where this process addresses every shard,
            # by a gather where it does not
            if not x.is_fully_addressable:
                x = x.addressable_data(0) if x.is_fully_replicated \
                    else self._gathered(x)
            return NDArray(jax.device_put(_read_to_host(x, False)[0],
                                          host_device))
        for n in self.train_names:
            arg_params[n] = host(state["params"][n])
        for n in self.fixed_names:
            arg_params[n] = host(state["fixed"][n])
        for n in self.aux_names:
            aux_params[n] = host(state["aux"][n])
