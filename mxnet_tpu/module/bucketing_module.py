"""BucketingModule: variable-length sequence training via per-bucket executors
sharing memory.

Reference: python/mxnet/module/bucketing_module.py (switch_bucket at 189-213,
shared binding 245-258); docs/how_to/bucketing.md.

TPU-native: each bucket is a separately jit-compiled program (per-shape
executable cache); buckets share parameter NDArrays through shared_module, so
"shared memory pool" becomes shared jax buffers + XLA executable cache —
exactly the per-shape jit-cache design SURVEY §5.7 prescribes.
"""
from __future__ import annotations

import logging

from ..base import MXNetError
from ..initializer import Uniform
from .base_module import BaseModule, _recorded
from .module import Module

__all__ = ["BucketingModule"]


class BucketingModule(BaseModule):
    """Bucketing over a sym_gen(bucket_key) factory (reference
    bucketing_module.py:16)."""

    def __init__(self, sym_gen, default_bucket_key=None, logger=logging,
                 context=None, work_load_list=None):
        super().__init__(logger=logger)
        assert default_bucket_key is not None
        self._default_bucket_key = default_bucket_key
        self._sym_gen = sym_gen
        self._context = context
        self._work_load_list = work_load_list
        self._buckets = {}
        self._curr_module = None

    def _reset_bind(self):
        self.binded = False
        self._buckets = {}
        self._curr_module = None

    @property
    def data_names(self):
        if self.binded:
            return self._curr_module.data_names
        _, data_names, _ = self._call_sym_gen(self._default_bucket_key)
        return data_names

    @property
    def output_names(self):
        if self.binded:
            return self._curr_module.output_names
        symbol, _, _ = self._call_sym_gen(self._default_bucket_key)
        return symbol.list_outputs()

    @property
    def data_shapes(self):
        assert self.binded
        return self._curr_module.data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._curr_module.label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._curr_module.output_shapes

    def _call_sym_gen(self, bucket_key):
        res = self._sym_gen(bucket_key)
        if isinstance(res, tuple):
            return res
        return (res, ("data",), ("softmax_label",))

    def _bucket_module(self, bucket_key):
        """An unbound Module over ``bucket_key``'s symbol, whose spans
        carry this module's number."""
        symbol, data_names, label_names = self._call_sym_gen(bucket_key)
        module = Module(symbol, data_names, label_names,
                        logger=self.logger, context=self._context,
                        work_load_list=self._work_load_list)
        module._trace_module = self._trace_module
        return module

    def get_params(self):
        """The current bucket's ``Module.get_params``.  The buckets'
        modules share ONE pair of dicts, and ``Module``'s rule holds for
        it once: only the default bucket's module can own a fused state
        (the others borrow its optimizer), and binding a second bucket
        ends it, reading the weights back into the dicts first."""
        assert self.binded and self.params_initialized
        return self._curr_module.get_params()

    @_recorded("module:init_params")
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        self._curr_module.init_params(initializer=initializer,
                                      arg_params=arg_params,
                                      aux_params=aux_params,
                                      allow_missing=allow_missing,
                                      force_init=force_init)
        self.params_initialized = True

    @_recorded("module:bind", "for_training")
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind the default bucket (reference bucketing_module.py:137)."""
        assert shared_module is None, \
            "shared_module for BucketingModule is not supported"
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        module = self._bucket_module(self._default_bucket_key)
        module.bind(data_shapes, label_shapes, for_training,
                    inputs_need_grad, force_rebind=False, shared_module=None,
                    grad_req=grad_req)
        self._curr_module = module
        self._buckets[self._default_bucket_key] = module

    def switch_bucket(self, bucket_key, data_shapes, label_shapes=None):
        """Switch to a bucket, binding it lazily with shared memory
        (reference bucketing_module.py:189-213)."""
        assert self.binded, "call bind before switching bucket"
        if bucket_key not in self._buckets:
            module = self._bucket_module(bucket_key)
            module.bind(data_shapes, label_shapes, self._curr_module.for_training,
                        self._curr_module.inputs_need_grad,
                        force_rebind=False,
                        shared_module=self._buckets[self._default_bucket_key],
                        grad_req=self._curr_module._exec_group.grad_req)
            self._buckets[bucket_key] = module
        self._curr_module = self._buckets[bucket_key]

    @_recorded("module:prepare")
    def prepare(self, bucket_shapes):
        """Pre-bind and pre-compile bucket executables off the hot loop.

        The reference kept bucket switching cheap through the shared
        memory pool (graph_executor.h:50-56 shared_exec); here each bucket
        is its own jit-compiled program, so the first batch of a new
        bucket inside the training loop would otherwise stall on full XLA
        compilation.  ``prepare`` pays those compiles up front by binding
        every bucket and driving one zero-batch through its
        forward(+backward when bound for training) path.

        Parameters
        ----------
        bucket_shapes : dict bucket_key -> (data_shapes, label_shapes)
            or iterable of (bucket_key, data_shapes, label_shapes).
            Shapes use the usual [(name, shape), ...] form; label_shapes
            may be None.
        """
        assert self.binded and self.params_initialized, \
            "call bind and init_params before prepare"
        # cold buckets share arg/grad arrays with the live bucket
        # (simple_bind shared_exec), so warming them between backward()
        # and update() would overwrite the live bucket's pending
        # gradients with zero-batch ones
        assert not getattr(self._curr_module, "_grads_pending", False), \
            "prepare() must not be called between backward() and " \
            "update(): warming shares (and would clobber) the live " \
            "bucket's pending gradient arrays"
        from ..io import DataBatch
        from ..ndarray import zeros as nd_zeros, waitall

        if isinstance(bucket_shapes, dict):
            items = [(k, v[0], v[1]) for k, v in bucket_shapes.items()]
        else:
            items = [tuple(it) for it in bucket_shapes]
        # already-bound but still-cold buckets (e.g. the default bucket
        # right after bind(): never forwarded, empty executable cache)
        # get warmed at their bound shapes too — a prepared module must
        # not compile anything inside the loop.
        listed = {it[0] for it in items}
        for key, mod in self._buckets.items():
            if key not in listed and self._is_cold(mod):
                items.append((key, mod._data_shapes, mod._label_shapes))

        keep = self._curr_module
        for key, data_shapes, label_shapes in items:
            self.switch_bucket(key, data_shapes, label_shapes)
            mod = self._curr_module
            if not self._is_cold(mod):
                # already compiled AND holding live outputs/gradients in
                # its (shared) exec group — warming again would clobber
                # them for nothing
                continue
            batch = DataBatch(
                data=[nd_zeros(s) for _, s in data_shapes],
                label=[nd_zeros(s) for _, s in (label_shapes or [])],
                bucket_key=key,
                provide_data=list(data_shapes),
                provide_label=list(label_shapes) if label_shapes else None)
            if mod._fused is not None and self.for_training:
                # fused single-program path: compile the donated step on
                # a throwaway copy of the state (running the real step
                # would both donate the live buffers and apply a
                # zero-gradient optimizer update)
                mod._fused_warmup(batch)
            else:
                mod.forward(batch, is_train=self.for_training)
                if self.for_training:
                    mod.backward()
                    # the warmup's zero-batch grads are throwaway — no
                    # update() will consume them, so they must not trip
                    # the pending-gradient guard on a later prepare()
                    mod._grads_pending = False
        waitall()
        self._curr_module = keep

    @staticmethod
    def _is_cold(mod):
        """True when no program has been compiled for this bucket yet."""
        if mod._fused is not None:
            step = mod._fused._step
            if step is None:
                return True
            # cached_jit wrapper: exists as soon as _build_step ran, but
            # is only warm once something compiled/loaded through it
            return not getattr(step, "has_compiled", True)
        return all(not ex.has_compiled() for ex in mod._exec_group.execs)

    def precompile(self, bucket_shapes, threads=None):
        """Bind every listed bucket and AOT-compile its programs through
        a bounded thread pool — the parallel, compile-only successor to
        ``prepare()``: nothing executes, so no aux state moves, no
        shared gradient arrays are clobbered, and N buckets compile in
        max(compile) wall time instead of sum (XLA releases the GIL).
        A restarted process traces each bucket again here and reads its
        executable from JAX's persistent cache, where one is placed.

        Parameters
        ----------
        bucket_shapes : dict bucket_key -> (data_shapes, label_shapes)
            or iterable of (bucket_key, data_shapes, label_shapes)
            (the ``prepare()`` forms).
        threads : int, optional
            Pool bound; default min(n_buckets, cpu count).
        """
        assert self.binded and self.params_initialized, \
            "call bind and init_params before precompile"
        if self.for_training and not self.optimizer_initialized:
            # same contract as Module.prepare: the hot loop's program
            # form (fused vs classic) is decided by init_optimizer
            raise MXNetError(
                "precompile() on a training-bound bucketing module "
                "needs init_optimizer first")
        from ..compile_cache import parallel_warm

        if isinstance(bucket_shapes, dict):
            items = [(k, v[0], v[1]) for k, v in bucket_shapes.items()]
        else:
            items = [tuple(it) for it in bucket_shapes]
        listed = {it[0] for it in items}
        for key, mod in self._buckets.items():
            if key not in listed and self._is_cold(mod):
                items.append((key, mod._data_shapes, mod._label_shapes))

        # bind sequentially (cheap; switch_bucket mutates shared module
        # state), collect one compile thunk per cold bucket
        keep = self._curr_module
        tasks = []
        try:
            for key, data_shapes, label_shapes in items:
                self.switch_bucket(key, data_shapes, label_shapes)
                mod = self._curr_module
                if not self._is_cold(mod):
                    continue
                label = "bucket %r (data %s)" % (key, list(data_shapes))
                if mod._fused is not None and self.for_training:
                    from ..io import DataBatch
                    from ..ndarray import zeros as nd_zeros
                    mod._fused_ensure_state()
                    batch = mod._fused.make_batch(DataBatch(
                        data=[nd_zeros(s) for _, s in data_shapes],
                        label=[nd_zeros(s)
                               for _, s in (label_shapes or [])]))
                    tasks.append((label,
                                  lambda m=mod, b=batch: m._fused.warm_step(
                                      m._fused_state, b, m._fused_key)))
                else:
                    kinds = None if self.for_training else ("fwd_eval",)
                    for ex in mod._exec_group.execs:
                        tasks.append((label,
                                      lambda e=ex, k=kinds: e.precompile(k)))
        finally:
            self._curr_module = keep
        parallel_warm(tasks, threads=threads)
        return [label for label, _ in tasks]

    @_recorded("module:init_optimizer")
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=None, force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring.")
            return
        if optimizer_params is None:
            optimizer_params = (("learning_rate", 0.01),)
        self._curr_module.init_optimizer(kvstore, optimizer, optimizer_params,
                                         force_init=force_init)
        for mod in self._buckets.values():
            if mod is not self._curr_module:
                mod.borrow_optimizer(self._curr_module)
        self.optimizer_initialized = True

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self.switch_bucket(data_batch.bucket_key,
                           data_batch.provide_data,
                           data_batch.provide_label)
        self._curr_module.forward(data_batch, is_train=is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._curr_module.backward(out_grads=out_grads)

    def update(self):
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._curr_module.update()

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._curr_module.get_outputs(
            merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._curr_module.get_input_grads(
            merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        assert self.binded and self.params_initialized
        self._curr_module.update_metric(eval_metric, labels)

    @property
    def symbol(self):
        assert self.binded
        return self._curr_module.symbol

    def install_monitor(self, mon):
        assert self.binded
        for mod in self._buckets.values():
            mod.install_monitor(mon)
