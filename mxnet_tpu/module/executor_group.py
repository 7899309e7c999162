"""DataParallelExecutorGroup for the Module API.

Reference: python/mxnet/module/executor_group.py (431 LoC): per-device
executors, batch slicing, gradient aggregation views.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from .. import trace as _trace
from ..context import Context, cpu
from ..ndarray import NDArray, zeros as nd_zeros, concatenate as nd_concatenate
from ..executor_manager import (_split_input_slice, _load_data, _load_label)
from ..symbol import Symbol

__all__ = ["DataParallelExecutorGroup"]


class DataParallelExecutorGroup:
    """Executors over devices for one symbol (reference executor_group.py:15)."""

    def __init__(self, symbol: Symbol, contexts: Sequence[Context],
                 workload, data_shapes, label_shapes, param_names,
                 for_training, inputs_need_grad, shared_group=None,
                 input_types=None, logger=logging, fixed_param_names=None,
                 grad_req="write", no_slice_names=None):
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload if workload else [1] * len(contexts)
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.input_types = input_types
        self.logger = logger
        self.fixed_param_names = fixed_param_names or []
        # inputs the caller declares are NOT batch-major even if their
        # leading dim happens to equal the batch size (rcnn rois with
        # num_rois == batch_size would otherwise be silently split)
        self.no_slice = frozenset(no_slice_names or ())
        self.shared_group = shared_group

        self.batch_size = None
        self.slices = None
        self.execs: List = []
        self.data_shapes = None
        self.label_shapes = None
        self.data_names = None
        self.label_names = None
        self.data_arrays = None
        self.label_arrays = None
        self.param_arrays = None
        self.grad_arrays = None
        self.aux_arrays = None
        self.grad_req = grad_req

        self.bind_exec(data_shapes, label_shapes, shared_group)

    def bind_exec(self, data_shapes, label_shapes, shared_group=None):
        self.batch_size = data_shapes[0][1][0]
        self.slices = _split_input_slice(self.batch_size, self.workload)
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        self.data_names = [x[0] for x in data_shapes]
        self.label_names = [x[0] for x in label_shapes] if label_shapes else []

        grad_req = {}
        for name in self.arg_names:
            if self.for_training and name in self.param_names \
                    and name not in self.fixed_param_names:
                grad_req[name] = self.grad_req
            elif self.for_training and self.inputs_need_grad \
                    and name in self.data_names:
                grad_req[name] = self.grad_req
            else:
                grad_req[name] = "null"

        # inputs whose leading dim is NOT the batch size (Fast R-CNN rois
        # and roi-level labels, attention masks, ...) are not sliced —
        # each device gets the full array (with several devices such
        # inputs cannot be split consistently with the image slice, the
        # same limitation that made the reference's rcnn example carry
        # its own MutableModule)
        def _batch_major(name, s):
            return (name not in self.no_slice
                    and len(s) >= 1 and s[0] == self.batch_size)

        if len(self.contexts) > 1 and any(
                not _batch_major(name, s)
                for name, s in data_shapes + (label_shapes or [])):
            raise MXNetError(
                "inputs whose leading dim is not the batch size (or that "
                "bind() marked no-slice) cannot be split across devices "
                "(they are replicated whole); bind on a single context or "
                "restructure the input")

        self.execs = []
        for i, ctx in enumerate(self.contexts):
            n = self.slices[i].stop - self.slices[i].start
            shapes = {name: (tuple([n] + list(s[1:]))
                             if _batch_major(name, s) else tuple(s))
                      for name, s in data_shapes + (label_shapes or [])}
            shared_exec = shared_group.execs[i] if shared_group else None
            self.execs.append(self.symbol.simple_bind(
                ctx, grad_req=grad_req, type_dict=self.input_types,
                shared_exec=shared_exec, **shapes))

        def _targets(name, shape):
            full = slice(0, shape[0] if shape else 1)
            return [((self.slices[i] if _batch_major(name, shape) else full),
                     e.arg_dict[name]) for i, e in enumerate(self.execs)]

        self.data_arrays = [_targets(name, dict(data_shapes)[name])
                            for name in self.data_names]
        self.label_arrays = [_targets(name, dict(label_shapes or [])[name])
                             for name in self.label_names]
        self.param_arrays = [
            [e.arg_dict[name] for e in self.execs]
            for name in self.param_names]
        self.grad_arrays = [
            [e.grad_dict.get(name) for e in self.execs]
            for name in self.param_names] if self.for_training else []
        self.input_grad_arrays = [
            [e.grad_dict.get(name) for e in self.execs]
            for name in self.data_names] if self.inputs_need_grad else []
        self.aux_arrays = [
            [e.aux_dict[name] for e in self.execs]
            for name in self.aux_names]

    def release(self):
        """Drop the executors and every array they hold (arguments,
        gradients, aux states, outputs).  The shapes stay, so
        ``bind_exec(self.data_shapes, self.label_shapes)`` binds them
        anew, zeroed.  For the owner whose live weights are elsewhere:
        ``Module`` on the fused train step."""
        self.execs = []
        self.data_arrays = self.label_arrays = None
        self.param_arrays = self.grad_arrays = self.aux_arrays = None
        self.input_grad_arrays = None

    def set_params(self, arg_params, aux_params):
        for exe in self.execs:
            exe.copy_params_from(arg_params, aux_params)

    def get_params(self, arg_params, aux_params):
        """Average over devices into the given dicts (reference
        executor_group.py get_params)."""
        for name, block in zip(self.param_names, self.param_arrays):
            weight = sum(w.copyto(cpu())._get() for w in block) / len(block)
            arg_params[name] = NDArray(weight).astype(block[0].dtype)
        for name, block in zip(self.aux_names, self.aux_arrays):
            weight = sum(w.copyto(cpu())._get() for w in block) / len(block)
            aux_params[name] = NDArray(weight).astype(block[0].dtype)

    @_trace.span("executor:forward", cat="train")
    def forward(self, data_batch, is_train=None):
        _load_data(data_batch, self.data_arrays)
        if is_train is None:
            is_train = self.for_training
        if self.label_arrays and data_batch.label:
            _load_label(data_batch, self.label_arrays)
        for exe in self.execs:
            exe.forward(is_train=is_train)

    @_trace.span("executor:backward", cat="train")
    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to run backward"
        for i, exe in enumerate(self.execs):
            out_grads_slice = None
            if out_grads is not None:
                # slice only batch-major heads; roi-level outputs (rcnn)
                # carry all rows on every device
                out_grads_slice = [
                    g[self.slices[i].start:self.slices[i].stop]
                    if g.shape[0] == self.batch_size else g
                    for g in out_grads]
            exe.backward(out_grads=out_grads_slice)

    def get_outputs(self, merge_multi_context=True):
        outputs = [[exe.outputs[i] for exe in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            return [nd_concatenate(x, axis=0) if len(x) > 1 else x[0]
                    for x in outputs]
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        if merge_multi_context:
            return [nd_concatenate(x, axis=0) if len(x) > 1 else x[0]
                    for x in self.input_grad_arrays]
        return self.input_grad_arrays

    def update_metric(self, eval_metric, labels):
        names = list(self.label_names or [])
        names += [None] * (len(labels) - len(names))
        for texec, islice in zip(self.execs, self.slices):
            labels_slice = [label[islice.start:islice.stop]
                            if (name not in self.no_slice
                                and label.shape[0] == self.batch_size)
                            else label
                            for name, label in zip(names, labels)]
            eval_metric.update(labels_slice, texec.outputs)

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
