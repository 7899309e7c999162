"""Predictor: the deployment mini-API.

Reference: include/mxnet/c_predict_api.h (8 MXPred* functions: create a
predictor from symbol JSON + param blob only, set input, forward, get
output) + amalgamation/ (single-file predict build for mobile).

TPU-native: a Predictor loads the two checkpoint artifacts, jit-compiles
one inference XLA program per input shape, and exposes the same minimal
surface (set_input/forward/get_output + reshape).  The "amalgamation"
capability — deploy with minimal deps — holds because this module only
needs jax + numpy + the symbol/executor layers.

Executables are cached per input-shape set the way BucketingModule
caches per-bucket modules: ``reshape()`` back to a previously seen shape
reuses the compiled program (and all cached executors share one set of
parameter buffers through ``shared_exec``), so a serving loop cycling
through shape buckets never recompiles and ``set_params`` hot-swaps
weights into every bucket at once.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .base import MXNetError
from .context import Context, cpu
from .ndarray import NDArray, load as nd_load, array as nd_array
from .symbol import Symbol, load_json as sym_load_json

__all__ = ["Predictor", "load_ndarray_file", "create_predictor",
           "load_checkpoint_pair", "strip_param_prefixes"]


def strip_param_prefixes(params: Dict[str, NDArray]) -> Dict[str, NDArray]:
    """Drop the ``arg:``/``aux:`` checkpoint key prefixes (model.py
    save_checkpoint convention) — shared by the Python and C predict paths."""
    return {(k[4:] if k.startswith(("arg:", "aux:")) else k): v
            for k, v in params.items()}


def _as_nd(v) -> NDArray:
    """To NDArray PRESERVING dtype (nd.array defaults to f32, which
    would silently upcast int8/fp16 params on hot reload)."""
    if isinstance(v, NDArray):
        return v
    arr = np.asarray(v)
    return nd_array(arr, dtype=arr.dtype)


def load_ndarray_file(path: str) -> Dict[str, NDArray]:
    """MXNDListCreate analogue: read a saved param blob."""
    return strip_param_prefixes(nd_load(path))


def load_checkpoint_pair(prefix: str, epoch: int) -> Tuple[str, Dict]:
    """-> (symbol_json, params dict) for a ``save_checkpoint`` pair.

    Deployment-time analogue of model.load_checkpoint's error story:
    failures name the exact file and distinguish *missing* (with the
    candidate files that DO exist for this prefix listed) from *corrupt*
    (a torn write from a pre-atomic-save crash)."""
    import glob
    import os
    sym_file = "%s-symbol.json" % prefix
    param_file = "%s-%04d.params" % (prefix, epoch)
    if not os.path.exists(sym_file):
        pat = os.path.join(os.path.dirname(sym_file) or ".", "*-symbol.json")
        have = sorted(glob.glob(pat))
        raise MXNetError(
            "predictor symbol file missing: %r (symbol files present in "
            "that directory: %s)" % (sym_file, have or "none"))
    try:
        with open(sym_file) as f:
            sym_json = f.read()
        sym_load_json(sym_json)      # parse now: corrupt fails loud HERE
    except MXNetError as e:
        raise MXNetError(
            "predictor symbol file corrupt: %r (%s) — likely a torn write "
            "from a crashed save predating atomic publishes"
            % (sym_file, e)) from e
    except Exception as e:
        raise MXNetError(
            "predictor symbol file corrupt: %r (%s: %s) — likely a torn "
            "write from a crashed save predating atomic publishes"
            % (sym_file, type(e).__name__, e)) from e
    if not os.path.exists(param_file):
        have = sorted(glob.glob("%s-*.params" % prefix))
        raise MXNetError(
            "predictor params file missing: %r (existing param files for "
            "this prefix: %s)" % (param_file, have or "none"))
    try:
        params = load_ndarray_file(param_file)
    except MXNetError as e:
        raise MXNetError(
            "predictor params file corrupt: %r (%s) — likely a torn write "
            "from a crashed save predating atomic publishes"
            % (param_file, e)) from e
    except Exception as e:
        raise MXNetError(
            "predictor params file corrupt: %r (%s: %s) — likely a torn "
            "write from a crashed save predating atomic publishes"
            % (param_file, type(e).__name__, e)) from e
    return sym_json, params


class Predictor:
    """MXPredCreate analogue (c_predict_api.h:1-207)."""

    def __init__(self, symbol_json: str, param_bytes_or_path,
                 input_shapes: Dict[str, Tuple[int, ...]],
                 dev_type: str = "cpu", dev_id: int = 0,
                 type_dict: Optional[Dict] = None,
                 pipeline=None):
        self.symbol = sym_load_json(symbol_json) \
            if isinstance(symbol_json, str) and symbol_json.lstrip().startswith("{") \
            else sym_load_json(open(symbol_json).read())
        self.ctx = Context(dev_type, dev_id)
        if isinstance(param_bytes_or_path, (dict,)):
            params = strip_param_prefixes(param_bytes_or_path)
        else:
            params = load_ndarray_file(param_bytes_or_path)
        # graph-optimization hook (mxnet_tpu.passes): run the pipeline on
        # the checkpointed f32 graph, bind the TRANSFORMED symbol (its
        # graph attrs carry the pipeline fingerprint).  set_params
        # replays the params-side transform (re-quantize/cast) so hot
        # weight reload keeps working against the rewritten graph.
        self._pipeline = pipeline
        if pipeline is not None:
            sym, qparams = pipeline.run(self.symbol, params)
            self.symbol, params = sym, dict(qparams)
            # a pass that retypes an input (u8 wire) publishes it here;
            # explicit caller type_dict entries still win below
            overrides = dict(pipeline.type_overrides)
            overrides.update(type_dict or {})
            type_dict = overrides
        # each list_arguments() call walks the whole graph — compute the
        # name sets ONCE (set_params runs them under the serving lock)
        self._arg_names = frozenset(self.symbol.list_arguments())
        self._aux_names = frozenset(self.symbol.list_auxiliary_states())
        self._arg_params = {k: v for k, v in params.items()
                            if k in self._arg_names}
        self._aux_params = {k: v for k, v in params.items()
                            if k in self._aux_names}
        # Bind every argument at its STORED dtype (an fp16 checkpoint
        # binds an fp16 program, not an f32 one that silently upcasts),
        # and default the non-param inputs to the params' common float
        # dtype so "load an fp16 model, predict" works without a
        # type_dict.  Explicit type_dict entries win.
        self._type_dict: Dict[str, np.dtype] = {
            k: np.dtype(getattr(v, "dtype", np.float32))
            for k, v in self._arg_params.items()}
        float_dts = {dt for dt in self._type_dict.values() if dt.kind == "f"}
        if len(float_dts) == 1:
            common = float_dts.pop()
            param_names = set(self._type_dict)
            for name in self._arg_names:
                if name not in param_names:
                    self._type_dict[name] = common
        for k, v in (type_dict or {}).items():
            self._type_dict[k] = np.dtype(v)
        # per-shape executor cache (BucketingModule's bucket-cache idea):
        # key -> bound executor; all executors share parameter buffers
        self._exec_cache: Dict[Tuple, object] = {}
        self._bind(dict(input_shapes))

    @staticmethod
    def _shape_key(input_shapes: Dict[str, Tuple[int, ...]]) -> Tuple:
        return tuple(sorted((k, tuple(v)) for k, v in input_shapes.items()))

    def _bind(self, input_shapes: Dict[str, Tuple[int, ...]]):
        self._input_shapes = input_shapes
        key = self._shape_key(input_shapes)
        cached = self._exec_cache.get(key)
        if cached is not None:
            self._exec = cached
            return
        # new shape set: bind sharing the parameter NDArrays of the first
        # executor (simple_bind shared_exec reuses identically-shaped
        # arrays, which params always are — only input shapes vary)
        shared = next(iter(self._exec_cache.values())) \
            if self._exec_cache else None
        self._exec = self.symbol.simple_bind(
            self.ctx, grad_req="null", type_dict=dict(self._type_dict),
            shared_exec=shared, **input_shapes)
        self._exec.copy_params_from(self._arg_params, self._aux_params,
                                    allow_extra_params=True)
        self._exec_cache[key] = self._exec

    def set_input(self, name: str, data) -> None:
        """MXPredSetInput: cast to the BOUND input's dtype — the executor
        decides (fp16/int32/uint8 models), not a hardcoded float32."""
        arr = self._exec.arg_dict[name]
        arr[:] = np.asarray(data, dtype=arr.dtype)

    def set_params(self, arg_params: Optional[Dict] = None,
                   aux_params: Optional[Dict] = None) -> None:
        """Hot-swap weights into EVERY cached executor (they share param
        buffers, but iterating keeps the swap correct even for executors
        bound before sharing was possible).  Later ``_bind`` calls copy
        from the updated host dicts, so new shapes see the new weights.

        With a pass pipeline bound, incoming f32 weights are pushed
        through ``pipeline.transform_params`` first — re-folded,
        re-quantized to int8 + wscale, re-cast — so a training loop can
        keep hot-reloading checkpoints into a quantized serving graph."""
        if self._pipeline is not None and (arg_params or aux_params):
            merged = dict(strip_param_prefixes(dict(arg_params or {})))
            merged.update(strip_param_prefixes(dict(aux_params or {})))
            merged = self._pipeline.transform_params(merged)
            arg_params, aux_params = merged, None
        if arg_params:
            arg_params = strip_param_prefixes(dict(arg_params))
            for k, v in arg_params.items():
                if k in self._arg_names:
                    self._arg_params[k] = _as_nd(v)
                elif k in self._aux_names:
                    self._aux_params[k] = _as_nd(v)
        if aux_params:
            for k, v in strip_param_prefixes(dict(aux_params)).items():
                if k in self._aux_names:
                    self._aux_params[k] = _as_nd(v)
        seen = set()
        for ex in self._exec_cache.values():
            if id(ex) in seen:
                continue
            seen.add(id(ex))
            ex.copy_params_from(self._arg_params, self._aux_params,
                                allow_extra_params=True)

    def forward(self) -> None:
        """MXPredForward."""
        self._exec.forward(is_train=False)

    def get_output(self, index: int) -> np.ndarray:
        """MXPredGetOutput."""
        return self._exec.outputs[index].asnumpy()

    def get_output_shape(self, index: int) -> Tuple[int, ...]:
        """MXPredGetOutputShape."""
        return tuple(self._exec.outputs[index].shape) if self._exec._outputs_nd \
            else tuple(self.symbol.infer_shape(**self._input_shapes)[1][index])

    def reshape(self, input_shapes: Dict[str, Tuple[int, ...]]) -> "Predictor":
        """MXPredReshape: new input shapes, shared weights.  A previously
        seen shape set reuses its compiled executor from the cache."""
        self._bind(dict(input_shapes))
        return self

    def ensure_bound(self, input_shapes: Dict[str, Tuple[int, ...]]):
        """Bind (or fetch) the executor for this shape set WITHOUT
        switching the predictor's current executor — the warmup path:
        ServeEngine binds its whole bucket grid up front (sequentially;
        binding shares the parameter buffers) and then compiles the
        executors' programs in parallel via ``Executor.precompile``.
        Returns the (cached) executor."""
        key = self._shape_key(input_shapes)
        cached = self._exec_cache.get(key)
        if cached is not None:
            return cached
        keep_exec, keep_shapes = self._exec, self._input_shapes
        try:
            self._bind(dict(input_shapes))
            return self._exec
        finally:
            self._exec, self._input_shapes = keep_exec, keep_shapes

    def precompile(self, shape_sets, threads=None):
        """Bind every shape set and AOT-compile its inference program
        through a bounded thread pool (see compile_cache.parallel_warm);
        with JAX's persistent cache placed, a warm process start reads
        the executables instead of compiling."""
        from .compile_cache import parallel_warm
        execs = [(dict(s), self.ensure_bound(s)) for s in shape_sets]
        return parallel_warm(
            [("shapes %s" % (sorted(s.items()),),
              lambda e=ex: e.precompile(("fwd_eval",)))
             for s, ex in execs], threads=threads)

    def predict(self, data) -> np.ndarray:
        """Convenience one-shot: set first input, forward, output 0."""
        first = next(iter(self._input_shapes))
        self.set_input(first, data)
        self.forward()
        return self.get_output(0)


def create_predictor(prefix: str, epoch: int, input_shapes,
                     dev_type="cpu", dev_id=0, type_dict=None) -> Predictor:
    """Build a Predictor from a save_checkpoint pair.  Missing or corrupt
    artifacts raise a clear MXNetError naming candidates (see
    load_checkpoint_pair)."""
    sym_json, params = load_checkpoint_pair(prefix, epoch)
    return Predictor(sym_json, params, input_shapes, dev_type, dev_id,
                     type_dict=type_dict)
