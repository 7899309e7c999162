"""Operator registry: the TPU-native replacement for the reference's
OperatorProperty + SimpleOp registries.

Reference: include/mxnet/operator.h:76-480 (OperatorProperty: param init via
dmlc::Parameter, InferShape/InferType, ListArguments/Outputs/AuxiliaryStates),
include/mxnet/operator_util.h:92-486 (SimpleOp dual ndarray+symbol
registration), src/operator/operator.cc.

TPU-native design: an op is **metadata + a pure jnp/lax forward function**.
There is no hand-written Backward — JAX autodiff provides gradients; ops whose
reference backward is *not* the derivative of their forward (loss layers like
SoftmaxOutput, MakeLoss, regression outputs, BlockGrad) wrap ``custom_vjp`` so
executor.backward reproduces reference gradient semantics exactly.  Mutable
auxiliary states (BatchNorm moving stats) are threaded functionally: forward
returns aux updates, the executor carries them (SURVEY §7 hard-part 6).

The registry metadata (names, param schemas with dmlc-style string parsing,
shape/type rules, input/output names) is the part reproduced 1:1 — it is what
makes ``mx.sym.*`` / ``mx.nd.*`` constructors, docstrings, kwarg validation
and JSON serialization work like the reference.
"""
from __future__ import annotations

import ast
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import MXNetError, _AttrDict

__all__ = ["Param", "OpDef", "register_op", "get_op", "list_ops", "OpContext"]

_OP_REGISTRY: Dict[str, "OpDef"] = {}


def _parse_shape(v):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    if isinstance(v, (int, np.integer)):
        return (int(v),)
    if isinstance(v, str):
        v = v.strip()
        val = ast.literal_eval(v)
        if isinstance(val, (int, float)):
            return (int(val),)
        return tuple(int(x) for x in val)
    raise ValueError("cannot parse shape from %r" % (v,))


def _parse_bool(v):
    if isinstance(v, str):
        return v.lower() in ("1", "true", "yes")
    return bool(v)


class Param:
    """One dmlc::Parameter field: typed, defaulted, documented, str-parseable."""

    def __init__(self, name: str, typ, default=None, required: bool = False,
                 doc: str = "", enum: Optional[Sequence[str]] = None):
        self.name = name
        self.typ = typ
        self.default = default
        self.required = required
        self.doc = doc
        self.enum = enum

    def parse(self, value):
        if value is None:
            return None
        if self.typ == "shape":
            return _parse_shape(value)
        if self.typ == "symbol":
            # a node that holds a graph (ops/control_flow.py): the Symbol
            # itself, or its JSON as a saved graph carries it
            from ..symbol import Symbol, load_json
            return value if isinstance(value, Symbol) else load_json(value)
        if self.typ is bool:
            return _parse_bool(value)
        if self.typ is int:
            return int(float(value)) if isinstance(value, str) else int(value)
        if self.typ is float:
            return float(value)
        if self.typ is str:
            value = str(value)
            if self.enum and value not in self.enum:
                raise MXNetError("param %s expects one of %s, got %r"
                                 % (self.name, self.enum, value))
            return value
        return value

    def to_string(self, value) -> str:
        """Serialize for symbol JSON attrs (reference stores param strings)."""
        if self.typ == "shape":
            return "(" + ", ".join(str(x) for x in value) + ")"
        if self.typ == "symbol":
            return value.tojson(indent=None)
        if self.typ is bool:
            return "True" if value else "False"
        return str(value)


class OpContext:
    """Per-call execution context handed to forward (is_train flag + PRNG key).

    Reference analogue: OpContext{is_train, RunContext, requested resources}
    (include/mxnet/operator.h:46-66); the RNG resource becomes a jax PRNG key.
    """

    def __init__(self, is_train: bool = True, rng=None):
        self.is_train = is_train
        self.rng = rng


class OpDef:
    """Base class for op definitions.  Subclass and register with @register_op.

    Override: ``params`` (list of Param), ``list_arguments``, ``list_outputs``,
    ``list_auxiliary_states``, ``infer_shape``, ``infer_type``, ``forward``.
    """

    params: List[Param] = []
    # name hint used by NameManager for auto-naming (e.g. "fullyconnected")
    hint: Optional[str] = None
    # if True this op needs a PRNG key at runtime (Dropout, RReLU, samplers)
    needs_rng: bool = False
    # key_var_num_args analogue: op takes variable #inputs (Concat, ElementWiseSum)
    variable_args: Optional[str] = None  # name of the num_args param
    # ops forwarding arbitrary kwargs to a user plugin (Custom: reference
    # custom-inl.h keeps them as the kwargs_ vector handed to the prop
    # creator); unknown params are collected under p._extras as strings
    allow_extra_params: bool = False
    # True for ops whose backward ignores the incoming head gradient (loss
    # layers with injected gradients, BlockGrad): executor.backward() may
    # zero-pad an unsupplied head grad for these outputs only — the
    # analogue of the reference's ref_count==0 omission check
    # (graph_executor.cc:1017-1024)
    head_grad_optional: bool = False
    # False for a node that holds a graph of its own (Repeat): the
    # executor enters no scope around it, its body's nodes name theirs
    own_scope: bool = True

    def __init__(self, name: str):
        self.name = name

    # -- metadata -----------------------------------------------------------
    def parse_params(self, kwargs: Dict[str, Any]) -> _AttrDict:
        p = _AttrDict()
        schema = {x.name: x for x in self.params}
        extras = {}
        for k, v in kwargs.items():
            if k not in schema:
                if self.allow_extra_params:
                    extras[k] = str(v)
                    continue
                raise MXNetError("%s got unknown parameter %r (accepts: %s)"
                                 % (self.name, k, sorted(schema)))
            p[k] = schema[k].parse(v)
        if self.allow_extra_params:
            p["_extras"] = extras
        for x in self.params:
            if x.name not in p:
                if x.required:
                    raise MXNetError("%s requires parameter %r" % (self.name, x.name))
                p[x.name] = x.parse(x.default) if x.default is not None else None
        return p

    def serialize_params(self, p) -> Dict[str, str]:
        out = {}
        for x in self.params:
            v = p.get(x.name)
            if v is not None:
                out[x.name] = x.to_string(v)
        if self.allow_extra_params:
            out.update(p.get("_extras") or {})
        return out

    def list_arguments(self, p) -> List[str]:
        return ["data"]

    def list_outputs(self, p) -> List[str]:
        return ["output"]

    def implied_inputs(self, p, given: Dict[str, Any]) -> Dict[str, Any]:
        """Arguments the op wires itself from those ``given`` (name ->
        ``(node, output index)``): asked by the symbol constructor
        before it auto-creates a variable for a missing argument, and by
        ``load_json`` for a graph saved before the op gained an input."""
        return {}

    def list_auxiliary_states(self, p) -> List[str]:
        return []

    # -- inference ----------------------------------------------------------
    def infer_shape(self, p, in_shapes: List[Optional[Tuple[int, ...]]]):
        """Return (in_shapes, out_shapes, aux_shapes); None = unknown.

        Default: single-input elementwise (output shape = input shape).
        """
        d = in_shapes[0]
        return in_shapes, [d], []

    def infer_type(self, p, in_types: List[Optional[np.dtype]]):
        t = next((x for x in in_types if x is not None), np.dtype(np.float32))
        return [t] * len(in_types), [t] * len(self.list_outputs(p)), \
               [t] * len(self.list_auxiliary_states(p))

    # -- execution ----------------------------------------------------------
    def forward(self, p, inputs: List[Any], aux: List[Any], ctx: OpContext):
        """Compute outputs.  Return list-of-outputs, or
        (list-of-outputs, list-of-new-aux) when the op has auxiliary states."""
        raise NotImplementedError(self.name)


def register_op(name: str, hint: Optional[str] = None):
    """MXNET_REGISTER_OP_PROPERTY / MXNET_REGISTER_SIMPLE_OP analogue."""
    def deco(cls):
        op = cls(name)
        if hint is not None:
            op.hint = hint
        elif op.hint is None:
            op.hint = name.lstrip("_").lower()
        _OP_REGISTRY[name] = op
        return cls
    return deco


def register_simple_op(name: str, fn: Callable, nin: int = 1,
                       infer_shape=None, hint=None, needs_rng=False,
                       params: Optional[List[Param]] = None):
    """Register a function-backed op (SimpleOp path, operator_util.h:479).

    ``fn(p, *inputs)`` -> single jax array.  Used for the elementwise /
    broadcast / reduction family where metadata is uniform.
    """
    class _SimpleOp(OpDef):
        pass

    _SimpleOp.params = params or []
    _SimpleOp.needs_rng = needs_rng
    op = _SimpleOp(name)
    op.hint = hint or name.lstrip("_").lower()
    op._fn = fn
    op._nin = nin

    def list_arguments(p, _n=nin):
        if _n == 1:
            return ["data"]
        if _n == 2:
            return ["lhs", "rhs"]
        return ["arg%d" % i for i in range(_n)]
    op.list_arguments = list_arguments

    if infer_shape is not None:
        op.infer_shape = lambda p, s: infer_shape(p, s)
    else:
        def _default_is(p, in_shapes, _n=nin):
            if _n == 2:
                d = in_shapes[0] if in_shapes[0] is not None else in_shapes[1]
                return [d, d], [d], []
            return in_shapes, [in_shapes[0]], []
        op.infer_shape = _default_is

    def forward(p, inputs, aux, ctx, _fn=fn):
        if op.needs_rng:
            return [_fn(p, *inputs, rng=ctx.rng)]
        return [_fn(p, *inputs)]
    op.forward = forward
    _OP_REGISTRY[name] = op
    return op


def get_op(name: str) -> OpDef:
    if name not in _OP_REGISTRY:
        raise MXNetError("operator %r is not registered (have %d ops)"
                         % (name, len(_OP_REGISTRY)))
    return _OP_REGISTRY[name]


def list_ops() -> List[str]:
    """MXSymbolListAtomicSymbolCreators analogue."""
    return sorted(_OP_REGISTRY)
