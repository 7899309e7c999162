"""Symbolic graph construction.

Reference: include/mxnet/symbolic.h:40-317, src/symbol/symbol.cc (806 LoC),
src/symbol/static_graph.cc (615 LoC), python/mxnet/symbol.py (1182 LoC).

TPU-native design: a Symbol is a DAG of ``_Node`` (op + params + attrs +
inputs) exactly like the reference's shared-ptr Node graph — but there is no
separate StaticGraph/MakeBackwardPass: lowering happens in the Executor, which
traces the DAG into one jit-compiled XLA program and gets the backward pass
from jax.vjp (the reference's MakeBackwardPass + gradient-aggregation nodes,
static_graph.cc:397-520, collapse into autodiff; gradient mirroring /
memonger maps to jax.checkpoint driven by the same ``force_mirroring`` attr).

Atomic symbol constructors (mx.sym.FullyConnected, ...) are generated from
the op registry at import, mirroring the C-registry-driven codegen of the
reference (symbol.py _init_symbol_module).
"""
from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .base import MXNetError, _AttrDict
from .attribute import AttrScope
from .name import NameManager
from .ops import get_op, list_ops, OpDef

__all__ = ["Symbol", "Variable", "Group", "load", "load_json", "var"]


class _Node:
    """Graph node: op application or variable (reference symbolic.h:262-281)."""

    __slots__ = ("op", "name", "attrs", "params", "inputs", "is_aux")

    def __init__(self, op: Optional[OpDef], name: str,
                 params=None, attrs=None, inputs=None, is_aux=False):
        self.op = op
        self.name = name
        self.params = params if params is not None else {}
        self.attrs = dict(attrs) if attrs else {}
        self.inputs: List[Tuple["_Node", int]] = list(inputs) if inputs else []
        self.is_aux = is_aux

    @property
    def is_variable(self):
        return self.op is None

    def num_outputs(self):
        return 1 if self.op is None else len(self.op.list_outputs(self.params))


def _topo(heads: Sequence[Tuple[_Node, int]]) -> List[_Node]:
    """DFS post-order over the graph — matches reference traversal order."""
    visited = set()
    order: List[_Node] = []

    def visit(node: _Node):
        if id(node) in visited:
            return
        visited.add(id(node))
        for (inp, _) in node.inputs:
            visit(inp)
        order.append(node)

    for (n, _) in heads:
        visit(n)
    return order


def cast_compute(args: dict, compute_dtype, skip: set) -> dict:
    """Mixed-precision cast for a train-step's input dict: float tensors go
    to `compute_dtype` except names in `skip` (labels and id-valued inputs
    — integers >= 257 are not exactly representable in bf16)."""
    import jax.numpy as jnp
    if compute_dtype is None:
        return args
    return {k: v.astype(compute_dtype)
            if k not in skip and jnp.issubdtype(v.dtype, jnp.floating)
            else v for k, v in args.items()}


def id_valued_inputs(symbol: "Symbol") -> set:
    """Variable names whose float values are integer ids (embedding
    tokens): mixed-precision paths must not cast those to bf16 — ids
    >= 257 would misround and look up the wrong rows."""
    ids = set()
    for node in _topo(symbol._heads):
        if node.is_variable or node.op is None:
            continue
        if getattr(node.op, "name", "") == "Embedding" and node.inputs:
            src = node.inputs[0][0]
            if src.is_variable:
                ids.add(src.name)
    return ids


class Symbol:
    """Symbol = list of output heads over a shared DAG."""

    def __init__(self, heads: Sequence[Tuple[_Node, int]],
                 graph_attrs: Optional[Dict[str, str]] = None):
        self._heads: List[Tuple[_Node, int]] = list(heads)
        # graph-LEVEL attrs (vs per-node attrs): serialized into the json
        # "attrs" block and restored by load_json.  mxnet_tpu.passes stamps
        # the pipeline fingerprint here (``__passes__``) so a transformed
        # symbol's identity — and through tojson, its compile-cache fast
        # key — can never alias the untransformed graph's.
        self._graph_attrs: Dict[str, str] = dict(graph_attrs or {})

    # -- composition --------------------------------------------------------
    def __call__(self, *args, **kwargs):
        """Compose: substitute this symbol's free variables with other symbols
        (reference symbolic.h:77-142)."""
        s = self.__copy__()
        s._compose(*args, **kwargs)
        return s

    def _compose(self, *args, **kwargs):
        name = kwargs.pop("name", None)
        arg_names = self.list_arguments()
        if args:
            if len(args) > len(arg_names):
                raise MXNetError("too many positional arguments")
            kwargs.update(dict(zip(arg_names, args)))
        sub = {}
        for k, v in kwargs.items():
            if not isinstance(v, Symbol):
                raise TypeError("compose expects Symbol arguments")
            if len(v._heads) != 1:
                raise MXNetError("cannot compose with grouped symbol")
            if k not in arg_names:
                raise MXNetError("unknown argument %r (has %s)" % (k, arg_names))
            sub[k] = v._heads[0]
        for node in _topo(self._heads):
            node.inputs = [sub.get(inp.name, (inp, idx)) if inp.is_variable else (inp, idx)
                           for (inp, idx) in node.inputs]
        if name is not None and len(self._heads) == 1:
            self._heads[0][0].name = name

    def __copy__(self) -> "Symbol":
        """Deep copy of the reachable graph."""
        mapping: Dict[int, _Node] = {}
        for node in _topo(self._heads):
            # params must stay an _AttrDict: op infer_shape/forward read
            # them as attributes, and a plain dict() copy used to make
            # every copied/composed symbol unbindable
            new = _Node(node.op, node.name, _AttrDict(node.params),
                        dict(node.attrs),
                        [(mapping[id(i)], x) for (i, x) in node.inputs], node.is_aux)
            mapping[id(node)] = new
        return Symbol([(mapping[id(n)], i) for (n, i) in self._heads],
                      graph_attrs=self._graph_attrs)

    def __deepcopy__(self, memo=None):
        return self.__copy__()

    copy = __copy__

    # -- arithmetic sugar (reference symbol.py operator overloads) ----------
    def _binop(self, other, opname, scalar_opname, rscalar=None):
        if isinstance(other, Symbol):
            return _create(opname, [self, other])
        if isinstance(other, (int, float, np.generic)):
            return _create(scalar_opname, [self], scalar=float(other))
        raise TypeError("unsupported operand type %s" % type(other))

    def __add__(self, other): return self._binop(other, "_plus", "_plus_scalar")
    def __radd__(self, other): return self.__add__(other)
    def __sub__(self, other): return self._binop(other, "_minus", "_minus_scalar")

    def __rsub__(self, other):
        if isinstance(other, (int, float, np.generic)):
            return _create("_rminus_scalar", [self], scalar=float(other))
        raise TypeError()

    def __mul__(self, other): return self._binop(other, "_mul", "_mul_scalar")
    def __rmul__(self, other): return self.__mul__(other)
    def __div__(self, other): return self._binop(other, "_div", "_div_scalar")
    __truediv__ = __div__

    def __rdiv__(self, other):
        if isinstance(other, (int, float, np.generic)):
            return _create("_rdiv_scalar", [self], scalar=float(other))
        raise TypeError()

    __rtruediv__ = __rdiv__

    def __pow__(self, other): return self._binop(other, "_power", "_power_scalar")
    def __neg__(self): return self.__mul__(-1.0)

    # -- introspection ------------------------------------------------------
    @property
    def name(self) -> Optional[str]:
        nodes = {id(n) for (n, _) in self._heads}
        if len(nodes) == 1:
            return self._heads[0][0].name
        return None

    def list_arguments(self) -> List[str]:
        return [n.name for n in _topo(self._heads) if n.is_variable and not n.is_aux]

    def list_outputs(self) -> List[str]:
        out = []
        for (node, idx) in self._heads:
            if node.is_variable:
                out.append(node.name)
            else:
                names = node.op.list_outputs(node.params)
                out.append("%s_%s" % (node.name, names[idx])
                           if len(names) > 1 else "%s_%s" % (node.name, names[0]))
        return out

    def list_auxiliary_states(self) -> List[str]:
        out = []
        for n in _topo(self._heads):
            if n.is_variable and n.is_aux:
                out.append(n.name)
            elif not n.is_variable:
                for aux in n.op.list_auxiliary_states(n.params):
                    out.append("%s_%s" % (n.name, aux))
        return out

    def get_internals(self) -> "Symbol":
        """All internal outputs (reference symbol.cc GetInternals)."""
        heads = []
        for node in _topo(self._heads):
            if node.is_variable:
                heads.append((node, 0))
            else:
                for i in range(node.num_outputs()):
                    heads.append((node, i))
        return Symbol(heads, graph_attrs=self._graph_attrs)

    def __getitem__(self, index) -> "Symbol":
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise MXNetError("cannot find output %r in %s" % (index, names))
            index = names.index(index)
        if not isinstance(index, int):
            raise TypeError("index must be int or str")
        return Symbol([self._heads[index]], graph_attrs=self._graph_attrs)

    def __len__(self):
        return len(self._heads)

    def __iter__(self):
        return (self[i] for i in range(len(self._heads)))

    # -- attributes ---------------------------------------------------------
    def attr(self, key: str) -> Optional[str]:
        if len(self._heads) == 1:
            return self._heads[0][0].attrs.get(key)
        return None

    def list_attr(self, recursive=False) -> Dict[str, str]:
        if recursive:
            ret = {}
            for node in _topo(self._heads):
                for k, v in node.attrs.items():
                    ret["%s_%s" % (node.name, k)] = v
            return ret
        return dict(self._heads[0][0].attrs) if len(self._heads) == 1 else {}

    attr_dict_flat = list_attr

    def attr_dict(self) -> Dict[str, Dict[str, str]]:
        ret = {}
        for node in _topo(self._heads):
            if node.attrs:
                ret[node.name] = dict(node.attrs)
        return ret

    def _set_attr(self, **kwargs):
        for (node, _) in self._heads:
            node.attrs.update(kwargs)

    # -- shape / type inference (reference symbolic.h InferShape) -----------
    def infer_shape(self, *args, **kwargs):
        try:
            return self._infer_shape_impl(False, *args, **kwargs)
        except MXNetError:
            raise

    def infer_shape_partial(self, *args, **kwargs):
        return self._infer_shape_impl(True, *args, **kwargs)

    def _infer_shape_impl(self, partial, *args, **kwargs):
        arg_names = self.list_arguments()
        known: Dict[str, Tuple[int, ...]] = {}
        if args:
            for name, shape in zip(arg_names, args):
                if shape is not None:
                    known[name] = tuple(shape)
        for k, v in kwargs.items():
            if k not in arg_names:
                raise MXNetError("unknown argument %r in infer_shape (has %s)"
                                 % (k, arg_names))
            known[k] = tuple(v)

        node_out_shapes: Dict[Tuple[int, int], Optional[Tuple[int, ...]]] = {}
        var_shapes: Dict[str, Optional[Tuple[int, ...]]] = {}
        aux_shapes_map: Dict[str, Optional[Tuple[int, ...]]] = {}

        for node in _topo(self._heads):
            if node.is_variable:
                shape = known.get(node.name)
                if shape is None and "__shape__" in node.attrs:
                    import ast
                    shape = tuple(int(x) for x in
                                  ast.literal_eval(node.attrs["__shape__"]))
                var_shapes.setdefault(node.name, shape)
                node_out_shapes[(id(node), 0)] = var_shapes[node.name]
            else:
                in_shapes = [node_out_shapes.get((id(i), x)) for (i, x) in node.inputs]
                new_in, out_s, aux_s = node.op.infer_shape(node.params, in_shapes)
                # write back inferred input shapes onto variable inputs
                for (inp, x), s in zip(node.inputs, new_in):
                    if s is not None:
                        prev = node_out_shapes.get((id(inp), x))
                        if prev is None:
                            node_out_shapes[(id(inp), x)] = tuple(s)
                            if inp.is_variable:
                                var_shapes[inp.name] = tuple(s)
                        elif tuple(prev) != tuple(s) and not partial:
                            raise MXNetError(
                                "shape inconsistency at %s: %s vs %s"
                                % (node.name, prev, s))
                for i, s in enumerate(out_s):
                    node_out_shapes[(id(node), i)] = tuple(s) if s is not None else None
                aux_names = node.op.list_auxiliary_states(node.params)
                for an, s in zip(aux_names, aux_s):
                    aux_shapes_map["%s_%s" % (node.name, an)] = \
                        tuple(s) if s is not None else None

        arg_shapes = [var_shapes.get(n) for n in arg_names]
        out_shapes = [node_out_shapes.get((id(n), i)) for (n, i) in self._heads]
        aux_shapes = [aux_shapes_map.get(n) for n in self.list_auxiliary_states()]
        if not partial and any(s is None for s in arg_shapes + out_shapes):
            return None, None, None
        return arg_shapes, out_shapes, aux_shapes

    def infer_type(self, *args, **kwargs):
        arg_names = self.list_arguments()
        known: Dict[str, Any] = {}
        if args:
            for name, t in zip(arg_names, args):
                if t is not None:
                    known[name] = np.dtype(t)
        for k, v in kwargs.items():
            known[k] = np.dtype(v)
        node_types: Dict[Tuple[int, int], Any] = {}
        var_types: Dict[str, Any] = {}
        aux_types_map: Dict[str, Any] = {}
        for node in _topo(self._heads):
            if node.is_variable:
                t = known.get(node.name, np.dtype(np.float32))
                var_types.setdefault(node.name, t)
                node_types[(id(node), 0)] = var_types[node.name]
            else:
                in_types = [node_types.get((id(i), x)) for (i, x) in node.inputs]
                new_in, out_t, aux_t = node.op.infer_type(node.params, in_types)
                for i, t in enumerate(out_t):
                    node_types[(id(node), i)] = t
                for an, t in zip(node.op.list_auxiliary_states(node.params), aux_t):
                    aux_types_map["%s_%s" % (node.name, an)] = t
        arg_types = [var_types.get(n, np.dtype(np.float32)) for n in arg_names]
        out_types = [node_types.get((id(n), i)) for (n, i) in self._heads]
        aux_types = [aux_types_map.get(n) for n in self.list_auxiliary_states()]
        return arg_types, out_types, aux_types

    # -- serialization (reference Symbol::Save JSON) ------------------------
    def tojson(self, indent=2) -> str:
        nodes = _topo(self._heads)
        idx = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        for n in nodes:
            if n.is_variable:
                jnodes.append({"op": "null", "name": n.name,
                               "attr": dict(n.attrs), "inputs": []})
            else:
                jnodes.append({
                    "op": n.op.name, "name": n.name,
                    "param": n.op.serialize_params(n.params),
                    "attr": dict(n.attrs),
                    "inputs": [[idx[id(i)], x] for (i, x) in n.inputs]})
        heads = [[idx[id(n)], i] for (n, i) in self._heads]
        arg_nodes = [i for i, n in enumerate(nodes) if n.is_variable]
        attrs = {"mxnet_tpu_version": 1}
        attrs.update(self._graph_attrs)
        return json.dumps({"nodes": jnodes, "arg_nodes": arg_nodes,
                           "heads": heads, "attrs": attrs},
                          indent=indent)

    def save(self, fname: str) -> None:
        from .base import atomic_local_write, is_local_path, open_stream
        if not is_local_path(fname):
            with open_stream(fname, "w") as f:
                f.write(self.tojson())
            return
        # local paths publish atomically: checkpoint pairs must never
        # expose a truncated -symbol.json (see base.atomic_local_write)
        with atomic_local_write(fname, "w") as f:
            f.write(self.tojson())

    def debug_str(self) -> str:
        lines = []
        for node in _topo(self._heads):
            if node.is_variable:
                lines.append("Variable:%s" % node.name)
            else:
                lines.append("--------------------")
                lines.append("Op:%s, Name=%s" % (node.op.name, node.name))
                for (i, x) in node.inputs:
                    lines.append("arg[%d]=%s(%d)" % (x, i.name, x))
        return "\n".join(lines)

    def __repr__(self):
        if len(self._heads) == 1:
            return "<Symbol %s>" % self.name
        return "<Symbol group [%s]>" % ", ".join(self.list_outputs())

    # -- binding (implemented in executor.py, attached there) ---------------
    def simple_bind(self, ctx, grad_req="write", type_dict=None, group2ctx=None,
                    **kwargs):
        from .executor import simple_bind as _sb
        return _sb(self, ctx, grad_req=grad_req, type_dict=type_dict,
                   group2ctx=group2ctx, **kwargs)

    def bind(self, ctx, args, args_grad=None, grad_req="write", aux_states=None,
             group2ctx=None, shared_exec=None):
        from .executor import bind as _bind
        return _bind(self, ctx, args, args_grad=args_grad, grad_req=grad_req,
                     aux_states=aux_states, group2ctx=group2ctx,
                     shared_exec=shared_exec)

    def grad(self, wrt):
        raise MXNetError("symbol.grad is deprecated; use bind + backward")

    # -- eager eval sugar ---------------------------------------------------
    def eval(self, ctx=None, **kwargs):
        from .context import cpu
        ex = self.bind(ctx if ctx is not None else cpu(), kwargs)
        return ex.forward()


def Variable(name: str, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None) -> Symbol:
    """Create a symbolic variable (reference symbol.py Variable)."""
    if not isinstance(name, str):
        raise TypeError("Expect a string for variable name")
    attr = AttrScope.current().get(attr)
    attr = dict(attr) if attr else {}
    if shape is not None:
        attr["__shape__"] = str(tuple(shape))
    if lr_mult is not None:
        attr["lr_mult"] = str(lr_mult)
    if wd_mult is not None:
        attr["wd_mult"] = str(wd_mult)
    if init is not None:
        # the variable's own initializer (``initializer.create`` reads it
        # back; ``Module.init_params`` prefers it to the one it is handed)
        attr["__init__"] = init if isinstance(init, str) else init.dumps()
    node = _Node(None, name, attrs=attr)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols: Sequence[Symbol]) -> Symbol:
    """Group symbols into one multi-output symbol (reference symbol.py Group)."""
    heads = []
    gattrs: Dict[str, str] = {}
    for s in symbols:
        if not isinstance(s, Symbol):
            raise TypeError("Expected Symbol in Group")
        heads.extend(s._heads)
        gattrs.update(s._graph_attrs)
    return Symbol(heads, graph_attrs=gattrs)


def load(fname: str) -> Symbol:
    from .base import open_stream
    with open_stream(fname) as f:
        return load_json(f.read())


def load_json(json_str: str) -> Symbol:
    data = json.loads(json_str)
    nodes: List[_Node] = []
    for jn in data["nodes"]:
        if jn["op"] == "null":
            nodes.append(_Node(None, jn["name"], attrs=jn.get("attr", {})))
        else:
            op = get_op(jn["op"])
            params = op.parse_params(jn.get("param", {}))
            inputs = [(nodes[i], x) for (i, x) in jn["inputs"]]
            if type(op).implied_inputs is not OpDef.implied_inputs:
                # saved before the op gained its trailing input(s)?
                arg_names = op.list_arguments(params)
                implied = op.implied_inputs(
                    params, dict(zip(arg_names, inputs)))
                inputs += [implied[an] for an in arg_names[len(inputs):]
                           if an in implied]
            nodes.append(_Node(op, jn["name"], params=params,
                               attrs=jn.get("attr", {}), inputs=inputs))
    heads = [(nodes[i], x) for (i, x) in data["heads"]]
    graph_attrs = {k: v for k, v in (data.get("attrs") or {}).items()
                   if k != "mxnet_tpu_version"}
    return Symbol(heads, graph_attrs=graph_attrs)


# ---------------------------------------------------------------------------
# atomic symbol constructor codegen (reference symbol.py _init_symbol_module)

def _create(op_name: str, input_syms: Sequence[Symbol], name: Optional[str] = None,
            attr=None, **params) -> Symbol:
    op = get_op(op_name)
    # split Symbol-valued kwargs (named inputs) from params
    named_inputs = {k: v for k, v in params.items() if isinstance(v, Symbol)}
    for k in named_inputs:
        params.pop(k)
    if op.variable_args is not None and op.variable_args not in params:
        params[op.variable_args] = len(input_syms) + len(named_inputs)
    p = op.parse_params(params)
    arg_names = op.list_arguments(p)

    # positional inputs fill from the front; named inputs by name
    inputs_by_name: Dict[str, Symbol] = {}
    for s, an in zip(input_syms, arg_names):
        inputs_by_name[an] = s
    for k, v in named_inputs.items():
        if k not in arg_names:
            raise MXNetError("%s got unexpected input %r (args: %s)"
                             % (op_name, k, arg_names))
        inputs_by_name[k] = v

    attr = AttrScope.current().get(attr)
    name = NameManager.current().get(name, op.hint)
    inputs: List[Tuple[_Node, int]] = []
    for s in inputs_by_name.values():
        if len(s._heads) != 1:
            raise MXNetError("cannot use grouped symbol as input")
    implied = op.implied_inputs(
        p, {an: s._heads[0] for an, s in inputs_by_name.items()})
    for an in arg_names:
        if an in inputs_by_name:
            inputs.append(inputs_by_name[an]._heads[0])
        elif an in implied:
            inputs.append(implied[an])
        else:
            # auto-create missing argument variable, e.g. fc1_weight;
            # inherits scope attrs (ctx_group etc.) like the reference
            vnode = _Node(None, "%s_%s" % (name, an),
                          attrs=dict(attr) if attr else {})
            inputs.append((vnode, 0))
    node = _Node(op, name, params=p, attrs=dict(attr) if attr else {},
                 inputs=inputs)
    return Symbol([(node, i) for i in range(node.num_outputs())])


def _make_atomic_symbol_function(op_name: str):
    def creator(*args, **kwargs):
        name = kwargs.pop("name", None)
        attr = kwargs.pop("attr", None)
        input_syms = [a for a in args if isinstance(a, Symbol)]
        return _create(op_name, input_syms, name=name, attr=attr, **kwargs)
    creator.__name__ = op_name
    creator.__doc__ = "Auto-generated constructor for operator %s" % op_name
    return creator


def _init_symbol_module():
    module = sys.modules[__name__]
    for op_name in list_ops():
        fn = _make_atomic_symbol_function(op_name)
        setattr(module, op_name, fn)
        public = op_name.lstrip("_")
        if not hasattr(module, public):
            setattr(module, public, fn)


_init_symbol_module()


def Repeat(body: Symbol, carry: Dict[str, Symbol], num_steps: int,
           name: Optional[str] = None, recompute: bool = True, attr=None,
           **bound: Symbol) -> Symbol:
    """``body`` applied ``num_steps`` times with one set of weights, as
    ONE node of the graph (``ops/control_flow.py``).

    ``carry`` maps free variables of ``body`` to their first values; the
    body's first ``len(carry)`` outputs, in that order, are what the next
    pass reads in their place.  ``bound`` maps further free variables to
    symbols of this graph that every pass reads (the flattened labels, a
    weight another node shares).  Every free variable left is a weight:
    it becomes a ``Variable`` of this graph under the name and the
    attributes it has in the body, listed once whatever ``num_steps`` is.
    Outputs: the last carry, then each further output of the body stacked
    ``(num_steps, ...)``.  ``recompute``: the backward pass forms a pass
    again from its carry and keeps no pass's activations."""
    if not isinstance(body, Symbol):
        raise TypeError("Repeat expects a Symbol body")
    carry = dict(carry)
    for k, v in list(carry.items()) + list(bound.items()):
        if not isinstance(v, Symbol):
            raise TypeError("Repeat: %r is bound to %r, not a Symbol"
                            % (k, v))
    free = {n.name: n for n in _topo(body._heads)
            if n.is_variable and not n.is_aux}
    unknown = [k for k in list(carry) + list(bound) if k not in free]
    if unknown:
        raise MXNetError("Repeat: %s are not free variables of the body, "
                         "which has %s" % (unknown, sorted(free)))
    weights = {k: Symbol([(_Node(None, k, attrs=node.attrs), 0)])
               for k, node in free.items()
               if k not in carry and k not in bound}
    return _create("Repeat", [], name=name, attr=attr, body=body.tojson(),
                   num_steps=num_steps, carry=",".join(carry),
                   recompute=recompute, **carry, **bound, **weights)


def __getattr__(name):
    """Late-registered ops (plugins, custom ops) resolve lazily."""
    from .ops.registry import _OP_REGISTRY
    if name in _OP_REGISTRY:
        fn = _make_atomic_symbol_function(name)
        setattr(sys.modules[__name__], name, fn)
        return fn
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
