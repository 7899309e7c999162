"""Executor: binds a Symbol to devices/arrays and runs forward/backward.

Reference: src/symbol/graph_executor.cc (1164 LoC), include/mxnet/symbolic.h:
323-391, python/mxnet/executor.py (339 LoC).

TPU-native design (SURVEY §7): instead of the reference's per-node engine
dispatch with a hand-written memory planner, the whole graph lowers to ONE
XLA program per (shapes, dtypes, is_train) via jax.jit — XLA does fusion,
layout, rematerialization and memory planning (the reference's
GraphStorageAllocator / bulk-exec InitOpSegs collapse into the compiler).
The backward pass is jax.vjp over the traced graph — the reference's
MakeBackwardPass gradient nodes + addto aggregation come from autodiff, with
loss-layer semantics preserved by the ops' custom_vjp definitions.

Two execution modes mirror the reference's bulk-exec vs NaiveEngine split:
* jit mode (default): fused whole-graph program; used for speed.
* eager mode: node-by-node execution with per-op device placement and
  monitor callbacks — this is what powers Monitor, debug_str parity, and
  ctx_group model parallelism (AssignContext + _CrossDeviceCopy insertion,
  graph_executor.cc:391-508, becomes per-node jax.device_put).

``force_mirroring`` attrs / MXNET_BACKWARD_DO_MIRROR map onto jax.checkpoint
(the memonger hook, static_graph.cc:404-437).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError, get_env
from .context import Context, cpu, current_context
from .ndarray import NDArray, zeros as nd_zeros, array as nd_array
from .ops.registry import OpContext
from .ops.transformer import node_scope
from .parallel.mesh import tracing_over
from . import random as _random
from .symbol import Symbol, _topo, _Node

__all__ = ["Executor", "bind", "simple_bind"]


def _node_aux_names(node: _Node) -> List[str]:
    return ["%s_%s" % (node.name, an)
            for an in node.op.list_auxiliary_states(node.params)]


def _head_grad_unused(node: _Node, memo: dict) -> bool:
    """True when an omitted head gradient for this output cannot reach any
    argument: every backward path from the head hits an op whose vjp
    ignores the incoming gradient (BlockGrad, the injected-loss layers) —
    the graph-walk analogue of the reference's ref_count==0 omission
    check (graph_executor.cc:1017-1024).  A bare Reshape/slice wrapper
    around a BlockGrad'd state therefore still qualifies."""
    key = id(node)
    if key in memo:
        return memo[key]
    if node.is_variable:
        result = False       # gradient would land on a parameter
    elif getattr(node.op, "head_grad_optional", False):
        result = True        # vjp discards the incoming gradient
    else:
        memo[key] = True     # break cycles conservatively-optional
        result = all(_head_grad_unused(inp, memo)
                     for (inp, _) in node.inputs)
    memo[key] = result
    return result


class _GraphProgram:
    """Pure function over (args, aux, rng, is_train) compiled once per mode."""

    def __init__(self, symbol: Symbol, node_ctx: Dict[int, Context],
                 single_ctx: Optional[Context], do_mirror: bool):
        self.symbol = symbol
        self.topo = _topo(symbol._heads)
        self.node_ctx = node_ctx
        self.single_ctx = single_ctx
        self.do_mirror = do_mirror
        self._monitor = None

    def set_monitor(self, cb):
        self._monitor = cb

    def eval(self, args: Dict[str, Any], aux: Dict[str, Any], rng,
             is_train: bool, eager: bool = False):
        """Evaluate the graph; returns (outputs, new_aux)."""
        vals: Dict[Tuple[int, int], Any] = {}
        new_aux: Dict[str, Any] = {}
        for k, node in enumerate(self.topo):
            if node.is_variable:
                if node.name not in args:
                    raise MXNetError("executor missing argument %r" % node.name)
                v = args[node.name]
                if eager and self.node_ctx.get(id(node)) is not None:
                    v = jax.device_put(v, self.node_ctx[id(node)].jax_device())
                vals[(id(node), 0)] = v
                continue
            ins = [vals[(id(i), x)] for (i, x) in node.inputs]
            if eager:
                tgt = self.node_ctx.get(id(node))
                if tgt is not None:
                    dev = tgt.jax_device()
                    ins = [jax.device_put(x, dev) for x in ins]
            aux_names = _node_aux_names(node)
            aux_in = [aux[a] for a in aux_names]
            mirror = (self.do_mirror
                      or node.attrs.get("force_mirroring", "").lower() == "true")
            # every device operation of the node, its key's too, carries
            # the node's scope (trace/scopes.py); a node that holds a body
            # leaves the naming to the body's nodes
            with node_scope(node.attrs.get("__scope__"),
                            node.op.name if node.op.own_scope else None,
                            node.name):
                key = jax.random.fold_in(rng, k) if node.op.needs_rng else None
                opctx = OpContext(is_train=is_train, rng=key)
                if mirror and not aux_names:
                    outs = jax.checkpoint(
                        lambda *i: node.op.forward(node.params, list(i), [],
                                                   opctx))(*ins)
                else:
                    outs = node.op.forward(node.params, ins, aux_in, opctx)
            if isinstance(outs, tuple):
                outs, aux_out = outs
                for a, v in zip(aux_names, aux_out):
                    new_aux[a] = v
            for i, o in enumerate(outs):
                vals[(id(node), i)] = o
            if self._monitor is not None and eager:
                out_names = node.op.list_outputs(node.params)
                for i, o in enumerate(outs):
                    nm = ("%s_%s" % (node.name, out_names[i])
                          if len(outs) > 1 else "%s_output" % node.name)
                    self._monitor(nm, o)
        outputs = [vals[(id(n), i)] for (n, i) in self.symbol._heads]
        return outputs, new_aux


class Executor:
    """Bound executor (reference python/mxnet/executor.py)."""

    def __init__(self, symbol: Symbol, ctx: Context,
                 arg_dict: Dict[str, NDArray],
                 grad_dict: Dict[str, Optional[NDArray]],
                 grad_req: Dict[str, str],
                 aux_dict: Dict[str, NDArray],
                 group2ctx: Optional[Dict[str, Context]] = None,
                 shared_exec: Optional["Executor"] = None):
        self._symbol = symbol
        self._ctx = ctx
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        self._grad_req = grad_req
        self._group2ctx = group2ctx or {}
        self._monitor_callback = None
        self._outputs_nd: Optional[List[NDArray]] = None
        self._pending_grads = None
        self._rng_seed = 0

        self.arg_arrays = [arg_dict[n] for n in symbol.list_arguments()]
        self.grad_arrays = [grad_dict.get(n) for n in symbol.list_arguments()]
        self.aux_arrays = [aux_dict[n] for n in symbol.list_auxiliary_states()]

        # device placement per node (AssignContext, graph_executor.cc:391-508)
        node_ctx: Dict[int, Context] = {}
        multi_ctx = False
        for node in _topo(symbol._heads):
            grp = node.attrs.get("ctx_group")
            c = self._group2ctx.get(grp, ctx) if grp else ctx
            node_ctx[id(node)] = c
            if c != ctx:
                multi_ctx = True
        do_mirror = bool(get_env("MXNET_BACKWARD_DO_MIRROR", 0, int))
        # MXNET_EXEC_PREFER_BULK_EXEC analogue: fuse train fwd+bwd in one jit
        self._fused_train = bool(get_env("MXNET_EXEC_PREFER_BULK_EXEC", 1, int))
        self._prog = _GraphProgram(symbol, node_ctx,
                                   None if multi_ctx else ctx, do_mirror)
        self._eager = multi_ctx
        self._jit_cache: Dict[Any, Any] = {}
        # stats/report tag: symbol head + a shape hint so per-bucket
        # executors of one symbol stay distinguishable in compile_report
        import zlib
        outs = symbol.list_outputs()
        shapes = ",".join("%s:%s" % (n, tuple(a.shape))
                          for n, a in sorted(arg_dict.items()))
        self._prog_tag = "%s@%08x" % (outs[0] if outs else "exec",
                                      zlib.crc32(shapes.encode()))

        # names of args that receive gradients
        self._grad_names = [n for n in symbol.list_arguments()
                            if grad_req.get(n, "null") != "null"
                            and grad_dict.get(n) is not None]

        # multichip inference placement (set_mesh): mesh + replicated
        # sharding for the RNG operand; None = classic single-device
        self._mesh = None
        self._mesh_rep = None

    # -- multichip placement -------------------------------------------------
    def set_mesh(self, mesh, param_specs=None, input_specs=None) -> None:
        """Place EVERY bound array on ``mesh`` for GSPMD execution:
        params/aux at their declared PartitionSpecs (``param_specs``,
        name -> spec; replicated when absent), inputs at
        ``input_specs`` (e.g. the batch input at ``P("dp", ...)``).
        One jit program cannot mix mesh-committed and single-device-
        committed operands, which is why everything moves.

        Inference-only (the tp-sharded ServeEngine path): a training
        executor's gradients live outside this placement story — the
        fused train step owns multichip training."""
        from jax.sharding import NamedSharding, PartitionSpec
        from .parallel.mesh import normalize_spec, validate_spec
        if self._grad_names:
            raise MXNetError(
                "Executor.set_mesh is inference-only (grad_req='null'); "
                "multichip training goes through Module.fit(mesh=...)")
        specs = {}
        for src in (param_specs, input_specs):
            for n, sp in (src or {}).items():
                specs[n] = normalize_spec(sp)
        known = set(self.arg_dict) | set(self.aux_dict)
        unknown = sorted(set(specs) - known)
        if unknown:
            raise MXNetError(
                "set_mesh specs name no bound array: %s (have: %s)"
                % (unknown, sorted(known)))
        for name, nd in list(self.arg_dict.items()) + \
                list(self.aux_dict.items()):
            sp = specs.get(name, PartitionSpec())
            validate_spec(name, sp, mesh, shape=nd.shape)
            nd._place(NamedSharding(mesh, sp))
        self._mesh = mesh
        self._mesh_rep = NamedSharding(mesh, PartitionSpec())
        self._jit_cache.clear()     # programs are traced over the mesh

    # -- helpers ------------------------------------------------------------
    @property
    def outputs(self) -> List[NDArray]:
        if self._outputs_nd is None:
            raise MXNetError("call forward() first")
        return self._outputs_nd

    @property
    def output_dict(self) -> Dict[str, NDArray]:
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def _args_jax(self):
        return {k: v._get() for k, v in self.arg_dict.items()}

    def _aux_jax(self):
        return {k: v._get() for k, v in self.aux_dict.items()}

    def _next_rng(self):
        self._rng_seed += 1
        key = _random.new_key()
        # pin the key to the executor's device: jax would otherwise leave it
        # on the DEFAULT device, and a cpu-ctx executor in a process that
        # also has a TPU would feed mixed-device args to one jit (the
        # reference analogue: the RNG resource lives on the op's stream,
        # resource.cc:20-121).  A mesh-placed executor pins it replicated
        # on the mesh instead — all operands must share one device set.
        if self._mesh_rep is not None:
            import jax
            return jax.device_put(key, self._mesh_rep)
        if self._ctx is not None:
            import jax
            key = jax.device_put(key, self._ctx.jax_device())
        return key

    def _get_jit(self, kind: str):
        """kind: 'fwd_train' | 'fwd_eval' | 'fwdbwd'.  Every whole-graph
        program goes through compile_cache.cached_jit: a process restart
        traces and lowers it again and reads the executable from JAX's
        persistent cache, where an entry point placed one."""
        if kind in self._jit_cache:
            return self._jit_cache[kind]
        from .compile_cache import cached_jit
        name = "exec:%s:%s" % (kind, self._prog_tag)
        prog = self._prog
        if kind in ("fwdbwd", "fwdbwd_ones"):
            with_head = (kind == "fwdbwd")

            def fn(gargs, sargs, aux, rng, head_grads=None):
                def inner(gargs):
                    allargs = dict(sargs)
                    allargs.update(gargs)
                    outs, new_aux = prog.eval(allargs, aux, rng, True)
                    return outs, new_aux
                outs, vjp_fn, new_aux = jax.vjp(inner, gargs, has_aux=True)
                if head_grads is None:
                    head_grads = [jnp.ones_like(o) for o in outs]
                grads = vjp_fn(list(head_grads))[0]
                return outs, grads, new_aux
            if with_head:
                jfn = cached_jit(fn, name=name)
            else:
                jfn = cached_jit(lambda gargs, sargs, aux, rng:
                                 fn(gargs, sargs, aux, rng, None),
                                 name=name)
        else:
            is_train = (kind == "fwd_train")

            def fn(args, aux, rng, _t=is_train):
                # set_mesh's programs span the mesh: an op with a kernel
                # lowering has to see that (parallel.mesh.traced_devices)
                with tracing_over(self._mesh):
                    return prog.eval(args, aux, rng, _t)
            jfn = cached_jit(fn, name=name)
        self._jit_cache[kind] = jfn
        return jfn

    def default_program_kinds(self) -> Tuple[str, ...]:
        """The jit program(s) this executor's hot loop will request:
        the fused train+backward program when bound for training (see
        forward()), the eval forward otherwise."""
        if self._grad_names and self._fused_train:
            return ("fwdbwd_ones",)
        return ("fwd_eval",)

    def precompile(self, kinds: Optional[Sequence[str]] = None) -> Tuple[str, ...]:
        """AOT-compile whole-graph programs WITHOUT executing them (no
        output buffers, no aux updates, no donation).  Safe to run from a
        warmup thread pool: tracing/compilation touch no executor state
        beyond the jit-program cache entry being built.  Eager-mode
        executors (ctx_group placement, monitors) have no whole-graph
        program and return ().  Returns the kinds made ready."""
        if self._eager or self._monitor_callback is not None:
            return ()
        if kinds is None:
            kinds = self.default_program_kinds()
        args, aux = self._args_jax(), self._aux_jax()
        # a DUMMY key with the real key's aval/placement: only the aval
        # matters for compilation, and drawing from the global RNG chain
        # here would make the seeded run's stream depend on the warmup
        # thread count (parallel warmers advance thread-local chains,
        # serial warmup advances the main one)
        rng = jnp.zeros((2,), jnp.uint32)
        if self._mesh_rep is not None:
            rng = jax.device_put(rng, self._mesh_rep)
        elif self._ctx is not None:
            rng = jax.device_put(rng, self._ctx.jax_device())
        done = []
        for kind in kinds:
            if kind == "fwdbwd":
                raise MXNetError(
                    "precompile cannot build the explicit-head-gradient "
                    "program (head grads arrive at backward() time); "
                    "precompile 'fwdbwd_ones' instead")
            jfn = self._get_jit(kind)
            if kind == "fwdbwd_ones":
                gargs = {k: args[k] for k in self._grad_names}
                sargs = {k: v for k, v in args.items() if k not in gargs}
                jfn.warm(gargs, sargs, aux, rng)
            else:
                jfn.warm(args, aux, rng)
            done.append(kind)
        return tuple(done)

    def has_compiled(self) -> bool:
        """Whether any whole-graph program has been built (compiled or
        executed) for this executor."""
        return any(getattr(f, "has_compiled", True)
                   for f in self._jit_cache.values())

    # -- forward / backward -------------------------------------------------
    def forward(self, is_train: bool = False, **kwargs) -> List[NDArray]:
        """Run forward (reference executor.py:60).  kwargs update args."""
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %r" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k][:] = v
            else:
                self.arg_dict[k][:] = nd_array(v, dtype=self.arg_dict[k].dtype)
        args, aux = self._args_jax(), self._aux_jax()
        rng = self._next_rng()
        self._pending_grads = None
        if self._eager or self._monitor_callback is not None:
            self._prog.set_monitor(self._monitor_callback)
            outs, new_aux = self._prog.eval(args, aux, rng, is_train, eager=True)
        elif is_train and self._grad_names and self._fused_train:
            # fused train step: forward + backward in ONE XLA program (the
            # reference's bulk-exec idea taken to its limit) with unit head
            # gradients; backward() then just commits the grads.  A later
            # backward(out_grads=...) falls back to the explicit-head jit.
            gargs = {k: args[k] for k in self._grad_names}
            sargs = {k: v for k, v in args.items() if k not in gargs}
            outs, grads, new_aux = self._get_jit("fwdbwd_ones")(
                gargs, sargs, aux, rng)
            self._pending_grads = grads
        else:
            outs, new_aux = self._get_jit(
                "fwd_train" if is_train else "fwd_eval")(args, aux, rng)
        if is_train:
            for k, v in new_aux.items():
                self.aux_dict[k]._set(v)
        self._outputs_nd = [NDArray(o) for o in outs]
        self._last_rng = rng
        return self._outputs_nd

    def backward(self, out_grads=None) -> None:
        """Run backward (reference executor.py:91): fills grad arrays
        honoring grad_req write/add/null."""
        if self._outputs_nd is None:
            raise MXNetError("backward() requires a prior forward(is_train=True)")
        if out_grads is None and self._pending_grads is not None:
            self._commit_grads(self._pending_grads)
            return
        if out_grads is None:
            head_grads = [jnp.ones_like(o._get()) for o in self._outputs_nd]
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            head_grads = [g._get() if isinstance(g, NDArray) else jnp.asarray(g)
                          for g in out_grads]
            if len(head_grads) > len(self._outputs_nd):
                raise MXNetError(
                    "backward() got %d out_grads for %d outputs"
                    % (len(head_grads), len(self._outputs_nd)))
            if len(head_grads) < len(self._outputs_nd):
                # the reference permits omission only for outputs whose
                # gradient is unused (ref_count==0,
                # graph_executor.cc:1017-1024) — here, heads produced by
                # ops whose backward ignores the incoming gradient (loss
                # layers with injected gradients, BlockGrad'd states).
                # Omitting a REQUIRED head grad is a caller bug that must
                # not silently train with zero gradients.
                for k in range(len(head_grads), len(self._outputs_nd)):
                    node = self._symbol._heads[k][0]
                    if not _head_grad_unused(node, {}):
                        raise MXNetError(
                            "backward() got %d out_grads but output %d "
                            "(%s) requires a head gradient" %
                            (len(head_grads), k, node.name))
                head_grads += [jnp.zeros_like(o._get())
                               for o in self._outputs_nd[len(head_grads):]]
            # caller-made head grads may live on another device (default-
            # device arrays fed to a cpu-ctx executor, or — model parallel —
            # a loss head living on a non-default device).  Rebase each onto
            # ITS output's device so the vjp never mixes assignments: the
            # analogue of the reference's head-grad CopyFromTo at bind
            # (graph_executor.cc:1003-1027)
            head_grads = [
                jax.device_put(g, list(o._get().devices())[0])
                for g, o in zip(head_grads, self._outputs_nd)]
        args, aux = self._args_jax(), self._aux_jax()
        gargs = {k: args[k] for k in self._grad_names}
        sargs = {k: v for k, v in args.items() if k not in gargs}
        if self._eager or self._monitor_callback is not None:
            def inner(gargs):
                allargs = dict(sargs)
                allargs.update(gargs)
                outs, new_aux = self._prog.eval(allargs, aux, self._last_rng,
                                                True, eager=True)
                return outs, new_aux
            # monitor stats were already collected on concrete values during
            # forward(); the vjp re-trace must not fire callbacks on tracers
            self._prog.set_monitor(None)
            try:
                outs, vjp_fn, _ = jax.vjp(inner, gargs, has_aux=True)
                grads = vjp_fn(list(head_grads))[0]
            finally:
                self._prog.set_monitor(self._monitor_callback)
        else:
            _, grads, _ = self._get_jit("fwdbwd")(
                gargs, sargs, aux, self._last_rng, tuple(head_grads))
        self._commit_grads(grads)

    def _commit_grads(self, grads):
        for name in self._grad_names:
            g = grads[name]
            tgt = self.grad_dict[name]
            if self._grad_req.get(name) == "add":
                tgt._set(tgt._get() + g)
            else:
                tgt._set(jnp.asarray(g, dtype=tgt.dtype))

    # -- misc API ------------------------------------------------------------
    def reshape(self, partial_shaping=False, allow_up_sizing=False, **new_shapes):
        """Return a new executor with new input shapes (reference executor.py
        reshape); weights are shared by value."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**new_shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes for reshape")
        new_args = {}
        for name, sh in zip(self._symbol.list_arguments(), arg_shapes):
            old = self.arg_dict[name]
            if tuple(old.shape) == tuple(sh):
                new_args[name] = old
            else:
                new_args[name] = nd_zeros(sh, ctx=self._ctx, dtype=old.dtype)
        new_grads = {}
        for name, sh in zip(self._symbol.list_arguments(), arg_shapes):
            old = self.grad_dict.get(name)
            if old is None:
                continue
            new_grads[name] = old if tuple(old.shape) == tuple(sh) else \
                nd_zeros(sh, ctx=self._ctx, dtype=old.dtype)
        new_aux = {}
        for name, sh in zip(self._symbol.list_auxiliary_states(), aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = old if tuple(old.shape) == tuple(sh) else \
                nd_zeros(sh, ctx=self._ctx, dtype=old.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, new_aux, self._group2ctx)

    def copy_params_from(self, arg_params: Dict[str, NDArray],
                         aux_params: Optional[Dict[str, NDArray]] = None,
                         allow_extra_params: bool = False):
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name][:] = arr
            elif not allow_extra_params:
                raise MXNetError("Found name %r not in executor arguments" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name][:] = arr
                elif not allow_extra_params:
                    raise MXNetError("Found name %r not in executor aux states" % name)

    def set_monitor_callback(self, callback):
        """Install per-op output monitor (reference symbolic.h:386-390);
        switches execution to the node-level (eager) mode."""
        def cb(name, jarr):
            callback(name, NDArray(jarr))
        self._monitor_callback = cb

    def debug_str(self) -> str:
        """Execution plan dump (reference graph_executor.cc:955-988)."""
        lines = ["Symbol Outputs:", "\t" + ", ".join(self._symbol.list_outputs())]
        total = 0
        for node in self._prog.topo:
            if node.is_variable:
                lines.append("Variable:%s ctx=%s" % (
                    node.name, self._prog.node_ctx.get(id(node), self._ctx)))
            else:
                lines.append("Op:%s Name=%s ctx=%s" % (
                    node.op.name, node.name,
                    self._prog.node_ctx.get(id(node), self._ctx)))
                for (i, x) in node.inputs:
                    lines.append("\targ[%d]=%s" % (x, i.name))
        for arr in list(self.arg_dict.values()) + list(self.aux_dict.values()):
            total += arr.size * arr.dtype.itemsize
        lines.append("Total %.1f MB allocated (args+aux)" % (total / 2**20))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# binding entry points (reference c_api.cc MXExecutorBind / symbol.py bind)

def bind(symbol: Symbol, ctx: Context, args, args_grad=None, grad_req="write",
         aux_states=None, group2ctx=None, shared_exec=None) -> Executor:
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()

    if isinstance(args, (list, tuple)):
        if len(args) != len(arg_names):
            raise MXNetError("bind needs %d args, got %d" % (len(arg_names), len(args)))
        arg_dict = dict(zip(arg_names, args))
    else:
        arg_dict = dict(args)
        missing = [n for n in arg_names if n not in arg_dict]
        if missing:
            raise MXNetError("bind missing arguments %s" % missing)

    if args_grad is None:
        grad_dict = {}
    elif isinstance(args_grad, (list, tuple)):
        grad_dict = dict(zip(arg_names, args_grad))
    else:
        grad_dict = dict(args_grad)

    if isinstance(grad_req, str):
        req = {n: grad_req for n in arg_names}
    elif isinstance(grad_req, (list, tuple)):
        req = dict(zip(arg_names, grad_req))
    else:
        req = dict(grad_req)
    for n in arg_names:
        if n not in grad_dict:
            req[n] = "null"

    if aux_states is None:
        aux_list = []
        if aux_names:
            _, _, aux_shapes = symbol.infer_shape(
                **{n: a.shape for n, a in arg_dict.items()})
            for n, sh in zip(aux_names, aux_shapes):
                aux_list.append(nd_zeros(sh, ctx=ctx))
        aux_dict = dict(zip(aux_names, aux_list))
    elif isinstance(aux_states, (list, tuple)):
        aux_dict = dict(zip(aux_names, aux_states))
    else:
        aux_dict = dict(aux_states)

    return Executor(symbol, ctx, arg_dict, grad_dict, req, aux_dict,
                    group2ctx=group2ctx, shared_exec=shared_exec)


def simple_bind(symbol: Symbol, ctx: Context, grad_req="write", type_dict=None,
                group2ctx=None, shared_exec=None, **kwargs) -> Executor:
    """Infer shapes, allocate arrays, bind (reference symbol.py:630-700)."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise MXNetError("simple_bind cannot infer all shapes from %s" % kwargs)
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    type_dict = type_dict or {}
    attrs = symbol.attr_dict()

    def _ctx_for(name):
        grp = attrs.get(name, {}).get("ctx_group")
        if grp and group2ctx and grp in group2ctx:
            return group2ctx[grp]
        return ctx

    arg_dict = {}
    for name, sh in zip(arg_names, arg_shapes):
        dt = type_dict.get(name, np.float32)
        # reuse shared_exec arrays of identical shape (bucketing memory share,
        # reference graph_executor.h:50-56 GraphStoragePool)
        if shared_exec is not None and name in shared_exec.arg_dict and \
                tuple(shared_exec.arg_dict[name].shape) == tuple(sh):
            arg_dict[name] = shared_exec.arg_dict[name]
        else:
            arg_dict[name] = nd_zeros(sh, ctx=_ctx_for(name), dtype=dt)

    if isinstance(grad_req, str):
        req = {n: grad_req for n in arg_names}
    elif isinstance(grad_req, (list, tuple)):
        req = dict(zip(arg_names, grad_req))
    else:
        req = {n: grad_req.get(n, "null") for n in arg_names}

    grad_dict = {}
    for name, sh in zip(arg_names, arg_shapes):
        if req.get(name, "null") != "null":
            if shared_exec is not None and name in shared_exec.grad_dict and \
                    shared_exec.grad_dict[name] is not None and \
                    tuple(shared_exec.grad_dict[name].shape) == tuple(sh):
                grad_dict[name] = shared_exec.grad_dict[name]
            else:
                grad_dict[name] = nd_zeros(sh, ctx=_ctx_for(name),
                                           dtype=type_dict.get(name, np.float32))

    aux_dict = {}
    for name, sh in zip(aux_names, aux_shapes):
        if shared_exec is not None and name in shared_exec.aux_dict and \
                tuple(shared_exec.aux_dict[name].shape) == tuple(sh):
            aux_dict[name] = shared_exec.aux_dict[name]
        else:
            aux_dict[name] = nd_zeros(sh, ctx=ctx)

    return Executor(symbol, ctx, arg_dict, grad_dict, req, aux_dict,
                    group2ctx=group2ctx, shared_exec=shared_exec)
