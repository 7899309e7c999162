"""Test utilities. Reference: tests/python/unittest/check_utils.py
(reldiff, numeric_grad, check_numeric_gradient at line 257)."""
import numpy as np

import mxnet_tpu as mx


def reldiff(a, b):
    diff = np.sum(np.abs(a - b))
    norm = np.sum(np.abs(a))
    if diff == 0:
        return 0
    return diff / (norm + 1e-12)


def same(a, b):
    return np.sum(a != b) == 0


def numeric_grad(executor, location, eps=1e-4, is_train=False):
    """Finite-difference gradients of sum(outputs[0]) wrt each location arg
    (reference check_utils.py numeric_grad).  `is_train=True` runs the
    perturbed forwards in train mode — required for ops whose train-mode
    forward differs deterministically from eval (BatchNorm batch stats)."""
    args = executor.arg_dict
    for k, v in location.items():
        args[k][:] = np.asarray(v, dtype=np.float32)
    approx_grads = {k: np.zeros(v.shape, dtype=np.float32)
                    for k, v in location.items()}

    executor.forward(is_train=is_train)
    f_x = executor.outputs[0].asnumpy().sum()

    for k in location:
        old_value = location[k].copy()
        flat = old_value.reshape(-1)
        ap = approx_grads[k].reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            args[k][:] = old_value.reshape(location[k].shape)
            executor.forward(is_train=is_train)
            f_eps = executor.outputs[0].asnumpy().sum()
            ap[i] = (f_eps - f_x) / eps
            flat[i] = orig
        args[k][:] = old_value.reshape(location[k].shape)
    return approx_grads


def check_numeric_gradient(sym, location, aux_states=None, numeric_eps=1e-3,
                           check_eps=0.06, grad_nodes=None, rtol=None,
                           fd_is_train=False):
    """Compare autodiff gradients against finite differences
    (reference check_utils.py check_numeric_gradient)."""
    kwargs = {k: v.shape for k, v in location.items()}
    arg_shapes, _, aux_shapes = sym.infer_shape(**kwargs)
    arg_names = sym.list_arguments()
    if grad_nodes is None:
        grad_nodes = [k for k in location]
    grad_req = {n: ("write" if n in grad_nodes else "null") for n in arg_names}
    executor = sym.simple_bind(mx.current_context(), grad_req=grad_req, **kwargs)
    for k, v in location.items():
        executor.arg_dict[k][:] = np.asarray(v, dtype=np.float32)
    if aux_states is not None:
        for k, v in aux_states.items():
            executor.aux_dict[k][:] = np.asarray(v, dtype=np.float32)

    executor.forward(is_train=True)
    executor.backward()
    sym_grads = {k: executor.grad_dict[k].asnumpy() for k in grad_nodes}

    fd_exec = sym.simple_bind(mx.current_context(), grad_req="null", **kwargs)
    if aux_states is not None:
        for k, v in aux_states.items():
            fd_exec.aux_dict[k][:] = np.asarray(v, dtype=np.float32)
    num_grads = numeric_grad(fd_exec, {k: np.asarray(v, dtype=np.float32)
                                       for k, v in location.items()},
                             eps=numeric_eps, is_train=fd_is_train)
    for name in grad_nodes:
        rd = reldiff(num_grads[name], sym_grads[name])
        assert rd < check_eps, \
            "gradient mismatch for %s: reldiff=%g\nnumeric=%s\nsymbolic=%s" % (
                name, rd, num_grads[name], sym_grads[name])


def check_symbolic_forward(sym, location, expected, check_eps=1e-4):
    kwargs = {k: v.shape for k, v in location.items()}
    executor = sym.simple_bind(mx.current_context(), grad_req="null", **kwargs)
    for k, v in location.items():
        executor.arg_dict[k][:] = np.asarray(v, dtype=np.float32)
    executor.forward(is_train=False)
    for out, exp in zip(executor.outputs, expected):
        assert reldiff(out.asnumpy(), exp) < check_eps, \
            "forward mismatch: %s vs %s" % (out.asnumpy(), exp)


def jaxpr_eqns(jaxpr, name):
    """The equations called ``name`` of a jaxpr and of every jaxpr inside
    it (a ``pallas_call``'s kernel body, a ``jit``'s, a ``custom_vjp``'s)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for value in eqn.params.values():
            for x in value if isinstance(value, (list, tuple)) else [value]:
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from jaxpr_eqns(x, name)
