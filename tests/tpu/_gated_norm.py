"""What the two delta-rule cells' chip tests share of ISSUE 68: the
output stage's kernel pair alone against the plain form, and the text of
a cell's step compiled for the chip."""
import importlib
import json
import os
import re
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SHAPE = (1, 4096, 4096)
# the bytes a pass moves at 819 GB/s: three and five (4096, 4096)
# bfloat16 arrays, 0.12 and 0.20 ms
FWD_MS, BWD_MS = 0.2, 0.35


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _chained_ms(fn, *a, calls=10, reps=5):
    """ms a call of ``calls`` calls chained inside one program (a call
    takes less than its dispatch does): ``fn(carry, *a) -> carry``."""
    import jax

    def chain(x, *a):
        for _ in range(calls):
            x = fn(x, *a)
        return x

    run = jax.jit(chain)
    jax.block_until_ready(run(*a))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = run(*a)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps / calls * 1e3)
    return best


def pair_against_the_plain_form(act, eps):
    """``gated_rms_norm`` at the cells' ``(1, 4096, 4096)`` bfloat16 rows,
    ``D`` = 128: both lowerings compiled for the chip against the plain
    form in float32 from the same inputs (output and the three
    cotangents), and each pass's ms, the kernels' and the plain form's."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import gated_norm as gn
    rng = np.random.RandomState(68)
    bf16, f32 = jnp.bfloat16, jnp.float32
    x, gate, dy = (jnp.asarray(rng.standard_normal(SHAPE), bf16)
                   for _ in range(3))
    gamma = jnp.asarray(1 + 0.1 * rng.standard_normal(128), bf16)

    def both(fn):
        def step(x, gate, gamma, dy):
            out, vjp = jax.vjp(fn, x, gate, gamma)
            return (out,) + vjp(dy.astype(out.dtype))
        return jax.jit(step)

    def plain(x, gate, gamma):
        return gn._plain(x, gate, gamma, eps, act)

    def kernels(x, gate, gamma):
        return gn._two_lowerings(x, gate, gamma, eps, act, False)

    want = both(plain)(x.astype(f32), gate.astype(f32), gamma.astype(f32),
                       dy.astype(f32))
    names = ("y", "dx", "dgate", "dgamma")
    out = {"shape": list(SHAPE), "act": act}
    for name, fn in (("kernel", kernels), ("plain", plain)):
        step = both(fn)
        out[name] = {"rel_err": {n: _rel(a, b) for n, a, b in
                                 zip(names, step(x, gate, gamma, dy), want)}}
    text = both(kernels).lower(x, gate, gamma, dy).compile().as_text()
    out["kernel"]["custom_calls"] = [
        n for n in ("gated_norm_fwd", "gated_norm_bwd") if n in text]
    kw = dict(eps=eps, act=act, interpret=False)
    out["kernel"]["fwd_ms"] = _chained_ms(
        lambda x, gate, gamma: gn._norm_fwd(x, gate, gamma, **kw),
        x, gate, gamma)
    out["kernel"]["bwd_ms"] = _chained_ms(
        lambda dy, x, gate, gamma: gn._norm_bwd(x, gate, gamma, dy, **kw)[0],
        dy, x, gate, gamma)
    out["plain"]["fwd_ms"] = _chained_ms(plain, x, gate, gamma)
    out["plain"]["bwd_ms"] = _chained_ms(
        lambda dy, x, gate, gamma: jax.vjp(plain, x, gate, gamma)[1](dy)[0],
        dy, x, gate, gamma)
    return out


def check_pair(report):
    kernel, plain = report["kernel"], report["plain"]
    assert kernel["custom_calls"] == ["gated_norm_fwd", "gated_norm_bwd"]
    for n, err in kernel["rel_err"].items():
        assert err <= max(plain["rel_err"][n], 4e-3), n
    assert kernel["fwd_ms"] < FWD_MS and kernel["bwd_ms"] < BWD_MS
    assert kernel["fwd_ms"] < plain["fwd_ms"]
    assert kernel["bwd_ms"] < plain["bwd_ms"]


def compiled_step_text(config, inputs=None):
    """The text of a cell's fused step (its configuration's builder,
    arguments, optimizer and compute dtype, one sequence a step) compiled
    for the chip from shapes alone: nothing is bound or held.
    ``inputs``: the shapes of ``data`` and ``softmax_label`` where they
    are not ``(1, seq_len)``."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.module.fused import FusedTrainStep
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    module, name = cfg["model"]["builder"].rsplit(".", 1)
    kwargs = cfg["model"]["kwargs"]
    net = getattr(importlib.import_module(module), name)(**kwargs)
    shape = (1, kwargs["seq_len"])
    inputs = inputs or {"data": shape, "softmax_label": shape}
    arg_shapes, _, aux_shapes = net.infer_shape(**inputs)
    shapes = dict(zip(net.list_arguments(), arg_shapes))
    params = [n for n in shapes if n not in inputs]
    fts = FusedTrainStep(
        net, [mx.tpu(0)], ["data"], ["softmax_label"], params, [],
        mx.optimizer.create(cfg["optimizer"]["name"],
                            **cfg["optimizer"]["params"]),
        label_shapes=[("softmax_label", inputs["softmax_label"])],
        compute_dtype=cfg["compute_dtype"])

    def arr(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(s), dtype)

    weights = {n: arr(shapes[n]) for n in params}
    state = {"params": weights,
             "opt": {n: jax.eval_shape(fts._opt_init, w)
                     for n, w in weights.items()},
             "aux": {n: arr(s) for n, s in zip(
                 net.list_auxiliary_states(), aux_shapes)},
             "fixed": {}, "t": arr((), jnp.int32)}
    batch = {n: arr(s, jnp.int32) for n, s in inputs.items()}
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return jax.jit(fts._make_step_fn(), donate_argnums=(0,)).lower(
        state, batch, arr(()), key).compile().as_text()


def head_layout_copies(text, dtype="f32", heads=32, scope=""):
    """The entry computation's ``copy`` and ``reshape`` operations that
    write a ``<dtype>[.., heads, 128]`` array: a relayout between rows
    with the tokens on the sublanes and heads on the sublanes.
    ``scope``: only those whose ``op_name`` holds it."""
    entry = text[text.index("ENTRY "):]
    return [line.strip()[:200] for line in entry.splitlines() if re.match(
        r"\s*(?:ROOT )?%?[\w.-]+ = " + dtype
        + r"\[[0-9,]*%d,128\]\S* (copy|reshape)\(" % heads, line)
        and scope in line]
