"""What the grouped-head cells' chip tests share of ISSUE 70: q's and k's
norm and rotation as the kernel pair ``head_rotary_fwd`` /
``head_rotary_bwd`` alone against the plain form, and what a cell's step
compiled for the chip holds of the head form in ``attn_proj``'s part
(the step's text and the relayout search are ``_gated_norm``'s)."""
import re
import time

import numpy as np

from _gated_norm import (_chained_ms, _rel, compiled_step_text,
                         head_layout_copies)

ROWS = 8192
# q's and k's rows of the SDAR and Keye cells: 32 and 4 heads of 128
WIDTHS = (4096, 512)
HBM_BYTES_PER_MS = 819e6
# a pass may take this many times what its bytes take at the published
# rate (x in, y out and the table; x, dy in, dx out and the table)
BYTES_TIMES = {4096: 1.6, 512: 1.8}


def bytes_ms(width, arrays, itemsize=2):
    return (arrays * ROWS * width * itemsize + ROWS * 128 * 4) \
        / HBM_BYTES_PER_MS


def pair_against_the_plain_form(width, eps, norm=True, **rotation):
    """``head_norm_rotary`` of ``(8192, width)`` bfloat16 rows, ``D`` =
    128, one sequence of 8192 rows (``rotation``: ``theta``, ``period``,
    ``sections``; none, the norm alone): both lowerings compiled for the
    chip against the plain form in float32 from the same inputs (output
    and both cotangents), and each pass's ms, the kernels' and the plain
    form's, by ten calls chained in one program."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import head_rotary as hr
    rng = np.random.RandomState(70)
    bf16, f32 = jnp.bfloat16, jnp.float32
    x, dy = (jnp.asarray(rng.standard_normal((ROWS, width)), bf16)
             for _ in range(2))
    gamma = jnp.asarray(1 + 0.1 * rng.standard_normal(128), bf16) \
        if norm else None
    seq_len = ROWS if rotation else 0
    how = (eps, seq_len, float(rotation.get("theta", 1e4)),
           int(rotation.get("period", 0)),
           tuple(rotation.get("sections", ())))
    table = jax.jit(lambda: hr.rotary_table(
        ROWS, 128, seq_len, *how[2:]))() if rotation else None

    def both(fn):
        def step(x, gamma, dy):
            out, vjp = jax.vjp(fn, x, gamma)
            return (out,) + tuple(g for g in vjp(dy.astype(out.dtype))
                                  if g is not None)
        return jax.jit(step)

    def plain(x, gamma):
        return hr._plain(x, gamma, None, 128, *how)

    def kernels(x, gamma):
        return hr._two_lowerings(x, gamma, None, table, 128, how, False)

    want = both(plain)(x.astype(f32), None if gamma is None
                       else gamma.astype(f32), dy.astype(f32))
    names = ("y", "dx", "dgamma")
    out = {"shape": [ROWS, width], "norm": norm, "rotation": rotation,
           "bytes_ms": {"fwd": bytes_ms(width, 2), "bwd": bytes_ms(
               width, 3 if norm else 2)}}
    for name, fn in (("kernel", kernels), ("plain", plain)):
        out[name] = {"rel_err": {n: _rel(a, b) for n, a, b in zip(
            names, both(fn)(x, gamma, dy), want)}}
    text = both(kernels).lower(x, gamma, dy).compile().as_text()
    out["kernel"]["custom_calls"] = [
        n for n in ("head_rotary_fwd", "head_rotary_bwd") if n in text]
    kw = dict(eps=eps, interpret=False)
    out["kernel"]["fwd_ms"] = _chained_ms(
        lambda x, gamma, table: hr._rotary_fwd(x, gamma, table, **kw),
        x, gamma, table)
    out["kernel"]["bwd_ms"] = _chained_ms(
        lambda dy, x, gamma, table: hr._rotary_bwd(
            x if norm else None, gamma, table, dy, **kw)[0],
        dy, x, gamma, table)
    out["plain"]["fwd_ms"] = _chained_ms(plain, x, gamma)
    out["plain"]["bwd_ms"] = _chained_ms(
        lambda dy, x, gamma: jax.vjp(plain, x, gamma)[1](dy)[0],
        dy, x, gamma)
    return out


def check_pair(report):
    kernel, plain = report["kernel"], report["plain"]
    width = report["shape"][1]
    assert kernel["custom_calls"] == ["head_rotary_fwd", "head_rotary_bwd"]
    for n, err in kernel["rel_err"].items():
        assert err <= max(plain["rel_err"][n], 4e-3), n
    for which in ("fwd", "bwd"):
        ms = kernel[which + "_ms"]
        assert ms < BYTES_TIMES[width] * report["bytes_ms"][which], which
        assert ms < plain[which + "_ms"], which


def head_form_in_attn_proj(config, inputs=None):
    """Of a cell's step compiled for the chip: the ``rotary:lowering``
    samples its trace left, how many distinct
    ``head_rotary_fwd`` / ``head_rotary_bwd`` calls it holds, and the
    entry computation's ``copy`` / ``reshape`` operations under
    ``attn_proj`` that write a float32 or bfloat16 ``[.., 32, 128]`` or
    ``[.., 4, 128]`` array, and every float32 result of the entry
    computation in q's head form, whole heads or their halves (SDAR's
    step held 23 of ``[1, 8192, 32, 128]`` until this op)."""
    import mxnet_tpu as mx
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = compiled_step_text(config, inputs)
        chosen = mx.trace.counter_events(["rotary:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    return {
        # one sample a traced op: its track, and whether it is the pair
        "lowering": [[e["id"], e["args"]["kernel"]] for e in chosen],
        "float32_head_form": [
            line.strip()[:160]
            for line in text[text.index("ENTRY "):].splitlines()
            if re.match(r"\s*(?:ROOT )?%?[\w.-]+ = f32\[[0-9,]*,32,(128|64)\]",
                        line)],
        "calls": [len(set(re.findall(r"%%head_rotary_%s\.\d+ = " % which,
                                     text))) for which in ("fwd", "bwd")],
        "head_layout_copies": [
            line for dtype in ("f32", "bf16") for heads in (32, 4)
            for line in head_layout_copies(text, dtype, heads,
                                           scope="attn_proj")]}
