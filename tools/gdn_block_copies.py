#!/usr/bin/env python
"""What XLA:TPU builds around the Gated DeltaNet mixer's convolution,
read without a chip: a plain ``jnp`` replica of one mixer block of
Qwen3-Next at the published widths (bfloat16 compute, float32
parameters cast, forward and ``jax.vjp``, the rule ``gated_delta_net``
itself, on a TPU its kernels) compiled for a described v5e, in three
forms:

  chain  the block as it was built before PR 53: cut q, k, v, z ->
         ``Concat`` -> ``causal_conv1d`` -> SiLU -> cut; the output
         stage ``RMSNorm`` x ``silu(z)`` over ``(rows * heads, 128)``
  op     the block as it was built from PR 53 to PR 67: ``causal_conv``
         on the projection where it lies (on a TPU the kernel pair),
         the output stage as in ``chain``
  norm   the block as ``models/qwen3_next.py`` builds it: ``op`` with
         the output stage as ``gated_rms_norm`` on the rows the rule
         writes (on a TPU the kernel pair ``gated_norm_fwd`` / ``_bwd``)

and prints, a form, XLA's ``bytes accessed`` and the entry computation's
``copy`` operations and fusions by kind with the bytes of their results
(what a relayout writes).  ``bytes accessed`` books asynchronous
prefetches twice, so the numbers are ratios between forms, not times.

    JAX_PLATFORMS=cpu python tools/gdn_block_copies.py [--seq 4096] [--text DIR]
"""
import argparse
import collections
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from mxnet_tpu.ops.causal_conv import causal_conv, causal_conv1d  # noqa: E402
from mxnet_tpu.ops.gated_norm import gated_rms_norm  # noqa: E402
from mxnet_tpu.ops.linear_attention import gated_delta_net  # noqa: E402

FORMS = ("chain", "op", "norm")

HIDDEN, HK, HV, D, TAPS = 2048, 16, 32, 128, 4
GROUP = HV // HK
BYTES = {"bf16": 2, "f32": 4, "s32": 4, "pred": 1, "u32": 4, "f16": 2,
         "s8": 1, "u8": 1}


def rms(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            ).astype(x.dtype) * w


def param_shapes():
    return {"qkvz": (HK * (2 + 2 * GROUP) * D, HIDDEN),
            "conv": ((2 * HK + HV) * D, TAPS), "ba": (2 * HV, HIDDEN),
            "a_log": (HV,), "dt_bias": (HV,), "norm": (D,),
            "o": (HIDDEN, HV * D)}


def block(form, params, h, dtype):
    p = {n: x.astype(dtype) for n, x in params.items()}
    t = h.shape[0]
    qkvz = (h @ p["qkvz"].T).reshape(1, t, HK, (2 + 2 * GROUP) * D)
    z = qkvz[..., (2 + GROUP) * D:]
    if form == "chain":
        q, k, v = qkvz[..., :D], qkvz[..., D:2 * D], qkvz[..., 2 * D:(
            2 + GROUP) * D]
        mixed = jnp.concatenate([x.reshape(1, t, -1) for x in (q, k, v)], 2)
        mixed = jax.nn.silu(causal_conv1d(mixed, p["conv"]))
    else:
        mixed, z = causal_conv(qkvz, p["conv"], "silu", (D, D, GROUP * D))
    q, k, v = (x.reshape(1, t, n, D) for x, n in zip(
        jnp.split(mixed, [HK * D, 2 * HK * D], axis=2), (HK, HK, HV)))
    ba = (h @ p["ba"].T).reshape(1, t, HK, 2 * GROUP)
    b, a = (x.reshape(1, t, HV) for x in (ba[..., :GROUP], ba[..., GROUP:]))
    o = gated_delta_net(q, k, v, a, b, p["a_log"], p["dt_bias"])
    if form == "norm":
        o = gated_rms_norm(o.reshape(1, t, HV * D), p["norm"], z, 1e-6)
    else:
        o = rms(o.reshape(-1, D), p["norm"])
        o = o * jax.nn.silu(z.reshape(-1, D))
    return o.reshape(t, HV * D) @ p["o"].T


def step(form, params, h, dy, dtype=jnp.bfloat16):
    y, vjp = jax.vjp(lambda p, h: block(form, p, h, dtype), params, h)
    return (y,) + vjp(dy)


def shape_bytes(text):
    """Bytes of every array shape ``dtype[dims]`` in ``text``."""
    total = 0
    for dtype, dims in re.findall(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]", text):
        if dtype in BYTES:
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * BYTES[dtype]
    return total


def entry_ops(text):
    """{kind: [count, bytes of results]} of the entry computation's
    copies and fusions (the compiled text names operands, not shapes)."""
    entry = text[text.index("ENTRY "):]
    kinds = collections.defaultdict(lambda: [0, 0])
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = (.*?) (copy|fusion)\((.*)",
                     line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        kind = op
        if op == "fusion":
            k = re.search(r"kind=k(\w+)", rest)
            kind = "fusion:" + (k.group(1) if k else "?")
            if "convolution" in name or "dot" in name:
                kind += ":matmul"
        kinds[kind][0] += 1
        kinds[kind][1] += shape_bytes(result)
    return kinds


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--text", help="directory for each form's compiled text")
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1",
        chips_per_host_bounds=(1, 1, 1), num_slices=1)
    chip = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = {n: arr(s, jnp.float32) for n, s in param_shapes().items()}
    rows = arr((args.seq, HIDDEN), jnp.bfloat16)
    for form in FORMS:
        compiled = jax.jit(step, static_argnums=0).lower(
            form, params, rows, rows).compile()
        text = compiled.as_text()
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            with open(os.path.join(args.text, form + ".txt"), "w") as f:
                f.write(text)
        cost = compiled.cost_analysis()
        print("%-5s bytes accessed %.3f GB, flops %.3f T, kernels %d"
              % (form, cost.get("bytes accessed", 0) / 1e9,
                 cost.get("flops", 0) / 1e12, text.count("tpu_custom_call")))
        for kind, (n, b) in sorted(entry_ops(text).items()):
            print("      %-28s %3d  %.4f GB" % (kind, n, b / 1e9))


if __name__ == "__main__":
    main()
