"""Per-layer conv attribution for ResNet-50 on the real chip.

VERDICT r2 asked for measurement, not claimed ceilings: this times every
unique Convolution configuration in the flagship model separately
(fwd+bwd, bf16), reports achieved TFLOP/s against the bf16 matmul probe
peak, and prints the weighted ceiling — the MFU the whole model could
reach if only conv time existed.  Run with MXNET_CONV_LAYOUT=NHWC to
A/B the channels-last lowering (ops/nn.py).

Usage:  python bench_layers.py [--batch 256] [--iters 8]
Output: a markdown table (paste into docs/perf.md) + one JSON line.
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def conv_configs(batch):
    """(name, count, x_shape, w_shape, stride, pad, groups, out_shape)
    for each UNIQUE conv config in ResNet-50, counts aggregated."""
    import jax
    from mxnet_tpu.models import get_resnet50

    net = get_resnet50(1000)
    graph = json.loads(net.tojson())
    nodes = graph["nodes"]
    ints = net.get_internals()
    outs = ints.list_outputs()
    _, out_shapes, _ = ints.infer_shape(data=(batch, 3, 224, 224),
                                        softmax_label=(batch,))
    shape_of = dict(zip(outs, [tuple(s) for s in out_shapes]))
    arg_shapes, _, _ = net.infer_shape(data=(batch, 3, 224, 224),
                                       softmax_label=(batch,))
    arg_shape = dict(zip(net.list_arguments(),
                         [tuple(s) for s in arg_shapes]))

    def node_out_shape(idx):
        n = nodes[idx]
        if n["op"] == "null":
            return arg_shape.get(n["name"]) or shape_of.get(n["name"])
        return shape_of[n["name"] + "_output"]

    uniq = {}
    for n in nodes:
        if n.get("op") != "Convolution":
            continue
        p = n["param"]
        x_shape = node_out_shape(n["inputs"][0][0])
        w_shape = arg_shape[nodes[n["inputs"][1][0]]["name"]]
        stride = eval(p["stride"])
        pad = eval(p["pad"])
        groups = int(p["num_group"])
        o_shape = shape_of[n["name"] + "_output"]
        key = (x_shape, w_shape, stride, pad, groups)
        if key in uniq:
            uniq[key][1] += 1
        else:
            uniq[key] = [n["name"], 1, x_shape, w_shape, stride, pad,
                         groups, o_shape]
    return list(uniq.values())


def conv_flops(w_shape, out_shape, groups):
    """fwd MACs*2: every output element needs I/g * kh * kw MACs."""
    o, i, kh, kw = w_shape
    n, _, oh, ow = out_shape
    return 2.0 * n * oh * ow * o * i * kh * kw


# one probe, one statistic: per-layer mfu must share the headline
# bench's denominator or the two sets of numbers stop being comparable
from bench import probe_peak_tflops  # noqa: E402


def time_conv(x_shape, w_shape, stride, pad, groups, iters, windows=3):
    """Median seconds per fwd+bwd of one conv in bf16."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    nhwc = os.environ.get("MXNET_CONV_LAYOUT", "NCHW").upper() == "NHWC"

    def fwd(x, w):
        if nhwc:
            out = lax.conv_general_dilated(
                jnp.transpose(x, (0, 2, 3, 1)), jnp.transpose(w, (2, 3, 1, 0)),
                window_strides=stride,
                padding=[(pad[0], pad[0]), (pad[1], pad[1])],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups)
            return jnp.transpose(out, (0, 3, 1, 2))
        return lax.conv_general_dilated(
            x, w, window_strides=stride,
            padding=[(pad[0], pad[0]), (pad[1], pad[1])],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups)

    @jax.jit
    def step(x, w):
        out, vjp = jax.vjp(lambda a, b: fwd(a, b), x, w)
        gx, gw = vjp(jnp.ones_like(out))
        return gx.sum() + gw.sum() + out.sum()

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*x_shape), jnp.bfloat16)
    w = jnp.asarray(rng.randn(*w_shape) * 0.05, jnp.bfloat16)
    step(x, w).block_until_ready()
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            step(x, w).block_until_ready()
        rates.append((time.perf_counter() - t0) / iters)
    return sorted(rates)[len(rates) // 2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    layout = os.environ.get("MXNET_CONV_LAYOUT", "NCHW").upper()

    cfgs = conv_configs(args.batch)
    peak = probe_peak_tflops()
    sys.stderr.write("peak probe: %.1f TFLOP/s bf16; %d unique conv "
                     "configs (batch %d, layout %s)\n"
                     % (peak, len(cfgs), args.batch, layout))

    rows, tot_time, tot_flops = [], 0.0, 0.0
    for name, count, xs, ws, st, pd, g, os_ in cfgs:
        sec = time_conv(xs, ws, st, pd, g, args.iters)
        fl = 3.0 * conv_flops(ws, os_, g)      # fwd + ~2x bwd
        tflops = fl / sec / 1e12
        rows.append((name, count, xs, ws, st, sec, tflops,
                     100.0 * tflops / peak))
        tot_time += sec * count
        tot_flops += fl * count
        sys.stderr.write("  %-24s x%-2d %.2fms  %6.1f TF/s  %5.1f%% peak\n"
                         % (name, count, sec * 1e3, tflops,
                            100.0 * tflops / peak))

    rows.sort(key=lambda r: -r[5] * r[1])
    print("| conv (first of group) | n | input | weight | stride | "
          "ms/call | TFLOP/s | % peak |")
    print("|---|---|---|---|---|---|---|---|")
    for name, count, xs, ws, st, sec, tf, pct in rows[:12]:
        print("| %s | %d | %s | %s | %s | %.2f | %.1f | %.1f |"
              % (name, count, "x".join(map(str, xs)),
                 "x".join(map(str, ws)), st, sec * 1e3, tf, pct))
    ceiling = tot_flops / tot_time / 1e12 / peak
    print()
    print(json.dumps({
        "metric": "resnet50_conv_weighted_ceiling_mfu",
        "value": round(ceiling, 4),
        "unit": "fraction_of_bf16_probe_peak",
        "layout": layout,
        "batch": args.batch,
        "peak_tflops": round(peak, 1),
        "conv_time_per_batch_ms": round(tot_time * 1e3, 2),
    }))


if __name__ == "__main__":
    main()
