#!/usr/bin/env python3
"""Compile one LM cell's fused training step for a DESCRIBED v5e chip, from
shapes alone, and print XLA's memory analysis: no chip, no weights, nothing
bound (the `on-chip-measurement` guide's third rehearsal).

    JAX_PLATFORMS=cpu python tools/cell_rehearsal.py <configuration> [--text FILE]

``<configuration>`` is a file name under ``benchmark/configs`` without
``.json``.  The step is the one ``Module.fit`` runs on the chip: the
configuration's builder, arguments, optimizer and compute dtype, one
sequence ``(1, seq_len)`` a step.  What the TPU's compiler refuses (a
kernel's tiling, too much fast memory, a program that does not fit the
chip's 15.75 GiB) it refuses here.  Printed: arguments (the state: 12 B a
parameter under Adam), temporaries (the float32 gradients and the
activations the backward pass keeps) and their sum in GiB, and how many
Mosaic kernels and ``copy`` operations (relayouts and same-layout copies
the compiler adds) the program holds.  ``--text`` writes the compiled program.
A compile that passes is not a chip run.
"""
import argparse
import importlib
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def compiled_step(config: str):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import mxnet_tpu as mx
    from mxnet_tpu.module.fused import FusedTrainStep

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    module, name = cfg["model"]["builder"].rsplit(".", 1)
    kwargs = cfg["model"]["kwargs"]
    net = getattr(importlib.import_module(module), name)(**kwargs)
    inputs = {n: (1, kwargs["seq_len"]) for n in ("data", "softmax_label")}
    arg_shapes, _, aux_shapes = net.infer_shape(**inputs)
    shapes = dict(zip(net.list_arguments(), arg_shapes))
    params = [n for n in shapes if n not in inputs]
    fts = FusedTrainStep(
        net, [mx.cpu(0)], ["data"], ["softmax_label"], params, [],
        mx.optimizer.create(cfg["optimizer"]["name"],
                            **cfg["optimizer"]["params"]),
        label_shapes=[("softmax_label", inputs["softmax_label"])],
        compute_dtype=cfg["compute_dtype"])
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def arr(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: arr(a.shape, a.dtype), tree)

    weights = {n: arr(shapes[n]) for n in params}
    state = {"params": weights,
             "opt": {n: on_chip(jax.eval_shape(fts._opt_init, w))
                     for n, w in weights.items()},
             "aux": {n: arr(s) for n, s in zip(
                 net.list_auxiliary_states(), aux_shapes)},
             "fixed": {}, "t": arr((), jnp.int32)}
    batch = {n: arr(s, jnp.int32) for n, s in inputs.items()}
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    held = sum(math.prod(shapes[n]) for n in params)
    return held, jax.jit(fts._make_step_fn(), donate_argnums=(0,)).lower(
        state, batch, arr(()), key).compile()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--text", help="write the compiled program here")
    args = ap.parse_args()
    t0 = time.time()
    held, compiled = compiled_step(args.config)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    gib = 2.0 ** 30
    print(json.dumps({
        "config": args.config, "parameters": held,
        "argument_gib": round(mem.argument_size_in_bytes / gib, 3),
        "temporaries_gib": round(mem.temp_size_in_bytes / gib, 3),
        "output_gib": round(mem.output_size_in_bytes / gib, 3),
        "alias_gib": round(mem.alias_size_in_bytes / gib, 3),
        "argument_plus_temporaries_gib": round(
            (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gib, 3),
        "mosaic_kernels": text.count("tpu_custom_call"),
        "copies": text.count(" copy("),
        "compile_s": round(time.time() - t0, 1)}))


if __name__ == "__main__":
    main()
