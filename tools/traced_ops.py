#!/usr/bin/env python
"""Every device operation of one traced run of a benchmark cell, by the
scope that made it: what an issue prices a lever from and what its PR is
checked against (``PERF.md`` §6, PR 70).  Written to
``chiprun_out/ops_<cell>_traced.json``, which git ignores: the lists a PR
rests on are copied to ``docs/traced_ops/`` and committed.

Runs the cell as ``benchmark/run.py --trace 1`` does (the same driver,
one process, only on a TPU) and joins the trace's ``op_seconds`` with
the step program's own tables (``mx.trace.program_scopes`` and
``program_op_names``), which the result line sums by kind and this file
keeps whole: ``{"cell", "seed", "steps", "metrics", "ops": [[ms a traced
step, scope or null, "<instruction> <opcode> <largest array>", JAX's
op_name], ..]}``, longest first.  It reads the benchmark and edits
nothing of it; its numbers are a traced run's, not results.

    chiprun -- python tools/traced_ops.py --workload <cell> --seed <n> \
        [--seconds 20] [--out chiprun_out/ops_<cell>_traced.json]
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import run as bench
    cell = bench._manifest.Manifest(ROOT).cell(args.workload)
    os.environ["MXNET_COMPUTE_DTYPE"] = cell.config["compute_dtype"]
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        bench.fail(bench.EXIT_NO_DEVICE, "only a TPU is traced, JAX found %r"
                   % device.platform)
    import mxnet_tpu as mx
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    driver = bench._manifest.load_module("drivers", cell.driver,
                                         cell.bench_dir)
    result = driver.run(
        cell, [mx.tpu(i) for i in range(cell.chips)], args.seed,
        args.seconds, True, bench.T_PROCESS,
        bench._manifest.load_peaks(device.device_kind, cell.bench_dir),
        bench.log)
    obs = result.pop("_obs")
    trace = obs["trace"]
    scopes = mx.trace.program_scopes("fused:step") or {}
    names = mx.trace.program_op_names("fused:step") or {}
    ops = sorted(
        ([round(1e3 * s / trace["steps"], 5), scopes.get(instruction), key,
          names.get(instruction)]
         for key, s in trace["op_seconds"].items()
         for instruction in [key.split(" ", 1)[0]]), key=lambda o: -o[0])
    out = args.out or os.path.join(
        ROOT, "chiprun_out", "ops_%s_traced.json" % cell.name)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    head = {"cell": cell.name, "seed": args.seed,
            "device": device.device_kind, "steps": trace["steps"],
            "correct": result["correct"],
            "memory_peak_bytes": obs["memory"]["peak_bytes"],
            "traced_rate": obs["traced_rate"],
            "metrics": bench.layer_metrics(cell, obs)}
    with open(out, "w") as f:
        # an operation a line
        f.write(json.dumps(head)[:-1] + ', "ops": [\n' + ",\n".join(
            json.dumps(op, separators=(",", ":")) for op in ops) + "\n]}\n")
    print(json.dumps({"cell": cell.name, "out": os.path.relpath(out, ROOT),
                      "steps": trace["steps"], "ops": len(ops),
                      "ms_a_step": round(sum(o[0] for o in ops), 3),
                      "correct": result["correct"]}), flush=True)


if __name__ == "__main__":
    main()
