"""The benchmark's command: one cell, one run, one result line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Everything a cell is made of is found by name
through BENCHMARK.json (see README.md); this file holds no cell,
configuration, traffic or metric name.  Only a TPU is measured: where
JAX finds another platform, or fewer chips than the cell asks for, the
command says so, prints no result and exits with a code other than 0.
The last line of standard output is the result, one JSON object.
"""
import time

T_PROCESS = time.perf_counter()        # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import manifest as _manifest  # noqa: E402
import stats  # noqa: E402

EXIT_NO_DEVICE = 3
EXIT_BAD_MANIFEST = 4
EXIT_NO_PROGRAM = 5


def log(msg):
    print("[%7.1fs] %s" % (time.perf_counter() - T_PROCESS, msg), flush=True)


def fail(code, msg):
    sys.stderr.write("benchmark: %s\n" % msg)
    sys.exit(code)


def layer_metrics(cell, obs):
    """Every per-layer metric of this cell whose reader finds something
    to read."""
    out = {}
    for entry in cell.per_layer:
        reader = _manifest.load_module("layer_metrics",
                                       cell.reader_of(entry["name"]),
                                       cell.bench_dir)
        if obs["driver"] not in reader.DRIVERS:
            continue
        for key, declared in (("unit", reader.UNIT), ("layer", reader.LAYER),
                              ("source", reader.SOURCE),
                              ("better", reader.BETTER)):
            if entry[key] != declared:
                raise _manifest.ManifestError(
                    "per-layer metric %r: BENCHMARK.json says %s %r, its "
                    "reader %r" % (entry["name"], key, entry[key], declared))
        got = reader.read(obs)
        if got is None:
            continue
        value, extra = got if isinstance(got, tuple) else (got, {})
        out[entry["name"]] = stats.metric(value, entry["unit"], **extra)
    return out


def absent_metrics(cell, metrics):
    """Per-layer metrics BENCHMARK.json lists for this cell that the
    line does not carry."""
    return [m["name"] for m in cell.per_layer if m["name"] not in metrics]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = _manifest.Manifest(ROOT).cell(args.workload)
    except _manifest.ManifestError as e:
        fail(EXIT_BAD_MANIFEST, str(e))

    import importlib.util
    if importlib.util.find_spec("mxnet_tpu") is None:
        fail(EXIT_NO_PROGRAM, "the program (mxnet_tpu) is not in this "
             "checkout: there is nothing to measure")

    # the one variable the harness sets for the program
    os.environ["MXNET_COMPUTE_DTYPE"] = cell.config["compute_dtype"]
    log("host cores (os.cpu_count) %s; cell %s, seed %d, %.0f s, trace %d"
        % (os.cpu_count(), cell.name, args.seed, args.seconds, args.trace))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(EXIT_NO_DEVICE, "JAX found platform %r (%s): only a TPU is "
             "measured, there is no result" % (devices[0].platform, devices))
    if len(devices) < cell.chips:
        fail(EXIT_NO_DEVICE, "cell %r needs %d chips, JAX found %d"
             % (cell.name, cell.chips, len(devices)))
    try:
        peaks = _manifest.load_peaks(devices[0].device_kind, cell.bench_dir)
    except _manifest.ManifestError as e:
        fail(EXIT_BAD_MANIFEST, str(e))
    import mxnet_tpu as mx
    from mxnet_tpu.compile_cache import place_jax_cache
    log("JAX's persistent compilation cache: %s" % place_jax_cache())

    driver = _manifest.load_module("drivers", cell.driver, cell.bench_dir)
    contexts = [mx.tpu(i) for i in range(cell.chips)]
    result = driver.run(cell, contexts, args.seed, args.seconds,
                        bool(args.trace), T_PROCESS, peaks, log)
    obs = result.pop("_obs")
    e2e = result.pop("_e2e")
    log("reference: %s" % json.dumps(result.pop("_reference")))
    log("memory: %s" % json.dumps(obs["memory"]))
    if args.trace:
        result["metrics"] = layer_metrics(cell, obs)
        absent = absent_metrics(cell, result["metrics"])
        if absent:
            # the line is printed as it is: whoever checks it wants every
            # metric BENCHMARK.json lists for this cell, and should see why
            sys.stderr.write(
                "benchmark: nothing to read for %s in cell %r: list a "
                "per-layer metric only for cells whose every traced run "
                "reports it\n" % (", ".join(absent), cell.name))
        log("end-to-end readings of this traced run (not results): %s"
            % json.dumps(e2e))
    else:
        for entry in cell.end_to_end:
            if entry["name"] not in e2e:
                raise _manifest.ManifestError(
                    "cell %r reports end-to-end metric %r, which its driver "
                    "did not measure (it has %s)"
                    % (cell.name, entry["name"], sorted(e2e)))
            result["metrics"][entry["name"]] = stats.metric(
                e2e[entry["name"]], entry["unit"])
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
