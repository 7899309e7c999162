"""BENCHMARK.json against the contract and against the files it names;
the loader's refusals; the command's refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import cellbench_util as util
import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(util.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(util.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= doc["run_seconds"] <= 51 and \
        isinstance(doc["run_seconds"], int)
    # a full check with all 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (doc["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= len(doc["paths"]) <= 16
    assert len(doc["command"]) <= 32
    assert 2 <= len(doc["workloads"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16
    assert 1 <= len(doc["per_layer"]) <= 128


def test_entries_have_just_the_contracts_keys(doc):
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert len(c["reduced"]) <= 16
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_cells_and_configs_line_up(doc):
    cells = [w["name"] for w in doc["workloads"]]
    assert len(cells) == len(set(cells))
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells)
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))


def test_a_per_layer_metric_is_listed_only_where_its_moved_metric_is(doc):
    """The driver takes an entry without a ``workloads`` list as reported
    in every cell, and refuses a per-layer metric in a cell that does
    not report the metric it moves."""
    cells = {w["name"] for w in doc["workloads"]}
    moved_in = {m["name"]: set(m.get("workloads", cells))
                for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        assert set(m.get("workloads", cells)) <= moved_in[m["moves"]], \
            m["name"]


def test_every_cell_resolves_and_reports_what_the_contract_asks(doc):
    man = manifest.Manifest()
    for w in doc["workloads"]:
        cell = man.cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.traffic["rate_metric"] in e2e
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
        for kind, name in (("drivers", cell.driver),
                           ("generators", cell.traffic["generator"]),
                           ("reference", cell.config_name)):
            assert os.path.isfile(os.path.join(util.BENCH, kind,
                                               name + ".py"))
        assert cell.config["reduced"] == \
            man.configs[cell.config_name]["reduced"]


def test_per_layer_entries_agree_with_their_readers(doc):
    for m in doc["per_layer"]:
        reader = manifest.load_module("layer_metrics",
                                      m["name"].split(".", 1)[0])
        assert (m["unit"], m["layer"], m["source"], m["better"]) == \
            (reader.UNIT, reader.LAYER, reader.SOURCE, reader.BETTER), \
            m["name"]
    layers = {m["layer"] for m in doc["per_layer"]}
    with open(os.path.join(util.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert "| %s |" % layer in perf, \
            "PERF.md's list of layers lacks %r" % layer


# what holds of any BENCHMARK.json, later additions at its lists' ends
# included: test_cellbench_rehearsal.py finds these by name and runs
# them against copies with such additions
check_top_level_keys_and_limits = test_top_level_keys_and_limits
check_entries_have_just_the_contracts_keys = \
    test_entries_have_just_the_contracts_keys
check_cells_and_configs_line_up = test_cells_and_configs_line_up
check_a_per_layer_metric_is_listed_only_where_its_moved_metric_is = \
    test_a_per_layer_metric_is_listed_only_where_its_moved_metric_is
check_per_layer_entries_agree_with_their_readers = \
    test_per_layer_entries_agree_with_their_readers


def test_no_cell_name_in_harness_code(doc):
    """The harness is driven by data: no cell, configuration, traffic or
    metric name appears in its code."""
    names = {w["name"] for w in doc["workloads"]} \
        | {w["traffic"] for w in doc["workloads"]} \
        | {c["name"] for c in doc["configs"]} \
        | {m["name"] for m in doc["end_to_end"]} - {"setup_s"}
    for rel in ("run.py", "manifest.py", "stats.py", "trace_reduce.py",
                "flops.py", "kernel_rooflines.py",
                os.path.join("drivers", "train_fit.py")):
        with open(os.path.join(util.BENCH, rel)) as f:
            text = f.read()
        for n in names:
            assert '"%s"' % n not in text and "'%s'" % n not in text, \
                "%s names %r" % (rel, n)


# -- refusals ------------------------------------------------------------------

def test_peaks_refuse_an_unknown_device():
    assert manifest.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError, match="not in peaks.json"):
        manifest.load_peaks("TPU v9 imaginary")
    with pytest.raises(manifest.ManifestError, match="not in peaks.json"):
        manifest.load_peaks("cpu")


@pytest.mark.parametrize("bad", ["", "has space", "a/b", "../x", "a,b",
                                 "-leading", "x" * 65, None, "µs"])
def test_a_bad_name_is_refused(bad):
    with pytest.raises(manifest.ManifestError, match="is not a name"):
        manifest.check_name(bad, "test")


@pytest.mark.parametrize("good", ["a", "_x", "9lives", "resnet50-dp4-b512",
                                  "step_ms_p50.img", "x" * 64])
def test_a_good_name_passes(good):
    assert manifest.check_name(good, "test") == good


def _broken_copy(tmp_path, mutate):
    root = str(tmp_path)
    shutil.copytree(util.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    with open(os.path.join(util.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    mutate(doc, root)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return root, doc["workloads"][0]["name"]


def test_a_missing_configuration_file_is_refused(tmp_path):
    def mutate(doc, root):
        os.remove(os.path.join(root, doc["configs"][0]["file"]))
    root, _ = _broken_copy(tmp_path, mutate)
    man = manifest.Manifest(root)
    cell = next(w["name"] for w in man.doc["workloads"]
                if w["config"] == man.doc["configs"][0]["name"])
    with pytest.raises(manifest.ManifestError, match="no file"):
        man.cell(cell)


def test_a_missing_traffic_file_and_an_unknown_cell_are_refused(tmp_path):
    def mutate(doc, root):
        doc["workloads"][0]["traffic"] = "no-such-traffic"
    root, cell = _broken_copy(tmp_path, mutate)
    with pytest.raises(manifest.ManifestError, match="no file"):
        manifest.Manifest(root).cell(cell)
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.Manifest(root).cell("no-such-cell")


def test_a_metric_that_moves_nothing_is_refused(tmp_path):
    def mutate(doc, root):
        doc["per_layer"][0]["moves"] = "no_such_metric"
    root, _ = _broken_copy(tmp_path, mutate)
    with pytest.raises(manifest.ManifestError, match="no end-to-end"):
        manifest.Manifest(root)


def test_a_bad_cell_name_in_the_manifest_is_refused(tmp_path):
    def mutate(doc, root):
        doc["workloads"][0]["name"] = "bad name"
    root, _ = _broken_copy(tmp_path, mutate)
    with pytest.raises(manifest.ManifestError, match="is not a name"):
        manifest.Manifest(root)


def _run(cmd, cwd, **env):
    full = dict(os.environ, **env)
    return subprocess.run(cmd, cwd=cwd, env=full, capture_output=True,
                          text=True, timeout=300)


def test_without_a_chip_the_command_fails_and_prints_no_result(doc):
    cell = doc["workloads"][0]["name"]
    p = _run([sys.executable, os.path.join("benchmark", "run.py"),
              "--workload", cell, "--seed", "1", "--seconds", "1",
              "--trace", "0"], util.ROOT, JAX_PLATFORMS="cpu")
    assert p.returncode not in (0, None)
    assert "only a TPU is measured" in p.stderr
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout


def test_an_unknown_cell_fails_before_jax_is_touched(doc):
    p = _run([sys.executable, os.path.join("benchmark", "run.py"),
              "--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
              "--trace", "0"], util.ROOT, JAX_PLATFORMS="cpu")
    assert p.returncode == 4 and "no workload" in p.stderr
    assert p.stdout.strip() == ""


def test_a_directory_without_the_program_fails_and_prints_no_result(
        tmp_path, doc):
    """Only BENCHMARK.json and the files under ``paths``: exit 5."""
    root = str(tmp_path)
    shutil.copy(os.path.join(util.ROOT, "BENCHMARK.json"), root)
    for rel in doc["paths"]:
        shutil.copytree(os.path.join(util.ROOT, rel),
                        os.path.join(root, rel),
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         doc["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 5, p.stderr
    assert "not in this checkout" in p.stderr
    assert p.stdout.strip() == ""


def test_no_chip_call_at_import_time():
    """Importing the harness's modules describes no topology and loads no
    TPU library: nothing of jax or the program is imported by them."""
    code = ("import sys; sys.path.insert(0, %r); import manifest, stats, "
            "flops, trace_reduce, kernel_rooflines; "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith('jax.') or m.startswith('mxnet_tpu')]; "
            "print(bad); sys.exit(1 if bad else 0)" % util.BENCH)
    p = _run([sys.executable, "-c", code], util.ROOT)
    assert p.returncode == 0, p.stdout + p.stderr
