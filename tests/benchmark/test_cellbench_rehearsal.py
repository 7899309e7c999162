"""A rehearsal of the next ``model_config`` PR, in temporary copies: a
configuration, a ``.tok`` cell and a per-layer entry are appended, each
at the end of its list, and every check of ``BENCHMARK.json`` that the
tests hold still passes.  It fails when a test pins a position again: the
last place of ``per_layer`` or of a ``workloads`` list, a list's length,
a cell as a list's only member.

The checks are found, not listed: every function named ``check_*`` whose
one argument is ``doc``, in every ``test_*.py`` of this directory.  Each
runs against two copies.  In ``by-suffix`` the new cell joins the rate
and every ``.tok`` entry, as a plain language model would; in
``like-newest`` it is appended with ``add_cell(..., like=<the last
one-chip ``.tok`` cell of the real file>)``, so it joins that cell's
``moe_*`` and roofline lists too, as the next transformer will."""
import collections
import glob
import importlib
import inspect
import os
import shutil

import pytest

import cellbench_util as util
import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "next-tok"
RATE = "train_tok_per_s"
ENTRY = {"name": "step_ms_p99.tok", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "entry points",
         "moves": RATE, "workloads": [CELL]}
Copy = collections.namedtuple("Copy", "root kind")


def _test_files(pattern):
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(HERE, pattern)))


def _checks_of(module_name):
    """name -> function, of the module's ``check_*(doc)``."""
    if module_name == __name__:
        return {}
    module = importlib.import_module(module_name)
    return {name: f for name, f in sorted(vars(module).items())
            if name.startswith("check_") and inspect.isfunction(f)
            and list(inspect.signature(f).parameters) == ["doc"]}


CHECKS = [pytest.param(f, id="%s.%s" % (module, name))
          for module in _test_files("test_*.py")
          for name, f in _checks_of(module).items()]


def newest_tok_cell(doc):
    """The last one-chip cell of ``doc`` that reports the token rate."""
    rate = next(m for m in doc["end_to_end"] if m["name"] == RATE)
    return [w["name"] for w in doc["workloads"]
            if w["chips"] == 1 and w["name"] in rate["workloads"]][-1]


@pytest.fixture(scope="module", params=("by-suffix", "like-newest"))
def copy(request, tmp_path_factory):
    root = util.tiny_copy(tmp_path_factory.mktemp("cellbench_rehearsal"))
    bench = os.path.join(root, "benchmark")
    for kind, ext in (("configs", ".json"), ("reference", ".py")):
        shutil.copy(os.path.join(bench, kind, "lstm-tiny" + ext),
                    os.path.join(bench, kind, "next-tiny" + ext))
    doc = util._load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({"name": "next-tiny", "source": "test",
                           "file": "benchmark/configs/next-tiny.json",
                           "reduced": [], "why": "test"})
    if request.param == "by-suffix":
        doc["workloads"].append({"name": CELL, "config": "next-tiny",
                                 "traffic": "tiny-buckets", "chips": 1,
                                 "why": "test"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if m["name"] == RATE or m["name"].endswith(".tok"):
                m["workloads"].append(CELL)
    else:
        util.add_cell(doc, CELL, "next-tiny", "tiny-buckets",
                      like=newest_tok_cell(manifest.Manifest().doc))
    # an entry of the cell's own, with a reader that is there and has none
    doc["per_layer"].append(dict(ENTRY))
    util._dump(doc, os.path.join(root, "BENCHMARK.json"))
    return Copy(root, request.param)


def test_the_appended_cell_resolves_with_every_metric_it_joined(copy):
    cell = manifest.Manifest(copy.root).cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    real = manifest.Manifest().doc
    if copy.kind == "by-suffix":
        joined = [m["name"] for m in real["per_layer"]
                  if m["name"].endswith(".tok") or "workloads" not in m]
    else:
        like = newest_tok_cell(real)
        joined = [m["name"] for m in real["per_layer"]
                  if like in m.get("workloads", [like])]
        # that cell's own entries and kernels too, which no suffix finds
        assert [m["name"] for m in real["per_layer"]
                if like in m.get("workloads", [])
                and not m["name"].endswith(".tok")]
    assert names == joined + [ENTRY["name"]]
    assert len(names) == len(set(names))
    assert [m["name"] for m in cell.end_to_end] == [RATE, "setup_s"]


def test_what_was_there_did_not_move(copy):
    """Every entry of the real file is in the copy at its index, and every
    ``workloads`` list begins with the real one."""
    real, doc = manifest.Manifest().doc, manifest.Manifest(copy.root).doc
    for key in ("configs", "workloads", "per_layer"):
        assert len(doc[key]) > len(real[key])
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(real[key], doc[key]):
            was, now = dict(was), dict(now)
            cells = was.pop("workloads", [])
            assert now.pop("workloads", [])[:len(cells)] == cells
            assert was == now
    for w in real["workloads"]:
        assert [m["name"] for m in manifest.Manifest(copy.root).cell(
            w["name"]).per_layer] == [m["name"] for m in
                                      manifest.Manifest().cell(
                                          w["name"]).per_layer]


@pytest.mark.parametrize("check", CHECKS)
def test_a_check_of_the_real_file_passes_on_the_copy(copy, check):
    check(manifest.Manifest(copy.root).doc)


@pytest.mark.parametrize("name", _test_files("test_cell_*.py"))
def test_a_cells_test_file_exposes_its_checks_of_the_real_file(name):
    """A per-cell file that reads the real ``BENCHMARK.json`` inside a
    test only is not rehearsed: the next PR's additions would meet its
    pins first in the driver's run."""
    assert _checks_of(name), (
        "%s.py exposes no check_*(doc): put whatever it holds of the real "
        "BENCHMARK.json (its cell, its entries, the lists it is on) into a "
        "module-level function named check_<what>(doc) that finds entries "
        "by name and checks membership, and call it from a test with "
        "manifest.Manifest().doc; this rehearsal then runs it against "
        "copies to which a configuration, a cell and an entry were "
        "appended" % name)
