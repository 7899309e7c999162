"""BENCHMARK.json and the files it names, as the harness sees them.

The harness holds no cell, configuration, traffic or metric name: a cell
is one entry of ``workloads``; its configuration is the file its
``configs`` entry names; its traffic is ``traffic/<traffic>.json``, which
names the generator (``generators/<generator>.py``) and the kind of
driver (``drivers/<driver>.py``); a per-layer metric ``<reader>`` or
``<reader>.<tag>`` is read by ``layer_metrics/<reader>.py``; the
configuration's reference and FLOP function are
``reference/<config>.py``.  Adding any of them is adding files and
entries.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class ManifestError(Exception):
    """BENCHMARK.json, or a file it names, cannot be used."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(
            "%s %r is not a name (a letter, digit or '_', then at most 63 "
            "letters, digits, '_', '.', '-')" % (what, name))
    return name


def _read_json(path: str, what: str) -> Dict:
    if not os.path.isfile(path):
        raise ManifestError("%s: no file %s" % (what, path))
    try:
        with open(path) as f:
            return json.load(f)
    except ValueError as e:
        raise ManifestError("%s: %s is not JSON (%s)" % (what, path, e))


def load_module(kind_dir: str, name: str, bench_dir: str = HERE):
    """``<bench_dir>/<kind_dir>/<name>.py`` as a module.  Names may hold
    '-' and '.', so the file is loaded by path, not imported by name."""
    check_name(name, kind_dir + " module")
    path = os.path.join(bench_dir, kind_dir, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError("no file %s" % path)
    mod_name = "benchmark_%s_%s" % (kind_dir, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str, bench_dir: str = HERE) -> Dict:
    """The published peaks of ``device_kind``.  A device that is not in
    the table is an error, never a default."""
    table = _read_json(os.path.join(bench_dir, "peaks.json"), "peaks")
    if device_kind not in table:
        raise ManifestError(
            "device_kind %r is not in peaks.json (have %s): add its "
            "published peaks with their source" % (device_kind,
                                                   sorted(table)))
    return table[device_kind]


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, entry, config, config_path, traffic, end_to_end,
                 per_layer, bench_dir):
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        self.config = config
        self.config_path = config_path
        self.traffic = traffic
        self.driver = traffic["driver"]
        self.end_to_end = end_to_end      # entries of this cell
        self.per_layer = per_layer        # entries of this cell
        self.bench_dir = bench_dir

    def reader_of(self, metric_name: str) -> str:
        return metric_name.split(".", 1)[0]


class Manifest:
    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"),
                              "manifest")
        for key in ("command", "paths", "run_seconds", "configs",
                    "workloads", "end_to_end", "per_layer"):
            if key not in self.doc:
                raise ManifestError("BENCHMARK.json lacks %r" % key)
        self.bench_dir = os.path.join(root, self.doc["paths"][0])
        self.configs = self._by_name(self.doc["configs"], "configuration")
        self.workloads = self._by_name(self.doc["workloads"], "workload")
        self.end_to_end = self._by_name(self.doc["end_to_end"],
                                        "end-to-end metric")
        self.per_layer = self._by_name(self.doc["per_layer"],
                                       "per-layer metric")
        both = set(self.end_to_end) & set(self.per_layer)
        if both:
            raise ManifestError("metric names used twice: %s" % sorted(both))
        for m in self.per_layer.values():
            if m["moves"] not in self.end_to_end:
                raise ManifestError(
                    "per-layer metric %r moves %r, which is no end-to-end "
                    "metric" % (m["name"], m["moves"]))

    @staticmethod
    def _by_name(entries: List[Dict], what: str) -> Dict[str, Dict]:
        out = {}
        for e in entries:
            name = check_name(e.get("name"), what)
            if name in out:
                raise ManifestError("%s %r appears twice" % (what, name))
            out[name] = e
        return out

    def _applies(self, metric: Dict, cell_name: str) -> bool:
        only = metric.get("workloads")
        return only is None or cell_name in only

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise ManifestError("no workload %r in BENCHMARK.json (have %s)"
                                % (name, sorted(self.workloads)))
        entry = self.workloads[name]
        check_name(entry["config"], "config")
        check_name(entry["traffic"], "traffic")
        if entry["config"] not in self.configs:
            raise ManifestError("workload %r names configuration %r, which "
                                "BENCHMARK.json does not list"
                                % (name, entry["config"]))
        if entry["chips"] not in (1, 4):
            raise ManifestError("workload %r asks for %r chips"
                                % (name, entry["chips"]))
        config_path = os.path.join(self.root,
                                   self.configs[entry["config"]]["file"])
        config = _read_json(config_path, "configuration %r"
                            % entry["config"])
        traffic = _read_json(
            os.path.join(self.bench_dir, "traffic",
                         entry["traffic"] + ".json"),
            "traffic %r" % entry["traffic"])
        for key in ("driver", "generator"):
            check_name(traffic.get(key), "traffic %r's %s"
                       % (entry["traffic"], key))
        e2e = [m for m in self.doc["end_to_end"]
               if self._applies(m, name)]
        reported = {m["name"] for m in e2e}
        layer = [m for m in self.doc["per_layer"]
                 if self._applies(m, name) and m["moves"] in reported]
        return Cell(entry, config, config_path, traffic, e2e, layer,
                    self.bench_dir)
