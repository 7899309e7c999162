"""Shared by the benchmark's CPU tests: where the benchmark lives, and a
temporary copy of it with tiny configurations added as files."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _load(path):
    with open(path) as f:
        return json.load(f)


def tiny_copy(tmp):
    """Copy BENCHMARK.json and ``benchmark/`` into ``tmp`` and add, as new
    files and entries only, a third and a fourth configuration (tiny
    widths, for the CPU), three traffic mixes and three cells.  -> root
    of the copy."""
    tmp = str(tmp)
    shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = os.path.join(tmp, "benchmark")
    doc = _load(os.path.join(ROOT, "BENCHMARK.json"))

    cfg = _load(os.path.join(bench, "configs", "resnet50.json"))
    cfg["name"] = "resnet-tiny"
    cfg["model"]["kwargs"].update(units=[1, 1, 1, 1],
                                  filter_list=[8, 16, 32, 64, 128],
                                  num_classes=10, image_shape=[3, 32, 32])
    cfg["input"] = {"image_shape": [3, 32, 32], "num_classes": 10}
    cfg["chance_loss_classes"] = 10
    cfg["compute_dtype"] = "float32"
    cfg["reference"].update(
        weights=["fc1_weight", "stem_conv_weight"], loss_rtol=1e-4,
        update_rtol={"fc1_weight": 1e-3, "stem_conv_weight": 1e-3})
    _dump(cfg, os.path.join(bench, "configs", "resnet-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", "resnet50.py"),
                os.path.join(bench, "reference", "resnet-tiny.py"))

    cfg = _load(os.path.join(bench, "configs", "ptb-lstm.json"))
    cfg["name"] = "lstm-tiny"
    cfg["model"]["kwargs"].update(input_size=50, num_hidden=16,
                                  num_embed=16, num_label=50)
    cfg["input"] = {"buckets": [5, 10]}
    cfg["chance_loss_classes"] = 50
    cfg["compute_dtype"] = "float32"
    cfg["reference"].update(
        loss_rtol=1e-4, update_rtol={"cls_weight": 0.06,
                                     "l0_i2h_weight": 0.06})
    _dump(cfg, os.path.join(bench, "configs", "lstm-tiny.json"))
    shutil.copy(os.path.join(bench, "reference", "ptb-lstm.py"),
                os.path.join(bench, "reference", "lstm-tiny.py"))

    t = _load(os.path.join(bench, "traffic", "synthetic-device.json"))
    t.update(batch_per_chip=8, distinct_batches=4, base_grid=4,
             warmup_steps=3)
    _dump(t, os.path.join(bench, "traffic", "tiny-device.json"))
    t = _load(os.path.join(bench, "traffic", "ptb-bucketed.json"))
    t.update(batch_per_chip=8, warmup_steps=3)
    t["corpus"].update(sentences=400, length_shape=2.0, length_scale=2.0)
    _dump(t, os.path.join(bench, "traffic", "tiny-buckets.json"))

    doc["configs"] += [
        {"name": "resnet-tiny", "source": "test",
         "file": "benchmark/configs/resnet-tiny.json", "reduced": [],
         "why": "test"},
        {"name": "lstm-tiny", "source": "test",
         "file": "benchmark/configs/lstm-tiny.json", "reduced": [],
         "why": "test"}]
    doc["workloads"] += [
        {"name": "tiny-dev", "config": "resnet-tiny",
         "traffic": "tiny-device", "chips": 1, "why": "test"},
        {"name": "tiny-lstm", "config": "lstm-tiny",
         "traffic": "tiny-buckets", "chips": 1, "why": "test"}]
    added = {"train_img_per_s": ["tiny-dev"], "train_tok_per_s": ["tiny-lstm"]}
    for m in doc["end_to_end"]:
        m.get("workloads", []).extend(added.get(m["name"], []))
    for m in doc["per_layer"]:
        # the four-chip cell's collectives stay its own
        if len(m.get("workloads", [])) > 1 or m["moves"] == "train_tok_per_s":
            m["workloads"].extend(added.get(m["moves"], []))
    _dump(doc, os.path.join(tmp, "BENCHMARK.json"))
    return tmp
