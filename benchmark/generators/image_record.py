"""Traffic generator ``image_record``: JPEGs from a ``.rec`` through the
program's own ``mx.io.ImageRecordIter``.

The pool of images is a property of the traffic file (``pool_seed``),
not of ``--seed``: it is written once per checkout into the cache
directory and found again by every later run, whatever its seed, so that
set-up stays the same from run to run.  ``--seed`` seeds the iterator's
crops and mirrors (and the weights).  Content is the program's
``bench_io._build_jpeg_rec`` recipe (a copy): a smooth low-frequency
base, mid-frequency gratings and per-pixel texture noise, so that
libjpeg pays a photograph's Huffman and IDCT cost (~95 KB a file at
edge 256, q95).  Here the base is keyed on the label, so the labels can
be learned.
"""
from __future__ import annotations

import hashlib
import io as _io
import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# what decides the bytes of the pool: a change to any of these is a new file
POOL_KEYS = ("images", "num_classes", "shorter_edge", "longer_extra",
             "quality", "pool_seed", "base_grid")
ENCODE_THREADS = 8


def encode_image(index: int, pool: dict) -> bytes:
    """JPEG bytes of image ``index`` of the pool (a function of the
    pool's parameters and the index alone)."""
    from PIL import Image
    rng = np.random.RandomState([int(pool["pool_seed"]), index])
    label = index % int(pool["num_classes"])
    edge = int(pool["shorter_edge"])
    h, wd = edge, edge + int(rng.randint(0, int(pool["longer_extra"]) + 1))
    if rng.rand() < 0.5:
        h, wd = wd, h
    g = int(pool["base_grid"])
    base = np.random.RandomState([int(pool["pool_seed"]), 1 << 20, label]) \
        .randint(0, 255, (g, g, 3)).astype(np.uint8)
    smooth = np.asarray(Image.fromarray(base).resize((wd, h),
                                                     Image.BILINEAR),
                        np.float32)
    yy, xx = np.mgrid[0:h, 0:wd].astype(np.float32)
    grating = sum(40.0 * np.sin(2 * np.pi * (xx * fx + yy * fy))
                  for fx, fy in ((0.11, 0.07), (0.23, 0.31), (0.43, 0.17)))
    texture = rng.normal(0.0, 45.0, (h, wd, 3)).astype(np.float32)
    img = np.clip(smooth + grating[..., None] + texture,
                  0, 255).astype(np.uint8)
    buf = _io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG",
                              quality=int(pool["quality"]))
    return buf.getvalue()


def write_pool(path: str, pool: dict) -> float:
    """Pack the pool into ``path`` (atomically); returns the mean KB a
    file."""
    from mxnet_tpu import recordio
    n = int(pool["images"])
    classes = int(pool["num_classes"])
    tmp = path + ".tmp"
    total = 0
    writer = recordio.MXRecordIO(tmp, "w")
    try:
        with ThreadPoolExecutor(ENCODE_THREADS) as ex:
            payloads = ex.map(lambda i: encode_image(i, pool), range(n))
            for i, payload in enumerate(payloads):
                total += len(payload)
                writer.write(recordio.pack(
                    recordio.IRHeader(0, float(i % classes), i, 0), payload))
    finally:
        writer.close()
    os.replace(tmp, path)
    return total / n / 1024.0


def pool_path(cache_dir: str, pool: dict) -> str:
    digest = hashlib.sha256(json.dumps(
        {k: pool[k] for k in POOL_KEYS}, sort_keys=True).encode()).hexdigest()
    return os.path.join(cache_dir, "image_record-%s.rec" % digest[:16])


class Traffic:
    def __init__(self, traffic, config, seed, contexts, cache_dir):
        import mxnet_tpu as mx
        pool = dict(traffic["pool"])
        pool["num_classes"] = int(config["input"]["num_classes"])
        os.makedirs(cache_dir, exist_ok=True)
        self.path = pool_path(cache_dir, pool)
        self.pool_written_kb = None
        if not os.path.isfile(self.path):
            self.pool_written_kb = write_pool(self.path, pool)
        self.batch = int(traffic["batch_per_chip"]) * len(contexts)
        shape = tuple(config["input"]["image_shape"])
        self._iter = mx.io.ImageRecordIter(
            path_imgrec=self.path, data_shape=shape, batch_size=self.batch,
            seed=int(seed), **traffic["iterator"])
        self.provide_data = self._iter.provide_data
        self.provide_label = self._iter.provide_label
        self.bucket_shapes = None
        self.bucket_keys = ()

    def next(self):
        return self._iter.next()

    def reset(self):
        self._iter.reset()

    def samples(self, batch) -> int:
        return self.batch - int(batch.pad or 0)

    def eval_metric(self, config):
        return config["eval_metric"]

    def reference_batch(self, n):
        b = self._iter.next()
        self._iter.reset()
        return ({"data": b.data[0].asnumpy()[:n]},
                {"softmax_label": b.label[0].asnumpy()[:n]}, None)

    def close(self):
        self._iter = None


def build(traffic, config, seed, contexts, cache_dir):
    return Traffic(traffic, config, seed, contexts, cache_dir)
