"""Traffic generator ``image_device``: labelled images made on the device.

``distinct_batches`` batches are made once, on the devices that train, by
one jitted program called once per batch with a key folded from
``--seed``, and stay there in the layout the train step takes (float32
NCHW, the batch axis split over the chips): the feed does no work in the
window.  A label decides the image's low-frequency base (a
``base_grid`` x ``base_grid`` pattern per class, from the same seed), so
the labels can be learned and a falling loss means something; per-pixel
noise is added on top.  Values are in the range of mean-subtracted
pixels.
"""
from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, traffic, config, seed, contexts):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import mxnet_tpu as mx

        self.batch = int(traffic["batch_per_chip"]) * len(contexts)
        shape = tuple(config["input"]["image_shape"])
        classes = int(config["input"]["num_classes"])
        grid = int(traffic["base_grid"])
        if shape[1] % grid or shape[2] % grid:
            raise ValueError("base_grid %d does not divide the image %s"
                             % (grid, shape))
        amp = float(traffic["base_amplitude"])
        sigma = float(traffic["noise_sigma"])
        devices = [c.jax_device() for c in contexts]
        # the batch axis over the chips, as the fused step shards it
        sharding = NamedSharding(Mesh(np.array(devices), ("dp",)), P("dp"))
        batch = self.batch

        def one_batch(seed_key, index):
            # the class table depends on the seed only: every batch draws
            # its bases from the same table
            k_table = jax.random.fold_in(seed_key, 0x7ab1e)
            k_label, k_noise = jax.random.split(
                jax.random.fold_in(seed_key, index))
            table = amp * jax.random.normal(
                k_table, (classes, shape[0], grid, grid), jnp.float32)
            label = jax.random.randint(k_label, (batch,), 0, classes)
            base = table[label]
            base = jnp.repeat(jnp.repeat(base, shape[1] // grid, axis=2),
                              shape[2] // grid, axis=3)
            noise = sigma * jax.random.normal(k_noise, (batch,) + shape,
                                              jnp.float32)
            return base + noise, label.astype(jnp.float32)

        gen = jax.jit(one_batch, out_shardings=(sharding, sharding))
        key = jax.random.PRNGKey(int(seed))
        self._batches = []
        for i in range(int(traffic["distinct_batches"])):
            x, y = gen(key, np.int32(i + 1))
            self._batches.append(mx.io.DataBatch(
                data=[mx.nd.NDArray(x)], label=[mx.nd.NDArray(y)], pad=0))
        jax.block_until_ready([b.data[0]._get() for b in self._batches])
        self.provide_data = [("data", (batch,) + shape)]
        self.provide_label = [("softmax_label", (batch,))]
        self.bucket_shapes = None
        self.bucket_keys = ()
        self._cursor = 0

    # -- the iterator protocol the window wrapper drives -------------------
    def next(self):
        if self._cursor >= len(self._batches):
            raise StopIteration
        b = self._batches[self._cursor]
        self._cursor += 1
        return b

    def reset(self):
        self._cursor = 0

    def samples(self, batch) -> int:
        return self.batch

    def eval_metric(self, config):
        return config["eval_metric"]

    def reference_batch(self, n):
        """The first ``n`` images of the first batch, on the host."""
        b = self._batches[0]
        return ({"data": np.asarray(b.data[0]._get()[:n])},
                {"softmax_label": np.asarray(b.label[0]._get()[:n])}, None)

    def close(self):
        self._batches = []


def build(traffic, config, seed, contexts, cache_dir):
    del cache_dir
    return Traffic(traffic, config, seed, contexts)
