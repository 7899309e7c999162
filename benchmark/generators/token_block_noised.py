"""Traffic generator ``token_block_noised``: packed language-model
sequences for the block-diffusion objective (arXiv:2503.09573, the
vectorised form): every step gets the noised sequence and the clean one
side by side, targets at the masked positions only, and a weight a
position.

The corpus is ``token_packed``'s (its ``markov_stream``: a seeded sticky
Markov chain, documents of gamma length joined by the end-of-document id
0 and packed back to back, no mask between documents, nothing padded),
drawn over ONE ID FEWER than the configuration holds: the last held id is
MASK (``input.mask_id``) and never occurs in clean text.

Noise, from ``--seed`` like the corpus: the clean sequence of ``T`` tokens
is cut into blocks of ``model.kwargs.block_len``; a block draws ``u ~
U(0, 1)`` and ``t = eps + (1 - eps) u`` (``noise.eps``); each of its
positions becomes MASK independently with probability ``t``.  A batch is

* ``data`` ``(B, 2 T)`` int32: ``[x_t ; x_0]``, the noised copy then the
  clean one;
* ``softmax_label`` ``(B, 2, T)`` float32 (ids are exact in float32):
  ``[:, 0]`` the clean id where the position is masked and -1 where it is
  not, ``[:, 1]`` the weight ``1 / t`` of the position's block.

``distinct_batches`` batches are made once as host arrays and cycled, so
a batch's noise repeats each cycle.  A step counts ``B T`` samples: the
clean positions, not the ``2 T`` rows the model processes and not the
masked positions alone.
"""
from __future__ import annotations

import gc
import os

import numpy as np

import manifest

_packed = manifest.load_module(
    "generators", "token_packed",
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def block_noise(rng, clean, block_len, eps, mask_id):
    """``clean`` (rows, T) ids -> (noised ids, targets, weights), each
    (rows, T): one ``t`` a block, each position masked with probability
    ``t``; a target is -1 where the position is not masked."""
    rows, seq_len = clean.shape
    u = rng.rand(rows, seq_len // block_len)
    t = np.repeat(eps + (1.0 - eps) * u, block_len, axis=1)
    masked = rng.rand(rows, seq_len) < t
    return (np.where(masked, mask_id, clean).astype(np.int32),
            np.where(masked, clean, -1).astype(np.float32),
            (1.0 / t).astype(np.float32))


class Traffic:
    def __init__(self, traffic, config, seed, contexts):
        import mxnet_tpu as mx
        self.batch = int(traffic["batch_per_chip"]) * len(contexts)
        self.seq_len = int(config["input"]["seq_len"])
        mask_id = int(config["input"]["mask_id"])
        block_len = int(config["model"]["kwargs"]["block_len"])
        n_batches = int(traffic["distinct_batches"])
        corpus = traffic["corpus"]
        rng = np.random.RandomState(int(seed) % (2 ** 32))
        rows = n_batches * self.batch
        # the corpus never holds MASK: it is drawn over the ids below it
        clean = _packed.markov_stream(
            rng, rows * self.seq_len, mask_id,
            float(corpus["stickiness"]), float(corpus["length_shape"]),
            float(corpus["length_mean"])).reshape(rows, self.seq_len)
        noised, target, weight = block_noise(
            rng, clean, block_len, float(traffic["noise"]["eps"]), mask_id)
        data = np.concatenate([noised, clean], axis=1)
        label = np.stack([target, weight], axis=1)
        self._host = (data, label)
        self._batches = []
        for i in range(n_batches):
            rows_i = slice(i * self.batch, (i + 1) * self.batch)
            self._batches.append(mx.io.DataBatch(
                data=[mx.nd.array(data[rows_i], ctx=mx.cpu(),
                                  dtype=np.int32)],
                label=[mx.nd.array(label[rows_i], ctx=mx.cpu(),
                                   dtype=np.float32)], pad=0))
        self.provide_data = [("data", (self.batch, 2 * self.seq_len))]
        self.provide_label = [("softmax_label",
                               (self.batch, 2, self.seq_len))]
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
        del batch
        return self.batch * self.seq_len       # the clean positions

    def eval_metric(self, config):
        """The mean of the model's weighted per-position loss head
        (output 0): ``(1 / T) sum [masked] CE / t``."""
        import mxnet_tpu as mx
        # as token_packed: what the reference check's module left in
        # reference cycles is freed before the cell's own bind
        gc.collect()
        return mx.metric.OutputMean(0, name=config["eval_metric"])

    def reference_batch(self, n):
        """The first ``n`` sequences, on the host."""
        data, label = self._host
        return ({"data": data[:n]}, {"softmax_label": label[:n]}, None)

    def close(self):
        self._batches = []


def build(traffic, config, seed, contexts, cache_dir):
    del cache_dir
    return Traffic(traffic, config, seed, contexts)
