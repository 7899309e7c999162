"""Traffic generator ``token_packed``: packed language-model sequences,
every position a label.

The corpus is ``sentence_buckets``'s idea at document length (a copy,
made of ids directly): a first-order Markov chain in which each word
strongly predicts one successor (``stickiness``), so a language model has
signal to fit.  Document lengths follow a gamma profile (``length_shape``,
``length_mean``); documents are joined by the end-of-document id 0 and
packed back to back into sequences of exactly ``seq_len`` tokens, with no
mask between documents and no padding: the label of a position is the
next token of the stream, across document borders too.
``distinct_batches`` batches are made once from ``--seed`` as host int32
arrays and cycled; ``next()`` hands the step a host batch, as a data
loader would.
"""
from __future__ import annotations

import gc

import numpy as np

EOD = 0


def markov_stream(rng, n_tokens, vocab_size, stickiness, length_shape,
                  length_mean):
    """``n_tokens`` ids of documents joined by EOD: words are 1..V-1."""
    scale = length_mean / length_shape
    lengths = []
    while sum(lengths) < n_tokens:
        more = np.maximum(1, np.rint(rng.gamma(
            length_shape, scale, size=max(16, 2 * n_tokens
                                          // int(length_mean)))))
        lengths.extend(int(x) + 1 for x in more)       # + its EOD
    lengths = np.asarray(lengths, np.int64)
    lengths = lengths[:int(np.searchsorted(np.cumsum(lengths),
                                           n_tokens)) + 1]
    n = int(lengths.sum())
    words = vocab_size - 1
    successor = rng.randint(0, words, size=words)
    fresh = rng.randint(0, words, size=n)
    stick = rng.rand(n) < stickiness
    starts = np.concatenate(([0], np.cumsum(lengths)[:-1]))
    stick[starts] = False                      # a document starts anew
    # depth of each position in its run of sticky steps; position i at
    # depth k is successor^k of the fresh word k places before it
    idx = np.arange(n)
    last_fresh = np.maximum.accumulate(np.where(stick, -1, idx))
    depth = idx - last_fresh
    tok = np.where(stick, 0, fresh)
    for k in range(1, int(depth.max()) + 1):
        at = np.nonzero(depth == k)[0]
        tok[at] = successor[tok[at - 1]]
    tok += 1                                   # 0 is the end of a document
    tok[starts + lengths - 1] = EOD
    return tok[:n_tokens].astype(np.int32)


class Traffic:
    def __init__(self, traffic, config, seed, contexts):
        import mxnet_tpu as mx
        self.batch = int(traffic["batch_per_chip"]) * len(contexts)
        self.seq_len = int(config["input"]["seq_len"])
        vocab = int(config["input"]["vocab_size"])
        n_batches = int(traffic["distinct_batches"])
        corpus = traffic["corpus"]
        rng = np.random.RandomState(int(seed) % (2 ** 32))
        rows = n_batches * self.batch
        stream = markov_stream(
            rng, rows * self.seq_len + 1, vocab,
            float(corpus["stickiness"]), float(corpus["length_shape"]),
            float(corpus["length_mean"]))
        data = stream[:-1].reshape(rows, self.seq_len)
        label = stream[1:].reshape(rows, self.seq_len)
        self._host = (data, label)
        self._batches = []
        for i in range(n_batches):
            rows_i = slice(i * self.batch, (i + 1) * self.batch)
            self._batches.append(mx.io.DataBatch(
                data=[mx.nd.array(data[rows_i], ctx=mx.cpu(),
                                  dtype=np.int32)],
                label=[mx.nd.array(label[rows_i], ctx=mx.cpu(),
                                   dtype=np.int32)], pad=0))
        self.provide_data = [("data", (self.batch, self.seq_len))]
        self.provide_label = [("softmax_label", (self.batch, self.seq_len))]
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
        return self.batch * self.seq_len       # every position is a label

    def eval_metric(self, config):
        """The mean of the model's per-token loss head (output 0)."""
        import mxnet_tpu as mx
        # the driver asks for the metric once more between its reference
        # check and the cell's own bind: what the check's module left in
        # reference cycles is freed here, not at some later collection,
        # so the cell's peak memory is the cell's
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
