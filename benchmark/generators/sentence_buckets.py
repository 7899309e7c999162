"""Traffic generator ``sentence_buckets``: a bucketed language-model
corpus through a copy of ``example/rnn/bucket_io.BucketSentenceIter``.

The corpus is the program's ``synthetic_markov_corpus`` idea (a copy,
made of ids directly): a first-order Markov chain in which each word
strongly predicts one successor, so a language model has signal to fit.
Sentence lengths follow a gamma profile with PTB's mean; sentences
longer than the largest bucket are dropped, as ``bucket_io`` drops them.
Id 0 is padding.  Two things differ from the program's iterator, both
for the seed's sake: it takes sentences as id lists (no text file), and
it shuffles with a ``RandomState`` made from ``--seed``, not NumPy's
global generator.  Batches are host arrays, as the example yields them.
"""
from __future__ import annotations

import numpy as np


def markov_sentences(rng, sentences, vocab_size, stickiness, length_shape,
                     length_scale, max_len):
    """A list of id arrays (ids 1..vocab_size-1), lengths 2..max_len."""
    lengths = np.rint(rng.gamma(length_shape, length_scale,
                                size=sentences)).astype(np.int64)
    lengths = lengths[(lengths >= 2) & (lengths <= max_len)]
    n = int(lengths.sum())
    words = vocab_size - 1
    successor = rng.randint(0, words, size=words)
    fresh = rng.randint(0, words, size=n)
    stick = rng.rand(n) < stickiness
    starts = np.concatenate(([0], np.cumsum(lengths)[:-1]))
    stick[starts] = False                      # a sentence starts anew
    # depth of each position in its run of sticky steps; position i at
    # depth k is successor^k of the fresh word k places before it
    idx = np.arange(n)
    last_fresh = np.maximum.accumulate(np.where(stick, -1, idx))
    depth = idx - last_fresh
    tok = np.where(stick, 0, fresh)
    for k in range(1, int(depth.max()) + 1):
        at = np.nonzero(depth == k)[0]
        tok[at] = successor[tok[at - 1]]
    tok += 1                                   # 0 is padding
    return np.split(tok, starts[1:])


class BucketSentenceIter:
    """Group sentences by length bucket (copy of the program's
    ``example/rnn/bucket_io.BucketSentenceIter``)."""

    def __init__(self, sentences, buckets, batch_size, init_states, rng,
                 data_name="data", label_name="softmax_label"):
        import mxnet_tpu as mx
        self._mx = mx
        self.rng = rng
        self.data_name, self.label_name = data_name, label_name
        self.batch_size = batch_size
        self.buckets = sorted(buckets)
        rows = [[] for _ in self.buckets]
        for ids in sentences:
            for i, bkt in enumerate(self.buckets):
                if bkt >= len(ids):
                    row = np.zeros(bkt, np.float32)
                    row[:len(ids)] = ids
                    rows[i].append(row)
                    break
        self.data = [np.asarray(x, np.float32) if x else
                     np.zeros((0, b), np.float32)
                     for x, b in zip(rows, self.buckets)]
        self.init_states = list(init_states)
        self.init_state_arrays = [mx.nd.zeros(s) for _, s in init_states]
        self.default_bucket_key = max(self.buckets)
        counts = [len(x) // batch_size for x in self.data]
        self.data = [x[:n * batch_size] for x, n in zip(self.data, counts)]
        self.bucket_plan = np.hstack([np.zeros(n, int) + i
                                      for i, n in enumerate(counts)])
        self.reset()

    @property
    def provide_data(self):
        return [(self.data_name,
                 (self.batch_size, self.default_bucket_key))] + \
            self.init_states

    @property
    def provide_label(self):
        return [(self.label_name,
                 (self.batch_size, self.default_bucket_key))]

    def provide_bucket_shapes(self):
        return [(b, [(self.data_name, (self.batch_size, b))]
                 + self.init_states,
                 [(self.label_name, (self.batch_size, b))])
                for b in self.buckets]

    def reset(self):
        self.rng.shuffle(self.bucket_plan)
        self.bucket_idx_all = [self.rng.permutation(len(x))
                               for x in self.data]
        self.bucket_curr_idx = [0 for _ in self.data]
        self._plan_pos = 0

    def next(self):
        if self._plan_pos >= len(self.bucket_plan):
            raise StopIteration
        i_bucket = self.bucket_plan[self._plan_pos]
        self._plan_pos += 1
        at = self.bucket_curr_idx[i_bucket]
        self.bucket_curr_idx[i_bucket] += self.batch_size
        rows = self.bucket_idx_all[i_bucket][at:at + self.batch_size]
        return self.batch_of(i_bucket, rows)

    def batch_of(self, i_bucket, rows):
        """The DataBatch of these rows of bucket ``i_bucket``."""
        mx = self._mx
        data = self.data[i_bucket][rows]
        seq_len = self.buckets[i_bucket]
        label = np.zeros_like(data)
        label[:, :-1] = data[:, 1:]
        batch = mx.io.DataBatch(
            data=[mx.nd.array(data)] + self.init_state_arrays,
            label=[mx.nd.array(label)], pad=0, bucket_key=seq_len,
            provide_data=[(self.data_name, (self.batch_size, seq_len))]
            + self.init_states,
            provide_label=[(self.label_name, (self.batch_size, seq_len))])
        # label tokens that are not padding: what the cell counts as trained
        batch.bench_samples = int(np.count_nonzero(label))
        return batch


def time_major_ce(label, pred):
    """Cross-entropy of a time-major ``(seq*batch, vocab)`` softmax
    against ``(batch, seq)`` labels: the built-in ``ce`` ravels the
    labels batch-major and would score each row against another
    position's label (``bucket_io.perplexity_metric``'s transpose).
    Returns (sum, count) over every position, padding included, which
    is what the program's SoftmaxOutput trains on."""
    label = np.asarray(label).T.reshape(-1).astype(np.int64)
    picked = np.asarray(pred[np.arange(label.size), label], np.float32)
    return float(-np.log(np.maximum(picked, 1e-10)).sum()), label.size


class Traffic:
    def __init__(self, traffic, config, seed, contexts):
        self.batch = int(traffic["batch_per_chip"]) * len(contexts)
        model = config["model"]["kwargs"]
        buckets = list(config["input"]["buckets"])
        hidden, layers = int(model["num_hidden"]), int(model["num_lstm_layer"])
        self.init_states = \
            [("l%d_init_c" % l, (self.batch, hidden)) for l in range(layers)] \
            + [("l%d_init_h" % l, (self.batch, hidden)) for l in range(layers)]
        rng = np.random.RandomState(int(seed))
        corpus = traffic["corpus"]
        sentences = markov_sentences(
            rng, int(corpus["sentences"]), int(model["input_size"]),
            float(corpus["stickiness"]), float(corpus["length_shape"]),
            float(corpus["length_scale"]), max(buckets))
        self._sentences = sentences
        self._iter = BucketSentenceIter(sentences, buckets, self.batch,
                                        self.init_states, rng)
        self.provide_data = self._iter.provide_data
        self.provide_label = self._iter.provide_label
        self.default_bucket_key = self._iter.default_bucket_key
        self.bucket_shapes = self._iter.provide_bucket_shapes()
        self.bucket_keys = tuple(self._iter.buckets)
        self.data_names = ["data"] + [n for n, _ in self.init_states]
        self.label_names = ["softmax_label"]

    def next(self):
        return self._iter.next()

    def reset(self):
        self._iter.reset()

    def samples(self, batch) -> int:
        return batch.bench_samples

    def warmup_batches(self, visits):
        """Every bucket ``visits`` times, in turn (a bucket that holds
        no full batch is not in the plan and needs no program)."""
        it = self._iter
        first = np.arange(it.batch_size)
        for _ in range(visits):
            for i in sorted(set(it.bucket_plan.tolist())):
                yield it.batch_of(i, first)

    def eval_metric(self, config):
        import mxnet_tpu as mx
        del config
        return mx.metric.CustomMetric(time_major_ce, name="time_major_ce")

    def reference_batch(self, n):
        """``n`` sentences of the second-smallest bucket, with zero
        initial states, and that bucket's key."""
        key = self._iter.buckets[min(1, len(self._iter.buckets) - 1)]
        rows = self._iter.data[self._iter.buckets.index(key)][:n]
        label = np.zeros_like(rows)
        label[:, :-1] = rows[:, 1:]
        data = {"data": rows}
        for name, shape in self.init_states:
            data[name] = np.zeros((n,) + tuple(shape[1:]), np.float32)
        return data, {"softmax_label": label}, key

    def close(self):
        self._iter = None


def build(traffic, config, seed, contexts, cache_dir):
    del cache_dir
    return Traffic(traffic, config, seed, contexts)
