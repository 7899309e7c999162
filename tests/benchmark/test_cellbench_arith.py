"""The benchmark's arithmetic: rates, percentiles, spreads, FLOPs."""
import math
import os
import sys

import numpy as np
import pytest

import cellbench_util as util  # noqa: F401  (puts benchmark/ on the path)
import flops
import stats


@pytest.mark.parametrize("q", [0, 25, 50, 75, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_is_numpys(q, n):
    xs = list(np.random.RandomState(n).rand(n) * 100)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_nothing_and_bad_q():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


@pytest.mark.parametrize("n,q,reported", [(999, 99, False), (1000, 99, True),
                                          (199, 95, False), (200, 95, True)])
def test_tail_needs_ten_samples_beyond_it(n, q, reported):
    got = stats.tail(list(range(n)), q)
    assert (got is not None) == reported


def test_rate_and_spread():
    assert stats.rate(1280, 10.0, 10.5) == 2560.0
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)
    # quartiles of 1..5 are 2 and 4, the median 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2.0 / 3.0)


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), None])
def test_a_metric_is_a_finite_number(bad):
    with pytest.raises((ValueError, TypeError)):
        stats.metric(bad, "ms")
    assert stats.metric(212.4071, "ms", samples=3) == {
        "value": 212.4071, "unit": "ms", "samples": 3}


R50 = ([3, 4, 6, 3], [64, 256, 512, 1024, 2048], 1000, 224)


def test_resnet50_flops_match_the_programs_function():
    """The copy, with the stride where the program's function assumes it
    (the first 1x1), gives the program's 23.15 GFLOP per image."""
    sys.path.insert(0, os.path.join(util.ROOT, "tools"))
    from profile_resnet import analytic_train_gflop_per_img
    ours = flops.resnet_bottleneck_train_flops(*R50, stride_on="1x1") / 1e9
    assert ours == pytest.approx(analytic_train_gflop_per_img(), rel=1e-12)
    assert ours == pytest.approx(23.15, abs=0.005)


def test_resnet50_flops_of_the_symbol_as_built():
    """The configuration's number (stride on the 3x3, as
    models.resnet._bottleneck builds it) is the sum over the symbol's own
    convolution and FC shapes."""
    from mxnet_tpu.models import get_resnet50
    internals = get_resnet50(1000).get_internals()
    shapes = dict(zip(internals.list_outputs(),
                      internals.infer_shape(data=(1, 3, 224, 224))[1]))
    args = dict(zip(internals.list_arguments(),
                    internals.infer_shape(data=(1, 3, 224, 224))[0]))
    total = 0
    for name, w in args.items():
        if name.endswith("_conv_weight"):
            out = shapes[name[:-len("_weight")] + "_output"]
            total += 2 * out[1] * out[2] * out[3] * w[1] * w[2] * w[3]
        elif name == "fc1_weight":
            total += 2 * w[0] * w[1]
    ours = flops.resnet_bottleneck_train_flops(*R50, stride_on="3x3")
    assert ours == 3 * total
    assert ours / 1e9 == pytest.approx(24.535, abs=0.001)


def test_lstm_flops_match_the_programs_function():
    sys.path.insert(0, util.ROOT)
    import bench_lstm
    assert flops.lstm_lm_train_flops(2, 200, 200, 10000) / 1e6 == \
        pytest.approx(bench_lstm.train_mflop_per_token(), rel=1e-12)
    assert flops.lstm_lm_train_flops(1, 1024, 1024, 10000) / 1e6 == \
        pytest.approx(bench_lstm.train_mflop_per_token(1, 1024, 1024, 10000))


def test_markov_corpus_is_seeded_and_in_range():
    import manifest
    sb = manifest.load_module("generators", "sentence_buckets")
    a = sb.markov_sentences(np.random.RandomState(3), 500, 100, 0.85,
                            4.4, 4.8, 40)
    b = sb.markov_sentences(np.random.RandomState(3), 500, 100, 0.85,
                            4.4, 4.8, 40)
    assert len(a) == len(b) and all((x == y).all() for x, y in zip(a, b))
    lens = [len(x) for x in a]
    assert min(lens) >= 2 and max(lens) <= 40
    flat = np.concatenate(a)
    assert flat.min() >= 1 and flat.max() <= 99
    # the chain is predictable: the commonest successor of a word follows
    # it far more often than 1/vocabulary
    pairs = np.stack([np.concatenate([x[:-1] for x in a]),
                      np.concatenate([x[1:] for x in a])])
    w = np.bincount(pairs[0]).argmax()
    succ = pairs[1][pairs[0] == w]
    assert np.bincount(succ).max() / len(succ) > 0.5
    assert math.isclose(np.mean(lens), 20, abs_tol=4)


def test_jpeg_pool_is_a_function_of_its_parameters():
    import manifest
    ir = manifest.load_module("generators", "image_record")
    pool = {"images": 4, "num_classes": 10, "shorter_edge": 40,
            "longer_extra": 8, "quality": 95, "pool_seed": 5, "base_grid": 8}
    assert ir.encode_image(2, pool) == ir.encode_image(2, pool)
    assert ir.encode_image(2, pool) != ir.encode_image(3, pool)
    assert ir.pool_path("/c", pool) == ir.pool_path("/c", dict(pool))
    assert ir.pool_path("/c", pool) != \
        ir.pool_path("/c", dict(pool, quality=90))
