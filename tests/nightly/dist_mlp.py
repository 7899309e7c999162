"""Distributed data-parallel training convergence test.

Reference: tests/nightly/dist_lenet.py — real dist_sync training with data
partitioned by rank, final-accuracy gate.  Synthetic blobs stand in for
MNIST (zero-egress image); the gate checks the same property: multi-worker
sync training converges.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import mxnet_tpu as mx


def make_blobs(n, dim=10, classes=4, seed=0):
    centers = np.random.RandomState(1234).randn(classes, dim) * 3
    rng = np.random.RandomState(seed)
    ys = rng.randint(classes, size=n)
    X = centers[ys] + rng.randn(n, dim) * 0.5
    return X.astype(np.float32), ys.astype(np.float32)


def main():
    kv = mx.kv.create("dist_sync")
    rank, nworker = kv.rank, kv.num_workers
    X, y = make_blobs(800)
    # partition by rank (reference: part_index/num_parts)
    shard = len(X) // nworker
    Xs = X[rank * shard:(rank + 1) * shard]
    ys = y[rank * shard:(rank + 1) * shard]
    it = mx.io.NDArrayIter(Xs, ys, batch_size=50, shuffle=True)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=6, kvstore=kv,
            optimizer_params={"learning_rate": 0.5})
    # dist_sync must ride the fused global-mesh train step (one donated
    # XLA program per batch, cross-process psum by GSPMD) — not the
    # per-param python kvstore loop
    import os as _os
    if _os.environ.get("MXNET_FUSED_TRAIN", "1") != "0":
        assert mod._fused is not None and mod._fused.global_dp, \
            "dist_sync training did not engage the fused path"
    Xv, yv = make_blobs(400, seed=99)
    val = mx.io.NDArrayIter(Xv, yv, batch_size=50)
    acc = mod.score(val, "acc")[0][1]
    print("dist_mlp rank %d/%d final accuracy=%.4f" % (rank, nworker, acc))
    assert acc >= 0.95, "accuracy gate failed: %f" % acc
    print("dist_mlp rank %d: PASSED" % rank)


if __name__ == "__main__":
    main()
