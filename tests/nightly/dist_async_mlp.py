"""Asynchronous distributed training convergence test.

Reference capability: dist_async training (docs/how_to/multi_node.md,
kvstore_dist_server.h:194-202) — each worker pushes gradients that the
parameter server applies immediately; workers train on stale weights.
Launched by tools/launch.py -n 2 -s 2; gate: async SGD still converges on
the synthetic-blob task (same oracle as dist_mlp.py for sync).
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
    kv = mx.kv.create("dist_async")
    rank, nworker = kv.rank, kv.num_workers
    X, y = make_blobs(800)
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
            optimizer_params={"learning_rate": 0.3})
    Xv, yv = make_blobs(400, seed=99)
    val = mx.io.NDArrayIter(Xv, yv, batch_size=50)
    acc = mod.score(val, "acc")[0][1]
    print("dist_async_mlp rank %d/%d final accuracy=%.4f"
          % (rank, nworker, acc))
    assert acc >= 0.90, "accuracy gate failed: %f" % acc
    kv.barrier()
    kv.close()
    print("dist_async_mlp rank %d: PASSED" % rank)


if __name__ == "__main__":
    main()
