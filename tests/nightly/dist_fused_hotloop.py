"""The dist_sync hot loop must do NO per-parameter python kvstore work:
after init, zero kvstore push/pull calls while the fused global-mesh
program trains (reference contract 'python only pushes pointers',
SURVEY §3.1, now held across processes)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import mxnet_tpu as mx


def main():
    kv = mx.kv.create("dist_sync")
    rank = kv.rank
    calls = {"push": 0, "pull": 0}
    real_push, real_pull = kv.push, kv.pull

    def push(*a, **k):
        calls["push"] += 1
        return real_push(*a, **k)

    def pull(*a, **k):
        calls["pull"] += 1
        return real_pull(*a, **k)

    kv.push, kv.pull = push, pull

    rng = np.random.RandomState(0)
    X = rng.randn(200, 10).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=25)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.init_optimizer(kvstore=kv, optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None and mod._fused.global_dp, \
        "fused dist path did not engage"
    if os.environ.get("MXNET_SHARD_WEIGHT_UPDATE") == "1":
        assert mod._fused.shard_update, "sharded update did not engage"
    init_pushes, init_pulls = calls["push"], calls["pull"]

    n_batches = 0
    for batch in it:
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
        n_batches += 1
    mod.get_params()   # epoch-end sync, as fit() does

    hot_pushes = calls["push"] - init_pushes
    hot_pulls = calls["pull"] - init_pulls
    print("rank %d: %d batches, hot-loop kv pushes=%d pulls=%d "
          "(init: %d/%d)" % (rank, n_batches, hot_pushes, hot_pulls,
                             init_pushes, init_pulls))
    assert hot_pushes == 0 and hot_pulls == 0, \
        "per-param kvstore traffic in the fused hot loop"
    print("dist_fused_hotloop rank %d: PASSED" % rank)


if __name__ == "__main__":
    main()
