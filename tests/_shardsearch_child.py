"""One ``sharding="auto"`` resolution in a fresh process.

The parent test runs this twice against the same ``MXNET_AUTOTUNE_DIR``
(4 forced host devices, dp=2 x mp=2 mesh): the first process must run
the search and persist the winner, the second must resolve from the
store without compiling a single candidate.  Prints::

    SHARD_PRE_HIT <0|1>        # was the fingerprint already in the store
    SHARD_KEY <fingerprint>
    SHARD_ELAPSED <seconds>    # set_mesh + init_optimizer wall
    SHARD_SPECS <sorted json>  # the persisted winner's spec entries
    SHARD_NLOG <n>             # audit-log length (all candidates)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def main():
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.autotune import store
    from mxnet_tpu.dist.shardsearch import fingerprint

    mx.random.seed(5)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc3")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 12))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mesh = parallel.make_mesh([("dp", 2), ("mp", 2)])
    arg_params, _ = mod.get_params()
    shapes = {n: tuple(arg_params[n].shape) for n in mod._param_names}
    key = fingerprint(mod._symbol, shapes, mesh)
    print("SHARD_PRE_HIT %d" % (1 if store.load_config(key) else 0))
    print("SHARD_KEY %s" % key)
    t0 = time.perf_counter()
    mod.set_mesh(mesh, sharding="auto")
    mod.init_optimizer(optimizer_params={"learning_rate": 0.05})
    print("SHARD_ELAPSED %.3f" % (time.perf_counter() - t0))
    doc = store.load_config(key)
    assert doc is not None, "search did not persist a winner"
    print("SHARD_SPECS %s" % json.dumps(doc["config"]["specs"],
                                        sort_keys=True))
    print("SHARD_NLOG %d" % len(doc.get("log") or []))
    # the resolved mesh still trains: one real batch through the fused
    # step proves the winning specs are loadable AND runnable
    import numpy as np
    batch = mx.io.DataBatch(
        data=[mx.nd.array(np.random.RandomState(0).randn(8, 12)
                          .astype(np.float32))],
        label=[mx.nd.array(np.zeros(8, np.float32))])
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    print("SHARD_STEP_OK")


if __name__ == "__main__":
    main()
