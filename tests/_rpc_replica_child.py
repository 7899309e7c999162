"""Child process hosting one serve engine behind the dist.rpc seam.

Builds the same tiny MLP engine the router tests use (deterministic
params from ``--seed``), wraps it in :func:`mxnet_tpu.dist.rpc.
serve_engine` (authkey from ``MXNET_DIST_RPC_AUTHKEY``), prints
``RPC_READY <port>`` and parks.  The parent test connects an
``RpcReplica``, floods it, SIGKILLs it, or closes it over the wire —
whatever the scenario needs.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

IN_DIM, HID, CLASSES = 6, 8, 3


def main():
    seed = 0
    if "--seed" in sys.argv:
        seed = int(sys.argv[sys.argv.index("--seed") + 1])
    import mxnet_tpu as mx
    from mxnet_tpu.dist.rpc import serve_engine
    from mxnet_tpu.serve import ServeEngine

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=HID, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(seed)
    params = {"fc1_weight": rng.randn(HID, IN_DIM).astype(np.float32),
              "fc1_bias": np.zeros(HID, np.float32),
              "fc2_weight": rng.randn(CLASSES, HID).astype(np.float32),
              "fc2_bias": np.zeros(CLASSES, np.float32)}
    engine = ServeEngine(net, params,
                         {"data": (1, IN_DIM), "softmax_label": (1,)},
                         batch_buckets=(1, 2, 4), max_delay_ms=2.0,
                         name="rpc-child")
    server = serve_engine(engine)
    print("RPC_READY %d" % server.port, flush=True)
    server.join()           # parks until the wire close op (or SIGKILL)


if __name__ == "__main__":
    main()
