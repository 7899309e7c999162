"""Two-process global-mesh training must follow the SAME loss
trajectory as one process over the same (forced-host) devices: the
multi-host lift (mxnet_tpu.dist) changes where devices live, not what
the program computes.

Two modes:

* default — launched by ``tools/launch.py -n 2 --launcher local``: each
  worker owns 1 CPU device, the dp=2 mesh spans both PROCESSES
  (dist_sync kvstore engages the global_dp fused path), each rank
  feeds its half of the deterministic global batch;
* ``--ref`` — one process, ``XLA_FLAGS=--xla_force_host_platform_
  device_count=2``: the same dp=2 mesh over 2 local devices, full
  global batch.

Both print per-half losses (``PARITY_LOSS <step> <half> <loss>``) and a
final global-param digest (``PARITY_PARAMS <who> <sha>``); the pytest
caller matches dist rank r against ref half r within 1e-4 and requires
the two ranks' digests to be IDENTICAL (the global params are one
array).  Steps >= 2 run under the compile guard: zero XLA backend
compiles in the steady loop, across processes too.
"""
import hashlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "common"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

STEPS = 8
GLOBAL_BS = 16
DIM = 10
WARM_STEPS = 2      # first = compile, second = lr-cache etc settle


def global_batch(step):
    rng = np.random.RandomState(1000 + step)
    X = rng.randn(GLOBAL_BS, DIM).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    return X, y


def softmax_ce(probs, labels):
    p = probs[np.arange(len(labels)), labels.astype(np.int64)]
    return float(-np.mean(np.log(np.maximum(p, 1e-12))))


def main():
    ref = "--ref" in sys.argv
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    import jax
    from compile_guard import count_backend_compiles

    if ref:
        assert len(jax.devices()) == 2, \
            "--ref needs XLA_FLAGS=--xla_force_host_platform_device_count=2"
        kv, rank, bs = None, 0, GLOBAL_BS
    else:
        kv = mx.kv.create("dist_sync")
        rank, bs = kv.rank, GLOBAL_BS // 2

    mx.random.seed(7)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (bs, DIM))],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params()
    mod.set_mesh(parallel.make_mesh([("dp", 2)]))
    mod.init_optimizer(kvstore=kv, optimizer_params={
        "learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused is not None, "fused mesh path did not engage"
    if not ref:
        assert mod._fused._multiprocess(), \
            "dp=2 mesh over 2 processes did not register as multiprocess"

    def run_step(step):
        X, y = global_batch(step)
        if ref:
            Xl, yl = X, y
        else:
            Xl = X[rank * bs:(rank + 1) * bs]
            yl = y[rank * bs:(rank + 1) * bs]
        batch = mx.io.DataBatch(data=[mx.nd.array(Xl)],
                                label=[mx.nd.array(yl)])
        mod.forward(batch, is_train=True)
        outs = mod.get_outputs()[0].asnumpy()
        mod.backward()
        mod.update()
        if ref:
            half = GLOBAL_BS // 2
            for h in range(2):
                print("PARITY_LOSS %d %d %.8f"
                      % (step, h, softmax_ce(outs[h * half:(h + 1) * half],
                                             y[h * half:(h + 1) * half])))
        else:
            print("PARITY_LOSS %d %d %.8f"
                  % (step, rank, softmax_ce(outs, yl)))

    for step in range(WARM_STEPS):
        run_step(step)
    with count_backend_compiles() as guard:
        for step in range(WARM_STEPS, STEPS):
            run_step(step)
    assert guard.count == 0, \
        "steady loop recompiled %d time(s)" % guard.count
    print("COMPILE_OK %s" % ("ref" if ref else "rank%d" % rank))

    arg_params, aux_params = mod.get_params()
    h = hashlib.sha256()
    for n in sorted(arg_params):
        h.update(n.encode())
        h.update(np.ascontiguousarray(arg_params[n].asnumpy()).tobytes())
    for n in sorted(aux_params):
        h.update(n.encode())
        h.update(np.ascontiguousarray(aux_params[n].asnumpy()).tobytes())
    print("PARITY_PARAMS %s %s"
          % ("ref" if ref else "rank%d" % rank, h.hexdigest()))
    print("dist_mesh_parity %s: PASSED"
          % ("ref" if ref else "rank %d" % rank))
    if not ref:
        # exit barrier: a rank tearing down its sockets while the peer
        # is still inside a trailing collective reads as a job failure
        from jax.experimental import multihost_utils as mhu
        mhu.sync_global_devices("dist_mesh_parity_done")


if __name__ == "__main__":
    main()
