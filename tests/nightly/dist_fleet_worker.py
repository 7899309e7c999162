"""One rank of the fleet chaos run (driven by dist.FleetSupervisor).

Trains a deterministic MLP over a dp=2 mesh spanning 2 processes, with
per-step checkpointing and ``resume=True`` — so a fleet that gets one
rank SIGKILL'd (the ``dist.host`` fault point, targeted per-rank via
``MXNET_FAULTS=points=dist.host@rank1,kinds=crash,...``) restarts from
the latest COMMIT and must land on a final global state BITWISE equal
to a fault-free run.  Rank identity, coordinator, and fault attempt all
arrive via env (the supervisor's rendezvous).

Prints ``FLEET_FINAL rank<r> <sha256 of params>`` + ``PASSED``.
"""
import hashlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

BS = 8          # per-process batch
EPOCHS = 2
N = 64          # rows per process-epoch -> 8 steps/epoch, 16 total


def main():
    ckpt_dir = sys.argv[sys.argv.index("--ckpt") + 1]
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    rank = jax.process_index()

    mx.random.seed(11)
    rng = np.random.RandomState(3)      # same rows everywhere; each
    X = rng.randn(N, 12).astype(np.float32)   # rank feeds its slice by
    y = (X.sum(axis=1) > 0).astype(np.float32)  # construction of the iter
    half = N // 2
    Xl = X[rank * half:(rank + 1) * half] if jax.process_count() > 1 \
        else X
    yl = y[rank * half:(rank + 1) * half] if jax.process_count() > 1 \
        else y
    it = mx.io.NDArrayIter(Xl, yl, batch_size=BS, shuffle=False)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=EPOCHS, kvstore=None,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            mesh=parallel.make_mesh([("dp", 2)]),
            checkpoint=ckpt_dir, checkpoint_every=1, resume=True)

    arg_params, aux_params = mod.get_params()
    h = hashlib.sha256()
    for n in sorted(arg_params):
        h.update(n.encode())
        h.update(np.ascontiguousarray(arg_params[n].asnumpy()).tobytes())
    for n in sorted(aux_params):
        h.update(n.encode())
        h.update(np.ascontiguousarray(aux_params[n].asnumpy()).tobytes())
    print("FLEET_FINAL rank%d %s" % (rank, h.hexdigest()), flush=True)
    print("dist_fleet_worker rank %d: PASSED" % rank, flush=True)
    if jax.process_count() > 1:
        # exit barrier: a rank tearing down its sockets while the peer
        # is still inside a trailing collective reads as a fleet death
        from jax.experimental import multihost_utils as mhu
        mhu.sync_global_devices("dist_fleet_worker_done")


if __name__ == "__main__":
    main()
