"""dist_async parameter-server test (reference tests/nightly pattern:
launched by tools/launch.py -n W -s S with the local launcher).

Asserts exact arithmetic of the async server's default accumulate mode
(stored += merged, kvstore_dist_server.h default), big-array striping
across servers, and server-side optimizer updates (pickled SGD shipped via
the command channel).  Determinism argument: each worker's own push→pull on
one FIFO connection flushes its pushes; the barrier then orders all
workers' flushed pushes before the final pull, and accumulation/SGD(+wd=0)
updates are commutative.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# small stripe threshold so the "big array" path is cheap to test
os.environ.setdefault("MXNET_KVSTORE_BIGARRAY_BOUND", "1000")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import mxnet_tpu as mx


def main():
    kv = mx.create_kvstore("dist_async")
    rank = kv.rank
    nworker = kv.num_workers
    nrepeat = 3

    # -- accumulate mode, small key ----------------------------------------
    shape = (4, 5)
    kv.init(3, mx.nd.ones(shape))
    for _ in range(nrepeat):
        kv.push(3, mx.nd.ones(shape) * (rank + 1))
    out = mx.nd.zeros(shape)
    kv.pull(3, out=out)          # flushes this worker's pushes
    kv.barrier()
    kv.pull(3, out=out)
    expected = 1 + nrepeat * sum(r + 1 for r in range(nworker))
    assert np.allclose(out.asnumpy(), expected), (out.asnumpy().flat[0],
                                                  expected)

    # -- big array: striped across all servers -----------------------------
    big_shape = (50, 60)         # 3000 > bound => striped
    kv.init(99, mx.nd.ones(big_shape))
    for _ in range(nrepeat):
        kv.push(99, mx.nd.ones(big_shape) * (rank + 1))
    big_out = mx.nd.zeros(big_shape)
    kv.pull(99, out=big_out)
    kv.barrier()
    kv.pull(99, out=big_out)
    assert np.allclose(big_out.asnumpy(), expected), (
        big_out.asnumpy().flat[0], expected)

    # -- server-side optimizer (async update-per-push) ---------------------
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, wd=0.0,
                                      rescale_grad=1.0))
    kv.init(7, mx.nd.ones(shape))
    for _ in range(nrepeat):
        kv.push(7, mx.nd.ones(shape))          # grad = 1 per push
    w = mx.nd.zeros(shape)
    kv.pull(7, out=w)
    kv.barrier()
    kv.pull(7, out=w)
    w_expected = 1.0 - 0.1 * nrepeat * nworker
    assert np.allclose(w.asnumpy(), w_expected, atol=1e-6), (
        w.asnumpy().flat[0], w_expected)

    kv.barrier()
    kv.close()
    print("PASSED dist_async rank %d/%d" % (rank, nworker))


if __name__ == "__main__":
    main()
