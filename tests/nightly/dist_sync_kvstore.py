"""Distributed kvstore arithmetic test.

Reference: tests/nightly/dist_sync_kvstore.py:1-48 — run with
``python tools/launch.py -n 4 python tests/nightly/dist_sync_kvstore.py``;
asserts exact arithmetic of synchronous aggregation across workers for
small and big (striped in the reference; whole-tensor here) arrays.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
# CPU multi-process: each worker is one jax process
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import mxnet_tpu as mx


def check_diff_to_scalar(A, x):
    assert np.sum(np.abs((A - x).asnumpy())) == 0, (A.asnumpy(), x)


def test_sync_push_pull():
    kv = mx.kv.create("dist_sync")
    n = kv.num_workers
    rate = 2
    shape = (2, 3)
    big_shape = (1200, 1200)  # reference: above MXNET_KVSTORE_BIGARRAY_BOUND

    kv.init(3, mx.nd.ones(shape))
    kv.init(99, mx.nd.ones(big_shape))
    # issue nrepeat pushes; each worker pushes rank+1 * rate
    nrepeat = 3
    for i in range(nrepeat):
        kv.push(3, mx.nd.ones(shape) * (kv.rank + 1) * rate)
        kv.push(99, mx.nd.ones(big_shape) * (kv.rank + 1) * rate)

    num = (n + 1) * n * rate / 2 * nrepeat + 1
    val = mx.nd.zeros(shape)
    kv.pull(3, out=val)
    check_diff_to_scalar(val, num)
    val2 = mx.nd.zeros(big_shape)
    kv.pull(99, out=val2)
    check_diff_to_scalar(val2, num)
    print("dist_sync_kvstore rank %d: PASSED (num=%s)" % (kv.rank, num))


if __name__ == "__main__":
    test_sync_push_pull()
