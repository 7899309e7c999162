"""Clean-exit detection test: a worker that finishes WITHOUT calling
kv.close() (the normal Module.fit pattern — nothing in model.py closes the
kvstore) must not be mistaken for a dead peer.  PSWorkerClient registers
the stop handshake via atexit, so normal interpreter exit stays clean and
the whole job returns 0."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import mxnet_tpu as mx


def main():
    kv = mx.create_kvstore("dist_async")
    shape = (4, 5)
    kv.init(9, mx.nd.ones(shape))
    kv.push(9, mx.nd.ones(shape))
    out = mx.nd.zeros(shape)
    kv.pull(9, out=out)
    kv.barrier()
    print("PASSED rank %d (no explicit close)" % kv.rank)
    # NO kv.close(): interpreter exit must still do the stop handshake


if __name__ == "__main__":
    main()
