"""Dead-peer detection test (launched by tools/launch.py -n 2 -s 1).

Worker rank 1 "dies" (exits without the stop handshake) after a few
pushes.  The scheduler must detect the dropped connection and broadcast an
abort so worker rank 0 — blocked in a barrier that can now never complete —
fails fast with a clean message instead of hanging forever (the reference
job hung on node death and needed tools/kill-mxnet.py by hand; SURVEY
§5.3).  Rank 0 prints ABORT-DETECTED on the expected RuntimeError.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# deliberately leave MXNET_PS_RECV_TIMEOUT at its 600s default: only the
# abort broadcast can make this test finish inside its runner timeout, so
# a regression in abort delivery fails the test instead of hiding behind
# the RPC-timeout fallback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import mxnet_tpu as mx


def main():
    kv = mx.create_kvstore("dist_async")
    rank = kv.rank
    shape = (4, 5)
    kv.init(7, mx.nd.ones(shape))
    kv.push(7, mx.nd.ones(shape))
    out = mx.nd.zeros(shape)
    kv.pull(7, out=out)

    mode = sys.argv[1] if len(sys.argv) > 1 else "exit"
    if rank == 1:
        # simulate a crash: no kv close, no scheduler stop handshake.
        # The delay parks rank 0 in the barrier first, so the abort
        # broadcast (not a socket race) is what surfaces there.
        import time
        time.sleep(2.0)
        sys.stdout.flush()
        if mode == "raise":
            # unhandled exception: atexit still runs, but the excepthook
            # marks the client fatal so the stop handshake is skipped and
            # the scheduler sees a death, not a clean exit
            raise ValueError("simulated worker crash")
        os._exit(0)

    try:
        kv.barrier()          # can never complete: the peer dies mid-job
    except RuntimeError as e:
        msg = str(e)
        assert "abort" in msg.lower() or "connection lost" in msg, msg
        print("ABORT-DETECTED rank %d: %s" % (rank, msg))
        sys.stdout.flush()
        sys.exit(3)           # job must fail, but with this clean message
    print("UNEXPECTED: barrier completed with a dead peer")
    sys.exit(4)


if __name__ == "__main__":
    main()
