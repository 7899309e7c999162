"""TPU re-run of tests/test_ndarray.py (reference: tests/python/gpu/
test_operator_gpu.py re-collects the unit suite on the accelerator)."""
from _mirror import tpu_gate

pytestmark = tpu_gate()

from test_ndarray import *  # noqa: F401,F403,E402

# needs multiple host devices; the TPU session exposes a single one
del test_multi_cpu_devices  # noqa: F821


# -- asnumpy on the chip: a transfer, not a transfer and a copy -------------

def _settled_rss_mib():
    """Resident memory once jax has let go of finished transfers'
    targets: it does at its next dispatch or collection, whichever
    comes first (a training loop dispatches every step, a test does
    not)."""
    import gc
    gc.collect()
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    raise RuntimeError("no VmRSS in /proc/self/status")


def test_asnumpy_large_tpu_read_is_direct_and_unshared():
    """64 MiB on the chip, where jax's fetch is a copy it would cache:
    the route reads ``direct``, the result is writable and independent
    of the array, of a second read and of later device work, and the
    device array is left with no host twin."""
    import numpy as np
    import mxnet_tpu as mx

    a = mx.nd.array(np.random.uniform(-1, 1, (4096, 4096)))     # 64 MiB
    assert a.context.device_type == "tpu"
    value = np.asarray(a.copy()._get())
    mx.trace.reset()
    first = a.asnumpy()
    second = a.asnumpy()
    samples = mx.trace.counter_events(names=["ndarray:asnumpy"])
    assert [(e["args"]["bytes"], e["args"]["direct"]) for e in samples] == \
        [(64 << 20, 1)] * 2
    for h in (first, second):
        assert h.flags.writeable and h.flags.owndata
    assert not np.shares_memory(first, second)
    assert a._get()._npy_value is None
    first[...] = 0
    assert (second == value).all()
    assert (a.asnumpy() == value).all()
    assert ((a * 2).asnumpy() == value * 2).all()
    # a copy started ahead is waited for and copied, not fetched again
    a._start_host_copy()
    mx.trace.reset()
    third = a.asnumpy()
    assert mx.trace.counter_events(
        names=["ndarray:asnumpy"])[0]["args"]["cached"] == 1
    assert third.flags.writeable and (third == value).all()
    third[...] = 0
    assert (a.asnumpy() == value).all()


def test_asnumpy_keeps_no_host_copy_per_array():
    """Four live device arrays of 256 MiB read in turn, as the bucketing
    module's four executors' outputs are: the host's resident memory
    ends where it began (less than one array above), where a cached
    twin an array would leave it 1 GiB up.  Numbers to
    ``chiprun_out/asnumpy_tpu.json``."""
    import json
    import os
    import numpy as np
    import jax
    import mxnet_tpu as mx

    each = 256 << 20
    arrays = [mx.nd.array(np.full((each // 4,), i, np.float32))
              for i in range(4)]
    mx.nd.waitall()
    before = _settled_rss_mib()
    for i, a in enumerate(arrays):
        h = a.asnumpy()
        assert h[0] == i and h[-1] == i
        del h
    after = _settled_rss_mib()
    # what jax's own read leaves behind, on the same arrays
    for a in arrays:
        np.asarray(a._get())
    cached = _settled_rss_mib()
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/asnumpy_tpu.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "arrays": 4, "bytes_each": each,
                   "rss_mib_before": before, "rss_mib_after_asnumpy": after,
                   "rss_mib_after_jax_cached_reads": cached}, f)
    assert after - before < 256, (before, after)
    assert cached - after > 3 * 256, (after, cached)
