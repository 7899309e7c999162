"""The trace ring is the process's, and the readers take each block's last
samples from it (``moe_load.window_samples``): a model's ``moe:load``
blocks left there by an earlier test of the same worker would count in
the next cell's test.  Every test of this directory starts on an empty
ring, whichever files the scheduler gives its worker."""
import pytest


@pytest.fixture(autouse=True)
def _empty_trace_ring():
    import mxnet_tpu as mx
    mx.trace.reset()
    yield
