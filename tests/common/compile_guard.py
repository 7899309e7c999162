"""Shared steady-state recompile guard.

``count_backend_compiles()`` (mxnet_tpu.compile_cache) counts the
programs that reached JAX's backend-compile stage via the public
``jax.monitoring`` events.  ``assert_no_compiles()`` turns "a retrace in
the steady loop" from a silent 10x regression into a tier-1 test
failure: test_serve's no-compiles-in-the-serving-loop assertion,
generalized for fit / superstep / score / serve loops.
"""
import contextlib

from mxnet_tpu.compile_cache import (  # noqa: F401  (re-exported)
    CompileCounter, count_backend_compiles)


@contextlib.contextmanager
def assert_no_compiles(what="steady-state loop"):
    """Fail the test if ANY XLA backend compilation happens inside the
    block: every program the block runs must already have been built."""
    with count_backend_compiles() as counter:
        yield counter
    n = counter.count
    assert n == 0, (
        "%s triggered %d XLA compile(s); every program must be built "
        "before the steady loop (a retrace here is a silent 10x "
        "regression in production)" % (what, n))
