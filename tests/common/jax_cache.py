"""JAX's persistent compilation cache in a directory of the test's own.

The one cache a restart keeps (``mxnet_tpu.compile_cache.place_jax_cache``
places it for the entry points).  A test that stands for "a second
process" builds its objects again under this fixture and reads
``count_backend_compiles()``: ``compiled`` must be 0.  Import the fixture
by name (``from jax_cache import jax_cache_dir  # noqa: F401``).
"""
import pytest


@pytest.fixture
def jax_cache_dir(tmp_path):
    """JAX's persistent cache in a directory of the test's own, keeping
    every program (tier-1's keeps compiles over half a second)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update(keys[0], str(tmp_path / "jax_cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    yield str(tmp_path / "jax_cache")
    cc.reset_cache()
    for k, v in was.items():
        jax.config.update(k, v)
