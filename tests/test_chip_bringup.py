"""What the chip bring-up (ISSUE 21) guarantees, as far as a machine
without a chip can show it: asking for the chip and not getting it is an
error everywhere — in a Context, in chip_smoke.py — and JAX's persistent
compile cache is placed from outside, at a path that never moves.
"""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.compile_cache import (count_backend_compiles, jax_cache_dir,
                                     place_jax_cache)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
def test_accelerator_context_raises_on_cpu_only_backend(make):
    """No modulo, no fallback: tpu(0) on a CPU-only backend names what
    JAX found, and tpu(9) is out of range rather than wrapped."""
    for dev_id in (0, 9):
        with pytest.raises(MXNetError) as exc:
            make(dev_id).jax_device()
        assert "has none" in str(exc.value) and "Cpu" in str(exc.value)
    with pytest.raises(MXNetError):
        mx.mod.Module(mx.sym.SoftmaxOutput(mx.sym.Variable("data")),
                      context=make(0)).bind([("data", (2, 4))],
                                            [("softmax_label", (2,))])


def test_accelerator_context_out_of_range(monkeypatch):
    """With accelerators present, device_id >= their count raises (the
    old code wrapped tpu(3) onto chip 0 of a one-chip machine)."""
    import jax

    class FakeChip:
        platform = "tpu"

        def __init__(self, i):
            self.id = i

        def __repr__(self):
            return "FakeChip(%d)" % self.id

    chips = [FakeChip(0), FakeChip(1)]
    monkeypatch.setattr(jax, "local_devices", lambda backend=None: chips)
    assert mx.tpu(1).jax_device() is chips[1]
    for dev_id in (2, 3, -1):
        with pytest.raises(MXNetError) as exc:
            mx.tpu(dev_id).jax_device()
        assert "out of range" in str(exc.value) and "2 tpu" in str(exc.value)


def test_cpu_context_keeps_the_fake_device_wrap():
    """cpu(k) still wraps over the forced host devices tier-1 relies on."""
    import jax
    n = len(jax.local_devices(backend="cpu"))
    assert mx.cpu(n + 1).jax_device() == mx.cpu(1).jax_device()
    assert mx.current_context().device_type == "cpu"


def test_chip_smoke_fails_fast_without_a_tpu(tmp_path):
    """JAX_PLATFORMS=cpu python chip_smoke.py: says what it found, exits
    non-zero within seconds, prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    res = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode == 1, res.stdout + res.stderr
    assert res.stdout.splitlines()[0].startswith("platform=cpu device_kind=")
    assert "needs a TPU; JAX found platform 'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


def test_jax_cache_dir_honours_the_variable_else_the_checkout():
    assert jax_cache_dir("/some/dir") == "/some/dir"
    for unset in (None, ""):
        assert jax_cache_dir(unset) == os.path.join(REPO, ".jax_cache")


def test_place_jax_cache_is_what_this_process_runs_with():
    """conftest placed the cache through the helper: the directory in
    effect is the variable's if it was set, else <checkout>/.jax_cache;
    placing again changes nothing, and children inherit the choice."""
    import jax
    d = place_jax_cache()
    assert d == jax.config.jax_compilation_cache_dir
    assert d == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert d == jax_cache_dir(os.environ["JAX_COMPILATION_CACHE_DIR"])
    assert place_jax_cache() == d


def test_compile_counter_uses_public_monitoring_api():
    """count_backend_compiles counts through jax.monitoring's public
    register/unregister pair: a new program is one request, a repeat is
    none, and nothing is counted after the block."""
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7.0)

    def prog(v):
        return jnp.tanh(v) * 3.0 + 1.0

    # lint: allow(raw-jit) — the counter under test counts raw jit compiles
    f = jax.jit(prog)
    with count_backend_compiles() as c:
        f(x).block_until_ready()
        assert c.count == 1
        f(x).block_until_ready()
    assert c.count == 1 and 0 <= c.cache_hits <= 1
    assert c.compiled == c.count - c.cache_hits
    # lint: allow(raw-jit) — as above
    jax.jit(lambda v: prog(v) - 2.0)(x).block_until_ready()
    assert c.count == 1                       # listener is gone
