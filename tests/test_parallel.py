"""mxnet_tpu.parallel under tier-1: mesh construction, the pipeline
schedule (pipeline_apply), and sequence parallelism (ring / Ulysses
attention) on the 8 forced host devices.  Mesh training itself is
Module.fit(mesh=): tests/test_multichip.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.parallel import PartitionSpec as P
from mxnet_tpu.parallel.ring import (attention_reference, make_ring_attention)


# -- mesh construction -------------------------------------------------------

def test_make_mesh_axes():
    mesh = parallel.make_mesh([("dp", 4), ("tp", 2)])
    assert dict(mesh.shape) == {"dp": 4, "tp": 2}
    assert mesh.devices.shape == (4, 2)


def test_make_mesh_absorb():
    mesh = parallel.make_mesh([("dp", -1), ("tp", 2)])
    assert dict(mesh.shape) == {"dp": 4, "tp": 2}


def test_make_mesh_too_many_devices():
    with pytest.raises(ValueError):
        parallel.make_mesh([("dp", 16)])


def test_make_mesh_two_absorb_axes():
    with pytest.raises(ValueError):
        parallel.make_mesh([("dp", -1), ("tp", -1)])


def test_parse_mesh_spec():
    assert parallel.parse_mesh_spec("dp=4,tp=2") == [("dp", 4), ("tp", 2)]
    assert parallel.parse_mesh_spec("dp=-1") == [("dp", -1)]
    with pytest.raises(ValueError):
        parallel.parse_mesh_spec("dp:4")
    with pytest.raises(ValueError):
        parallel.parse_mesh_spec("")


def test_make_mesh_string_form():
    mesh = parallel.make_mesh("dp=2,tp=2")
    assert dict(mesh.shape) == {"dp": 2, "tp": 2}


def test_mesh_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_MESH", "dp=8")
    mesh = parallel.mesh_from_env()
    assert dict(mesh.shape) == {"dp": 8}
    monkeypatch.setenv("MXNET_MESH", "")
    assert parallel.mesh_from_env() is None


def test_normalize_spec_forms():
    assert tuple(parallel.normalize_spec(None)) == ()
    assert tuple(parallel.normalize_spec(P("dp", None))) == ("dp", None)
    assert tuple(parallel.normalize_spec("None,tp")) == (None, "tp")
    assert tuple(parallel.normalize_spec(("tp", None))) == ("tp", None)
    with pytest.raises(ValueError):
        parallel.normalize_spec(3.14)


def test_sharding_attrs_from_symbol():
    w = mx.sym.Variable("fc_weight", attr={"__sharding__": "None,tp"})
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, weight=w, num_hidden=8, name="fc")
    specs = parallel.sharding_attrs(net)
    assert tuple(specs["fc_weight"]) == (None, "tp")


def test_dp_sharding_and_replicated():
    mesh = parallel.make_mesh([("dp", 8)])
    assert tuple(parallel.dp_sharding(mesh).spec) == ("dp",)
    assert tuple(parallel.replicated(mesh).spec) == ()


# -- pipeline_apply ----------------------------------------------------------

def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def test_pipeline_apply_matches_sequential():
    S, M, B, D = 4, 8, 2, 8
    mesh = parallel.make_mesh([("pp", S)])
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(S, D, D) * 0.3, jnp.float32),
              "b": jnp.zeros((S, D), jnp.float32)}
    micros = jnp.asarray(rng.randn(M, B, D), jnp.float32)
    outs = parallel.pipeline_apply(_stage_fn, mesh, params, micros)
    # sequential reference: run each microbatch through the S stages
    ref = []
    for m in range(M):
        h = micros[m]
        for s in range(S):
            h = _stage_fn({"w": params["w"][s], "b": params["b"][s]}, h)
        ref.append(h)
    ref = jnp.stack(ref)
    assert np.abs(np.asarray(outs) - np.asarray(ref)).max() < 1e-5


def test_pipeline_apply_stage_count_mismatch():
    mesh = parallel.make_mesh([("pp", 4)])
    params = {"w": jnp.zeros((3, 4, 4)), "b": jnp.zeros((3, 4))}
    with pytest.raises(ValueError):
        parallel.pipeline_apply(_stage_fn, mesh,
                                params, jnp.zeros((8, 2, 4)))


# -- ring / Ulysses attention ------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = parallel.make_mesh([("sp", 8)])
    rng = np.random.RandomState(0)
    B, T, H, D = 2, 32, 4, 8
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    fn = make_ring_attention(mesh, causal=causal)
    out = fn(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-4


def test_ulysses_attention_matches_reference():
    mesh = parallel.make_mesh([("sp", 4)], devices=jax.devices()[:4])
    rng = np.random.RandomState(0)
    B, T, H, D = 2, 16, 4, 8     # H divisible by sp
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    fn = make_ring_attention(mesh, axis="sp", impl="ulysses")
    out = fn(q, k, v)
    ref = attention_reference(q, k, v)
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-4


def test_make_mesh_zero_size_refused():
    with pytest.raises(ValueError, match="positive"):
        parallel.make_mesh("dp=0")
    with pytest.raises(ValueError, match="positive"):
        parallel.make_mesh([("dp", -2)])


def test_validate_spec_tuple_entry_uses_axis_product():
    """A tuple spec entry shards one dim over the PRODUCT of its axes:
    12 over ('dp','tp') on dp=4 x tp=2 is 8-way — uneven — and must be
    refused even though 12 divides by 4 and by 2 separately."""
    from mxnet_tpu.base import MXNetError
    mesh = parallel.make_mesh([("dp", 4), ("tp", 2)])
    spec = P(("dp", "tp"))
    with pytest.raises(MXNetError, match="8 ways"):
        parallel.validate_spec("w", spec, mesh, shape=(12,))
    parallel.validate_spec("w", spec, mesh, shape=(16,))   # 16 % 8 == 0


def test_validate_spec_overlong_refused():
    from mxnet_tpu.base import MXNetError
    mesh = parallel.make_mesh([("tp", 2)])
    with pytest.raises(MXNetError, match="entries"):
        parallel.validate_spec("b", P("tp", None), mesh, shape=(8,))


def test_mesh_axes_serialization():
    mesh = parallel.make_mesh([("dp", 4), ("tp", 2)])
    from mxnet_tpu.parallel.mesh import mesh_axes
    assert mesh_axes(mesh) == (("dp", 4), ("tp", 2))
