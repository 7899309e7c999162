"""Explicit pipeline parallelism (parallel/pipeline.py): GPipe microbatch
schedule over a pp mesh axis on the virtual 8-device host."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (forces platform setup via conftest)
from jax.sharding import Mesh
from mxnet_tpu.parallel.pipeline import pipeline_apply

rng = np.random.RandomState(0)


def _mesh(pp):
    devs = np.array(jax.devices("cpu")[:pp])
    return Mesh(devs, ("pp",))


def stage_fn(params, x):
    # one dense block with residual: x + tanh(x @ w + b)
    return x + jnp.tanh(x @ params["w"] + params["b"])


def _stacked_params(S, d):
    return {"w": rng.uniform(-0.3, 0.3, (S, d, d)).astype(np.float32),
            "b": rng.uniform(-0.1, 0.1, (S, d)).astype(np.float32)}


@pytest.mark.parametrize("S,M", [(2, 4), (4, 4), (4, 8)])
def test_pipeline_matches_sequential(S, M):
    """The pipelined stack computes exactly the sequential composition of
    the S stages, for any microbatch count."""
    d, per = 6, 3
    params = _stacked_params(S, d)
    data = rng.uniform(-1, 1, (M, per, d)).astype(np.float32)

    mesh = _mesh(S)
    stacked = {k: jax.device_put(
        jnp.asarray(v),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("pp")))
        for k, v in params.items()}
    out = pipeline_apply(stage_fn, mesh, stacked, jnp.asarray(data))
    out = np.asarray(out)

    expect = data.copy()
    for s in range(S):
        p = {"w": params["w"][s], "b": params["b"][s]}
        expect = np.asarray(stage_fn(p, jnp.asarray(expect)))
    assert np.allclose(out, expect, atol=1e-5), np.abs(out - expect).max()


def test_gpipe_gradients_match_sequential():
    """Autodiff through the pipeline (reverse ppermute hops) equals the
    gradient of the sequential composition."""
    S, M, d, per = 4, 4, 5, 2
    params = _stacked_params(S, d)
    data = rng.uniform(-1, 1, (M * per, d)).astype(np.float32)
    w_out = rng.uniform(-0.3, 0.3, (d,)).astype(np.float32)

    def seq_loss(p):
        h = jnp.asarray(data)
        for s in range(S):
            h = stage_fn({"w": p["w"][s], "b": p["b"][s]}, h)
        return jnp.mean((h @ w_out) ** 2)

    g_seq = jax.grad(seq_loss)({k: jnp.asarray(v)
                                for k, v in params.items()})

    mesh = _mesh(S)
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("pp"))
    stacked = {k: jax.device_put(jnp.asarray(v), spec)
               for k, v in params.items()}

    def pipe_loss(p):
        micros = jnp.asarray(data).reshape(M, per, d)
        outs = pipeline_apply(stage_fn, mesh, p, micros)
        h = outs.reshape(M * per, d)
        return jnp.mean((h @ w_out) ** 2)

    g_pipe = jax.grad(pipe_loss)(stacked)
    for k in g_seq:
        assert np.allclose(np.asarray(g_pipe[k]), np.asarray(g_seq[k]),
                           atol=1e-5), k
