"""Explicit pipeline parallelism: GPipe-style microbatching over a ``pp``
mesh axis.

Beyond reference parity (SURVEY §2.4: the reference's model-parallel LSTM
overlapped timesteps only implicitly through the engine's async
scheduling; no explicit schedule existed).  The TPU-native formulation:
stage parameters are stacked along a leading axis and sharded over
``pp``, every device runs the SAME stage function under ``shard_map``,
and activations hop stage-to-stage with ``lax.ppermute`` inside a
``lax.scan`` over pipeline ticks — the canonical compiler-friendly
pipeline (static shapes, no data-dependent control flow, collectives on
ICI).  JAX differentiates through scan + ppermute, so the backward
pipeline (reverse hops) comes from autodiff rather than a hand schedule.

Scope: homogeneous stages (each stage applies the same ``stage_fn`` with
its own parameter slice — e.g. a stack of identical residual/MLP blocks),
GPipe fill-drain schedule (bubble fraction (S-1)/(M+S-1) for S stages and
M microbatches; raise M to amortize).  Heterogeneous first/last layers
(embedding, classifier head) run outside the pipelined stack, which is
how the stacked-stage pattern is used in practice.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import shard_map_norep

__all__ = ["pipeline_apply"]


def pipeline_apply(stage_fn: Callable, mesh: Mesh, stacked_params, micros,
                   axis: str = "pp"):
    """Run microbatches through the stage pipeline; returns stacked
    outputs (M, ...) with the same sharding as the inputs.

    stage_fn(params_slice, x) -> y where y.shape == x.shape (homogeneous
    stages); stacked_params pytree leaves have leading dim = S (sharded
    over `axis`); micros has leading dim M (replicated).
    """
    S = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                "stacked param leading dim %d != pipeline stages %d "
                "(each leaf must stack one slice per pp-axis device)"
                % (leaf.shape[0], S))

    def run(params, micros_in):
        # params leaves: (1, ...) — this device's stage slice
        local = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = lax.axis_index(axis)
        M = micros_in.shape[0]
        T = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t while t < M (beyond that the
            # injected value is garbage that never reaches a recorded out)
            inject = micros_in[jnp.minimum(t, M - 1)]
            x = jnp.where(stage == 0, inject, buf)
            y = stage_fn(local, x)
            # the last stage records micro m = t - (S-1)
            m = t - (S - 1)
            record = (stage == S - 1) & (m >= 0)
            outs = lax.cond(
                record,
                lambda o: o.at[jnp.maximum(m, 0)].set(y),
                lambda o: o, outs)
            buf_next = lax.ppermute(y, axis, perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(micros_in[0])
        outs0 = jnp.zeros_like(micros_in)
        (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(T))
        # only the last stage holds real outputs; make the value
        # replicated so out_specs=P() is sound
        outs = lax.psum(jnp.where(stage == S - 1, outs,
                                  jnp.zeros_like(outs)), axis)
        return outs

    sharded = shard_map_norep(run, mesh, in_specs=(P(axis), P()),
                              out_specs=P())
    return sharded(stacked_params, micros)
