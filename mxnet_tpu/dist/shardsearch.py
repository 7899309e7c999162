"""Automatic GSPMD sharding search — ``fit(mesh=..., sharding="auto")``.

Hand-writing per-param PartitionSpecs is the last manual step between a
symbol graph and a multi-host mesh.  This module closes it with the
autotune recipe applied to sharding:

1. **Enumerate** a bounded set of global strategies from the symbol
   graph: replicate-everything (pure dp), column-sharded matmul params
   (last dim over the non-dp "model" axes), row-sharded (first dim),
   and the two alternating column/row assignments (the Megatron
   pairing, both phases).  Only params whose dim divides the model-axis
   product are sharded; everything else stays replicated — every
   candidate is valid by construction (``parallel.mesh.validate_spec``).

2. **Score** each candidate with the SHARED learned cost model
   (``autotune.costmodel`` — the same scorer JointTuner ranks with, no
   forked roofline): AOT-compile the real fused step (through the
   compile cache — a warm process re-scores for free), take per-device
   FLOPs + bytes from XLA cost analysis and the collective payload
   census from the post-partitioner HLO, featurize, and predict.  A
   single-process search uses the host's trained model; multi-process
   ranks score with the deterministic ``analytic_cost`` prior instead
   (per-host training sets differ, and every rank must shortlist
   identically — they are one collective program).

3. **Measure** only the shortlist (``MXNET_DIST_SHARDSEARCH_SHORTLIST``
   best estimates, default 2) by stepping the compiled program a few
   times (``MXNET_DIST_SHARDSEARCH_STEPS``, default 3) and timing the
   device wall.  The estimate ranks; the measurement decides.

4. **Persist** the winner keyed by a fingerprint of everything that
   changes the answer — symbol digest, param shapes, mesh axes, device
   platform/kind, process count — in the autotune store
   (``MXNET_AUTOTUNE_DIR``).  A store hit skips the whole search, so
   the second process (or the serving fleet) resolves ``"auto"``
   without compiling a single candidate.

Multi-process runs search in lockstep (every rank compiles and measures
the same candidates in the same order — they are one collective
program), then rank 0's measured winner is broadcast so every rank
installs byte-identical specs; only rank 0 writes the store.

``MXNET_DIST_SHARDSEARCH=0`` disables resolution (``sharding="auto"``
then means "just the ``__sharding__`` symbol attributes").
"""
from __future__ import annotations

import hashlib
import json
import time
from typing import Dict, List, Optional, Tuple

from ..base import MXNetError, get_env

__all__ = ["resolve_auto", "search_sharding", "enumerate_candidates",
           "fingerprint"]

_STORE_PREFIX = "shardsearch-"


# -- candidate enumeration ---------------------------------------------------
def _model_axes(mesh) -> List[Tuple[str, int]]:
    """The non-dp mesh axes with size > 1 — the axes a param can shard
    over (dp carries the batch)."""
    return [(str(a), int(s)) for a, s in mesh.shape.items()
            if str(a) != "dp" and int(s) > 1]


def enumerate_candidates(shapes: Dict[str, tuple], mesh) \
        -> List[Tuple[str, Dict[str, list]]]:
    """Bounded global strategies as ``(name, {param: spec_entries})``
    pairs.  ``spec_entries`` is the JSON form: a list per param of
    ``None`` / axis name / list of axis names.  Params not named stay
    replicated (modulo ``__sharding__`` attributes, which the fused
    step merges underneath)."""
    model = _model_axes(mesh)
    if not model:
        return [("dp", {})]
    axes = [a for a, _ in model]
    ways = 1
    for _, s in model:
        ways *= s
    entry = axes[0] if len(axes) == 1 else list(axes)
    eligible = [(n, tuple(shapes[n])) for n in sorted(shapes)
                if len(shapes[n]) >= 2]

    def col(nd):
        return [None] * (nd - 1) + [entry]

    def row(nd):
        return [entry] + [None] * (nd - 1)

    def strat(pick):
        specs = {}
        for i, (n, shape) in enumerate(eligible):
            kind = pick(i, shape)
            if kind == "col" and shape[-1] % ways == 0:
                specs[n] = col(len(shape))
            elif kind == "row" and shape[0] % ways == 0:
                specs[n] = row(len(shape))
        return specs

    cands: List[Tuple[str, Dict[str, list]]] = [("dp", {})]
    seen = {json.dumps({}, sort_keys=True)}
    for name, pick in (
            ("col", lambda i, s: "col"),
            ("row", lambda i, s: "row"),
            ("alt", lambda i, s: "col" if i % 2 == 0 else "row"),
            ("alt2", lambda i, s: "row" if i % 2 == 0 else "col")):
        specs = strat(pick)
        key = json.dumps(specs, sort_keys=True)
        if key not in seen:
            seen.add(key)
            cands.append((name, specs))
    return cands


def _to_partition_specs(specs: Dict[str, list]) -> dict:
    """JSON spec entries -> PartitionSpec map (inner lists become the
    tuple-of-axes form: one dim over the product of those axes)."""
    from jax.sharding import PartitionSpec as P
    out = {}
    for n, entries in specs.items():
        out[n] = P(*[tuple(e) if isinstance(e, list) else e
                     for e in entries])
    return out


# -- fingerprint -------------------------------------------------------------
def fingerprint(symbol, param_shapes: Dict[str, tuple], mesh) -> str:
    """Store key: everything that changes the search's answer — the
    model (symbol digest + param shapes), the topology (mesh axes +
    device platform/kind + process count)."""
    from ..parallel.mesh import mesh_axes
    devs = list(mesh.devices.ravel())
    nproc = len({d.process_index for d in devs})
    h = hashlib.sha1()
    h.update(symbol.tojson().encode())
    for n in sorted(param_shapes):
        h.update(("%s:%s;" % (n, tuple(param_shapes[n]))).encode())
    h.update(repr(mesh_axes(mesh)).encode())
    h.update(("%s:%s:%d:%d" % (devs[0].platform,
                               getattr(devs[0], "device_kind", ""),
                               len(devs), nproc)).encode())
    return _STORE_PREFIX + h.hexdigest()[:20]


# -- scoring + measurement ---------------------------------------------------
def _featurize(flops: float, bytes_accessed: float, census, mesh) \
        -> List[float]:
    """A candidate's compiled-program characteristics on the shared
    cost-model feature schema (autotune.costmodel.FEATURE_NAMES)."""
    from ..autotune.costmodel import features
    census = census or {}
    return features(
        gflops=float(flops) / 1e9,
        hbm_gb=float(bytes_accessed) / 1e9,
        coll_gb=float(census.get("total_bytes", 0.0)) / 1e9,
        coll_count=float(census.get("total_count", 0.0)),
        mesh_devices=float(mesh.devices.size),
        mesh_axes=float(len(mesh.axis_names)))


def _estimate_s(feat, multiprocess: bool) -> float:
    """Predicted step time from the shared cost model.  Multi-process
    ranks use the deterministic analytic prior (identical on every rank
    by construction); a single-process search gets the host's trained
    model (relative ranking is all the shortlist needs)."""
    from ..autotune import costmodel
    if multiprocess:
        return costmodel.analytic_cost(feat)
    return costmodel.get_model().predict(feat)


class _Trial:
    """One candidate's fused step + state + synthetic batch, built from
    the module's real bind (same symbol, optimizer, shapes)."""

    def __init__(self, module, mesh, specs: Dict[str, list]):
        from ..module.fused import FusedTrainStep
        from ..io import DataBatch
        from ..ndarray import zeros
        gdp = (module._kvstore is not None
               and "dist_sync" in module._kvstore.type)
        self.fused = FusedTrainStep(
            module._symbol, module._context, module._data_names,
            module._label_names, module._param_names,
            module._fixed_param_names, module._optimizer,
            label_shapes=module._label_shapes,
            remat=get_env("MXNET_BACKWARD_DO_MIRROR", False, bool),
            compute_dtype=get_env("MXNET_COMPUTE_DTYPE") or None,
            global_dp=gdp, mesh=mesh,
            sharding=_to_partition_specs(specs))
        # a trial's state is a copy: the module keeps its arrays
        self.state = self.fused.init_state(dict(module._arg_params),
                                           dict(module._aux_params))
        batch = DataBatch(
            data=[zeros(shape) for _, shape in module._data_shapes],
            label=[zeros(shape)
                   for _, shape in (module._label_shapes or [])])
        self.batch = self.fused.make_batch(batch)
        import jax
        from .. import random as _random
        key = _random.new_key()
        if self.fused._multiprocess():
            import numpy as np
            from jax.experimental import multihost_utils as mhu
            import jax.numpy as jnp
            kd = np.asarray(mhu.broadcast_one_to_all(
                np.asarray(jax.random.key_data(key))))
            key = jax.random.wrap_key_data(
                jnp.copy(jax.device_put(kd, self.fused._replicated())))
        self.key = key

    def compile_cost(self):
        """AOT-compile through the compile cache; returns the
        (flops, bytes, collective census) the estimator consumes."""
        flops = self.fused.aot_compile(self.state, self.batch, self.key)
        stats = self.fused.multichip_stats
        return (flops,
                stats.bytes_per_step if stats is not None else 0.0,
                stats.collectives if stats is not None else None)

    def measure_s(self, steps: int) -> float:
        """Median-free mean device wall of ``steps`` real steps (one
        unmeasured warmup dispatch absorbs any lazy work)."""
        import jax
        state, _ = self.fused.step(self.state, self.batch, self.key)
        jax.block_until_ready(next(iter(state["params"].values()),
                                   state["t"]))
        t0 = time.perf_counter()
        for _ in range(max(1, steps)):
            state, _ = self.fused.step(state, self.batch, self.key)
        jax.block_until_ready(next(iter(state["params"].values()),
                                   state["t"]))
        self.state = state
        return (time.perf_counter() - t0) / max(1, steps)

    def close(self) -> None:
        self.state = None
        self.batch = None
        self.fused = None


# -- the search --------------------------------------------------------------
def search_sharding(module, mesh, log_fn=None) \
        -> Tuple[Dict[str, list], list]:
    """Run the full search (no store involvement); returns
    ``(winning_spec_entries, measurement_log)`` where the log is
    ``[({"strategy": name, "specs": {...}, "est_s": e}, measured_s),
    ...]`` — the autotune-store audit format."""
    import numpy as np
    shapes = {n: tuple(module._arg_params[n].shape)
              for n in module._param_names}
    cands = enumerate_candidates(shapes, mesh)
    shortlist_n = max(1, get_env("MXNET_DIST_SHARDSEARCH_SHORTLIST",
                                 2, int))
    steps = max(1, get_env("MXNET_DIST_SHARDSEARCH_STEPS", 3, int))
    nproc = len({d.process_index for d in mesh.devices.ravel()})

    scored = []
    for name, specs in cands:
        trial = _Trial(module, mesh, specs)
        try:
            flops, nbytes, census = trial.compile_cost()
            feat = _featurize(flops, nbytes, census, mesh)
            est = _estimate_s(feat, multiprocess=nproc > 1)
        finally:
            trial.close()
        scored.append((est, name, specs, feat))
        if log_fn:
            log_fn("shardsearch: candidate %-4s est %.3es" % (name, est))
    # deterministic shortlist: estimate, then name — identical on every
    # rank (multi-process estimates come from the analytic prior, a pure
    # function of the compiled program and the env knobs)
    scored.sort(key=lambda t: (t[0], t[1]))
    shortlist = scored[:shortlist_n]

    measured = []
    mlog = []
    for est, name, specs, feat in shortlist:
        trial = _Trial(module, mesh, specs)
        try:
            trial.compile_cost()   # cache hit: installs the executable
            s = trial.measure_s(steps)
        finally:
            trial.close()
        measured.append((s, name, specs))
        # "_feat" makes this measurement training data for the shared
        # cost model (costmodel.refit_from_store walks the audit logs)
        mlog.append(({"strategy": name, "specs": specs,
                      "est_s": round(est, 9), "_feat": feat}, s))
        if log_fn:
            log_fn("shardsearch: measured  %-4s %.3es/step" % (name, s))
    for est, name, specs, feat in scored[shortlist_n:]:
        # the audit log records WHY the tail was never measured
        mlog.append(({"strategy": name, "specs": specs,
                      "est_s": round(est, 9), "shortlisted": False},
                     -1.0))

    best = min(range(len(measured)), key=lambda i: measured[i][0])
    if nproc > 1:
        # ranks' wall clocks differ; rank 0's pick is THE pick, or the
        # fleet installs divergent specs and wedges in its first step
        from jax.experimental import multihost_utils as mhu
        best = int(np.asarray(
            mhu.broadcast_one_to_all(np.int32(best))))
    _, name, specs = measured[best]
    if log_fn:
        log_fn("shardsearch: winner %s (%.3es/step over %d candidates, "
               "%d measured)" % (name, measured[best][0], len(cands),
                                 len(measured)))
    return specs, mlog


def resolve_auto(module, mesh) -> Optional[dict]:
    """``sharding="auto"`` entry point (Module._setup_fused): store
    hit -> the persisted winner; miss -> run the search, persist on
    rank 0, return PartitionSpecs (None = nothing to shard: the merge
    then leaves only the ``__sharding__`` attributes)."""
    if not get_env("MXNET_DIST_SHARDSEARCH", True, bool):
        return None
    if mesh is None:
        raise MXNetError("sharding='auto' needs a mesh to search over")
    from ..autotune import store
    shapes = {n: tuple(module._arg_params[n].shape)
              for n in module._param_names}
    key = fingerprint(module._symbol, shapes, mesh)
    doc = store.load_config(key)
    if doc is not None:
        specs = doc["config"].get("specs", {})
        return _to_partition_specs(specs) if specs else None
    log_fn = module.logger.info if hasattr(module, "logger") else None
    specs, mlog = search_sharding(module, mesh, log_fn=log_fn)
    best_s = min((s for _, s in mlog if s >= 0), default=0.0)
    import jax
    if jax.process_index() == 0:
        from ..parallel.mesh import mesh_axes
        store.save_config(
            key, {"specs": specs}, best_s,
            meta={"kind": "shardsearch",
                  "mesh": [list(ax) for ax in mesh_axes(mesh)],
                  "nparams": len(shapes)},
            log=mlog)
        # the featurized measurements just joined the training set —
        # fold them into the shared cost model for the next search
        from ..autotune.costmodel import refit_from_store
        refit_from_store()
    return _to_partition_specs(specs) if specs else None
