"""Device mesh helpers: the TPU-native substrate for every parallelism mode.

Reference analogue: the kvstore `device`/`dist_sync` machinery + ctx_group
model parallelism (SURVEY §2.4).  On TPU, all of them are shardings over a
jax.sharding.Mesh: data parallel = batch axis, model/tensor parallel =
feature axes, pipeline = stage axis — XLA inserts the collectives that the
reference implemented as cudaMemcpy reductions and ps-lite RPCs.
"""
from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["make_mesh", "parse_mesh_spec", "mesh_from_env",
           "normalize_spec", "spec_axes", "validate_spec",
           "sharding_attrs", "dp_sharding", "replicated",
           "tracing_over", "traced_devices",
           "PartitionSpec", "NamedSharding", "Mesh"]

_tracing = threading.local()


@contextlib.contextmanager
def tracing_over(mesh: Optional[Mesh]):
    """What a program's function enters around its body, so that the ops
    it traces can see how many devices the program spans (one where
    ``mesh`` is None): GSPMD shows a traced op neither the mesh nor its
    operands' shardings."""
    was = getattr(_tracing, "devices", 1)
    _tracing.devices = 1 if mesh is None else int(mesh.devices.size)
    try:
        yield
    finally:
        _tracing.devices = was


def traced_devices() -> int:
    """Devices of the program being traced by this thread (1 outside
    ``tracing_over``).  A Pallas call is one device's: an op with a
    kernel lowering keeps its plain one where this is more than 1."""
    return getattr(_tracing, "devices", 1)


def make_mesh(axes: Sequence[Tuple[str, int]], devices=None) -> Mesh:
    """Create a Mesh from (name, size) axes, e.g. [("dp", 4), ("tp", 2)].

    Sizes may use -1 once to absorb remaining devices.  ``axes`` may
    also be the string form ``"dp=4,tp=2"`` (the ``MXNET_MESH`` syntax).
    """
    if isinstance(axes, str):
        axes = parse_mesh_spec(axes)
    if devices is None:
        devices = jax.devices()
    names = [a for a, _ in axes]
    sizes = [int(s) for _, s in axes]
    n = len(devices)
    if any(s == 0 or s < -1 for s in sizes):
        raise ValueError(
            "mesh %s: axis sizes must be positive (-1 to absorb the "
            "remaining devices)" % (axes,))
    if sizes.count(-1) > 1:
        raise ValueError("mesh %s: only one axis may be -1" % (axes,))
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if known <= 0 or n % known:
            raise ValueError("mesh %s: %d devices do not divide into the "
                             "fixed axes" % (axes, n))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError("mesh %s needs %d devices, have %d" % (axes, total, n))
    arr = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(names))


def parse_mesh_spec(spec: str) -> List[Tuple[str, int]]:
    """Parse the ``MXNET_MESH`` axis syntax: ``"dp=4,tp=2"`` ->
    ``[("dp", 4), ("tp", 2)]``.  ``-1`` absorbs the remaining devices
    (``make_mesh`` resolves it)."""
    axes: List[Tuple[str, int]] = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                "bad mesh axis %r in %r (expected name=size, e.g. "
                "'dp=4,tp=2')" % (part, spec))
        name, size = part.split("=", 1)
        try:
            axes.append((name.strip(), int(size)))
        except ValueError:
            raise ValueError("bad mesh axis size %r in %r" % (size, spec))
    if not axes:
        raise ValueError("empty mesh spec %r" % (spec,))
    return axes


def mesh_from_env(devices=None) -> Optional[Mesh]:
    """Mesh from the ``MXNET_MESH`` env knob (``"dp=4,tp=2"``), or None
    when the knob is unset/empty."""
    from ..base import get_env
    spec = (get_env("MXNET_MESH", "") or "").strip()
    if not spec:
        return None
    return make_mesh(parse_mesh_spec(spec), devices=devices)


def normalize_spec(spec) -> PartitionSpec:
    """Canonical PartitionSpec from any accepted sharding-spec form:
    a PartitionSpec, a tuple/list of axis names (None entries allowed),
    the comma string form carried by symbol attributes
    (``"None,tp"``), or None (replicated)."""
    if spec is None:
        return PartitionSpec()
    if isinstance(spec, PartitionSpec):
        return spec
    if isinstance(spec, str):
        entries = [p.strip() for p in spec.split(",")]
        return PartitionSpec(*[None if p in ("", "None", "none", "-")
                               else p for p in entries])
    if isinstance(spec, (tuple, list)):
        return PartitionSpec(*[None if e in (None, "None") else e
                               for e in spec])
    raise ValueError(
        "cannot interpret sharding spec %r (want PartitionSpec, "
        "tuple of axis names, or 'None,tp'-style string)" % (spec,))


def mesh_axes(mesh) -> Tuple[Tuple[str, int], ...]:
    """Canonical ((name, size), ...) serialization of a mesh's axes —
    shared by the compile-cache fast-key descriptions (fused step,
    Executor.set_mesh) and the multichip profiler, which must agree on
    mesh identity byte-for-byte."""
    return tuple((str(a), int(s)) for a, s in mesh.shape.items())


def spec_axes(spec) -> List[str]:
    """The mesh axis names a PartitionSpec (or entry list) references,
    tuple entries flattened, Nones dropped."""
    return [a for e in spec
            for a in (e if isinstance(e, (tuple, list)) else (e,))
            if a is not None]


def validate_spec(name, spec, mesh, shape=None) -> None:
    """Shared spec sanity check for the training (FusedTrainStep) and
    serving (Executor.set_mesh) paths: every referenced axis must exist
    in ``mesh``, and — when ``shape`` is given — divide its dim evenly
    (uneven shards would break checkpoint shard indexes and the donated
    layout).  Raises MXNetError naming the param/axis/dim."""
    from ..base import MXNetError
    sizes = dict(mesh.shape)
    bad = sorted(set(spec_axes(spec)) - set(sizes))
    if bad:
        raise MXNetError(
            "sharding spec for %r uses mesh axes %s not in mesh %s"
            % (name, bad, sizes))
    if shape is None:
        return
    if len(tuple(spec)) > len(shape):
        raise MXNetError(
            "sharding spec %s for %r has %d entries but the array is "
            "%d-D (shape %s)" % (tuple(spec), name, len(tuple(spec)),
                                 len(shape), tuple(shape)))
    for i, entry in enumerate(tuple(spec)[:len(shape)]):
        axes = [a for a in (entry if isinstance(entry, (tuple, list))
                            else (entry,)) if a is not None]
        if not axes:
            continue
        # a tuple entry shards one dim over the PRODUCT of its axes —
        # per-axis divisibility alone would admit the uneven case
        # (12 over ('dp','tp')=8 passes 12%4 and 12%2)
        ways = 1
        for a in axes:
            ways *= int(sizes[a])
        if shape[i] % ways:
            raise MXNetError(
                "sharding spec %s for %r: dim %d (%d) is not "
                "divisible by mesh axes %s (%d ways)"
                % (tuple(spec), name, i, shape[i], tuple(axes), ways))


def sharding_attrs(symbol) -> dict:
    """Per-name PartitionSpecs declared ON the symbol graph: every
    variable carrying a ``__sharding__`` attribute (set via
    ``mx.sym.Variable(name, attr={"__sharding__": "None,tp"})``) —
    the GSPMD-constraint analogue of the reference's ``ctx_group``
    placement attributes."""
    specs = {}
    for name, attrs in symbol.attr_dict().items():
        if "__sharding__" in attrs:
            specs[name] = normalize_spec(attrs["__sharding__"])
    return specs


def dp_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Batch-dim sharding over the data-parallel axis."""
    return NamedSharding(mesh, PartitionSpec(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def shard_map_norep(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off (one spelling for every
    caller — ring attention and the pipeline both need unchecked outputs
    that are made replicated by explicit collectives)."""
    from jax import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)
