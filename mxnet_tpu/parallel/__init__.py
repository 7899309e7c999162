"""Parallelism: meshes, the pipeline schedule, collectives.

TPU-native replacement for the reference's kvstore/ps-lite distribution stack
(SURVEY §2.4, §5.8): data parallel = GSPMD batch sharding + XLA all-reduce
over ICI; model parallel = param PartitionSpecs (ctx_group analogue);
multi-host = the same mesh spanning processes over ICI+DCN.
"""
from .mesh import (make_mesh, parse_mesh_spec, mesh_from_env,
                   normalize_spec, spec_axes, validate_spec,
                   sharding_attrs, dp_sharding, replicated,
                   Mesh, NamedSharding, PartitionSpec)
from .pipeline import pipeline_apply

__all__ = ["make_mesh", "parse_mesh_spec", "mesh_from_env",
           "normalize_spec", "spec_axes", "validate_spec",
           "sharding_attrs", "dp_sharding", "replicated",
           "Mesh", "NamedSharding", "PartitionSpec", "pipeline_apply"]
