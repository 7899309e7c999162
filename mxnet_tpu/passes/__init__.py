"""``mxnet_tpu.passes`` — symbol-graph optimization pipeline.

The stack owns a symbolic graph layer above jax tracing; this package
uses it the way Relay/TVM use theirs: an ordered pass pipeline that
rewrites the graph BEFORE the compiler sees it —

* ``FoldConstantsPass``          scalar-chain + param-subgraph folding
* ``CSEPass``                    common-subexpression elimination
* ``DeadNodeEliminationPass``    inference-identity + unreachable nodes
* ``U8WirePass``                 in-graph uint8 cast/normalize prologue
* ``QuantizePass``               calibrated int8 (fp16 fallback) q/dq
                                 insertion for the matmul/conv family
* ``FuseEpiloguePass``           matmul/conv + bias + Activation
                                 (+ ``_contrib_quantize``) -> one
                                 ``_fused_*`` op (TVM's epilogue fusion)
* ``ElementwiseFusePass``        elementwise chains -> ``_fused_elemwise``
* ``MoEServeParityPass``         ``_moe_dispatch`` capacity pinned to
                                 no-drop on serving graphs (moe parity)

with per-pass trace spans and ``mx.profiler.passes_report()``, a
round-trip + attr-preservation verifier after every pass, and a pipeline
fingerprint stamped into the transformed symbol (``__passes__`` graph
attr), which the report and a saved graph's JSON carry.

Typical serving flow (what ``ServeEngine(quantize=...)`` runs)::

    table = passes.calibrate(sym, data_iter, num_batches=10,
                             arg_params=arg, aux_params=aux)
    pipe = passes.default_inference_pipeline(
        quantize=passes.QuantizePass(calib=table))
    qsym, qparams = pipe.run(sym, {**arg, **aux})
    # Predictor(qsym.tojson(), qparams, ...) binds int8 weights and
    # compiles the lower-precision program per serve bucket

See docs/quantize.md for the calibration workflow and the measured
numbers; tools/dump_passes.py prints per-pass before/after graphs.
"""
from .pipeline import Pass, PassError, PassPipeline, PassStats
from .verify import check_attrs_preserved, diff_attrs, verify_roundtrip
from .graph_passes import (CSEPass, DeadNodeEliminationPass,
                           FoldConstantsPass, U8WirePass, rebuild,
                           tensor_name)
from .calibrate import CalibrationTable, calibrate, calibrate_arrays
from .embed import SparseEmbedPass, default_embed_dedup
from .moe import MoEServeParityPass, default_moe_exact
from .fuse import (ElementwiseFusePass, FuseEpiloguePass, default_fuse,
                   fusion_passes)
from .quantize import (QuantizePass, build_serving_pipeline,
                       default_fallback_dtype, default_inference_pipeline,
                       default_quantize_ops, quantize_model)

__all__ = [
    "Pass", "PassError", "PassPipeline", "PassStats",
    "check_attrs_preserved", "diff_attrs", "verify_roundtrip",
    "CSEPass", "DeadNodeEliminationPass", "FoldConstantsPass",
    "U8WirePass", "rebuild", "tensor_name",
    "ElementwiseFusePass", "FuseEpiloguePass", "default_fuse",
    "fusion_passes", "SparseEmbedPass", "default_embed_dedup",
    "MoEServeParityPass", "default_moe_exact",
    "CalibrationTable", "calibrate", "calibrate_arrays",
    "QuantizePass", "build_serving_pipeline", "default_fallback_dtype",
    "default_inference_pipeline", "default_quantize_ops", "quantize_model",
]
