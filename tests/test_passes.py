"""mxnet_tpu.passes: symbol-graph optimization pipeline (tier-1, CPU).

ISSUE 9 contracts: golden-graph structure + f32 numeric parity for
fold/CSE/DCE; calibration determinism for a seeded feed sample;
quantized-vs-f32 output tolerance per serve bucket; pass-pipeline
fingerprints keeping quantized and f32 compile-cache entries disjoint
(grids warm side by side with zero cross-hits); zero XLA compiles in
the steady quantized serve loop; the uint8 wire prologue matching the
host normalize path bitwise; attr preservation (``__sharding__`` must
survive every pass, and a pass that drops it fails LOUD); and hot
weight reload re-quantizing fresh f32 weights.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
from jax_cache import jax_cache_dir  # noqa: E402,F401

import mxnet_tpu as mx
from mxnet_tpu import passes
from mxnet_tpu.passes import (CalibrationTable, CSEPass,
                              DeadNodeEliminationPass, FoldConstantsPass,
                              Pass, PassError, PassPipeline, QuantizePass,
                              U8WirePass, calibrate_arrays,
                              default_inference_pipeline, quantize_model,
                              verify_roundtrip)

IN_DIM = 16
HIDDEN = 32
CLASSES = 4


def _node_ops(sym):
    return [n["op"] for n in json.loads(sym.tojson())["nodes"]]


def _mlp(dropout=False):
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    if dropout:
        net = mx.sym.Dropout(net, p=0.5, name="drop1")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN, name="fc2")
    net = mx.sym.Activation(net, act_type="relu", name="relu2")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc3")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params(seed=0, scale=0.3):
    rng = np.random.RandomState(seed)
    return {
        "fc1_weight": (rng.randn(HIDDEN, IN_DIM) * scale).astype(np.float32),
        "fc1_bias": (rng.randn(HIDDEN) * 0.1).astype(np.float32),
        "fc2_weight": (rng.randn(HIDDEN, HIDDEN) * scale).astype(np.float32),
        "fc2_bias": (rng.randn(HIDDEN) * 0.1).astype(np.float32),
        "fc3_weight": (rng.randn(CLASSES, HIDDEN) * scale).astype(np.float32),
        "fc3_bias": np.zeros(CLASSES, np.float32),
    }


def _forward(sym, params, X, extra_shapes=None, dtype=None):
    shapes = {"data": tuple(X.shape)}
    shapes.update({"softmax_label": (X.shape[0],)}
                  if extra_shapes is None else extra_shapes)
    type_dict = {"data": dtype} if dtype else None
    exe = sym.simple_bind(mx.cpu(), grad_req="null",
                          type_dict=type_dict, **shapes)
    exe.copy_params_from(params, {}, allow_extra_params=True)
    exe.arg_dict["data"][:] = np.asarray(X, exe.arg_dict["data"].dtype)
    return np.asarray(exe.forward(is_train=False)[0]._get())


def _calib_feeds(n=4, batch=8, seed=1):
    rng = np.random.RandomState(seed)
    return [{"data": rng.rand(batch, IN_DIM).astype(np.float32)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# golden-graph structure + numeric parity: fold / CSE / DCE


def test_fold_scalar_chain_and_identity():
    x = mx.sym.Variable("data")
    y = ((x * 2.0) * 3.0) + 0.0          # chain merges, +0 disappears
    y = mx.sym.FullyConnected(y, num_hidden=CLASSES, name="fc")
    p = FoldConstantsPass(fold_params=False)
    pipe = PassPipeline([p], name="t-fold")
    params = {"fc_weight": _params()["fc3_weight"][:, :IN_DIM],
              "fc_bias": np.zeros(CLASSES, np.float32)}
    out, params2 = pipe.run(y, params)
    before = [o for o in _node_ops(y) if o.endswith("_scalar")]
    after = [o for o in _node_ops(out) if o.endswith("_scalar")]
    assert len(before) == 3 and len(after) == 1
    assert p.summary["scalar_folds"] == 2
    X = np.random.RandomState(2).rand(8, IN_DIM).astype(np.float32)
    np.testing.assert_allclose(
        _forward(y, params, X, extra_shapes={}),
        _forward(out, params2, X, extra_shapes={}), rtol=1e-5, atol=1e-5)


def test_fold_param_subgraph_bakes_new_param():
    w = mx.sym.Variable("w")
    scaled = w * 0.5                     # weight-only math: fold to a param
    data = mx.sym.Variable("data")
    y = mx.sym.broadcast_mul(data, scaled, name="mul")
    pipe = PassPipeline([FoldConstantsPass()], name="t-pfold")
    params = {"w": np.full((1, IN_DIM), 2.0, np.float32)}
    out, params2 = pipe.run(y, params)
    folded = [k for k in params2 if k.endswith("_folded")]
    assert len(folded) == 1
    np.testing.assert_allclose(params2[folded[0]], 1.0)
    assert len(_node_ops(out)) < len(_node_ops(y))
    X = np.random.RandomState(3).rand(4, IN_DIM).astype(np.float32)
    np.testing.assert_allclose(
        _forward(y, params, X, extra_shapes={"w": (1, IN_DIM)}),
        _forward(out, params2, X,
                 extra_shapes={folded[0]: (1, IN_DIM)}), rtol=1e-6)
    # transform_params replays the fold against fresh weights
    fresh = pipe.transform_params({"w": np.full((1, IN_DIM), 4.0,
                                                np.float32)})
    np.testing.assert_allclose(fresh[folded[0]], 2.0)


def test_cse_merges_identical_subgraphs():
    data = mx.sym.Variable("data")
    a = mx.sym.FullyConnected(data, num_hidden=HIDDEN, name="fc_a")
    r1 = mx.sym.Activation(a, act_type="relu", name="r1")
    r2 = mx.sym.Activation(a, act_type="relu", name="r2")  # duplicate
    y = r1 + r2
    pipe = PassPipeline([CSEPass()], name="t-cse")
    params = {"fc_a_weight": _params()["fc1_weight"],
              "fc_a_bias": _params()["fc1_bias"]}
    out, _ = pipe.run(y, params)
    assert _node_ops(y).count("Activation") == 2
    assert _node_ops(out).count("Activation") == 1
    X = np.random.RandomState(4).rand(8, IN_DIM).astype(np.float32)
    np.testing.assert_allclose(
        _forward(y, params, X, extra_shapes={}),
        _forward(out, params, X, extra_shapes={}), rtol=1e-6)


def test_dce_bypasses_inference_dropout():
    sym = _mlp(dropout=True)
    params = _params()
    pipe = PassPipeline([DeadNodeEliminationPass()], name="t-dce")
    out, _ = pipe.run(sym, params)
    assert "Dropout" in _node_ops(sym)
    assert "Dropout" not in _node_ops(out)
    X = np.random.RandomState(5).rand(8, IN_DIM).astype(np.float32)
    np.testing.assert_allclose(_forward(sym, params, X),
                               _forward(out, params, X), rtol=1e-6)


# ---------------------------------------------------------------------------
# verification: round trips and attr preservation


def test_pipeline_stamps_fingerprint_and_roundtrips():
    sym = _mlp()
    pipe = default_inference_pipeline(name="t-fp")
    out, _ = pipe.run(sym, _params())
    fp = out._graph_attrs["__passes__"]
    assert fp == pipe.fingerprint() and len(fp) == 64
    reloaded = verify_roundtrip(out)
    assert reloaded._graph_attrs["__passes__"] == fp
    # the fingerprint feeds the json, so tojson differs from the raw graph
    assert sym.tojson() != out.tojson()


def test_sharding_attr_survives_every_pass():
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("fc1_weight", attr={"__sharding__": "tp,None"})
    net = mx.sym.FullyConnected(data, weight=w, num_hidden=HIDDEN,
                                name="fc1", attr={"__sharding__": "x"})
    net = mx.sym.Dropout(net, p=0.5, name="drop")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    base = _params()
    params = {"fc1_weight": base["fc1_weight"], "fc1_bias": base["fc1_bias"],
              "fc2_weight": base["fc3_weight"], "fc2_bias": base["fc3_bias"]}
    calib = calibrate_arrays(net, _calib_feeds(), arg_params=params)
    pipe = default_inference_pipeline(
        quantize=QuantizePass(calib=calib, skip_output_layer=True),
        name="t-shard")
    out, _ = pipe.run(net, params)
    attrs = out.attr_dict()
    assert attrs.get("fc1_weight", {}).get("__sharding__") == "tp,None"
    assert attrs.get("fc1", {}).get("__sharding__") == "x"


def test_attr_dropping_pass_fails_loud():
    class DropAttrsPass(Pass):
        name = "drop_attrs"

        def apply(self, sym, params):
            from mxnet_tpu.passes import rebuild
            from mxnet_tpu.symbol import _Node

            def transform(node, new_inputs):
                if node.is_variable:
                    return None
                new = _Node(node.op, node.name, node.params, {},
                            new_inputs, node.is_aux)   # attrs dropped!
                return [(new, i) for i in range(node.num_outputs())]
            return rebuild(sym, transform), params

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=CLASSES, name="fc",
                                attr={"__sharding__": "dp,None"})
    pipe = PassPipeline([DropAttrsPass()], name="t-drop")
    with pytest.raises(PassError) as ei:
        pipe.run(net, None)
    assert "__sharding__" in str(ei.value)
    assert "drop_attrs" in str(ei.value)


# ---------------------------------------------------------------------------
# calibration


def test_calibration_deterministic_for_seeded_sample():
    sym = _mlp()
    params = _params()
    digests = set()
    for _ in range(2):
        t = calibrate_arrays(sym, _calib_feeds(), arg_params=params,
                             mode="percentile", percentile=99.9)
        digests.add(t.digest())
    assert len(digests) == 1
    # a different sample (or mode) must move the digest
    t2 = calibrate_arrays(sym, _calib_feeds(seed=9), arg_params=params,
                          mode="percentile", percentile=99.9)
    t3 = calibrate_arrays(sym, _calib_feeds(), arg_params=params,
                          mode="minmax")
    assert t2.digest() not in digests and t3.digest() not in digests


def test_self_calibration_sees_aux_states():
    """BatchNorm moving stats must reach the calibration executor: the
    serving path hands QuantizePass one MERGED arg+aux blob, and scales
    calibrated on default moving stats would quantize a different
    network than the one served."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=HIDDEN, name="fc1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    base = _params()
    args = {"fc1_weight": base["fc1_weight"], "fc1_bias": base["fc1_bias"],
            "fc2_weight": base["fc3_weight"], "fc2_bias": base["fc3_bias"],
            "bn1_gamma": np.ones(HIDDEN, np.float32),
            "bn1_beta": np.zeros(HIDDEN, np.float32)}
    # trained stats FAR from the (0, 1) defaults
    aux = {"bn1_moving_mean": np.full(HIDDEN, 50.0, np.float32),
           "bn1_moving_var": np.full(HIDDEN, 100.0, np.float32)}
    rng = np.random.RandomState(1)
    arr = rng.rand(16, IN_DIM).astype(np.float32)
    qp = QuantizePass(calib_data=arr,
                      calib_shapes={"data": (8, IN_DIM)})
    qp._ensure_calib(net, {**args, **aux})
    ref = calibrate_arrays(
        net, [{"data": arr[:8]}, {"data": arr[8:]}],
        arg_params=args, aux_params=aux,
        mode=qp.mode, percentile=qp.percentile)
    assert qp.calib.digest() == ref.digest()
    dropped = calibrate_arrays(
        net, [{"data": arr[:8]}, {"data": arr[8:]}],
        arg_params=args, aux_params={},
        mode=qp.mode, percentile=qp.percentile)
    assert qp.calib.digest() != dropped.digest()


def test_fp16_mode_skips_calibration_and_keeps_fingerprint_stable():
    from mxnet_tpu.passes import build_serving_pipeline
    with_cd = build_serving_pipeline(
        quantize="float16", calib_data=np.zeros((8, IN_DIM), np.float32),
        calib_shapes={"data": (8, IN_DIM)})
    without = build_serving_pipeline(quantize="float16")
    q = [p for p in with_cd.passes if p.name == "quantize"][0]
    assert q.calib_data is None          # no wasted self-calibration
    assert with_cd.fingerprint() == without.fingerprint()


def test_calibration_table_json_roundtrip(tmp_path):
    t = calibrate_arrays(_mlp(), _calib_feeds(), arg_params=_params())
    path = str(tmp_path / "calib.json")
    t.save(path)
    t2 = CalibrationTable.load(path)
    assert t2.digest() == t.digest()
    assert t2.scale("fc1_output") == t.scale("fc1_output")


# ---------------------------------------------------------------------------
# quantization: numerics per bucket, fingerprints, hot reload


def _quantized_pair():
    sym = _mlp()
    params = _params()
    calib = calibrate_arrays(sym, _calib_feeds(), arg_params=params)
    pipe = default_inference_pipeline(
        quantize=QuantizePass(calib=calib), name="t-q")
    qsym, qparams = pipe.run(sym, params)
    return sym, params, qsym, qparams, pipe


def test_quantize_rewrites_hidden_keeps_output_layer():
    _sym, _params_, qsym, qparams, _pipe = _quantized_pair()
    ops = _node_ops(qsym)
    assert ops.count("_quantized_FullyConnected") == 2   # fc1, fc2
    assert ops.count("FullyConnected") == 1              # fc3 (logits)
    assert qparams["fc1_weight"].dtype == np.int8
    assert qparams["fc1_weight_wscale"].dtype == np.float32
    assert qparams["fc3_weight"].dtype == np.float32


def test_quantized_output_tolerance_per_bucket():
    sym, params, qsym, qparams, _pipe = _quantized_pair()
    rng = np.random.RandomState(11)
    for bucket in (1, 2, 4, 8):
        X = rng.rand(bucket, IN_DIM).astype(np.float32)
        yf = _forward(sym, params, X)
        yq = _forward(qsym, qparams, X)
        np.testing.assert_allclose(yf, yq, atol=0.02)


def test_fingerprint_separates_quantized_from_f32_and_calibrations():
    sym = _mlp()
    params = _params()
    plain = default_inference_pipeline(name="p")
    q1 = default_inference_pipeline(
        quantize=QuantizePass(calib=calibrate_arrays(
            sym, _calib_feeds(), arg_params=params)), name="q1")
    q2 = default_inference_pipeline(
        quantize=QuantizePass(calib=calibrate_arrays(
            sym, _calib_feeds(seed=9), arg_params=params)), name="q2")
    fps = {plain.fingerprint(), q1.fingerprint(), q2.fingerprint()}
    assert len(fps) == 3


def test_quantize_model_offline_api():
    sym = _mlp()
    params = _params()
    calib_data = np.random.RandomState(1).rand(32, IN_DIM).astype(np.float32)
    qsym, qarg, qaux, pipe = quantize_model(
        sym, params, {}, calib_data=calib_data,
        calib_shapes={"data": (8, IN_DIM)})
    assert qarg["fc1_weight"].dtype == np.int8
    assert not qaux
    assert "_quantized_FullyConnected" in _node_ops(qsym)
    assert pipe.fingerprint() == qsym._graph_attrs["__passes__"]


def test_transform_params_requantizes_fresh_weights():
    _sym, params, _qsym, qparams, pipe = _quantized_pair()
    fresh = pipe.transform_params(
        {k: v * 2.0 if v.ndim == 2 else v for k, v in _params().items()})
    assert fresh["fc1_weight"].dtype == np.int8
    np.testing.assert_allclose(fresh["fc1_weight_wscale"],
                               qparams["fc1_weight_wscale"] * 2.0,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# profiler integration


def test_passes_report_lists_pipeline():
    pipe = default_inference_pipeline(name="t-report")
    pipe.run(_mlp(), _params())
    rep = mx.profiler.passes_report()
    mine = [p for p in rep.values() if p["pipeline"] == "t-report"]
    assert mine and mine[0]["runs"] == 1
    assert set(mine[0]["passes"]) == {"fold_constants", "cse", "dce",
                                      "moe_serve_parity"}
    assert mine[0]["fingerprint"] == pipe.fingerprint()
    assert "t-report" in mx.profiler.passes_report_str()
    assert "passes" in mx.profiler.unified_report()


# ---------------------------------------------------------------------------
# serving integration: buckets, u8 wire, reload, compile guard, cache keys


def _serve_pair(quantize="int8", **kwargs):
    from mxnet_tpu.serve import ServeEngine
    sym = _mlp()
    params = _params()
    calib = np.random.RandomState(1).rand(32, IN_DIM).astype(np.float32)
    shapes = {"data": (1, IN_DIM), "softmax_label": (1,)}
    f32 = ServeEngine(sym, dict(params), shapes, batch_buckets=(1, 2, 4),
                      name="t-f32", **kwargs)
    q = ServeEngine(sym, dict(params), shapes, batch_buckets=(1, 2, 4),
                    name="t-int8", quantize=quantize, calib_data=calib,
                    **kwargs)
    return f32, q, params


def test_quantized_serve_engine_matches_f32():
    f32, q, _params_ = _serve_pair()
    try:
        X = np.random.RandomState(12).rand(16, IN_DIM).astype(np.float32)
        yf = np.stack([f32.predict(x, timeout=60) for x in X])
        yq = np.stack([q.predict(x, timeout=60) for x in X])
        np.testing.assert_allclose(yf, yq, atol=0.02)
        assert q.pipeline is not None
        assert "quantize" in [p.name for p in q.pipeline.passes]
    finally:
        f32.close()
        q.close()


def test_quantized_serve_hot_reload_requantizes():
    f32, q, params = _serve_pair()
    try:
        fresh = _params(seed=42)
        f32.reload(dict(fresh))
        q.reload(dict(fresh))
        X = np.random.RandomState(13).rand(8, IN_DIM).astype(np.float32)
        yf = np.stack([f32.predict(x, timeout=60) for x in X])
        yq = np.stack([q.predict(x, timeout=60) for x in X])
        np.testing.assert_allclose(yf, yq, atol=0.02)
        # the reload really moved the weights
        assert q._predictor._arg_params["fc1_weight"].asnumpy().dtype \
            == np.int8
    finally:
        f32.close()
        q.close()


def test_quantized_serve_steady_loop_zero_compiles():
    from compile_guard import assert_no_compiles
    _f32, q, _params_ = _serve_pair()
    _f32.close()
    try:
        X = np.random.RandomState(14).rand(24, IN_DIM).astype(np.float32)
        for x in X[:4]:                      # touch the grid once
            q.predict(x, timeout=60)
        for fut in q.submit_many(X[:4]):
            fut.result(timeout=60)
        with assert_no_compiles("steady quantized serve loop"):
            for x in X[4:12]:
                q.predict(x, timeout=60)
            for fut in q.submit_many(X[12:]):
                fut.result(timeout=60)
    finally:
        q.close()


def test_u8_wire_serve_matches_host_normalize():
    from mxnet_tpu.serve import ServeEngine
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=4, pad=(1, 1),
                             name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    params = {"c1_weight": (rng.randn(4, 3, 3, 3) * 0.2).astype(np.float32),
              "c1_bias": np.zeros(4, np.float32),
              "fc_weight": (rng.randn(CLASSES, 4 * 8 * 8) * 0.1
                            ).astype(np.float32),
              "fc_bias": np.zeros(CLASSES, np.float32)}
    f32 = ServeEngine(net, dict(params),
                      {"data": (1, 3, 8, 8), "softmax_label": (1,)},
                      batch_buckets=(1, 2), name="t-f32c")
    u8 = ServeEngine(net, dict(params),
                     {"data": (1, 8, 8, 3), "softmax_label": (1,)},
                     batch_buckets=(1, 2), name="t-u8c",
                     u8_wire={"mean": 128.0, "scale": 1 / 128.0})
    try:
        assert u8._data_dtype == np.dtype(np.uint8)
        img = rng.randint(0, 256, (8, 8, 3)).astype(np.uint8)
        host = ((img.astype(np.float32) - 128.0) / 128.0).transpose(2, 0, 1)
        np.testing.assert_array_equal(f32.predict(host, timeout=60),
                                      u8.predict(img, timeout=60))
        # the wire really is 1 byte/px: a u8 item is what submit admits
        assert u8._validate(img).dtype == np.uint8
    finally:
        f32.close()
        u8.close()


def test_quantized_and_f32_programs_share_no_executable(jax_cache_dir):
    """Both grids warm side by side against one persistent cache and
    neither is handed the other's programs: the quantized grid compiles
    its own after the f32 grid filled the cache, and re-warming each from
    a fresh predictor compiles nothing."""
    from compile_guard import count_backend_compiles
    from mxnet_tpu.predictor import Predictor

    sym = _mlp()
    params = _params()
    calib = calibrate_arrays(sym, _calib_feeds(), arg_params=params)

    def mkpipe():
        return default_inference_pipeline(
            quantize=QuantizePass(calib=calib), name="t-cc")

    shapes = [{"data": (b, IN_DIM), "softmax_label": (b,)} for b in (1, 2)]

    def warm(pipeline):
        """(compile requests, compiled) of one fresh predictor's grid;
        building it (the pipeline's eager weight transforms) is not
        counted."""
        p = Predictor(sym.tojson(), dict(params), shapes[0],
                      pipeline=pipeline)
        with count_backend_compiles() as c:
            p.precompile(shapes, threads=1)
        return c.count, c.compiled

    n = len(shapes)
    assert warm(None)[1] >= n             # f32 grid: all compiled
    assert warm(mkpipe())[1] >= n         # quantized grid: its own programs
    assert warm(mkpipe()) == (n, 0)       # the same grid again: all read
    assert warm(None) == (n, 0)           # f32 again: reads its own


# ---------------------------------------------------------------------------
# tools/dump_passes.py


def test_dump_passes_tool(tmp_path):
    sym_path = str(tmp_path / "m-symbol.json")
    _mlp(dropout=True).save(sym_path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "dump_passes.py"),
         sym_path, "--diff"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "dce" in res.stdout and "-1 Dropout" in res.stdout
    assert "pipeline fingerprint:" in res.stdout
    assert "round-trips" in res.stdout
