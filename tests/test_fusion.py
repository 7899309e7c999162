"""mxnet_tpu.passes.fuse + ops.fused: operator fusion (tier-1, CPU).

ISSUE 11 contracts: golden-graph structure + numerical parity for every
fusion rewrite (f32 BITWISE — fusion reorders no math; int8 within the
calibrated tolerance the unfused quantized graph already meets);
single-consumer / non-head safety rules; ``__sharding__`` attr survival;
the pass-ordering footgun raising a loud PassError with the corrected
order; fused-vs-unfused compile-cache key disjointness; zero XLA
compiles in the steady fused serve loop; the Pallas epilogue kernel's
interpret-mode parity; and tools/dump_passes.py rendering the
``_fused_*`` census with ``--diff`` shrinkage and stage dumps.
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
from mxnet_tpu.passes import (ElementwiseFusePass, FuseEpiloguePass,
                              PassError, PassPipeline, QuantizePass,
                              build_serving_pipeline, calibrate_arrays,
                              default_inference_pipeline)

IN_DIM = 16
HIDDEN = 32
CLASSES = 4


def _node_ops(sym):
    return [n["op"] for n in json.loads(sym.tojson())["nodes"]]


def _mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=HIDDEN, name="fc2")
    net = mx.sym.Activation(net, act_type="tanh", name="tanh2")
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


def _forward(sym, params, X, extra_shapes=None):
    shapes = {"data": tuple(X.shape)}
    shapes.update({"softmax_label": (X.shape[0],)}
                  if extra_shapes is None else extra_shapes)
    exe = sym.simple_bind(mx.cpu(), grad_req="null", **shapes)
    exe.copy_params_from(params, {}, allow_extra_params=True)
    exe.arg_dict["data"][:] = np.asarray(X, exe.arg_dict["data"].dtype)
    return np.asarray(exe.forward(is_train=False)[0]._get())


def _calib_feeds(n=4, batch=8, seed=1):
    rng = np.random.RandomState(seed)
    return [{"data": rng.rand(batch, IN_DIM).astype(np.float32)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# epilogue fusion: golden graphs + parity


def test_fc_act_fusion_golden_and_bitwise():
    sym = _mlp()
    params = _params()
    p = FuseEpiloguePass()
    pipe = PassPipeline([p], name="t-fuse")
    out, _ = pipe.run(sym, params)
    ops = _node_ops(out)
    # fc1+relu1 and fc2+tanh2 fuse; fc3 (no activation) stays
    assert ops.count("_fused_FullyConnected") == 2
    assert ops.count("FullyConnected") == 1
    assert ops.count("Activation") == 0
    assert p.summary["rewrites"] == 2
    assert set(p.summary["act_fused"]) == {"relu1", "tanh2"}
    # fusion reorders no math: f32 parity is BITWISE
    X = np.random.RandomState(2).rand(8, IN_DIM).astype(np.float32)
    np.testing.assert_array_equal(_forward(sym, params, X),
                                  _forward(out, params, X))
    # the fused node carries the epilogue's name: outputs unchanged
    assert out.list_outputs() == sym.list_outputs()


def test_conv_act_fusion_golden_and_bitwise():
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=4, pad=(1, 1),
                             name="c1")
    net = mx.sym.Activation(net, act_type="relu", name="cr1")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    params = {"c1_weight": (rng.randn(4, 3, 3, 3) * 0.2).astype(np.float32),
              "c1_bias": (rng.randn(4) * 0.1).astype(np.float32),
              "fc_weight": (rng.randn(CLASSES, 4 * 8 * 8) * 0.1
                            ).astype(np.float32),
              "fc_bias": np.zeros(CLASSES, np.float32)}
    out, _ = PassPipeline([FuseEpiloguePass()], name="t-conv").run(net,
                                                                   params)
    ops = _node_ops(out)
    assert ops.count("_fused_Convolution") == 1
    assert ops.count("Convolution") == 0
    X = rng.rand(2, 3, 8, 8).astype(np.float32)
    np.testing.assert_array_equal(_forward(net, params, X),
                                  _forward(out, params, X))


def test_shared_producer_not_fused():
    """An FC whose output feeds the activation AND something else must
    not fuse: fusing would duplicate the GEMM (or change semantics)."""
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=HIDDEN, name="fc_s")
    act = mx.sym.Activation(fc, act_type="relu", name="r_s")
    y = act + fc                     # second consumer of fc
    p = FuseEpiloguePass()
    out, _ = PassPipeline([p], name="t-shared").run(y, None)
    ops = _node_ops(out)
    assert ops.count("_fused_FullyConnected") == 0
    assert ops.count("FullyConnected") == 1
    assert p.summary["rewrites"] == 0


def test_head_producer_not_fused():
    """An FC that is itself a graph output must survive fusion — its
    output is part of the external contract."""
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=HIDDEN, name="fc_h")
    act = mx.sym.Activation(fc, act_type="relu", name="r_h")
    grouped = mx.sym.Group([fc, act])
    out, _ = PassPipeline([FuseEpiloguePass()], name="t-head").run(grouped,
                                                                   None)
    ops = _node_ops(out)
    assert ops.count("FullyConnected") == 1
    assert ops.count("_fused_FullyConnected") == 0
    assert out.list_outputs() == grouped.list_outputs()


def test_quantized_epilogue_fusion_golden_and_tolerance():
    """After QuantizePass the hidden layers are _quantized_FC -> Act ->
    _contrib_quantize chains; fusion collapses each into ONE
    _fused_quantized_FullyConnected whose out_scale absorbs the q node
    (int8 out), bitwise-identical to the unfused quantized graph and
    within the calibrated tolerance of f32."""
    sym = _mlp()
    params = _params()
    calib = calibrate_arrays(sym, _calib_feeds(), arg_params=params)
    plain = default_inference_pipeline(
        quantize=QuantizePass(calib=calib), name="t-q-plain")
    fused = default_inference_pipeline(
        quantize=QuantizePass(calib=calib), fuse=True, name="t-q-fuse")
    qsym, qparams = plain.run(sym, params)
    fsym, fparams = fused.run(sym, params)
    qops, fops = _node_ops(qsym), _node_ops(fsym)
    assert qops.count("_quantized_FullyConnected") == 2
    assert fops.count("_fused_quantized_FullyConnected") == 2
    assert fops.count("_quantized_FullyConnected") == 0
    assert fops.count("Activation") == 0
    # the q node feeding fc2's data was absorbed into fc1's epilogue
    assert fops.count("_contrib_quantize") \
        == qops.count("_contrib_quantize") - 1
    # the absorbed epilogue carries the SAME scale the q node had
    fdoc = json.loads(fsym.tojson())
    out_scales = [float(n["param"]["out_scale"])
                  for n in fdoc["nodes"]
                  if n["op"] == "_fused_quantized_FullyConnected"
                  and "out_scale" in n.get("param", {})]
    assert len(out_scales) == 1 and out_scales[0] > 0
    X = np.random.RandomState(7).rand(8, IN_DIM).astype(np.float32)
    yq = _forward(qsym, qparams, X)
    yf = _forward(fsym, fparams, X)
    np.testing.assert_array_equal(yq, yf)          # same math, same order
    np.testing.assert_allclose(_forward(sym, params, X), yf, atol=0.02)


# ---------------------------------------------------------------------------
# elementwise chains


def test_elemwise_chain_fused_golden_and_bitwise():
    data = mx.sym.Variable("data")
    y = (data * 2.0) + 3.0
    y = mx.sym.exp(y, name="e1")
    y = mx.sym.FullyConnected(y, num_hidden=CLASSES, name="fc")
    p = ElementwiseFusePass()
    out, _ = PassPipeline([p], name="t-chain").run(y, None)
    ops = _node_ops(out)
    assert ops.count("_fused_elemwise") == 1
    assert not any(o.endswith("_scalar") for o in ops)
    assert "exp" not in ops
    assert p.summary["steps_fused"] == 3
    params = {"fc_weight": _params()["fc3_weight"][:, :IN_DIM],
              "fc_bias": np.zeros(CLASSES, np.float32)}
    X = np.random.RandomState(3).rand(8, IN_DIM).astype(np.float32)
    np.testing.assert_array_equal(
        _forward(y, params, X, extra_shapes={}),
        _forward(out, params, X, extra_shapes={}))


def test_elemwise_chain_stops_at_multi_consumer():
    """An interior node with a second consumer breaks the chain — its
    value is needed elsewhere, so it must stay materialized."""
    data = mx.sym.Variable("data")
    a = data * 2.0                     # 2 consumers: chain must not eat it
    b = mx.sym.exp(a + 1.0, name="e")
    y = b + a
    p = ElementwiseFusePass()
    out, _ = PassPipeline([p], name="t-multi").run(y, None)
    ops = _node_ops(out)
    assert ops.count("_mul_scalar") == 1           # survives un-fused
    assert ops.count("_fused_elemwise") == 1       # (+1.0, exp) chain
    X = np.random.RandomState(4).rand(4, IN_DIM).astype(np.float32)
    np.testing.assert_array_equal(
        _forward(y, {}, X, extra_shapes={}),
        _forward(out, {}, X, extra_shapes={}))


def test_u8_wire_prologue_chain_fuses_and_stays_bitwise():
    """The u8 wire's cast -> -mean -> *scale prologue: the scalar pair
    fuses into one _fused_elemwise and the served math is unchanged."""
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    params = {"fc_weight": _params()["fc3_weight"][:, :IN_DIM],
              "fc_bias": np.zeros(CLASSES, np.float32)}
    mk = lambda fuse: build_serving_pipeline(
        u8_wire={"mean": 128.0, "scale": 1 / 128.0, "hwc": False},
        fuse=fuse, name="t-u8f%s" % fuse)
    plain_sym, _ = mk(False).run(net, dict(params))
    fused_sym, _ = mk(True).run(net, dict(params))
    assert "_fused_elemwise" in _node_ops(fused_sym)
    X = np.random.RandomState(5).randint(
        0, 256, (4, IN_DIM)).astype(np.uint8)
    np.testing.assert_array_equal(_forward(plain_sym, params, X),
                                  _forward(fused_sym, params, X))


# ---------------------------------------------------------------------------
# safety: attrs, ordering, env knob


def test_sharding_attr_survives_fusion():
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("fcs_weight", attr={"__sharding__": "tp,None"})
    net = mx.sym.FullyConnected(data, weight=w, num_hidden=HIDDEN,
                                name="fcs", attr={"__sharding__": "x"})
    net = mx.sym.Activation(net, act_type="relu", name="rs")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    out, _ = PassPipeline([FuseEpiloguePass(), ElementwiseFusePass()],
                          name="t-attr").run(net, None)
    attrs = out.attr_dict()
    assert attrs.get("fcs_weight", {}).get("__sharding__") == "tp,None"
    # the fused node (named after the epilogue) inherits the producer's
    # attrs — the cross-layer contract rides along
    assert attrs.get("rs", {}).get("__sharding__") == "x"


def test_pass_ordering_footgun_raises_with_corrected_order():
    """Fusion before quantization silently defeats int8 epilogue fusion
    (quantize skips _fused_* nodes) — the pipeline refuses it LOUDLY and
    names the corrected order."""
    sym = _mlp()
    params = _params()
    calib = calibrate_arrays(sym, _calib_feeds(), arg_params=params)
    with pytest.raises(PassError) as ei:
        PassPipeline([FuseEpiloguePass(), QuantizePass(calib=calib)],
                     name="t-bad")
    msg = str(ei.value)
    assert "fuse_epilogue" in msg and "quantize" in msg
    assert "Corrected order" in msg
    assert msg.index("'quantize'", msg.index("Corrected order")) \
        < msg.index("'fuse_epilogue'", msg.index("Corrected order"))
    # elemwise_fuse before fuse_epilogue is the same class of bug
    with pytest.raises(PassError):
        PassPipeline([ElementwiseFusePass(), FuseEpiloguePass()],
                     name="t-bad2")
    # the canonical order is what default_inference_pipeline builds
    good = default_inference_pipeline(
        quantize=QuantizePass(calib=calib), fuse=True, name="t-good")
    assert [p.name for p in good.canonical_order()] \
        == [p.name for p in good.passes]


def test_fuse_env_knob(monkeypatch):
    monkeypatch.setenv("MXNET_FUSE", "0")
    off = build_serving_pipeline(name="t-envoff")
    assert "fuse_epilogue" not in [p.name for p in off.passes]
    monkeypatch.delenv("MXNET_FUSE")
    on = build_serving_pipeline(name="t-envon")
    assert [p.name for p in on.passes][-2:] == ["fuse_epilogue",
                                                "elemwise_fuse"]
    # fingerprints must differ: fused programs can never alias unfused
    assert off.fingerprint() != on.fingerprint()


# ---------------------------------------------------------------------------
# compile-cache keys + steady serve loop


def test_fused_and_unfused_programs_share_executables(jax_cache_dir):
    """Two halves.  (1) The graphs are told apart: the fused graph
    carries its own ``__passes__`` fingerprint.  (2) f32 fusion is
    EXACT — same jnp calls, same order — so both variants lower to the
    same StableHLO and JAX's persistent cache, which goes by the lowered
    program, holds ONE executable for both: warming the fused grid after
    the unfused one compiles nothing on the backend.  (Quantized fused
    programs lower differently and share nothing — the quantize-vs-f32
    test in test_passes.py covers that axis.)"""
    from compile_guard import count_backend_compiles
    from mxnet_tpu.predictor import Predictor

    sym = _mlp()
    params = _params()
    shapes = [{"data": (b, IN_DIM), "softmax_label": (b,)} for b in (1, 2)]

    def predictor(fuse):
        return Predictor(sym.tojson(), dict(params), shapes[0],
                         pipeline=build_serving_pipeline(
                             fuse=fuse, name="t-cc%s" % fuse))

    # (1) the graphs say which pipeline made them
    pu, pf = predictor(False), predictor(True)
    assert pu.symbol._graph_attrs["__passes__"] \
        != pf.symbol._graph_attrs["__passes__"]

    def warm(fuse):
        """(compile requests, compiled) of one fresh predictor's grid;
        building the predictor (eager casts) is not counted."""
        p = predictor(fuse)
        with count_backend_compiles() as c:
            p.precompile(shapes, threads=1)
        return c.count, c.compiled

    n = len(shapes)
    asked, compiled = warm(False)
    assert compiled >= n                  # the grid's programs, compiled
    # (2) fused grid: identical lowered programs -> the cache's
    # executables, zero new compiles
    assert warm(True) == (n, 0)
    assert warm(True) == (n, 0)           # warm again


def test_fused_serve_steady_loop_zero_compiles():
    from compile_guard import assert_no_compiles
    from mxnet_tpu.serve import ServeEngine
    eng = ServeEngine(_mlp(), _params(),
                      {"data": (1, IN_DIM), "softmax_label": (1,)},
                      batch_buckets=(1, 2, 4), name="t-fuse-serve",
                      fuse=True)
    try:
        assert "fuse_epilogue" in [p.name for p in eng.pipeline.passes]
        X = np.random.RandomState(14).rand(16, IN_DIM).astype(np.float32)
        for x in X[:4]:                      # touch the grid once
            eng.predict(x, timeout=60)
        for fut in eng.submit_many(X[:4]):
            fut.result(timeout=60)
        with assert_no_compiles("steady fused serve loop"):
            for x in X[4:10]:
                eng.predict(x, timeout=60)
            for fut in eng.submit_many(X[10:]):
                fut.result(timeout=60)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Pallas epilogue kernel


def test_pallas_fc_epilogue_interpret_parity():
    from mxnet_tpu.ops.pallas_kernels import HAS_PALLAS, fused_fc_epilogue
    if not HAS_PALLAS:
        pytest.skip("pallas unavailable")
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    w = jnp.asarray(rng.randn(256, 128).astype(np.float32))
    b = jnp.asarray(rng.randn(256).astype(np.float32))
    ref = np.asarray(x) @ np.asarray(w).T + np.asarray(b)
    out = fused_fc_epilogue(x, w, b, "relu", interpret=True)
    assert np.allclose(np.asarray(out), np.maximum(ref, 0), atol=2e-5)
    scale = 0.05
    outq = fused_fc_epilogue(x, w, b, "relu", out_scale=scale,
                             interpret=True)
    refq = np.clip(np.round(np.maximum(ref, 0) / scale), -127, 127)
    assert outq.dtype == jnp.int8
    # interpret-mode matmul rounds differently at the last ulp; only
    # boundary values may flip by one quantization step
    assert np.abs(np.asarray(outq).astype(np.int32)
                  - refq.astype(np.int32)).max() <= 1


def test_pallas_fc_epilogue_cpu_lowers_the_dense_body():
    """Lowered for the CPU, the hook runs the caller's jnp body — no
    Mosaic call in the program, bitwise the body's result — so CPU
    tier-1 numerics stay the unfused graph's.  (The choice is made per
    lowering platform, not from the process's default backend.)"""
    from mxnet_tpu.ops.pallas_kernels import fused_fc_epilogue
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    w = jnp.asarray(rng.randn(128, 128).astype(np.float32))

    def body(x, w, b):
        return jax.nn.relu(jnp.dot(x, w.T))

    def hooked(x, w):
        return fused_fc_epilogue(x, w, None, "relu", dense=body)

    cpu = jax.local_devices(backend="cpu")[0]
    x, w = jax.device_put(x, cpu), jax.device_put(w, cpu)
    # lint: allow(raw-jit) — one-off lowering inspection
    lowered = jax.jit(hooked).lower(x, w)
    assert "tpu_custom_call" not in lowered.as_text()
    assert np.array_equal(np.asarray(hooked(x, w)),
                          np.asarray(body(x, w, None)))
    # the same trace lowered for a TPU carries the Mosaic kernel
    # lint: allow(raw-jit) — one-off lowering inspection
    tpu = jax.jit(hooked).trace(x, w).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in tpu.as_text()


# ---------------------------------------------------------------------------
# tools/dump_passes.py renders the fused census + stage dumps


def test_dump_passes_shows_fusion_and_stage_dumps(tmp_path):
    sym_path = str(tmp_path / "m-symbol.json")
    _mlp().save(sym_path)
    prefix = str(tmp_path / "stage")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "dump_passes.py"),
         sym_path, "--diff", "--out-prefix", prefix],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "fuse_epilogue" in res.stdout
    assert "+2 _fused_FullyConnected" in res.stdout     # census delta
    assert "-2 Activation" in res.stdout                # shrinkage
    stage_files = sorted(os.listdir(str(tmp_path)))
    assert any("fuse_epilogue" in f for f in stage_files)
    # every stage dump is a loadable symbol
    from mxnet_tpu.symbol import load_json
    for f in stage_files:
        if f.startswith("stage."):
            with open(str(tmp_path / f)) as fh:
                load_json(fh.read())
