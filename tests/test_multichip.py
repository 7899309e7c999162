"""First-class multichip: ``Module.fit(mesh=...)`` + GSPMD sharding
constraints on the symbol graph + the tp-sharded ServeEngine.

Acceptance battery (ISSUE 7): an 8-device fit matches the 1-device loss
trajectory; dp=4 x tp=2 with per-layer specs trains params ACTUALLY
sharded on device; the generalized MXNET_SHARD_WEIGHT_UPDATE shards the
optimizer state over the dp axis of arbitrary meshes; superstep /
prefetch / checkpoint compose with the mesh unchanged; the steady loop
never recompiles; a tp-sharded ServeEngine serves the bucket grid with
output parity and survives hot reload.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
from jax.sharding import PartitionSpec as P               # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.base import MXNetError                     # noqa: E402
from compile_guard import assert_no_compiles              # noqa: E402


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc1"),
        act_type="relu")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=2, name="fc2"), name="softmax")


def _data(batch_size=16):
    rng = np.random.RandomState(0)
    X = rng.randn(64, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch_size)


def _fit(mesh=None, sharding=None, num_epoch=2, superstep=None,
         prefetch=False, symbol=None, **kwargs):
    mx.random.seed(7)
    mod = mx.mod.Module(symbol if symbol is not None else _mlp(),
                        context=mx.cpu(0))
    mod.fit(_data(), num_epoch=num_epoch,
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            mesh=mesh, sharding=sharding, superstep=superstep,
            prefetch_to_device=prefetch, **kwargs)
    return mod, {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


# -- fit(mesh=...) trajectory parity ----------------------------------------

def test_dp8_fit_matches_single_device():
    """The headline acceptance: an 8-device Module.fit(mesh=...) run
    matches the 1-device fit loss trajectory (same data, same seed)."""
    _, p1 = _fit()
    m8, p8 = _fit(mesh=[("dp", 8)])
    assert m8._fused is not None and m8._fused.named_mesh
    for k in p1:
        assert np.abs(p1[k] - p8[k]).max() < 1e-4, k


def test_dp4_tp2_with_specs_matches_and_shards():
    mt, pt = _fit(mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P("tp", None),
                            "fc1_bias": P("tp")})
    _, p1 = _fit()
    for k in p1:
        assert np.abs(p1[k] - pt[k]).max() < 1e-4, k
    # the constraint is real, not advisory: the live device state keeps
    # the tensor-parallel layout at rest
    w = mt._fused_state["params"]["fc1_weight"]
    assert tuple(w.sharding.spec)[:1] == ("tp",)
    assert not w.is_fully_replicated
    assert dict(w.sharding.mesh.shape) == {"dp": 4, "tp": 2}


def test_mesh_string_and_env_knob(monkeypatch):
    _, p1 = _fit()
    _, pa = _fit(mesh="dp=4,tp=2")
    monkeypatch.setenv("MXNET_MESH", "dp=8")
    mb, pb = _fit()
    assert dict(mb._fused.mesh.shape) == {"dp": 8}
    for k in p1:
        assert np.abs(p1[k] - pa[k]).max() < 1e-4, k
        assert np.abs(p1[k] - pb[k]).max() < 1e-4, k


def test_sharding_via_symbol_attr():
    data = mx.sym.Variable("data")
    w = mx.sym.Variable("fc1_weight", attr={"__sharding__": "tp,None"})
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, weight=w, num_hidden=8, name="fc1"),
        act_type="relu")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=2, name="fc2"), name="softmax")
    mt, pt = _fit(mesh=[("dp", 4), ("tp", 2)], symbol=net)
    assert tuple(mt._fused.param_specs["fc1_weight"])[:1] == ("tp",)
    assert not mt._fused_state["params"]["fc1_weight"].is_fully_replicated
    _, p1 = _fit()
    for k in p1:
        assert np.abs(p1[k] - pt[k]).max() < 1e-4, k


def test_shard_weight_update_generalizes_to_mesh(monkeypatch):
    """MXNET_SHARD_WEIGHT_UPDATE on a dp x tp mesh: optimizer state
    shards over the dp AXIS (for unspecced params whose dim0 divides)
    and stays tp-sharded for specced params — trajectory unchanged."""
    _, p1 = _fit()
    monkeypatch.setenv("MXNET_SHARD_WEIGHT_UPDATE", "1")
    mt, pt = _fit(mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P("tp", None)})
    for k in p1:
        assert np.abs(p1[k] - pt[k]).max() < 1e-4, k
    assert mt._fused.shard_update
    # fc1_bias (8,) unspecced: 8 % dp(4) == 0 -> momentum sharded over dp
    mom_bias = jax.tree_util.tree_leaves(
        mt._fused_state["opt"]["fc1_bias"])[0]
    assert "dp" in str(mom_bias.sharding.spec)
    # fc1_weight momentum keeps the tp layout
    mom_w = jax.tree_util.tree_leaves(
        mt._fused_state["opt"]["fc1_weight"])[0]
    assert "tp" in str(mom_w.sharding.spec)


# -- composition -------------------------------------------------------------

def test_superstep_composes_with_mesh():
    _, pk1 = _fit(mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P(None, "tp")})
    _, pk4 = _fit(mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P(None, "tp")}, superstep=4)
    for k in pk1:
        assert np.abs(pk1[k] - pk4[k]).max() < 1e-6, k


def test_prefetch_to_device_composes_with_mesh():
    _, pp = _fit(mesh=[("dp", 8)], prefetch=True)
    _, p1 = _fit()
    for k in p1:
        assert np.abs(p1[k] - pp[k]).max() < 1e-4, k


def test_prefetch_superstep_mesh_all_compose():
    _, pa = _fit(mesh=[("dp", 4), ("tp", 2)], superstep=2, prefetch=True)
    _, p1 = _fit()
    for k in p1:
        assert np.abs(p1[k] - pa[k]).max() < 1e-4, k


def test_score_on_mesh_matches():
    m1, _ = _fit()
    m8, _ = _fit(mesh=[("dp", 8)])
    r1 = dict(m1.score(_data(), "acc"))
    r8 = dict(m8.score(_data(), "acc"))
    assert abs(r1["accuracy"] - r8["accuracy"]) < 1e-6


def test_checkpoint_resume_onto_different_mesh(tmp_path):
    """Save mid-training under dp=4 x tp=2, resume under dp=8: shards
    land on the new mesh via restore(like=) and the final params match
    an uninterrupted dp=8 run."""
    ck = str(tmp_path / "ck")
    sharding = {"fc1_weight": P(None, "tp")}
    # uninterrupted reference on dp=8
    _, ref = _fit(mesh=[("dp", 8)], num_epoch=2)
    # epoch 0 under dp=4 x tp=2, checkpointed
    _fit(mesh=[("dp", 4), ("tp", 2)], sharding=sharding, num_epoch=1,
         checkpoint=ck)
    # resume epoch 1 under dp=8 (no specs)
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    mod.fit(_data(), num_epoch=2,
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            mesh=[("dp", 8)], checkpoint=ck, resume=True)
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in ref:
        assert np.abs(ref[k] - got[k]).max() < 1e-4, k


# -- steady-state compile guard ----------------------------------------------

def test_mesh_fit_steady_loop_no_compiles():
    """Zero steady-loop recompiles under the mesh path: after epoch 0
    built every program, a whole further fit epoch compiles nothing."""
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    kwargs = dict(optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
                  mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P(None, "tp")})
    mod.fit(_data(), num_epoch=1, **kwargs)
    with assert_no_compiles("mesh fit steady loop"):
        mod.fit(_data(), begin_epoch=1, num_epoch=2, **kwargs)


# -- refusals ----------------------------------------------------------------

def test_indivisible_batch_refused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with pytest.raises(MXNetError, match="not divisible"):
        mod.fit(_data(batch_size=12), num_epoch=1, mesh=[("dp", 8)])


def test_unknown_spec_name_refused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with pytest.raises(MXNetError, match="no bound parameter"):
        mod.fit(_data(), num_epoch=1, mesh=[("dp", 8)],
                sharding={"fc9_weight": P("dp")})


def test_unknown_spec_axis_refused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with pytest.raises(MXNetError, match="axes"):
        mod.fit(_data(), num_epoch=1, mesh=[("dp", 8)],
                sharding={"fc1_weight": P("tp", None)})


def test_indivisible_param_dim_refused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    # fc2_weight is (2, 8): dim0=2 does not divide tp-size 4
    with pytest.raises(MXNetError, match="divisible"):
        mod.fit(_data(), num_epoch=1, mesh=[("dp", 2), ("tp", 4)],
                sharding={"fc2_weight": P("tp", None)})


def test_mesh_without_dp_axis_refused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with pytest.raises(MXNetError, match="dp"):
        mod.fit(_data(), num_epoch=1, mesh=[("tp", 8)])


def test_mesh_with_monitor_refused():
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    mon = mx.monitor.Monitor(1)
    with pytest.raises(MXNetError, match="fused train step"):
        mod.fit(_data(), num_epoch=1, mesh=[("dp", 8)], monitor=mon)


def test_mesh_with_fused_off_refused(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_TRAIN", "0")
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with pytest.raises(MXNetError, match="fused train step"):
        mod.fit(_data(), num_epoch=1, mesh=[("dp", 8)])


# -- multichip profiler report -----------------------------------------------

def test_multichip_report_structure():
    mod, _ = _fit(mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P(None, "tp")})
    # populate the cost side the way bench does: AOT the live step
    f = mod._fused
    rng = np.random.RandomState(0)
    X = rng.randn(16, 6).astype(np.float32)
    y = np.zeros(16, np.float32)
    staged = mx.io.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    f.aot_compile(mod._fused_state, f.make_batch(staged), mod._fused_key)
    reports = mx.profiler.multichip_report(peak_tflops=1.0, ici_gbps=10.0)
    mine = [r for r in reports.values()
            if r["mesh"] == {"dp": 4, "tp": 2}]
    assert mine, reports.keys()
    r = mine[-1]
    assert r["devices"] == 8 and r["steps"] > 0
    assert r["per_axis"]["dp"]["batch_sharded"]
    assert r["per_axis"]["tp"]["param_sharded"]
    assert r["flops_per_step"] > 0
    # the partitioner inserted real collectives for this mesh
    assert r["collectives"]["total_count"] > 0
    assert r["collectives"]["total_bytes"] > 0
    assert 0.0 <= r["collective_frac_est"] <= 1.0
    txt = mx.profiler.multichip_report_str()
    assert "dp=4 x tp=2" in txt and "collectives/step" in txt


def test_multichip_crosslink_from_superstep_report():
    # the superstep registry holds its modules weakly: keep this one
    mod, _ = _fit(mesh=[("dp", 8)], superstep=2)
    assert "multichip_report_str" in mx.profiler.superstep_report_str()
    assert mod._fused is not None


# -- tp-sharded ServeEngine --------------------------------------------------

def _serve_pair(tmp_path):
    mod, _ = _fit(num_epoch=1)
    arg, aux = mod.get_params()
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 0, _mlp(), arg, aux)
    return prefix


SERVE_SHAPES = {"data": (1, 6), "softmax_label": (1,)}


def test_serve_tp_parity_and_reload(tmp_path):
    prefix = _serve_pair(tmp_path)
    rng = np.random.RandomState(1)
    xs = rng.randn(10, 6).astype(np.float32)
    with mx.serve.ServeEngine.from_checkpoint(
            prefix, 0, input_shapes=SERVE_SHAPES,
            batch_buckets=(1, 2, 4)) as ref, \
         mx.serve.ServeEngine.from_checkpoint(
            prefix, 0, input_shapes=SERVE_SHAPES, batch_buckets=(1, 2, 4),
            mesh="tp=2", param_specs={"fc1_weight": P("tp", None),
                                      "fc1_bias": P("tp")},
            name="serve_tp") as eng:
        # weights live sharded across 2 devices
        w = eng._predictor._exec.arg_dict["fc1_weight"]._get()
        assert len(w.devices()) == 2 and not w.is_fully_replicated
        want = [ref.predict(x) for x in xs]
        got = [eng.predict(x) for x in xs]
        for a, b in zip(want, got):
            assert np.abs(a - b).max() < 1e-5
        # hot reload mid-serve keeps the shard layout and the outputs
        version = eng.reload_from_checkpoint(prefix, 0)
        assert version == 1
        w2 = eng._predictor._exec.arg_dict["fc1_weight"]._get()
        assert not w2.is_fully_replicated
        got2 = [eng.predict(x) for x in xs]
        for a, b in zip(want, got2):
            assert np.abs(a - b).max() < 1e-5


def test_serve_dp_mesh_batches_shard(tmp_path):
    prefix = _serve_pair(tmp_path)
    rng = np.random.RandomState(2)
    xs = rng.randn(8, 6).astype(np.float32)
    with mx.serve.ServeEngine.from_checkpoint(
            prefix, 0, input_shapes=SERVE_SHAPES, batch_buckets=(1, 2, 4),
            mesh="dp=2,tp=2", param_specs={"fc1_weight": P(None, "tp")},
            name="serve_dptp") as eng, \
         mx.serve.ServeEngine.from_checkpoint(
            prefix, 0, input_shapes=SERVE_SHAPES,
            batch_buckets=(1, 2, 4)) as ref:
        futs = eng.submit_many(xs)
        want = [ref.predict(x) for x in xs]
        for f, w in zip(futs, want):
            assert np.abs(f.result(timeout=30) - w).max() < 1e-5


def test_serve_tp_steady_loop_no_compiles(tmp_path):
    prefix = _serve_pair(tmp_path)
    rng = np.random.RandomState(3)
    xs = rng.randn(16, 6).astype(np.float32)
    with mx.serve.ServeEngine.from_checkpoint(
            prefix, 0, input_shapes=SERVE_SHAPES, batch_buckets=(1, 2, 4),
            mesh="tp=2", param_specs={"fc1_weight": P("tp", None)},
            name="serve_tp_guard") as eng:
        for x in xs[:4]:       # touch several buckets once
            eng.predict(x)
        list(f.result(timeout=30) for f in eng.submit_many(xs[:4]))
        with assert_no_compiles("tp-sharded serving loop"):
            for f in eng.submit_many(xs):
                f.result(timeout=30)


def test_serve_param_specs_without_mesh_refused(tmp_path):
    prefix = _serve_pair(tmp_path)
    with pytest.raises(mx.serve.ServeError, match="mesh"):
        mx.serve.ServeEngine.from_checkpoint(
            prefix, 0, input_shapes=SERVE_SHAPES,
            param_specs={"fc1_weight": P("tp", None)})


def test_executor_set_mesh_training_refused():
    net = _mlp()
    it = _data()
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(it.provide_data, it.provide_label, for_training=True)
    mod.init_params()
    from mxnet_tpu.parallel import make_mesh
    with pytest.raises(MXNetError, match="inference-only"):
        mod._exec_group.execs[0].set_mesh(make_mesh([("tp", 2)]))


# -- host param gather -------------------------------------------------------

def test_get_params_gathers_sharded_state():
    mt, pt = _fit(mesh=[("dp", 4), ("tp", 2)],
                  sharding={"fc1_weight": P("tp", None)})
    # the host dict must hold the FULL weight, not shard 0
    assert pt["fc1_weight"].shape == (8, 6)
    dev = np.asarray(mt._fused_state["params"]["fc1_weight"])
    assert np.array_equal(pt["fc1_weight"], dev)


def test_shard_update_with_dp_spec_no_duplicate_axis(monkeypatch):
    """A declared spec that already spends 'dp' on a non-leading dim
    must not get a second 'dp' from the sharded weight update (a
    duplicate-axis PartitionSpec crashes deep in the opt init)."""
    monkeypatch.setenv("MXNET_SHARD_WEIGHT_UPDATE", "1")
    # fc1_weight is (8, 6): dp=2 divides BOTH dims, so without the
    # guard the update spec would become the invalid P('dp', 'dp')
    mt, pt = _fit(mesh=[("dp", 2), ("tp", 2)],
                  sharding={"fc1_weight": P(None, "dp")})
    _, p1 = _fit()
    for k in p1:
        assert np.abs(p1[k] - pt[k]).max() < 1e-4, k


def test_set_mesh_mid_training_carries_optimizer_state():
    """Re-meshing between epochs must carry momentum/Adam slots into
    the new layout, not silently zero them: dp=8 epoch 0 then
    dp=4 x tp=2 epoch 1 matches an uninterrupted 1-device run."""
    _, ref = _fit(num_epoch=2)
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    opt_params = {"learning_rate": 0.5, "momentum": 0.9}
    mod.fit(_data(), num_epoch=1, optimizer_params=opt_params,
            mesh=[("dp", 8)])
    t_before = mod._fused_t
    mod.set_mesh([("dp", 4), ("tp", 2)],
                 sharding={"fc1_weight": P(None, "tp")})
    assert mod._fused_t == t_before     # step counter carried
    mom = jax.tree_util.tree_leaves(mod._fused_state["opt"]["fc1_weight"])
    assert mom and float(np.abs(np.asarray(mom[0])).max()) > 0, \
        "momentum zeroed by the re-mesh"
    mod.fit(_data(), begin_epoch=1, num_epoch=2,
            optimizer_params=opt_params)
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for k in ref:
        assert np.abs(ref[k] - got[k]).max() < 1e-4, k


def test_parse_hlo_collectives_async_start_tuples():
    """TPU backends emit async (-start/-done) collectives whose -start
    result tuple aliases the operand: only the result half may count,
    and the -done halves not at all (else bytes double)."""
    txt = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1}}
  %ags = (f32[256]{0}, f32[1024]{0}) all-gather-start(f32[256]{0} %y)
  %agd = f32[1024]{0} all-gather-done((f32[256]{0}, f32[1024]{0}) %ags)
"""
    c = mx.profiler.parse_hlo_collectives(txt)
    assert c["all-reduce"] == {"count": 1, "bytes": 4096}
    assert c["all-gather"] == {"count": 1, "bytes": 4096}, c["all-gather"]
    assert c["total_count"] == 2
    assert c["total_bytes"] == 8192


def test_parse_hlo_collectives_permute_context_scalars():
    """collective-permute-start tuples carry u32 context scalars; the
    payload must be the data element, not the scalars."""
    txt = "%cps = (f32[8]{0}, f32[8]{0}, u32[], u32[]) " \
          "collective-permute-start(f32[8]{0} %x)"
    c = mx.profiler.parse_hlo_collectives(txt)
    assert c["collective-permute"] == {"count": 1, "bytes": 32}
