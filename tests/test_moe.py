"""mxnet_tpu.moe: top-k routed Mixture-of-Experts (ISSUE 19, tier-1).

Acceptance battery:

* routed forward at capacity=INF is BITWISE identical to the dense
  gather reference (every token through every expert, same einsum
  shapes, same k-term weighted sum); with capacity_factor <= 0 the ops
  take the sorted drop-free layout (T*k rows, no bucket: its numerics
  and its memory are tests/test_olmoe.py's);
* capacity dropping is sentinel-fold clean: over-capacity slots fold to
  the out-of-range sentinel, read zero on combine, and never corrupt an
  expert row — an expert that accepts no traffic keeps bitwise-frozen
  weights through a real fused train step;
* superstep K>1 composes bitwise (params, opt slots, and the on-device
  aux-loss metric);
* a dp x ep mesh fit matches the single-device loss trajectory with the
  stacked expert tensors ACTUALLY sharded, and the partitioner's
  collectives land in the multichip census;
* kill -9 mid-commit resumes bitwise (the checkpoint battery's chaos
  scenario, routed model);
* the steady train and decode loops compile nothing post-warmup;
* MoEServeParityPass pins serve-time capacity to no-drop, and
  DecodeEngine samples per-slot routing state into moe_report().
"""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
from jax.sharding import PartitionSpec as P               # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu import checkpoint as ck                    # noqa: E402
from mxnet_tpu.moe import (MoEFeedForward, find_moe_blocks,  # noqa: E402
                           resolve_capacity, with_aux_loss)
from mxnet_tpu.moe.dispatch import combine, dispatch      # noqa: E402
from mxnet_tpu.moe.router import route                    # noqa: E402
from compile_guard import assert_no_compiles              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

E, K, HID = 4, 2, 16


def _moe_net(cf=0.0, expert_axis=None, name="moe"):
    net = MoEFeedForward(mx.sym.Variable("data"), num_hidden=HID,
                         num_experts=E, k=K, capacity_factor=cf,
                         name=name, expert_axis=expert_axis)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="head")
    return with_aux_loss(mx.sym.SoftmaxOutput(net, name="softmax"))


def _moe_metric():
    """acc on the prediction head + the on-device aux-loss observer
    (the multi-head group needs the slice adapters — metric.OutputSlice
    keeps every child device-capable so superstep stays K>1)."""
    return mx.metric.CompositeEvalMetric(
        [mx.metric.OutputSlice("acc", 0, 1),
         mx.metric.OutputMean(1, name="moe_aux")])


def _data(batch_size=16, n=64, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch_size)


def _fit(mesh=None, superstep=None, cf=0.0, expert_axis=None,
         num_epoch=2, **kwargs):
    mx.random.seed(7)
    mod = mx.mod.Module(_moe_net(cf=cf, expert_axis=expert_axis),
                        context=mx.cpu(0))
    mod.fit(_data(), num_epoch=num_epoch, eval_metric=_moe_metric(),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            mesh=mesh, superstep=superstep, **kwargs)
    return mod, {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


# -- routing math ------------------------------------------------------------

def test_resolve_capacity():
    for no_drop in (0.0, None, -1.0):                 # sorted rows, no bucket
        with pytest.raises(ValueError, match="route_sorted"):
            resolve_capacity(no_drop, 64, 4, 2)
    assert resolve_capacity(1.0, 64, 4, 2) == 32      # cf*T*k/E
    assert resolve_capacity(1.25, 256, 8, 2) == 80
    assert resolve_capacity(0.01, 64, 4, 2) == 1      # floor
    assert resolve_capacity(100.0, 64, 4, 2) == 64    # clamp to worst


def test_uniform_router_aux_is_one():
    """The GShard balance loss is normalized so a uniform router scores
    exactly 1.0 regardless of where the (tied) top-k lands."""
    plan = route(jnp.zeros((32, E), jnp.float32), K, 32)
    assert float(plan.aux) == pytest.approx(1.0, abs=1e-6)
    assert float(plan.dropped) == 0.0


def test_routed_forward_bitwise_vs_dense_reference():
    """capacity=INF: dispatch -> per-expert FFN -> combine lands on the
    EXACT bits of the dense gather reference (same einsum shapes over
    all experts, same k-term weighted sum) — routing only permutes
    row-independent work."""
    T, D, H = 32, 8, 16
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    w1 = jnp.asarray((rng.randn(E, D, H) * 0.3).astype(np.float32))
    w2 = jnp.asarray((rng.randn(E, H, D) * 0.3).astype(np.float32))
    C = T                                     # cf=0 -> worst case
    plan = route(logits, K, C)
    buf = dispatch(x, plan.slot, E, C)
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", buf, w1))
    out = combine(jnp.einsum("ech,eho->eco", h, w2),
                  plan.slot, plan.weight, E, C)
    # dense reference: every token through EVERY expert
    xb = jnp.broadcast_to(x, (E, T, D))
    hd = jax.nn.relu(jnp.einsum("ecd,edh->ech", xb, w1))
    dense = jnp.einsum("ech,eho->eco", hd, w2)          # (E, T, D)
    expert = plan.slot // C                              # (T, k)
    rows = dense[expert, jnp.arange(T)[:, None]]         # (T, k, D)
    ref = (rows * plan.weight[..., None]).sum(axis=1)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_capacity_drop_is_sentinel_fold():
    """Over-capacity token-choices fold to the sentinel: zero combine
    weight, zero dispatch rows past each expert's accepted count, and
    counts clamp to capacity — never a corrupted expert row."""
    T, D, C = 16, 4, 2
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(T, D).astype(np.float32))
    logits = jnp.asarray(rng.randn(T, E).astype(np.float32))
    plan = route(logits, K, C)
    counts = np.asarray(plan.counts)
    assert counts.max() <= C
    assert float(plan.dropped) == T * K - counts.sum() > 0
    slot = np.asarray(plan.slot)
    weight = np.asarray(plan.weight)
    assert np.all(weight[slot == E * C] == 0.0)
    buf = np.asarray(dispatch(x, plan.slot, E, C))
    for e in range(E):
        assert np.all(buf[e, int(counts[e]):] == 0.0), e
    # dropped tokens read exactly zero on combine
    ones = jnp.ones((E, C, D), jnp.float32)
    back = np.asarray(combine(ones, plan.slot, plan.weight, E, C))
    gone = (slot == E * C).all(axis=1)
    assert gone.any() or True
    assert np.all(back[gone] == 0.0)


@pytest.mark.parametrize("cf,layout", [(0.0, "sorted"), (-1.0, "sorted"),
                                       (1.0, "buckets"), (0.5, "buckets")])
def test_dispatch_layout_follows_capacity_factor(cf, layout):
    """capacity_factor <= 0 drops nothing and buckets nothing: the
    dispatch node emits the T*k rows sorted by expert, never the
    (E, T, D) worst-case buffer it once did; > 0 keeps the (E, C, D)
    buckets.  The expert and combine nodes follow the rank."""
    T, D = 64, 6
    net = MoEFeedForward(mx.sym.Variable("data"), num_hidden=HID,
                         num_experts=E, k=K, capacity_factor=cf, name="moe")
    inter = net.get_internals()
    shapes = dict(zip(inter.list_outputs(),
                      inter.infer_shape(data=(T, D))[1]))
    if layout == "sorted":
        assert shapes["moe_dispatch_dispatched"] == (T * K, D)
        assert shapes["moe_experts_output"] == (T * K, D)
    else:
        C = resolve_capacity(cf, T, E, K)
        assert shapes["moe_dispatch_dispatched"] == (E, C, D)
        assert shapes["moe_experts_output"] == (E, C, D)
    assert shapes["moe_dispatch_counts"] == (E,)
    assert shapes["moe_dispatch_dropped"] == (1,)
    assert shapes["moe_combine_output"] == (T, D)
    # the plan: every choice has its own sorted row, or a bucket slot
    rng = np.random.RandomState(8)
    x = mx.nd.array(rng.randn(T, D).astype(np.float32))
    exe = inter.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
    exe.arg_dict["data"][:] = x
    for name, arr in exe.arg_dict.items():
        if name != "data":
            arr[:] = rng.randn(*arr.shape).astype(np.float32) * 0.3
    exe.forward(is_train=False)
    outs = dict(zip(inter.list_outputs(), exe.outputs))
    slot = outs["moe_dispatch_slot"].asnumpy()
    counts = outs["moe_dispatch_counts"].asnumpy()
    dropped = float(outs["moe_dispatch_dropped"].asnumpy()[0])
    assert counts.sum() + dropped == T * K
    if layout == "sorted":
        assert dropped == 0.0
        assert sorted(slot.reshape(-1)) == list(range(T * K))
        rows = outs["moe_dispatch_dispatched"].asnumpy()
        assert np.array_equal(rows[slot[:, 0]], x.asnumpy())


# -- untouched-expert freeze through a real train step -----------------------

def test_untouched_expert_rows_bitwise_frozen():
    """Steer the gate so one expert accepts zero tokens, run a real
    fused train step: that expert's stacked weight rows come out
    bitwise-identical while routed experts move."""
    rng = np.random.RandomState(3)
    X = rng.rand(32, 6).astype(np.float32)   # positive features
    y = (X.sum(axis=1) > 3).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mx.random.seed(5)
    mod = mx.mod.Module(_moe_net(cf=0.0), context=mx.cpu(0))
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    # gate logit_e = s * x[:, e]; x >= 0, so expert 3 (logit -5*x[:,3])
    # never makes top-2 against experts scoring +5*x[:, e]
    wg = np.zeros((E, 6), np.float32)
    for e in range(E):
        wg[e, e] = 5.0
    wg[3, 3] = -5.0
    args, auxs = mod.get_params()
    args = dict(args)
    args["moe_gate_weight"] = mx.nd.array(wg)
    mod.set_params(args, auxs, allow_missing=False)
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9, "wd": 0.0})
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    batch = next(iter(it))
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    for name in ("moe_experts_i2h_weight", "moe_experts_i2h_bias",
                 "moe_experts_h2o_weight", "moe_experts_h2o_bias"):
        assert np.array_equal(before[name][3], after[name][3]), \
            "untouched expert 3 moved in %s" % name
        assert not np.array_equal(before[name][:3], after[name][:3]), \
            "routed experts frozen in %s (test is vacuous)" % name


# -- superstep / mesh composition --------------------------------------------

def test_superstep4_bitwise_with_aux_metric():
    """superstep=4 vs sequential: params, optimizer slots, and the
    on-device aux-loss metric all bitwise-identical (the aux head
    accumulates in the superstep scan like any metric)."""
    mx.random.seed(7)
    mods, mets = [], []
    for ss in (1, 4):
        mx.random.seed(7)
        mod = mx.mod.Module(_moe_net(cf=0.5), context=mx.cpu(0))
        met = _moe_metric()
        mod.fit(_data(), num_epoch=2, eval_metric=met, superstep=ss,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        mods.append(mod)
        mets.append(met)
    m1, m4 = mods
    assert m4._fused is not None and m4._superstep_progs
    pa = {k: v.asnumpy() for k, v in m1.get_params()[0].items()}
    pb = {k: v.asnumpy() for k, v in m4.get_params()[0].items()}
    for k in pa:
        assert np.array_equal(pa[k], pb[k]), "param %s diverged" % k
    assert mets[0].get() == mets[1].get()


def test_dp_ep_mesh_matches_single_device_and_shards():
    """dp=2 x ep=2: the expert-parallel fit tracks the single-device
    loss trajectory, the stacked expert tensors are ACTUALLY sharded
    over ep at rest, and the partitioner's collectives (the dispatch/
    combine resharding) land in the multichip census."""
    _, p1 = _fit()
    mm, pm = _fit(mesh=[("dp", 2), ("ep", 2)], expert_axis="ep")
    for k in p1:
        assert np.abs(p1[k] - pm[k]).max() < 1e-4, k
    w = mm._fused_state["params"]["moe_experts_i2h_weight"]
    assert tuple(w.sharding.spec)[:1] == ("ep",)
    assert not w.is_fully_replicated
    assert dict(w.sharding.mesh.shape) == {"dp": 2, "ep": 2}
    # census: AOT the live step the way bench does, then read the report
    f = mm._fused
    rng = np.random.RandomState(0)
    staged = mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(16, 6).astype(np.float32))],
        label=[mx.nd.array(np.zeros(16, np.float32))])
    f.aot_compile(mm._fused_state, f.make_batch(staged), mm._fused_key)
    reports = mx.profiler.multichip_report()
    mine = [r for r in reports.values()
            if r["mesh"] == {"dp": 2, "ep": 2}]
    assert mine, reports.keys()
    assert mine[-1]["collectives"]["total_count"] > 0
    assert "dp=2 x ep=2" in mx.profiler.multichip_report_str()


def test_moe_geometry_and_report():
    mod, _ = _fit(cf=0.5, num_epoch=1)
    f = mod._fused
    assert f.moe_blocks and f.moe_stats is not None
    (name, spec), = f.moe_blocks.items()
    assert spec.num_experts == E and spec.k == K
    assert spec.capacity_factor == 0.5
    # bench-sampler seam: counts fed host-side surface in moe_report
    f.moe_stats.note_counts(name, np.array([8.0, 4.0, 2.0, 2.0]))
    rep = mx.profiler.moe_report()
    mine = [v for k, v in sorted(rep.items()) if k.startswith("fused#")]
    assert mine and mine[-1]["blocks"][name]["routed"] == 16.0
    assert "moe" in mx.profiler.unified_report()


# -- chaos: kill -9 mid-commit, bitwise resume -------------------------------

_CRASH_CHILD = """
import os, signal, sys
sys.path.insert(0, %(root)r)
sys.path.insert(0, os.path.join(%(root)r, "tests"))
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ck
from test_moe import _moe_net, _moe_metric, _data

store = sys.argv[1]
mx.faults.install(mx.faults.Rule(
    points="checkpoint.commit@shards_written", kinds="crash",
    when=lambda ctx: ctx["step"] >= 5))
mx.random.seed(123)
mod = mx.mod.Module(_moe_net(cf=0.5), context=mx.cpu(0))
mgr = ck.CheckpointManager(store, save_every_steps=3, keep_last_n=None)
mod.fit(_data(), num_epoch=2, eval_metric=_moe_metric(),
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        checkpoint=mgr)
sys.exit(3)   # unreachable: the save at step >= 5 kills us
"""


def test_kill9_mid_commit_resumes_bitwise(tmp_path):
    """kill -9 lands between shard write and COMMIT: the torn save is
    skipped, resume restores the last committed step, and the continued
    routed run is bitwise-identical to an uninterrupted one."""
    store = os.path.join(str(tmp_path), "store")
    script = os.path.join(str(tmp_path), "crash_child.py")
    with open(script, "w") as f:
        f.write(_CRASH_CHILD % {"root": ROOT})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, script, store],
                         capture_output=True, text=True, timeout=240,
                         env=env, cwd=ROOT)
    assert res.returncode == -signal.SIGKILL, (res.returncode, res.stderr)
    assert any(".tmp-" in n for n in os.listdir(store)), os.listdir(store)
    # epoch end (4 steps/epoch) commits step 4; the every-3 save at
    # step 6 is the one the fault tears
    assert ck.latest_step(store) == 4

    mx.random.seed(123)
    m_ref = mx.mod.Module(_moe_net(cf=0.5), context=mx.cpu(0))
    m_ref.fit(_data(), num_epoch=2, eval_metric=_moe_metric(),
              optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    ref = {k: v.asnumpy() for k, v in m_ref.get_params()[0].items()}

    mx.random.seed(999)
    m2 = mx.mod.Module(_moe_net(cf=0.5), context=mx.cpu(0))
    with ck.CheckpointManager(store, keep_last_n=None) as mgr2:
        m2.fit(_data(), num_epoch=2, eval_metric=_moe_metric(),
               optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
               checkpoint=mgr2, resume=True)
    p2 = {k: v.asnumpy() for k, v in m2.get_params()[0].items()}
    for k in ref:
        assert np.array_equal(ref[k], p2[k]), "param %s diverged" % k


# -- zero steady-loop compiles -----------------------------------------------

def test_no_compiles_in_steady_train_loop():
    it = _data()
    mx.random.seed(7)
    mod = mx.mod.Module(_moe_net(cf=0.5), context=mx.cpu(0))
    mod.fit(it, num_epoch=1, eval_metric=_moe_metric(),
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    it.reset()
    batch = next(iter(it))
    with assert_no_compiles("steady MoE train loop"):
        for _ in range(4):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()


# -- serving: parity pass, decode engine, moe_report -------------------------

SV_VOCAB, SV_EMB = 13, 8


def _decode_net(cf):
    from mxnet_tpu.moe import hit_symbols
    tok = mx.sym.Variable("data")
    hits = mx.sym.Variable("moe_hits")
    emb = mx.sym.Flatten(mx.sym.Embedding(
        tok, input_dim=SV_VOCAB, output_dim=SV_EMB, name="emb"))
    net = MoEFeedForward(emb, num_hidden=HID, num_experts=E, k=K,
                         capacity_factor=cf, name="dmoe")
    logits = mx.sym.FullyConnected(net, num_hidden=SV_VOCAB, name="out")
    return mx.sym.Group([logits, hits + hit_symbols(logits)[0]])


def _decode_params(seed=4):
    rng = np.random.RandomState(seed)

    def g(*s):
        return (rng.randn(*s) * 0.5).astype(np.float32)

    return {"emb_weight": g(SV_VOCAB, SV_EMB),
            "dmoe_gate_weight": g(E, SV_EMB),
            "dmoe_experts_i2h_weight": g(E, SV_EMB, HID),
            "dmoe_experts_i2h_bias": np.zeros((E, HID), np.float32),
            "dmoe_experts_h2o_weight": g(E, HID, SV_EMB),
            "dmoe_experts_h2o_bias": np.zeros((E, SV_EMB), np.float32),
            "out_weight": g(SV_VOCAB, SV_EMB),
            "out_bias": np.zeros(SV_VOCAB, np.float32)}


def _symbol_json_saved_before_counts(no_bias, cf):
    """``MoEFeedForward(..., name="moe").tojson()`` as the commit before
    the sorted layout wrote it: the expert node has 3 or 5 inputs (no
    ``counts``), no node has ``gated`` / ``layer``."""
    def var(name):
        return {"op": "null", "name": name, "attr": {}, "inputs": []}

    nodes = [var("data"), var("moe_gate_weight"),
             {"op": "FullyConnected", "name": "moe_gate",
              "param": {"num_hidden": str(E), "no_bias": "True"},
              "attr": {}, "inputs": [[0, 0], [1, 0]]},
             {"op": "_moe_dispatch", "name": "moe_dispatch",
              "param": {"num_experts": str(E), "k": str(K),
                        "capacity_factor": str(cf),
                        "renormalize": "False"},
              "attr": {}, "inputs": [[0, 0], [2, 0]]}]
    weights = ["moe_experts_" + n for n in
               (["i2h_weight", "h2o_weight"] if no_bias else
                ["i2h_weight", "i2h_bias", "h2o_weight", "h2o_bias"])]
    nodes += [var(n) for n in weights]
    ffn = len(nodes)
    nodes.append({"op": "_moe_expert_ffn", "name": "moe_experts",
                  "param": {"num_hidden": str(HID), "output_dim": "0",
                            "act_type": "relu", "no_bias": str(no_bias)},
                  "attr": {},
                  "inputs": [[3, 0]] + [[4 + i, 0]
                                        for i in range(len(weights))]})
    nodes.append({"op": "_moe_combine", "name": "moe_combine", "param": {},
                  "attr": {}, "inputs": [[ffn, 0], [3, 1], [3, 2]]})
    return json.dumps({
        "nodes": nodes, "heads": [[ffn + 1, 0]],
        "arg_nodes": [i for i, n in enumerate(nodes) if n["op"] == "null"],
        "attrs": {"mxnet_tpu_version": 1}})


@pytest.mark.parametrize("no_bias,cf", [(False, 0.0), (True, 0.0),
                                        (False, 1.25), (True, 0.5)])
def test_a_symbol_saved_before_counts_was_an_input_still_runs(no_bias, cf):
    """The expert node gained a trailing ``counts`` input with the
    sorted layout.  A graph saved before has none: ``load_json`` takes
    them from the dispatch node that feeds the expert node's data, so
    the old file binds with the arguments it always had and answers as
    today's builder does."""
    old = mx.sym.load_json(_symbol_json_saved_before_counts(no_bias, cf))
    new = MoEFeedForward(mx.sym.Variable("data"), num_hidden=HID,
                         num_experts=E, k=K, capacity_factor=cf,
                         no_bias=no_bias, name="moe")
    assert old.list_arguments() == new.list_arguments()
    assert not any("counts" in a for a in old.list_arguments())
    T, D = 32, 6
    rng = np.random.RandomState(11)
    outs = []
    for net in (old, new):
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        for name in net.list_arguments():
            exe.arg_dict[name][:] = np.random.RandomState(
                len(name)).randn(*exe.arg_dict[name].shape) * 0.3
        exe.forward(is_train=False)
        outs.append(exe.outputs[0].asnumpy())
    assert outs[0].shape == (T, D) and np.abs(outs[0]).sum() > 0
    assert np.array_equal(outs[0], outs[1])
    # saved again it is today's graph, counts wired
    again = json.loads(old.tojson())
    ffn = next(n for n in again["nodes"] if n["op"] == "_moe_expert_ffn")
    assert len(ffn["inputs"]) == (4 if no_bias else 6)


def test_expert_node_on_other_data_must_be_given_counts():
    """No silent ``<name>_counts`` variable: data that is not a dispatch
    node's output needs counts spelled out."""
    with pytest.raises(mx.base.MXNetError, match="give counts"):
        mx.sym._moe_expert_ffn(mx.sym.Variable("buf"), num_hidden=HID,
                               no_bias=True, name="ffn")
    net = mx.sym._moe_expert_ffn(mx.sym.Variable("buf"),
                                 counts=mx.sym.Variable("sizes"),
                                 num_hidden=HID, no_bias=True, name="ffn")
    assert net.list_arguments() == ["buf", "ffn_i2h_weight",
                                    "ffn_h2o_weight", "sizes"]


def test_serve_parity_pass_pins_capacity(monkeypatch):
    from mxnet_tpu.passes import (MoEServeParityPass,
                                  default_inference_pipeline)
    net = _moe_net(cf=0.5)
    spec0, = find_moe_blocks(net).values()
    assert spec0.capacity_factor == 0.5
    out, _ = default_inference_pipeline().run(net, {})
    spec, = find_moe_blocks(out).values()
    assert spec.capacity_factor == 0.0
    assert spec.num_experts == E and spec.k == K
    # already-exact nodes are left alone (the pass is idempotent)
    p = MoEServeParityPass()
    same, _ = p.apply(out, {})
    assert p.summary["rewritten"] == 0
    # the env knob keeps the training capacity for latency experiments
    monkeypatch.setenv("MXNET_MOE_SERVE_EXACT", "0")
    out2, _ = default_inference_pipeline().run(net, {})
    spec2, = find_moe_blocks(out2).values()
    assert spec2.capacity_factor == 0.5


def test_decode_engine_routes_and_reports():
    """Routed decode through DecodeEngine: the serving pipeline pins
    capacity to no-drop, per-slot hit state accumulates, and the
    engine samples it into moe_report() — with zero compiles in the
    steady decode loop."""
    from mxnet_tpu.passes import default_inference_pipeline
    from mxnet_tpu.serve import DecodeEngine, ServeError
    params = _decode_params()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, SV_VOCAB, 1 + rng.randint(0, 2))
               for _ in range(6)]
    eng = DecodeEngine(_decode_net(0.5), dict(params), num_slots=2,
                       state_shapes={"moe_hits": (E,)},
                       pipeline=default_inference_pipeline(),
                       moe_hits_state="moe_hits", moe_stats_every=1,
                       name="moe-decode")
    try:
        first = eng.generate(prompts[0], timeout=60, max_new_tokens=4)
        with assert_no_compiles("steady routed decode loop"):
            futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
        # deterministic: resubmitting the first prompt reproduces it
        assert np.array_equal(
            eng.generate(prompts[0], timeout=60, max_new_tokens=4), first)
        assert all(len(o) == 6 for o in outs)
    finally:
        eng.close()
    rep = mx.profiler.moe_report()
    mine = [v for k, v in rep.items() if "moe-decode" in k]
    assert mine and mine[-1]["blocks"]["moe_hits"]["routed"] > 0
    assert "moe" in mx.profiler.unified_report_str()
    # a state name that does not exist is a construction-time error
    with pytest.raises(ServeError):
        DecodeEngine(_decode_net(0.0), dict(params), num_slots=2,
                     state_shapes={"moe_hits": (E,)},
                     moe_hits_state="nope", name="moe-decode-bad")


# -- the sorted layout's row movement (PR 36) ---------------------------------

def _sorted_plan(held, dtype):
    """A ``SortedPlan`` over 6 experts with uneven groups and an empty
    one (expert 1 gets no token), all here or experts 2..4 held, and the
    block's arrays in ``dtype``."""
    from mxnet_tpu.moe.router import route_sorted
    T, D, n_exp, k = 24, 12, 6, 2
    rng = np.random.RandomState(7)
    logits = rng.randn(T, n_exp).astype(np.float32)
    logits[:, 1] = -9.0
    logits[: T // 2, 4] += 2.0
    plan = route_sorted(jnp.asarray(logits), k, renormalize=True,
                        held=(2, 3) if held else None)
    counts = np.asarray(plan.counts)
    assert counts[1] == 0 and len(set(counts)) > 2 and counts.sum() == T * k
    x = jnp.asarray(rng.randn(T, D), dtype)
    rows = jnp.asarray(rng.randn(T * k, D), dtype)
    if held:
        mine = int(counts[2:5].sum())
        assert 0 < mine < T * k
        rows = rows.at[mine:].set(0)
    return plan, x, rows


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("held", [False, True],
                         ids=["all-experts", "3-of-6-held"])
def test_sorted_layout_backward_equals_autodiff_of_plain_gathers(held, dtype,
                                                                 tol):
    """``sort_rows`` and ``combine_sorted`` against the same gathers
    written plainly and differentiated by JAX (scatter-adds): values and
    the gradients of the tokens, the expert rows and the combine
    weights, whose backward pass reads ``order`` where it used to sort
    ``slot``, and the gathered rows the forward pass saved where it used
    to gather them again."""
    from mxnet_tpu.moe.dispatch import combine_sorted, sort_rows
    plan, x, rows = _sorted_plan(held, dtype)
    T, k = plan.slot.shape
    flat = plan.slot.reshape(T * k)
    f32 = jnp.float32

    def ours(x, rows, weight):
        return (sort_rows(x, plan.order, plan.slot).astype(f32),
                combine_sorted(rows, plan.order, plan.slot,
                               weight).astype(f32))

    def plain(x, rows, weight):
        picked = rows[flat].reshape(T, k, -1)
        return (x[plan.order // k].astype(f32),
                (picked * weight[..., None].astype(rows.dtype)).sum(
                    axis=1).astype(f32))

    rng = np.random.RandomState(9)
    cot = (jnp.asarray(rng.randn(T * k, x.shape[1]), f32),
           jnp.asarray(rng.randn(T, x.shape[1]), f32))
    got, want = [], []
    for fn, into in ((ours, got), (plain, want)):
        outs, vjp = jax.vjp(fn, x, rows, plan.weight)
        into.extend(list(outs) + list(vjp(cot)))
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(w).max() > 0
        assert np.allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())


@pytest.mark.parametrize("n", [1, 7, 4096])
def test_scalars_moved_by_a_key_value_sort_land_where_a_scatter_puts_them(n):
    """``dispatch._moved``: ``out[place[i]] = values[i]``, which
    brings the ``T*k`` weights to sorted order in the combine's backward
    pass."""
    from mxnet_tpu.moe.dispatch import _moved
    rng = np.random.RandomState(n)
    place = rng.permutation(n).astype(np.int32)
    values = rng.randn(n).astype(np.float32)
    want = np.empty_like(values)
    want[place] = values
    got = _moved(jnp.asarray(values), jnp.asarray(place))
    assert got.dtype == jnp.float32 and np.array_equal(np.asarray(got), want)
    # and through a permutation's inverse it is the gather
    inverse = np.argsort(place).astype(np.int32)
    assert np.array_equal(np.asarray(_moved(jnp.asarray(values),
                                            jnp.asarray(inverse))),
                          values[place])


def _expert_ffn(no_bias=True, gated=True):
    op = mx.ops.get_op("_moe_expert_ffn")
    p = op.parse_params({"num_hidden": HID, "act_type": "silu",
                         "gated": gated, "no_bias": no_bias})
    return lambda *inputs: op.forward(p, list(inputs), [], None)[0]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_no_pass_over_the_sorted_rows_guards_or_finds_nothing():
    """The jaxpr of ``sort_rows -> gated FFN -> combine_sorted``, forward
    and under ``jax.grad``, the plan given (so a sort here is one
    outside the router): no select and no pad over a matrix of ``T*k``
    rows (every gather is in bounds and says so); no row gather beyond
    the four the layout is made of (the combine's backward pass reads
    the rows its forward pass gathered); the only sort is the backward
    pass's key-value sort of ``T*k`` scalars (the weights to sorted
    order), not an argsort for a permutation the plan holds; three
    grouped matmuls forward and six more backward, and the
    one sum of two cotangents ``(T*k, D)`` wide that gate and up, two
    products over the same rows, leave (merging them was measured and
    refused, PERF.md PR 36)."""
    from mxnet_tpu.moe.dispatch import combine_sorted, sort_rows
    plan, x, _ = _sorted_plan(False, jnp.float32)
    (T, k), D, n_exp = plan.slot.shape, x.shape[1], plan.counts.shape[0]
    rng = np.random.RandomState(8)
    wg, w1 = (jnp.asarray(rng.randn(n_exp, D, HID), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.randn(n_exp, HID, D), jnp.float32)
    ffn = _expert_ffn()

    def block(x, wg, w1, w2, weight):
        rows = sort_rows(x, plan.order, plan.slot)
        rows = ffn(rows, wg, w1, w2, plan.counts)
        return combine_sorted(rows, plan.order, plan.slot, weight).sum()

    args = (x, wg, w1, w2, plan.weight)
    forward = list(_eqns(jax.make_jaxpr(block)(*args).jaxpr))
    both = list(_eqns(jax.make_jaxpr(jax.grad(
        block, argnums=(0, 1, 2, 3, 4)))(*args).jaxpr))

    def named(eqns, *names):
        return [e for e in eqns if e.primitive.name in names]

    def sorted_rows(eqn, width=None):
        """Makes a matrix of ``T*k`` rows (``width`` wide)?"""
        return any(len(getattr(v.aval, "shape", ())) == 2
                   and v.aval.shape[0] == T * k
                   and width in (None, v.aval.shape[1])
                   for v in eqn.outvars)

    for eqns in (forward, both):
        assert not [e for e in named(eqns, "select_n", "pad")
                    if sorted_rows(e)]
    assert {v.aval.shape for e in both if sorted_rows(e)
            for v in e.outvars} >= {(T * k, D), (T * k, HID)}, \
        "the walk sees them"
    assert len([e for e in named(forward, "gather")
                if sorted_rows(e, D)]) == 2
    assert len([e for e in named(both, "gather")
                if sorted_rows(e, D)]) == 2 + 2
    assert not named(forward, "sort")
    sorts = named(both, "sort")
    assert len(sorts) == 1 and all(
        e.params["num_keys"] == 1 and len(e.invars) == 2
        and all(v.aval.shape == (T * k,) for v in e.invars) for e in sorts)
    assert len(named(forward, "ragged_dot_general")) == 3
    assert len(named(both, "ragged_dot_general")) == 3 + 6
    assert len([e for e in named(both, "add_any")
                if sorted_rows(e, D)]) == 1


def test_combine_node_takes_order_from_the_dispatch_node():
    """``order`` is the dispatch node's eighth output and the combine
    node's last input: wired by ``MoEFeedForward``, implied for a caller
    that gives ``slot`` alone, refused where ``slot`` is not a dispatch
    node's (no silent ``<name>_order`` variable), and no argument of the
    bound symbol in either layout."""
    for cf in (0.0, 1.25):
        net = MoEFeedForward(mx.sym.Variable("data"), num_hidden=HID,
                             num_experts=E, k=K, capacity_factor=cf,
                             name="moe")
        assert not any("order" in a for a in net.list_arguments())
        nodes = json.loads(net.tojson())["nodes"]
        disp = next(i for i, n in enumerate(nodes)
                    if n["op"] == "_moe_dispatch")
        comb = next(n for n in nodes if n["op"] == "_moe_combine")
        assert comb["inputs"][1:] == [[disp, 1], [disp, 2], [disp, 7]]
        shapes = dict(zip(net.get_internals().list_outputs(),
                          net.get_internals().infer_shape(data=(16, 6))[1]))
        assert shapes["moe_dispatch_order"] == (16 * K,)
    disp = mx.sym._moe_dispatch(mx.sym.Variable("data"),
                                mx.sym.Variable("logits"), num_experts=E,
                                k=K, name="d")
    implied = mx.sym._moe_combine(mx.sym.Variable("rows"), disp[1], disp[2],
                                  name="c")
    assert implied.list_arguments() == ["rows", "data", "logits"]
    with pytest.raises(mx.base.MXNetError, match="give order"):
        mx.sym._moe_combine(mx.sym.Variable("rows"), mx.sym.Variable("w"),
                            mx.sym.Variable("s"), name="c")


# -- the plain (ungated) form of one rank's share (ISSUE 71) ------------------

def _share_ops(act_type, gated, held, first, experts, k, hidden, width):
    get = mx.ops.get_op
    share = dict(experts_held=held, first_expert=first)
    ffn = dict(num_hidden=width, output_dim=hidden, act_type=act_type,
               no_bias=True, gated=gated, layer=1, **share)
    return {name: (get(op), get(op).parse_params(params)) for name, op, params
            in (("dispatch", "_moe_dispatch", dict(
                    num_experts=experts, k=k, capacity_factor=0.0,
                    renormalize=True, score="sigmoid", scale=2.5,
                    bias_rate=1e-3, layer=1, **share)),
                ("experts", "_moe_expert_ffn", ffn),
                ("combine", "_moe_combine", dict(layer=1)),
                ("share", "_moe_share_ffn", ffn))}


@pytest.mark.parametrize("bounded", [False, True], ids=["window", "bound"])
@pytest.mark.parametrize("act_type", ["relu2", "silu"])
def test_the_plain_share_node_is_the_three_node_layer(act_type, bounded,
                                                      monkeypatch):
    """``_moe_share_ffn`` with ``gated`` off (two stacked matrices,
    ``act(x W1) W2``: Nemotron-H's experts under the squared ReLU)
    against ``_moe_expert_ffn`` between the dispatch node and
    ``_moe_combine`` for a rank that holds 4 of 32 experts: forward and
    the gradients of the data, of the router's logits and of both
    stacked weights; as the one window of every row, and under a static
    row bound with its conditional."""
    share_rule = sys.modules["mxnet_tpu.moe.dispatch"]
    T, k, experts, held, first, D, H = 256, 4, 32, 4, 8, 24, 40
    if bounded:
        monkeypatch.setattr(share_rule, "BOUND_WORTH_ROWS", 0)
    assert (share_rule.held_rows_bound(T * k, experts, held) < T * k) \
        is bounded
    ops = _share_ops(act_type, False, held, first, experts, k, D, H)
    assert ops["share"][0].list_arguments(ops["share"][1])[-2:] \
        == ["i2h_weight", "h2o_weight"]

    class Train:
        is_train = True

    def run(which, *inputs, aux=()):
        op, p = ops[which]
        return op.forward(p, list(inputs), list(aux), Train)

    rng = np.random.RandomState(len(act_type) + bounded)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    logits = jnp.asarray(rng.randn(T, experts), jnp.float32)
    ws = [jnp.asarray(rng.randn(held, *s) / 5, jnp.float32)
          for s in ((D, H), (H, D))]
    ct = jnp.asarray(rng.randn(T, D), jnp.float32)
    bias = [jnp.asarray(0.1 * rng.randn(experts), jnp.float32)]

    def three(x, ws, d):
        rows = run("experts", d[0], *ws, d[4])[0]
        return run("combine", rows, d[1], d[2], d[7])[0]

    def one(x, ws, d):
        return run("share", x, d[1], d[2], d[7], d[4], *ws)[0]

    def graded(layer):
        def loss(x, logits, *ws):
            d = run("dispatch", x, logits, aux=bias)[0]
            out = layer(x, ws, d)
            return (out * ct).sum(), (out, d[4])
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    (_, (want, counts)), want_grads = graded(three)(x, logits, *ws)
    (_, (got, _)), got_grads = graded(one)(x, logits, *ws)
    mine = int(np.asarray(counts)[first:first + held].sum())
    assert 0 < mine < T * k and np.asarray(want).any()
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-6 * scale
    for name, a, b in zip(("data", "logits", "up", "down"), got_grads,
                          want_grads):
        a, b = np.asarray(a), np.asarray(b)
        assert b.any(), name
        assert np.abs(a - b).max() <= 5e-6 * np.abs(b).max(), name
