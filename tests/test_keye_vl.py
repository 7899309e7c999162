"""Keye-VL-2.0's language model through the Symbol graph (ISSUE 57,
tier-1): the whole tiny model against
``benchmark/reference/keye-vl-2.0-30b-a3b.py`` in float32 (both losses,
every gradient through Adam's first step, a rank's share of the experts),
the two losses' isolation at the model's own weights as EXACT zeros, the
rotation by sections against the reference's with three unequal axes,
the ``positions`` input, the selection's counter and span in ``fit`` and
nowhere else, the device scopes the step names."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.models import keye_lm, olmoe_lm, sdar_moe_lm  # noqa: E402
from mxnet_tpu.ops import sparse_attention as sa          # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402
from mxnet_tpu.trace.heads import DSA_SELECT              # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, placed_on_rows      # noqa: E402

REF = manifest.load_module("reference", "keye-vl-2.0-30b-a3b")

TINY = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, index_heads=2, index_dim=4, topk=8, num_experts=8,
            experts_per_tok=2, expert_width=16, vocab_size=50, seq_len=32,
            mrope_sections=(1, 1, 2), rope_theta=1e7, rms_eps=1e-6,
            aux_coef=0.01, experts_held=4, first_expert=2)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
INDEXER = ("index_q_proj_weight", "index_k_proj_weight",
           "index_k_norm_gamma", "index_k_norm_beta", "index_w_proj_weight")


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _batch(seed, vocab=TINY["vocab_size"], t=TINY["seq_len"]):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, vocab, (BATCH, t)).astype(np.int32),
            rng.randint(0, vocab, (BATCH, t)).astype(np.int32))


def _module(net, seed=3, sigma=0.3, **extra_data):
    t = TINY["seq_len"]
    mod = mx.mod.Module(net, data_names=["data"] + sorted(extra_data),
                        context=mx.cpu())
    mod.bind(data_shapes=[("data", (BATCH, t))] + [
        (k, v.shape) for k, v in sorted(extra_data.items())],
        label_shapes=[("softmax_label", (BATCH, t))])
    mx.random.seed(seed)
    mod.init_params(mx.init.Normal(sigma))
    return mod


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(sa, "DSA_BLOCK_Q", 16)


def test_the_model_is_the_reference_losses_gradients_and_adam_step():
    """One Adam step of the tiny model, a rank's share of the experts
    (4 of 8 from the third on): the cross entropy, each block's balance
    score and index loss, the counter's head, and every weight's update
    (the indexer's five tensors a block among them) against the
    reference's."""
    data, label = _batch(0)
    mod = _module(keye_lm(**TINY))
    mod.init_optimizer(optimizer="adam", optimizer_params=dict(ADAM))
    before = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    batch = mx.io.DataBatch(data=[mx.nd.array(data)],
                            label=[mx.nd.array(label)], pad=0)
    mod.forward_backward(batch)
    mod.update()
    names = mod._symbol.list_outputs()
    assert names == ["lm_output", "l0_moe_dispatch_aux_output",
                     "l1_moe_dispatch_aux_output", "l0_index_loss_output",
                     "l1_index_loss_output", "moe_load_output",
                     "dsa_select_output"]
    outs = dict(zip(names, (o.asnumpy() for o in mod.get_outputs())))
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    want = REF.reference_step({"model": {"kwargs": TINY}}, before,
                              {"data": data}, {"softmax_label": label},
                              ADAM, sorted(before))
    assert outs["lm_output"].mean() == pytest.approx(want["loss"], rel=1e-5)
    for l in range(2):
        assert outs["l%d_moe_dispatch_aux_output" % l][0] == pytest.approx(
            want["aux"][l], rel=1e-5)
        assert outs["l%d_index_loss_output" % l].shape == (BATCH,)
        assert outs["l%d_index_loss_output" % l].mean() == pytest.approx(
            want["index_loss"][l], rel=1e-5)
    kept = REF.selected_pairs(32, 8)
    assert kept == 8 * 9 // 2 + 24 * 8
    head = outs["dsa_select_output"]
    assert head.shape == (2, BATCH, 6)
    assert np.array_equal(head[:, :, :5], np.broadcast_to(
        [32, kept, 32 * 33 // 2, 1, 1], (2, BATCH, 5)))
    for l in range(2):
        assert np.array_equal(head[l, :, 5],
                              outs["l%d_index_loss_output" % l])
    assert len(before) == 37
    for name in sorted(before):
        assert _rel(after[name] - before[name], want["updates"][name]) \
            < 2e-4, name
        assert np.abs(want["updates"][name]).max() > 1e-4, name


def test_the_two_losses_reach_disjoint_weights_exactly():
    """``d (CE + balance) / d`` an indexer weight and ``d L_I / d`` every
    other weight are EXACT zeros in the program; each agrees with the
    reference's whole objective, whose indexer gradients do not move with
    the labels."""
    data, label = _batch(1)
    net = keye_lm(**TINY)
    params = {k: v.asnumpy() for k, v in
              _module(net).get_params()[0].items()}
    outputs = net.list_outputs()

    def gradients(heads):
        part = mx.sym.Group([net[outputs.index(h)] for h in heads])
        fed = {k: v for k, v in (("data", data), ("softmax_label", label))
               if k in part.list_arguments()}
        exe = part.simple_bind(mx.cpu(), grad_req="write",
                               **{k: v.shape for k, v in fed.items()})
        for name, value in dict(params, **fed).items():
            if name in exe.arg_dict:
                exe.arg_dict[name][:] = value
        exe.forward(is_train=True)
        exe.backward()
        return {k: exe.grad_dict[k].asnumpy() if k in exe.grad_dict
                else np.zeros_like(v) for k, v in params.items()}

    main = gradients(["lm_output", "l0_moe_dispatch_aux_output",
                      "l1_moe_dispatch_aux_output"])
    index = gradients(["l0_index_loss_output", "l1_index_loss_output"])
    for name in params:
        mine = name.split("_", 1)[1] in INDEXER
        assert mine == (not main[name].any()), name
        assert mine == bool(index[name].any()), name
    # the reference's whole objective: each weight's gradient is the one
    # part that reaches it, and the indexer's does not know the labels
    cfg = {"model": {"kwargs": TINY}}
    both = REF.loss_and_grads(cfg, params, data, label)["grads"]
    other = REF.loss_and_grads(cfg, params, data, label[::-1])["grads"]
    for name in params:
        if name.split("_", 1)[1] in INDEXER:
            assert _rel(index[name], both[name]) < 2e-4, name
            assert np.array_equal(both[name], other[name]), name
        else:
            assert _rel(main[name], both[name]) < 2e-4, name


@pytest.mark.parametrize("topk", [32, 8])
def test_the_index_loss_falls_against_a_fixed_target(topk):
    """The index-loss heads alone train the indexer alone, so the main
    attention does not move: under ``topk`` of the sequence the target is
    FIXED and plain gradient descent on ``L_I`` lowers every block's,
    step after step; under a top-8 the selection moves with the indexer
    and ``L_I`` still ends lower.  (In the cell it RISES with the steps:
    there the target, the model's own attention, sharpens as it learns;
    ``dsa_index_kl``'s docstring.)"""
    data, _ = _batch(4)
    net = keye_lm(**dict(TINY, topk=topk))
    params = {k: v.asnumpy() for k, v in
              _module(net).get_params()[0].items()}
    outputs = net.list_outputs()
    heads = mx.sym.Group([net[outputs.index("l%d_index_loss_output" % l)]
                          for l in range(2)])
    exe = heads.simple_bind(mx.cpu(), grad_req="write", data=data.shape)
    for name, value in dict(params, data=data).items():
        if name in exe.arg_dict:
            exe.arg_dict[name][:] = value
    curve = []
    for _ in range(12):
        exe.forward(is_train=True)
        curve.append([float(o.asnumpy().mean()) for o in exe.outputs])
        exe.backward()
        for name, grad in exe.grad_dict.items():
            if name != "data":
                assert name.split("_", 1)[1] in INDEXER or \
                    not grad.asnumpy().any(), name
                exe.arg_dict[name][:] = exe.arg_dict[name].asnumpy() \
                    - 2.0 * grad.asnumpy()
    curve = np.asarray(curve)
    assert (curve[-1] < 0.8 * curve[0]).all(), curve
    if topk == 32:
        assert (np.diff(curve, axis=0) < 0).all(), curve


def test_sections_turn_each_frequency_by_its_own_axis():
    """Three unequal position axes (an image's rows would have them): the
    op against the reference's rotation, and against plain rotary axis by
    axis: frequency ``i`` of a section turns as it would under that
    axis's positions alone."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 12, 3, 16).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, 40, (2, 3, 12)).astype(np.float32))
    sections = (2, 3, 3)
    got = tf_ops.sectioned_rotary(x, pos, theta=1e4, sections=sections)
    want = REF.rotate(x, 1e4, pos, sections)
    assert np.abs(got - want).max() < 1e-5
    lanes = np.repeat(np.arange(3), sections)
    for axis in range(3):
        alone = REF.rotate(x, 1e4, jnp.repeat(pos[:, axis:axis + 1], 3, 1),
                           sections)
        mine = np.concatenate([lanes == axis, lanes == axis])
        assert np.abs(got[..., mine] - alone[..., mine]).max() < 1e-5
    node = mx.sym.RotaryEmbedding(
        mx.sym.Variable("data"), positions=mx.sym.Variable("pos"),
        with_positions=True, theta=1e4, sections=sections)
    exe = node.simple_bind(mx.cpu(), data=x.shape, grad_req="null")
    exe.arg_dict["data"][:] = np.asarray(x)
    exe.arg_dict["pos"][:] = np.asarray(pos)
    exe.forward(is_train=False)
    assert np.abs(exe.outputs[0].asnumpy() - want).max() < 1e-5


def test_the_positions_input_with_equal_axes_is_the_text_model():
    data, label = _batch(2)
    t = TINY["seq_len"]
    rows = np.broadcast_to(np.arange(t, dtype=np.float32), (BATCH, 3, t))
    text = _module(keye_lm(**TINY))
    placed = _module(keye_lm(**TINY, positions=True), positions=rows)
    assert "positions" in placed._symbol.list_arguments()
    assert "positions" not in text._symbol.list_arguments()
    placed.set_params(*text.get_params())
    out = []
    for mod, inputs in ((text, [data]), (placed, [data, rows])):
        mod.forward(mx.io.DataBatch(
            data=[mx.nd.array(x) for x in inputs],
            label=[mx.nd.array(label)], pad=0), is_train=False)
        out.append([o.asnumpy() for o in mod.get_outputs()])
    for a, b in zip(*out):
        assert np.allclose(a, b, rtol=1e-5, atol=1e-6)
    # ... and moved axes move the result, through the reference's too
    moved = rows.copy()
    moved[:, 1] += 5.0
    moved[:, 2] = moved[:, 2][:, ::-1]
    placed.forward(mx.io.DataBatch(
        data=[mx.nd.array(data), mx.nd.array(moved)],
        label=[mx.nd.array(label)], pad=0), is_train=False)
    loss = placed.get_outputs()[0].asnumpy().mean()
    params = {k: v.asnumpy() for k, v in text.get_params()[0].items()}
    want = REF.loss_and_grads({"model": {"kwargs": TINY}}, params, data,
                              label, names=[], positions=moved)
    assert loss == pytest.approx(want["loss"], rel=1e-5)
    assert abs(loss - out[0][0].mean()) > 1e-4


def _fit(net, data, label):
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(data, label, batch_size=BATCH), num_epoch=1,
            eval_metric=mx.metric.OutputMean(0), optimizer="adam",
            initializer=mx.init.Normal(0.02), optimizer_params=dict(ADAM))
    counters = mx.trace.counter_events(["dsa:select", "moe:load"])
    spans = mx.trace.span_events(names=["fit:step", "fit:dsa_select",
                                        "fit:update_metric"])
    return mod, counters, spans


def _tokens(t, steps=4, vocab=50):
    rng = np.random.RandomState(5)
    return (rng.randint(0, vocab, (steps * BATCH, t)).astype(np.int32),
            rng.randint(0, vocab, (steps * BATCH, t)).astype(np.int32))


def test_fit_records_the_selection_once_a_step_and_block():
    data, label = _tokens(32)
    was = mx.trace.enabled()
    mx.trace.reset()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(keye_lm(**TINY), data, label)
        table = mx.trace.program_scopes("fused:step")
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert mod._fused.head("dsa_select") == 6 == DSA_SELECT.find(mod._symbol)
    samples = [e for e in counters if e["name"] == "dsa:select"]
    assert len(samples) == 4 * 2
    assert sorted({e["id"] for e in samples}) == ["l0", "l1"]
    kept = REF.selected_pairs(32, 8)
    for e in samples:
        a = dict(e["args"])
        assert 0.0 < a.pop("kl") < 5.0
        assert a == {"rows": 64.0, "selected_pairs": 2.0 * kept,
                     "causal_pairs": 2.0 * 32 * 33 / 2, "tiles_hit": 2.0,
                     "tiles_causal": 2.0}
    # inside the step, after the metric, once
    steps = [(e["ts"], e["ts"] + e["dur"]) for e in spans
             if e["name"] == "fit:step"]
    notes = [e for e in spans if e["name"] == "fit:dsa_select"]
    assert len(steps) == len(notes) == 4
    for (lo, hi), e in zip(steps, notes):
        assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi
    # the device scopes of the step: the indexer's plain ops and the op's
    # four passes, a block each
    kinds = {s.partition(".")[0] for s in table.values()}
    assert {"dsa_index", "dsa_score", "dsa_select", "dsa_attn", "dsa_kl",
            "attn_proj", "moe_experts", "lm_loss", "optimizer"} <= kinds
    # no ``attn`` scope; what the node's generic scope keeps is its
    # loops' plumbing (the ``while`` operations, which span their bodies
    # in a device trace, their counters and slices)
    assert "attn" not in kinds
    assert {"dsa_attn.l0", "dsa_attn.l1", "dsa_kl.l1",
            "dsa_index.l0"} <= set(table.values())


@pytest.mark.parametrize("builder,kwargs,shape", [
    (olmoe_lm, dict(num_layers=2, hidden_size=32, num_heads=2,
                    num_experts=8, experts_per_tok=2, expert_width=16,
                    vocab_size=50, seq_len=16), (16, 16)),
    (sdar_moe_lm, dict(num_layers=2, hidden_size=32, num_heads=4,
                       num_kv_heads=2, head_dim=8, num_experts=8,
                       experts_per_tok=2, expert_width=16, vocab_size=50,
                       seq_len=16, block_len=4), (32, (2, 16)))],
    ids=["olmoe", "sdar"])
def test_no_selection_counter_for_a_symbol_without_the_head(builder, kwargs,
                                                            shape):
    rng = np.random.RandomState(8)
    data = rng.randint(0, 49, (4 * BATCH, shape[0])).astype(np.int32)
    label = rng.randint(0, 49, (4 * BATCH,) + (
        shape[1] if isinstance(shape[1], tuple) else (shape[1],)))
    label = label.astype(np.float32 if builder is sdar_moe_lm else np.int32)
    was = mx.trace.enabled()
    mx.trace.reset()
    mx.trace.set_enabled(True)
    try:
        mod, counters, spans = _fit(builder(**kwargs), data, label)
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert mod._fused.head("dsa_select") is None
    assert not [e for e in counters if e["name"] == "dsa:select"]
    assert not [e for e in spans if e["name"] == "fit:dsa_select"]
    assert [e for e in counters if e["name"] == "moe:load"]


def test_nothing_is_recorded_while_tracing_is_off():
    data, label = _tokens(32)
    was = mx.trace.enabled()
    mx.trace.reset()
    mx.trace.set_enabled(False)
    try:
        mod, counters, spans = _fit(keye_lm(**TINY), data, label)
    finally:
        mx.trace.set_enabled(was)
    assert mod._fused.head("dsa_select") == 6
    assert not counters and not spans


def test_the_builder_refuses_sizes_that_are_no_model():
    with pytest.raises(ValueError):
        keye_lm(**dict(TINY, num_kv_heads=3))
    with pytest.raises(ValueError):
        keye_lm(**dict(TINY, mrope_sections=(1, 1, 1)))
    # the expert layers' nodes are mirrored, and only they
    nodes = json.loads(keye_lm(**TINY).tojson())["nodes"]
    marked = {n["name"] for n in nodes
              if (n.get("attr") or n.get("attrs") or {}).get(
                  "force_mirroring")}
    assert marked and all("moe" in name or "ffn" in name
                          for name in marked), sorted(marked)


def test_reference_flops_count_what_the_selection_keeps():
    cfg = {"model": {"kwargs": dict(TINY, num_experts=8, experts_held=4)}}
    D, T, L = 32, 32, 2
    proj = 2 * D * 8 * (2 * 4 + 2 * 2)
    index_proj = 2 * D * (2 * 4 + 4 + 2)
    scores = 2 * 2 * 4 * (T + 1) / 2
    attention = 4 * 8 * 4 * REF.selected_pairs(T, 8) / T
    sparse = 2 * D * 8 + 2 * 4 / 8 * 6 * D * 16
    forward = L * (proj + index_proj + scores + attention + sparse) \
        + 2 * D * 50
    assert REF.train_flops_per_sample(cfg) == pytest.approx(
        3 * forward - L * index_proj)
    whole = {"model": {"kwargs": dict(TINY, topk=64)}}
    assert REF.train_flops_per_sample(whole) > REF.train_flops_per_sample(cfg)
    assert REF.selected_pairs(8192, 2048) == 14_681_088


# -- ISSUE 70: q's and k's norm and rotation, one node on the rows ---------
@pytest.mark.parametrize("positions", [False, True])
def test_q_and_k_are_placed_by_one_node_on_the_rows(positions):
    """Every layer's q and k are normed and turned by the three position
    axes' sections by ONE ``HeadNormRotary`` under ``attn_proj.l<i>``,
    the ``positions`` input its third where the builder has one; the
    indexer's 4-D rotations keep their nodes."""
    net = keye_lm(**dict(TINY, positions=positions))
    where = ["positions"] * positions
    placed = placed_on_rows(net)
    assert [(name, scope, ins) for name, scope, _, ins in placed] == [
        ("l%d_%s_norm" % (l, x), "attn_proj.l%d" % l,
         ["l%d_%s_proj" % (l, x), "l%d_%s_norm_gamma" % (l, x)] + where)
        for l in range(TINY["num_layers"]) for x in "qk"]
    for _, _, how, _ in placed:
        assert (how["head_dim"], how["norm"], how["seq_len"], how["theta"],
                tuple(how["sections"]), bool(how["with_positions"])) == (
            TINY["head_dim"], True, TINY["seq_len"], TINY["rope_theta"],
            tuple(TINY["mrope_sections"]), positions)
    turned = nodes(net, "RotaryEmbedding")
    assert len(turned) == 2 * TINY["num_layers"]
    assert {n.attrs["__scope__"].split(".")[0] for n in turned} \
        == {"dsa_index"}
