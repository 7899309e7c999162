"""Ouro through the Symbol graph (ISSUE 54, tier-1): the whole tiny model
against ``benchmark/reference/ouro-2.6b.py`` in float32 (the objective,
every gradient, Adam's first step), the reference's stage-by-stage form
against ``jax.grad`` of its whole form, the exit distribution and the
gate's gradient against their closed forms, the FLOP count at one pass
against a plain dense decoder's, and what the graph lists, names and
feeds the trace."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.models import ouro_lm                      # noqa: E402
from mxnet_tpu.models.ouro import exit_objective          # noqa: E402
from mxnet_tpu.symbol import _topo                        # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, placed_on_rows      # noqa: E402

REF = manifest.load_module("reference", "ouro-2.6b")

TINY = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=4,
            head_dim=8, mlp_width=48, vocab_size=50, seq_len=16,
            total_ut_steps=4, rope_theta=1e6, rms_eps=1e-6, exit_beta=0.1)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(np.asarray(got) - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = ouro_lm(**kwargs)
    T = kwargs["seq_len"]
    arg_shapes, _, _ = net.infer_shape(data=(BATCH, T),
                                       softmax_label=(BATCH, T))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gamma"):
            params[name] = (1 + 0.1 * rng.randn(*shape)).astype(np.float32)
        else:
            # wide enough that the gate and attention are not flat
            params[name] = (0.2 * rng.randn(*shape)).astype(np.float32)
    tokens = rng.randint(0, kwargs["vocab_size"],
                         (BATCH, T)).astype(np.int32)
    return net, {"model": {"kwargs": kwargs}}, params, tokens, \
        np.roll(tokens, -1, axis=1)


def _bound(net, params, tokens, labels, optimizer, optimizer_params):
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", tokens.shape)],
             label_shapes=[("softmax_label", labels.shape)])
    mod.init_params(mx.init.Zero(), arg_params={
        k: mx.nd.array(v) for k, v in params.items()}, allow_missing=True)
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=optimizer_params)
    assert mod._fused is not None
    return mod, mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)], pad=0)


def _sgd_gradients(net, params, tokens, labels, lr=0.125):
    mod, batch = _bound(net, params, tokens, labels, "sgd", {
        "learning_rate": lr, "momentum": 0.0, "wd": 0.0,
        "rescale_grad": 1.0})
    mod.forward_backward(batch)
    mod.update()
    outs = {n: o.asnumpy() for n, o in zip(net.list_outputs(),
                                           mod.get_outputs())}
    after, _ = mod.get_params()
    return outs, {k: (params[k] - after[k].asnumpy()) / lr for k in params}


def _reference_gradients(cfg, params, tokens, labels):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: REF.objective(cfg, p, jnp.asarray(tokens),
                                    jnp.asarray(labels)))(
            {k: jnp.asarray(v) for k, v in params.items()})


# -- the graph ------------------------------------------------------------------
def test_the_graph_lists_each_weight_once_and_the_loop_is_one_node():
    net = ouro_lm(**TINY)
    assert net.list_outputs() == ["lm_output", "loop_exit_output"]
    names = net.list_arguments()
    assert len(names) == len(set(names))
    # 2 layers x (4 norms + 4 attention + 3 MLP projections), the
    # embedding, the final norm, the head, the gate's two, two inputs
    assert len(names) == 2 * 11 + 5 + 2
    for l in range(2):
        assert names.count("l%d_q_proj_weight" % l) == 1
    loops = [n for n in _topo(net._heads)
             if not n.is_variable and n.op.name == "Repeat"]
    assert len(loops) == 1 and loops[0].params.num_steps == 4
    assert loops[0].params.recompute is True
    # whatever the number of passes, the graph is the same size
    once = ouro_lm(**dict(TINY, total_ut_steps=1))
    assert once.list_arguments() == names
    shapes = dict(zip(names, net.infer_shape(
        data=(BATCH, 16), softmax_label=(BATCH, 16))[0]))
    assert shapes["l1_q_proj_weight"] == (32, 32)
    assert shapes["l1_down_proj_weight"] == (32, 48)
    assert shapes["lm_head_weight"] == (50, 32)
    assert shapes["exit_gate_weight"] == (1, 32)
    assert shapes["exit_gate_bias"] == (1,)
    assert net.infer_shape(data=(BATCH, 16),
                           softmax_label=(BATCH, 16))[1] \
        == [(BATCH * 16,), (5,)]
    again = mx.sym.load_json(net.tojson())
    assert again.tojson() == net.tojson()
    assert again.list_arguments() == names
    with pytest.raises(ValueError):
        ouro_lm(**dict(TINY, num_heads=3))


def test_the_initializer_reaches_the_loops_weights_by_name():
    net = ouro_lm(**TINY)
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, 16))],
             label_shapes=[("softmax_label", (BATCH, 16))])
    mx.random.seed(3)
    mod.init_params(mx.init.Normal(0.02))
    args, aux = mod.get_params()
    assert not aux
    assert np.all(args["l1_attn_post_norm_gamma"].asnumpy() == 1.0)
    assert np.all(args["exit_gate_bias"].asnumpy() == 0.0)
    q = args["l1_q_proj_weight"].asnumpy()
    assert 0.015 < q.std() < 0.025 and abs(q.mean()) < 0.005


def test_the_embeddings_own_initializer_wins_over_the_modules():
    """``embed_sigma``: the embedding variable carries its initializer
    (``Variable(init=)``, the attribute ``__init__``) and
    ``Module.init_params`` uses it for that variable alone; without it
    the symbol has no such attribute."""
    # (its device scope apart: ``embed`` names the table it makes)
    assert ouro_lm(**TINY).attr_dict()["embed_weight"] == {
        "__scope__": "embed"}
    net = ouro_lm(**dict(TINY, embed_sigma=4.0))
    assert net.attr_dict()["embed_weight"] == {
        "__init__": mx.init.Normal(4.0).dumps()}
    assert mx.sym.load_json(net.tojson()).attr_dict()["embed_weight"] \
        == net.attr_dict()["embed_weight"]
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (BATCH, 16))],
             label_shapes=[("softmax_label", (BATCH, 16))])
    mx.random.seed(4)
    mod.init_params(mx.init.Normal(0.02))
    args, _ = mod.get_params()
    assert 3.5 < args["embed_weight"].asnumpy().std() < 4.5
    assert 0.015 < args["lm_head_weight"].asnumpy().std() < 0.025
    # weights handed over still win over both
    mod.init_params(mx.init.Normal(0.02), force_init=True, allow_missing=True,
                    arg_params={"embed_weight": mx.nd.ones((50, 32))})
    args, _ = mod.get_params()
    assert np.all(args["embed_weight"].asnumpy() == 1.0)


@pytest.mark.parametrize("init", [
    mx.init.Uniform(0.3), mx.init.Normal(2.0), mx.init.Orthogonal(),
    mx.init.Xavier(magnitude=2), mx.init.MSRAPrelu(), mx.init.One(),
    mx.init.Zero()], ids=lambda i: type(i).__name__)
def test_an_initializer_comes_back_from_its_dump(init):
    again = mx.init.create(init.dumps())
    assert isinstance(init, type(again)) and vars(again) == vars(init)
    # a string is taken as the dump it is
    v = mx.sym.Variable("w", init=init.dumps())
    assert v.attr("__init__") == init.dumps()
    with pytest.raises(mx.base.MXNetError):
        mx.init.create('["nosuch", {}]')


# -- the whole model against the reference ---------------------------------------
def test_model_matches_reference_loss_and_every_gradient():
    """float32 against the float32 reference: 1e-5 on the objective (a
    mean of 32 float32 rows), 2e-4 on a gradient's norm (four passes of
    sums in another order than the reference's)."""
    net, cfg, params, tokens, labels = _tiny(7)
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    want, want_grads = _reference_gradients(cfg, params, tokens, labels)
    assert abs(outs["lm_output"].mean() - float(want)) \
        <= 1e-5 * abs(float(want))
    assert set(grads) == set(want_grads)
    worst = {k: _rel(grads[k], want_grads[k]) for k in grads}
    assert max(worst.values()) <= 2e-4, worst
    # every pass reaches every weight: the gate's too
    assert float(np.abs(want_grads["exit_gate_weight"]).max()) > 1e-4
    # the counter head: the rows' summed p_t, then their summed ce_R
    staged = REF.loss_and_grads(cfg, params, tokens, labels)
    head = outs["loop_exit_output"]
    rows = BATCH * 16
    np.testing.assert_allclose(head[:4] / rows, staged["p"], rtol=1e-5)
    np.testing.assert_allclose(head[4] / rows, staged["ce"][-1], rtol=1e-5)
    assert abs(head[:4].sum() - rows) <= 1e-4 * rows


def test_model_matches_reference_adam_step():
    net, cfg, params, tokens, labels = _tiny(8)
    names = ["l0_q_proj_weight", "l1_down_proj_weight",
             "l1_ffn_post_norm_gamma", "final_norm_gamma",
             "exit_gate_weight", "embed_weight", "lm_head_weight"]
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, _ = mod.get_params()
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    assert abs(float(mod.get_outputs()[0].asnumpy().mean()) - want["loss"]) \
        <= 1e-5 * abs(want["loss"])
    for n in names:
        # Adam's first step is lr * sign-like: an element whose gradient
        # is near zero may differ by its whole size
        assert _rel(after[n].asnumpy() - params[n], want["updates"][n]) \
            <= 2e-2, n
    assert len(want["exit"]["p"]) == 4
    assert abs(sum(want["exit"]["p"]) - 1.0) <= 1e-5


def test_reference_stage_by_stage_equals_grad_of_the_whole(monkeypatch):
    """``loss_and_grads`` (what the chip runs, one stage at a time, the
    head over blocks of rows) against ``jax.grad`` of ``objective``."""
    _, cfg, params, tokens, labels = _tiny(9)
    monkeypatch.setattr(REF, "ROWS", 8)          # four blocks of rows
    staged = REF.loss_and_grads(cfg, params, tokens, labels)
    want, want_grads = _reference_gradients(cfg, params, tokens, labels)
    assert abs(staged["loss"] - float(want)) <= 1e-6 * abs(float(want))
    assert set(staged["grads"]) == set(want_grads)
    worst = {k: _rel(staged["grads"][k], want_grads[k])
             for k in want_grads}
    assert max(worst.values()) <= 1e-5, worst
    only = REF.loss_and_grads(cfg, params, tokens, labels,
                              ["l1_up_proj_weight"])
    assert list(only["grads"]) == ["l1_up_proj_weight"]


def test_reference_reads_fewer_key_value_heads():
    """Grouped queries in the reference's two forms (the configuration
    has as many key/value heads as query heads; the builder takes both)."""
    net, cfg, params, tokens, labels = _tiny(10, num_kv_heads=2)
    staged = REF.loss_and_grads(cfg, params, tokens, labels)
    want, want_grads = _reference_gradients(cfg, params, tokens, labels)
    assert max(_rel(staged["grads"][k], want_grads[k])
               for k in want_grads) <= 1e-5
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    assert max(_rel(grads[k], want_grads[k]) for k in grads) <= 2e-4


# -- the objective ---------------------------------------------------------------
def _objective_program(steps, beta):
    ce, gate = mx.sym.Variable("ce"), mx.sym.Variable("gate")
    rows, p = exit_objective(ce, gate, steps, beta)
    return mx.sym.Group([mx.sym.MakeLoss(rows, normalization="batch"),
                         mx.sym.BlockGrad(p)])


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_exit_distribution_sums_to_one_and_matches_the_reference(steps):
    rng = np.random.RandomState(steps)
    ce = rng.rand(steps, 12).astype(np.float32) * 5
    gate = (rng.randn(steps, 12) * 4).astype(np.float32)
    ex = _objective_program(steps, 0.1).bind(
        mx.cpu(0), {"ce": mx.nd.array(ce), "gate": mx.nd.array(gate)})
    rows, p = (o.asnumpy() for o in ex.forward())
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, REF.exit_distribution(jnp.asarray(gate)),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(
        rows.mean(), REF.exit_loss(jnp.asarray(ce), jnp.asarray(gate), 0.1),
        rtol=1e-5)


def test_gate_gradient_matches_the_closed_form_at_two_passes():
    """R = 2: ``p = (lambda, 1 - lambda)``, so the objective's derivative
    by the first gate's logit is ``lambda (1 - lambda) [(ce_1 - ce_2) +
    beta (log lambda - log (1 - lambda))]`` over the rows, and the second
    gate's logit gets none: the last pass takes what is left."""
    rng = np.random.RandomState(2)
    n, beta = 10, 0.1
    ce = rng.rand(2, n).astype(np.float32) * 5
    gate = (rng.randn(2, n) * 2).astype(np.float32)
    args = {"ce": mx.nd.array(ce), "gate": mx.nd.array(gate)}
    grads = {k: mx.nd.zeros(v.shape) for k, v in args.items()}
    ex = _objective_program(2, beta).bind(mx.cpu(0), args, args_grad=grads)
    ex.forward(is_train=True)
    ex.backward()
    lam = 1.0 / (1.0 + np.exp(-gate[0].astype(np.float64)))
    want = lam * (1 - lam) * ((ce[0] - ce[1])
                              + beta * (np.log(lam) - np.log1p(-lam))) / n
    np.testing.assert_allclose(grads["gate"].asnumpy()[0], want, rtol=1e-4,
                               atol=1e-7)
    assert np.all(grads["gate"].asnumpy()[1] == 0.0)
    np.testing.assert_allclose(grads["ce"].asnumpy(),
                               np.stack([lam, 1 - lam]) / n, rtol=1e-5)


# -- the FLOP count ---------------------------------------------------------------
def test_flops_at_one_pass_are_a_plain_dense_decoders():
    kw = dict(num_layers=8, hidden_size=2048, num_heads=16, num_kv_heads=16,
              head_dim=128, mlp_width=5632, vocab_size=49152, seq_len=4096)
    one = REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kw, total_ut_steps=1)}})
    layer = 3 * 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) \
        + 3.5 * 4 * 128 * 16 * (4096 + 1) / 2
    assert one == 8 * layer + 3 * 2 * 2048 * (49152 + 1)
    four = REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kw, total_ut_steps=4)}})
    assert four == 4 * one
    # the issue's figure: ~14.2 GFLOP a token
    assert 14.1e9 < four < 14.3e9


# -- the trace --------------------------------------------------------------------
def test_fit_feeds_loop_exit_once_a_step_and_the_bind_loop_body():
    net = ouro_lm(**TINY)
    rng = np.random.RandomState(0)
    X = rng.randint(0, 50, (8, 16)).astype(np.float32)
    it = mx.io.NDArrayIter(X, np.roll(X, -1, 1), batch_size=BATCH,
                           label_name="softmax_label")
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1,
                eval_metric=mx.metric.OutputMean(0, name="lm_loss"),
                optimizer="adam", optimizer_params=dict(ADAM),
                initializer=mx.init.Normal(0.02))
        exits = mx.trace.counter_events(["loop:exit"], since_ns=mark)
        bodies = mx.trace.counter_events(["loop:body"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert len(exits) == 4                      # one a step
    for e in exits:
        a = e["args"]
        assert abs(a["p1"] + a["p2"] + a["p3"] + a["p4"] - 1.0) < 1e-5
        # at this width the gate starts near lambda = 1/2: 1/2, 1/4, 1/8,
        # 1/8 (the weights are the process's draw: some room)
        assert abs(a["depth"] - 1.875) < 0.2
        assert 3.0 < a["ce_last"] < 5.0         # ln 50 = 3.9
    assert bodies and all(
        b["args"] == {"num_steps": 4, "nodes": bodies[0]["args"]["nodes"],
                      "carry_bytes": BATCH * 16 * 32 * 4, "recompute": 1,
                      "kept_passes": 1}     # the last, as the forward left it
        for b in bodies)
    # the body's nodes: what one pass is made of, not four
    assert bodies[0]["args"]["nodes"] < 60


# -- ISSUE 70: q's and k's rotation, one node on the rows ------------------
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_q_and_k_are_placed_by_one_node_on_the_rows(kv_heads):
    """This model has no head norms: every layer of the loop's body
    rotates q and k by ONE ``HeadNormRotary`` (``norm`` off, no weight)
    under ``attn_proj.l<i>``, with equal heads as with grouped ones (one
    rule, by the head's width: the traced pair of ``PERF.md`` section 6,
    PR 70, reads the cell +4.1 % with the node)."""
    net = ouro_lm(**dict(TINY, num_kv_heads=kv_heads))
    placed = placed_on_rows(net)
    assert [(name, scope, ins) for name, scope, _, ins in placed] == [
        ("l%d_%s_rotary" % (l, x), "attn_proj.l%d" % l,
         ["l%d_%s_proj" % (l, x)])
        for l in range(TINY["num_layers"]) for x in "qk"]
    for _, _, how, _ in placed:
        assert (how["head_dim"], how["norm"], how["seq_len"],
                how["theta"]) == (TINY["head_dim"], False, TINY["seq_len"],
                                  TINY["rope_theta"])
    body = [n for n in _topo(net._heads)
            if not n.is_variable and n.op.name == "Repeat"][0].params["body"]
    assert not nodes(body, "RotaryEmbedding")
