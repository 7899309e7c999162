"""GLM-4.7-Flash through the Symbol graph (ISSUE 35, tier-1): the latent
attention assembly both decoders share (compressed queries and rotated
rope parts against a per-head reference; the Kimi form bit for bit what
it was), a loss row that has no target, the whole tiny model with its
prediction module (both losses, every gradient, Adam's first step, the
bias's first move) against ``benchmark/reference/glm-4.7-flash.py`` in
float32, the two uses of the shared embedding and head, the second
head's counter and span in ``fit``, the device scopes, and the TPU
lowering of attention at 256 against 256."""
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
from mxnet_tpu import symbol as sym                       # noqa: E402
from mxnet_tpu.executor import _GraphProgram              # noqa: E402
from mxnet_tpu.models import glm_moe_lite_lm, olmoe_lm    # noqa: E402
from mxnet_tpu.models.latent_attention import latent_attention  # noqa: E402
from mxnet_tpu.moe import find_load_heads                 # noqa: E402
from mxnet_tpu.trace.heads import MTP_LOSS                # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402

import manifest                                           # noqa: E402

REF = manifest.load_module("reference", "glm-4.7-flash")

TINY = dict(num_layers=3, hidden_size=32, dense_layers=1, heads=2,
            q_lora_rank=12, kv_lora_rank=16, qk_nope_dim=8, qk_rope_dim=4,
            v_head_dim=12, rope_theta=1e6, dense_width=64, num_experts=16,
            experts_per_tok=4, expert_width=24, shared_width=24,
            routed_scale=1.8, vocab_size=50, seq_len=24, nextn_layers=1,
            mtp_weight=0.3, experts_held=4, first_expert=4, bias_rate=1e-3,
            rms_eps=1e-5)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
BLOCKS = ["l1_moe_dispatch", "l2_moe_dispatch", "mtp_moe_dispatch"]


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _run(net, values, is_train=False):
    """Outputs of ``net`` on float32 ``values`` (names -> arrays)."""
    exe = net.simple_bind(mx.cpu(), grad_req="null",
                          **{k: v.shape for k, v in values.items()})
    for k, v in values.items():
        exe.arg_dict[k][:] = v
    exe.forward(is_train=is_train)
    return [o.asnumpy() for o in exe.outputs]


# -- the loss row that has no target ------------------------------------------

def test_a_row_without_a_target_is_outside_loss_and_normalization():
    """``SoftmaxCELoss(use_ignore=True)``: a row labelled
    ``ignore_label`` reads exactly 0 and its logits take no gradient;
    ``MakeLoss(normalization="valid")`` divides by the rows that have a
    target.  Without ``use_ignore`` the label -1 is a label like any."""
    rng = np.random.RandomState(0)
    logits = rng.randn(6, 5).astype(np.float32)
    label = np.array([1, -1, 4, 0, -1, 2], np.float32)
    loss = sym.SoftmaxCELoss(sym.Variable("x"), sym.Variable("y"),
                             use_ignore=True, ignore_label=-1)
    head = sym.MakeLoss(loss, grad_scale=0.3, normalization="valid")
    exe = head.simple_bind(mx.cpu(), x=logits.shape, y=label.shape,
                           grad_req={"x": "write", "y": "null"})
    exe.arg_dict["x"][:] = logits
    exe.arg_dict["y"][:] = label
    exe.forward(is_train=True)
    exe.backward()
    out = exe.outputs[0].asnumpy()
    kept = label >= 0
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -logp[np.arange(6), label.astype(int).clip(0)]
    assert np.array_equal(out[~kept], np.zeros(2, np.float32))
    assert np.allclose(out[kept], want[kept], rtol=1e-5)
    grad = exe.grad_dict["x"].asnumpy()
    assert not grad[~kept].any()
    onehot = np.eye(5, dtype=np.float32)[label.astype(int).clip(0)]
    assert np.allclose(grad[kept], 0.3 / 4 * (np.exp(logp) - onehot)[kept],
                       rtol=1e-4, atol=1e-7)
    plain = _run(sym.SoftmaxCELoss(sym.Variable("x"), sym.Variable("y")),
                 {"x": logits, "y": np.abs(label)})[0]
    assert (plain > 0).all()


# -- the one latent attention assembly ----------------------------------------

def _kimi_mla_as_it_was(h, pre, seq_len, hidden_size, mla_heads,
                        kv_lora_rank, qk_nope_dim, qk_rope_dim, v_head_dim,
                        rms_eps, l):
    """``kimi_linear.py``'s ``mla`` as PR 31 wrote it, kept here as the
    oracle of "unchanged"."""
    def norm(x, name):
        return sym.RMSNorm(x, eps=rms_eps, name=name)

    def proj(x, name, width):
        return sym.FullyConnected(x, num_hidden=width, no_bias=True,
                                  name=name)

    qk_dim = qk_nope_dim + qk_rope_dim
    q = sym.Reshape(proj(h, pre + "q_proj", mla_heads * qk_dim),
                    shape=(-1, seq_len, mla_heads, qk_dim))
    kv_a = proj(h, pre + "kv_a_proj", kv_lora_rank + qk_rope_dim)
    latent = norm(sym.slice_axis(kv_a, axis=1, begin=0,
                                 end=kv_lora_rank), pre + "kv_a_norm")
    k_shared = sym.Reshape(
        sym.slice_axis(kv_a, axis=1, begin=kv_lora_rank,
                       end=kv_lora_rank + qk_rope_dim),
        shape=(-1, seq_len, 1, qk_rope_dim))
    kv = sym.Reshape(
        proj(latent, pre + "kv_b_proj",
             mla_heads * (qk_nope_dim + v_head_dim)),
        shape=(-1, seq_len, mla_heads, qk_nope_dim + v_head_dim))
    k = sym.Concat(
        sym.slice_axis(kv, axis=3, begin=0, end=qk_nope_dim),
        sym.broadcast_axis(k_shared, axis=2, size=mla_heads), dim=3)
    v = sym.slice_axis(kv, axis=3, begin=qk_nope_dim,
                       end=qk_nope_dim + v_head_dim)
    a = sym.CausalSelfAttention(q, k, v, layer=l, name=pre + "attn")
    return proj(sym.Reshape(a, shape=(-1, mla_heads * v_head_dim)),
                pre + "o_proj", hidden_size)


def test_the_kimi_form_through_the_shared_assembly_is_what_it_was():
    """No compression, no rotation, no scope: the same graph node for
    node (names, parameters, attributes), and so the same bits."""
    sizes = (40, 32, 2, 16, 8, 4, 12, 1e-5)
    with mx.name.NameManager():
        was = _kimi_mla_as_it_was(sym.Variable("h"), "l4_", *sizes, 4)
    with mx.name.NameManager():
        now = latent_attention(sym.Variable("h"), "l4_", *sizes, layer=4)
    assert now.tojson() == was.tojson()
    assert not any("__scope__" in a for a in now.attr_dict().values())
    rng = np.random.RandomState(1)
    shapes, _, _ = now.infer_shape(h=(80, 32))
    values = {n: rng.randn(*s).astype(np.float32)
              for n, s in zip(now.list_arguments(), shapes)}
    assert np.array_equal(_run(now, values)[0], _run(was, values)[0])


def test_compressed_queries_and_rotated_parts_against_a_per_head_reference():
    m = {"heads": 3, "qk_nope_dim": 8, "qk_rope_dim": 6, "v_head_dim": 10,
         "kv_lora_rank": 16, "q_lora_rank": 12, "rms_eps": 1e-5,
         "rope_theta": 1e4}
    b, t, d = 2, 20, 32
    net = latent_attention(
        sym.Variable("h"), "a_", t, d, m["heads"], m["kv_lora_rank"],
        m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"], m["rms_eps"],
        layer=2, q_lora_rank=m["q_lora_rank"], rope_theta=m["rope_theta"],
        scope="")
    assert set(net.list_arguments()) == {
        "h", "a_q_a_proj_weight", "a_q_a_norm_gamma", "a_q_b_proj_weight",
        "a_kv_a_proj_weight", "a_kv_a_norm_gamma", "a_kv_b_proj_weight",
        "a_o_proj_weight"}
    rng = np.random.RandomState(2)
    shapes, _, _ = net.infer_shape(h=(b * t, d))
    values = {n: (0.3 * rng.randn(*s)).astype(np.float32)
              for n, s in zip(net.list_arguments(), shapes)}
    p = {k: jnp.asarray(v) for k, v in values.items()}
    with jax.default_matmul_precision("highest"):
        want = REF.mla(p, "a_", p["h"].reshape(b, t, d), m)
    got = _run(net, values)[0]
    assert _rel(got, np.asarray(want).reshape(b * t, d)) <= 2e-5
    # the rotation is not a no-op: with the positions reversed in one
    # sequence the reference moves
    scopes = {a["__scope__"] for a in net.attr_dict().values()
              if "__scope__" in a}
    # the attention op names its own scope (attn.l2) and takes none; the
    # rotated queries' cut and the output projection are attn_proj's
    assert scopes == {"mla_q.l2", "mla_kv.l2", "rope.l2", "attn_proj.l2"}
    assert "__scope__" not in net.attr_dict().get("a_attn", {})
    theta0 = dict(m, rope_theta=1.0)        # every angle = the position
    with jax.default_matmul_precision("highest"):
        moved = REF.mla(p, "a_", p["h"].reshape(b, t, d), theta0)
    assert _rel(np.asarray(moved), np.asarray(want)) > 1e-3


def test_rotation_is_relative_and_half_split():
    """``REF.rotate`` is the program's ``rotary_embedding``; scores of
    rotated pairs depend on the distance alone."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 9, 2, 8), jnp.float32)
    assert np.allclose(REF.rotate(x, 1e6),
                       tf_ops.rotary_embedding(x, 1e6), atol=1e-6)
    q = jnp.broadcast_to(x[:, :1], x.shape)
    k = jnp.broadcast_to(x[:, 1:2], x.shape)
    s = jnp.einsum("bqhd,bkhd->bhqk", REF.rotate(q, 100.0),
                   REF.rotate(k, 100.0))
    assert np.allclose(s[0, 0, 3, 1], s[0, 0, 7, 5], rtol=1e-4)
    assert not np.allclose(s[0, 0, 3, 1], s[0, 0, 3, 2], rtol=1e-4)


@pytest.mark.parametrize("dv", [256, 128], ids=["256x256", "256x128"])
def test_attention_at_256_lanes_lowers_to_the_kernel_on_a_tpu(dv):
    """bfloat16 q, k AND v of 256 a head (GLM-4.7-Flash's 192 + 64
    against 256), lowered for a TPU, is the splash kernel, forward and
    fused backward, with nothing padded; the track names one size."""
    q = jax.ShapeDtypeStruct((1, 1024, 4, 256), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1024, 4, dv), jnp.bfloat16)
    fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, 1 / 16).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(fn, platforms=["tpu"])(q, q, v) \
            .mlir_module()
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert text.count("tpu_custom_call") == 2
    assert "stablehlo.pad" not in text
    assert events[0]["args"] == {"kernel": 1, "plain": 0, "pair": "library",
                                 "mask_form": "library"}
    assert events[0]["id"] == "bfloat16[1, 1024, 4, 256]" + (
        "" if dv == 256 else "x128")
    assert tf_ops._kernel_tiles(4096) == (1024, 512)      # every head size


# -- the whole model -----------------------------------------------------------

def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = glm_moe_lite_lm(**kwargs)
    arg_shapes, _, _ = net.infer_shape(
        data=(BATCH, kwargs["seq_len"]),
        softmax_label=(BATCH, kwargs["seq_len"]))
    rng = np.random.RandomState(seed)
    params = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gamma"):
            params[name] = (1 + 0.1 * rng.randn(*shape)).astype(np.float32)
        else:
            # wide enough that routing and attention are not flat
            params[name] = (0.2 * rng.randn(*shape)).astype(np.float32)
    tokens = rng.randint(0, kwargs["vocab_size"],
                         (BATCH, kwargs["seq_len"])).astype(np.int32)
    return net, kwargs, params, tokens, np.roll(tokens, -1, axis=1)


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
    """(outputs, {name: gradient}) through one SGD step of the fused
    train step."""
    mod, batch = _bound(net, params, tokens, labels, "sgd", {
        "learning_rate": lr, "momentum": 0.0, "wd": 0.0,
        "rescale_grad": 1.0})
    mod.forward_backward(batch)
    mod.update()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    after, _ = mod.get_params()
    return outs, {k: (params[k] - after[k].asnumpy()) / lr for k in params}


def test_the_heads_are_found_by_what_they_are():
    net, kwargs, _, _, _ = _tiny(seed=0)
    assert net.list_outputs() == ["lm_output", "mtp_output",
                                  "moe_load_output"]
    assert find_load_heads(net) == (2, BLOCKS)
    assert MTP_LOSS.find(net) == (0, 1, 0.3, 0.0)
    # the order of the group is not what finds them
    turned = sym.Group([net[2], net[1], net[0]])
    assert find_load_heads(turned)[0] == 0
    assert MTP_LOSS.find(turned)[:2] == (1, 2)
    # one loss head, or none: nothing
    assert MTP_LOSS.find(glm_moe_lite_lm(
        **dict(kwargs, nextn_layers=0))) is None
    assert MTP_LOSS.find(net[2]) is None
    # the two shared weights are one argument each, used twice
    args = net.list_arguments()
    assert args.count("embed_weight") == args.count("lm_head_weight") == 1
    assert not [a for a in args if a.startswith("mtp_embed")
                or a.startswith("mtp_lm_head")]


def test_model_matches_reference_losses_gradients_adam_step_and_bias_move(
        monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    assert abs(float(outs[0].mean()) - ref["loss"]) <= 1e-5 * ref["loss"]
    # the second head: per position, the last of each sequence exactly 0
    second = outs[1].reshape(BATCH, -1)
    assert np.array_equal(second[:, -1], np.zeros(BATCH, np.float32))
    assert abs(float(second[:, :-1].mean()) - ref["mtp_loss"]) \
        <= 1e-5 * ref["mtp_loss"]
    assert abs(ref["mtp_loss"] - ref["loss"]) > 1e-3      # another number
    for row, block in zip(outs[2], BLOCKS):
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][block]))
        assert row[-1] == 0
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 2e-4, errors

    # the configuration's optimizer: Adam's first step and the bias
    names = ["l1_q_a_proj_weight", "l1_q_b_proj_weight",
             "l1_kv_b_proj_weight", "l1_moe_gate_weight",
             "l1_moe_experts_i2h_weight", "mtp_eh_proj_weight",
             "embed_weight", "lm_head_weight"]
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    assert (want["loss"], want["mtp_loss"]) == (ref["loss"],
                                                ref["mtp_loss"])
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, aux = mod.get_params()
    for name in names:
        got = after[name].asnumpy() - params[name]
        # an element whose gradient is ~0 may flip sign: Adam's first
        # step is lr * sign(g); such elements are a sliver of the norm
        assert _rel(got, want["updates"][name]) <= 0.02, name
    assert sorted(aux) == sorted(b + "_select_bias" for b in BLOCKS)
    for block in BLOCKS:
        moved = aux[block + "_select_bias"].asnumpy()
        assert np.allclose(moved, want["bias_moves"][block], atol=1e-9)
        assert np.allclose(np.abs(moved)[moved != 0], 1e-3)


def test_the_prediction_module_alone():
    """Targets moved one place; the shared embedding's and head's
    gradients are the sums of their two uses; the weight 0.3."""
    net, kwargs, params, tokens, labels = _tiny(seed=11)
    p = {k: jnp.asarray(v) for k, v in params.items()}
    tk, lb = jnp.asarray(tokens), jnp.asarray(labels)
    shared = ("embed_weight", "lm_head_weight")

    def losses(trunk_w, module_w):
        """(L_main, L_mtp) with the module reading ITS OWN copies."""
        pt = {**p, **dict(zip(shared, trunk_w))}
        x, _ = REF.trunk(pt, tk, kwargs)
        main = REF.cross_entropy(
            REF.head(pt, x, "final_norm_gamma", kwargs), lb).mean()
        logits2, _ = REF.prediction_module(
            {**p, **dict(zip(shared, module_w))}, x, lb, kwargs)
        return main, REF.mtp_loss(logits2, lb), logits2

    both = tuple(p[n] for n in shared)
    with jax.default_matmul_precision("highest"):
        main, mtp, logits2 = losses(both, both)
        g_trunk, g_module = jax.grad(
            lambda a, b: losses(a, b)[0] + 0.3 * losses(a, b)[1],
            argnums=(0, 1))(both, both)
        g_eh = jax.grad(lambda w: REF.mtp_loss(REF.prediction_module(
            {**p, "mtp_eh_proj_weight": w},
            REF.trunk(p, tk, kwargs)[0], lb, kwargs)[0], lb))(
                p["mtp_eh_proj_weight"])
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    # position i of the module is scored against the label of i + 1
    b, t = labels.shape
    per_row = np.asarray(REF.cross_entropy(
        logits2, jnp.roll(lb, -1, axis=1))).reshape(b, t)
    assert np.allclose(outs[1].reshape(b, t)[:, :-1], per_row[:, :-1],
                       rtol=1e-4, atol=1e-6)
    unmoved = np.asarray(REF.cross_entropy(logits2, lb)).reshape(b, t)
    assert _rel(outs[1].reshape(b, t)[:, :-1], unmoved[:, :-1]) > 0.1
    assert abs(float(outs[1].sum()) / (b * (t - 1)) - float(mtp)) \
        <= 1e-5 * float(mtp)
    assert abs(float(outs[0].mean()) - float(main)) <= 1e-5 * float(main)
    # one argument, two uses: the step's gradient is their sum, and
    # neither use is nothing
    for name, one, two in zip(shared, g_trunk, g_module):
        one, two = np.asarray(one), np.asarray(two)
        assert _rel(grads[name], one + two) <= 2e-4, name
        assert _rel(one + two, one) > 0.05 and _rel(one + two, two) > 0.05
    # a weight only the module reads takes 0.3 x the second loss's own
    assert _rel(grads["mtp_eh_proj_weight"], 0.3 * np.asarray(g_eh)) <= 2e-4
    # with no module the trunk's graph is the one-head model's
    plain = glm_moe_lite_lm(**dict(kwargs, nextn_layers=0))
    assert plain.list_outputs() == ["lm_output", "moe_load_output"]
    assert not [a for a in plain.list_arguments() if a.startswith("mtp_")]


# -- fit, its counter and span, the device scopes ------------------------------

def _fit(net, vocab, seq_len, steps=4):
    rng = np.random.RandomState(0)
    X = rng.randint(0, vocab, (steps * BATCH, seq_len)).astype(np.int32)
    it = mx.io.NDArrayIter(X, np.roll(X, -1, 1), batch_size=BATCH)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        since = time.perf_counter_ns()
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1, eval_metric=mx.metric.OutputMean(0),
                optimizer="adam", initializer=mx.init.Normal(0.02),
                optimizer_params=dict(ADAM))
        counters = mx.trace.counter_events(["mtp:loss", "moe:load"],
                                           since_ns=since)
        spans = mx.trace.span_events(
            names=["fit:step", "fit:mtp_loss", "fit:moe_load",
                   "fit:update_metric"], since_ns=since)
    finally:
        mx.trace.reset()         # the ring is the process's: leave none
        mx.trace.set_enabled(was)
    return mod, counters, spans


def test_fit_records_the_second_heads_loss_once_a_step():
    net, kwargs, _, _, _ = _tiny(seed=3)
    mod, counters, spans = _fit(net, kwargs["vocab_size"],
                                kwargs["seq_len"])
    assert mod._fused.head("mtp_loss") == (0, 1, 0.3, 0.0)
    losses = [e["args"] for e in counters if e["name"] == "mtp:loss"]
    assert len(losses) == 4
    chance = np.log(kwargs["vocab_size"])
    for a in losses:
        assert set(a) == {"main", "mtp", "weight"} and a["weight"] == 0.3
        assert abs(a["main"] - chance) < 0.5 and abs(a["mtp"] - chance) < 0.5
        assert a["main"] != a["mtp"]
    loads = [e for e in counters if e["name"] == "moe:load"]
    assert len(loads) == 4 * len(BLOCKS)
    assert {e["id"] for e in loads} == set(BLOCKS)
    # the span: once a step, inside fit:step, outside fit:update_metric
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append((e["ts"],
                                                  e["ts"] + e["dur"]))
    assert len(by_name["fit:mtp_loss"]) == len(by_name["fit:moe_load"]) == 4

    def inside(span, others):
        return any(a <= span[0] and span[1] <= b for a, b in others)

    for span in by_name["fit:mtp_loss"]:
        assert inside(span, by_name["fit:step"])
        assert not inside(span, by_name["fit:update_metric"])
        assert not inside(span, by_name["fit:moe_load"])


def test_a_symbol_with_one_loss_head_records_neither():
    net = olmoe_lm(num_layers=1, hidden_size=16, num_heads=2,
                   num_experts=4, experts_per_tok=2, expert_width=12,
                   vocab_size=40, seq_len=16)
    mod, counters, spans = _fit(net, 40, 16)
    assert mod._fused.head("mtp_loss") is None
    assert not [e for e in counters if e["name"] == "mtp:loss"]
    assert not [e for e in spans if e["name"] == "fit:mtp_loss"]
    assert [e for e in counters if e["name"] == "moe:load"]
    assert [e for e in spans if e["name"] == "fit:moe_load"]


def test_nothing_is_read_for_the_counter_while_tracing_is_off():
    net, kwargs, _, _, _ = _tiny(seed=3)
    was = mx.trace.enabled()
    mx.trace.set_enabled(False)
    try:
        since = time.perf_counter_ns()
        rng = np.random.RandomState(0)
        X = rng.randint(0, 50, (2 * BATCH, kwargs["seq_len"])) \
            .astype(np.int32)
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(mx.io.NDArrayIter(X, np.roll(X, -1, 1), batch_size=BATCH),
                num_epoch=1, eval_metric=mx.metric.OutputMean(0),
                optimizer="adam", initializer=mx.init.Normal(0.02),
                optimizer_params=dict(ADAM))
    finally:
        mx.trace.set_enabled(was)
    assert not mx.trace.counter_events(["mtp:loss"], since_ns=since)
    assert mod._fused.moe_stats.report()["blocks"]     # MoeStats still fed


def test_device_scopes_name_the_blocks_parts_and_the_module():
    """The step's lowered text carries the scopes a by-scope reader will
    look for; a node without ``__scope__`` enters none."""
    net, kwargs, params, tokens, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(tokens), softmax_label=jnp.asarray(labels))
    aux = {b + "_select_bias": jnp.zeros((kwargs["num_experts"],))
           for b in BLOCKS}
    text = jax.jit(lambda a, x: prog.eval(a, x, jax.random.PRNGKey(0),
                                          True)[0]).lower(args, aux) \
        .as_text(debug_info=True)
    for scope in ("mla_q.l0", "mla_kv.l1", "rope.l2", "attn.l2",
                  "moe_experts.l1", "moe_route.l2", "lm_loss",
                  "mtp.eh_proj", "mtp.mla_q", "mtp.mla_kv", "mtp.rope",
                  "mtp.attn", "mtp.moe_experts", "mtp.lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope
    assert "mtp.attn.l" not in text and "mtp.moe_experts.l" not in text
    # no scope is entered twice, and the trunk's own take no prefix
    assert "attn.l2/attn.l2" not in text and "mtp./" not in text
    assert "mtp.mtp." not in text
    # the prefix is gone once the node has run
    with tf_ops.node_scope("mtp."):
        pass
    with tf_ops.node_scope(None), tf_ops.layer_scope("attn", 3):
        pass
    assert getattr(tf_ops._scope, "prefix", "") == ""


def test_a_checkpoint_written_before_pr36_still_loads():
    """Two expert layers and the prediction module's block, 4 of 16
    experts held: parameters, saved graph and both losses are the commit
    before's (``tests/common/old_checkpoint.py``)."""
    from old_checkpoint import check_checkpoint_written_before_pr36
    net, _, _, tokens, labels = _tiny(seed=36)
    check_checkpoint_written_before_pr36("glm", net, tokens, labels,
                                         dict(ADAM))
