"""Qwen3-Next through the Symbol graph (ISSUE 50, tier-1): the whole tiny
model against ``benchmark/reference/qwen3-next-80b-a3b.py`` in float32
(loss, the balance scores, every gradient, Adam's first step), with each
tolerance's reason beside it and the same step in bfloat16 refused by it;
``GatedDeltaNet`` in both lowerings (the kernels under ``interpret``)
against a token-by-token recurrence, with fewer key heads than value
heads, a decay down to ``exp(-20)`` a token and, for the plain chunks, a
length that is no whole chunks; the op with its decay spread equals
``KimiDeltaAttention`` fed that decay on every lane; the kernels' head
form (ISSUE 51: the decay handed over a chunk a row) against
their lane form fed the same decay on every lane, and what the two
``pallas_call``s take and return for the decay; what the backward pass
keeps; the sixteen ranks' shares, the gated shared expert once, add
up to the uncut layer; the partial rotation leaves lanes 64.. bit for
bit; the gate half of ``q_proj`` gets gradient only through the gate;
``shared_gate`` unset is not in the graph; scopes and counters."""
import json
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
from mxnet_tpu.executor import _GraphProgram              # noqa: E402
from mxnet_tpu.models import decoder, qwen3_next_lm       # noqa: E402
from mxnet_tpu.moe import MoEFeedForward                  # noqa: E402
from mxnet_tpu.ops import linear_attention as kda_ops     # noqa: E402

from check_utils import jaxpr_eqns                        # noqa: E402
import manifest                                           # noqa: E402
from symbol_signature import nodes, signature             # noqa: E402

REF = manifest.load_module("reference", "qwen3-next-80b-a3b")

TINY = dict(num_layers=4, hidden_size=32, full_attention_interval=4,
            gdn_key_heads=2, gdn_value_heads=4, gdn_head_dim=8,
            conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=16,
            rotary_dim=4, rope_theta=1e7, num_experts=16, experts_per_tok=4,
            expert_width=24, shared_width=24, vocab_size=50, seq_len=24,
            rms_eps=1e-6, aux_coef=0.001, experts_held=4, first_expert=4)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
BLOCKS = ["l%d_moe_dispatch" % l for l in range(4)]


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = qwen3_next_lm(**kwargs)
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
            # wide enough that routing, decay and attention are not flat
            params[name] = (0.2 * rng.randn(*shape)).astype(np.float32)
    tokens = rng.randint(0, kwargs["vocab_size"],
                         (BATCH, T)).astype(np.int32)
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
    outs = {n: o.asnumpy() for n, o in zip(net.list_outputs(),
                                           mod.get_outputs())}
    after, _ = mod.get_params()
    return outs, {k: (params[k] - after[k].asnumpy()) / lr for k in params}


# -- the whole model against the reference -------------------------------------
def test_the_builder_names_its_heads_and_refuses_what_it_cannot_build():
    net = qwen3_next_lm(**TINY)
    assert net.list_outputs() == ["lm_output"] + [
        "l%d_moe_dispatch_aux_output" % l for l in range(4)] \
        + ["moe_load_output"]
    assert qwen3_next_lm(**dict(TINY, aux_coef=0.0)).list_outputs() \
        == ["lm_output", "moe_load_output"]
    names = net.list_arguments()
    # three layers of four mix by the rule, the fourth attends
    for l in range(3):
        assert "l%d_qkvz_proj_weight" % l in names
        assert "l%d_q_proj_weight" % l not in names
    assert "l3_q_proj_weight" in names and "l3_qkvz_proj_weight" not in names
    assert "l3_attn_gate_proj_weight" not in names
    shapes = dict(zip(names, net.infer_shape(data=(BATCH, 24),
                                             softmax_label=(BATCH, 24))[0]))
    assert shapes["l0_qkvz_proj_weight"] == (2 * (2 + 2 * 2) * 8, 32)
    assert shapes["l0_ba_proj_weight"] == (2 * 4, 32)
    assert shapes["l0_conv_weight"] == ((2 * 2 + 4) * 8, 4)
    assert shapes["l0_gdn_a_log_bias"] == shapes["l0_gdn_dt_bias"] == (4,)
    assert shapes["l3_q_proj_weight"] == (2 * 4 * 16, 32)
    assert shapes["l0_moe_shared_gate_weight"] == (1, 32)
    for bad in (dict(num_heads=3), dict(gdn_value_heads=3),
                dict(rotary_dim=3), dict(rotary_dim=32)):
        with pytest.raises(ValueError):
            qwen3_next_lm(**dict(TINY, **bad))


def test_model_matches_reference_loss_gradients_and_adam_step(monkeypatch):
    """float32 against the float32 reference.  The tolerances: 1e-5 on
    the loss and the balance scores (sums of T float32 terms); 5e-4 on a
    gradient's norm (the chunked rule's triangular solve and the
    token-by-token scan round differently; measured under 1e-4); 0.02 on
    an Adam update (an element whose gradient is ~0 flips sign and moves
    by 2 lr).  bfloat16 compute misses the gradient limit by two orders
    (the last test below)."""
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    assert abs(float(outs["lm_output"].mean()) - ref["loss"]) \
        <= 1e-5 * ref["loss"]
    for l, block in enumerate(BLOCKS):
        row = outs["moe_load_output"][l]
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][block]))
        assert row[-1] == 0 and row[:-1].sum() == 24 * BATCH * 4
        assert abs(float(outs[block + "_aux_output"][0]) - ref["aux"][l]) \
            <= 1e-5 * ref["aux"][l]
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 5e-4, errors

    names = ["l0_qkvz_proj_weight", "l1_gdn_dt_bias", "l1_gdn_a_log_bias",
             "l2_conv_weight", "l3_q_proj_weight",
             "l0_moe_shared_gate_weight", "l1_moe_gate_weight",
             "l1_moe_experts_i2h_weight", "embed_weight", "lm_head_weight"]
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    assert want["loss"] == ref["loss"]
    mod, batch = _bound(net, params, tokens, labels, "adam", dict(ADAM))
    mod.forward_backward(batch)
    mod.update()
    after, aux = mod.get_params()
    assert not aux
    for name in names:
        got = after[name].asnumpy() - params[name]
        assert _rel(got, want["updates"][name]) <= 0.02, name


def test_bfloat16_where_float32_is_stated_is_refused(monkeypatch):
    """The same tiny step computed in bfloat16 misses the float32
    gradient limit (5e-4) on nearly every weight: the limit holds the
    precision."""
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    ref = REF.loss_and_grads({"model": {"kwargs": kwargs}}, params, tokens,
                             labels)
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    _, grads = _sgd_gradients(net, params, tokens, labels)
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert sum(e > 5e-4 for e in errors.values()) >= 0.9 * len(errors)
    assert errors["l0_qkvz_proj_weight"] > 5e-3


def test_reference_flops_are_the_hand_count():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    D = 2048
    gdn = 2 * D * 12288 + 2 * D * 64 + 2 * 4 * 8192 + 2 * 4096 * D \
        + 6 * 32 * 128 * 128
    attn = 2 * D * 8192 + 2 * 2 * D * 512 + 2 * 4096 * D \
        + 4 * 256 * 16 * (4096 * 4097 // 2) / 4096
    moe = 2 * D * 512 + 3 * 2 * D * 512 + 2 * D \
        + 10 * 32 / 512 * 3 * 2 * D * 512
    want = 3.0 * (3 * gdn + attn + 4 * moe + 2 * D * 18992)
    assert REF.train_flops_per_sample(cfg) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(1.2808e9, rel=1e-4)
    # with every expert held the routed part is ten whole experts a token
    whole = dict(cfg["model"]["kwargs"], experts_held=0)
    assert REF.train_flops_per_sample({"model": {"kwargs": whole}}) \
        == pytest.approx(want + 3 * 4 * (10 - 0.625) * 6 * D * 512,
                         rel=1e-12)


# -- the op: both lowerings against the token-by-token recurrence --------------
def _gdn_inputs(t, lo, hi, hk=1, hv=2, d=128, seed=0, batch=1):
    """q, k at ``hk`` heads, v at ``hv``, the decay's and beta's
    projections, ``a_log`` and ``dt_bias`` such that the log-decay a
    token lies in ``(lo, hi)``."""
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.randn(batch, t, hk, d), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(batch, t, hv, d), jnp.float32)
    g = rng.uniform(lo, hi, (batch, t, hv))
    a_log = rng.uniform(-1, 1, (hv,))
    dt_bias = rng.uniform(-1, 1, (hv,))
    # softplus(decay + dt_bias) = -g / exp(a_log)
    sp = -g / np.exp(a_log)
    decay = np.log(np.expm1(sp)) - dt_bias
    beta = rng.uniform(-3, 3, (batch, t, hv))
    return (q, k, v, jnp.asarray(decay, jnp.float32),
            jnp.asarray(beta, jnp.float32), jnp.asarray(a_log, jnp.float32),
            jnp.asarray(dt_bias, jnp.float32))


def _token_by_token(q, k, v, decay, beta, a_log, dt_bias):
    """The op's definition from the reference's pieces."""
    group = v.shape[2] // q.shape[2]
    g = -jnp.exp(a_log) * jax.nn.softplus(decay + dt_bias)
    return REF.delta_rule(
        jnp.repeat(REF.l2norm(q), group, axis=2),
        jnp.repeat(REF.l2norm(k), group, axis=2), v, g,
        jax.nn.sigmoid(beta))


def _value_and_grads(fn, args):
    w = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(
        args[2].shape)
    return jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
        argnums=tuple(range(7)))(*args)


@pytest.mark.parametrize("lo,hi", [(-0.01, -1e-4), (-20.0, -5.0),
                                   (-20.0, -1e-4)],
                         ids=["decay-near-1", "decay-near-0", "wide"])
def test_plain_chunks_match_the_token_recurrence(lo, hi):
    """The plain lowering, T = 150 (two whole chunks and a tail of 22),
    2 key heads under 6 value heads of 16: the output and all seven
    gradients, the decay's through ``a_log`` and ``dt_bias``."""
    args = _gdn_inputs(150, lo, hi, hk=2, hv=6, d=16, batch=2)
    with jax.default_matmul_precision("highest"):
        (got, grads), (want, ref_grads) = (
            _value_and_grads(fn, args)
            for fn in (kda_ops.gated_delta_net, _token_by_token))
        out = kda_ops.gated_delta_net(*args)
        ref = _token_by_token(*args)
    assert out.shape == args[2].shape
    assert np.abs(np.asarray(out - ref)).max() \
        <= 1e-5 * np.abs(np.asarray(ref)).max()
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want))
    # a_log's and dt_bias's gradients are sums of g's over every token of
    # a head, of both signs and weighted by |g| up to 20: they cancel to
    # a tenth of their terms, so float32 leaves them ten times less
    for x, y, tol in zip(grads, ref_grads, [5e-4] * 5 + [5e-3] * 2):
        assert x.shape == y.shape
        assert np.abs(np.asarray(x - y)).max() \
            <= tol * np.abs(np.asarray(y)).max()


@pytest.mark.parametrize("lo,hi", [(-3.0, -0.01), (-20.0, -1e-4),
                                   (-0.01, -1e-4), (-20.0, -5.0)],
                         ids=["mixed", "wide", "decay-near-1",
                              "decay-near-0"])
def test_kernels_match_the_token_recurrence(lo, hi):
    """The kernel lowering under the Pallas interpreter: heads of 128,
    two chunks, 1 key head under 2 value heads, the head's decay handed
    to the kernels as it is, one number a head and token (their head
    form, ISSUE 51: the chunk's scores as one product under a ``(C, C)``
    decay matrix): the output and all seven gradients (q and k summed
    back over a key head's value heads, the decay's summed over the
    lanes inside the backward kernel), the decay from nearly none to
    ``exp(-20)`` a token."""
    args = _gdn_inputs(128, lo, hi)
    assert kda_ops._kernel_takes(args[0], args[2])
    with jax.default_matmul_precision("highest"):
        (got, grads), (want, ref_grads) = (
            _value_and_grads(fn, args) for fn in (
                lambda *a: kda_ops.gated_delta_net(*a, interpret=True),
                _token_by_token))
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want))
    # the five arrays a token agree to 1e-5 of their largest element;
    # a_log's and dt_bias's are sums over T of those, weighted by |g| up
    # to 20, that cancel: 1.4e-2 and 3e-3 in the wide band (measured)
    for x, y, tol in zip(grads, ref_grads, [1e-4] * 5 + [3e-2] * 2):
        assert x.shape == y.shape
        assert np.abs(np.asarray(x - y)).max() \
            <= tol * np.abs(np.asarray(y)).max()


def test_a_heads_decay_is_kimis_rule_with_that_decay_on_every_lane():
    """``GatedDeltaNet`` equals ``KimiDeltaAttention`` fed the same q
    and k at the value's heads, the decay's projection on every lane and
    ``dt_bias`` repeated: one rule, two front ends."""
    q, k, v, decay, beta, a_log, dt_bias = _gdn_inputs(
        96, -3.0, -0.01, hk=2, hv=4, d=16)
    with jax.default_matmul_precision("highest"):
        got = kda_ops.gated_delta_net(q, k, v, decay, beta, a_log, dt_bias)
        want = kda_ops.kimi_delta_attention(
            jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v,
            jnp.broadcast_to(decay[..., None], v.shape), beta, a_log,
            jnp.repeat(dt_bias, 16))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lo,hi", [(-3.0, -0.01), (-20.0, -1e-4),
                                   (-0.01, -1e-4)],
                         ids=["mixed", "wide", "decay-near-1"])
def test_the_head_form_is_the_lane_form_fed_that_decay_on_every_lane(lo, hi):
    """One rule, two factorisations (ISSUE 51): ``_kernel_rule`` and its
    vjp given a head's decay ``(B, T, H)`` against the same given that
    decay on every key lane ``(B, T, H, Dk)`` (the six halving levels:
    what ran until then), three chunks of two heads under the
    interpreter: the output and the five cotangents, the lanes' decay
    cotangent summed, to the tolerances either has against the plain
    chunks (``test_kernels_match_the_plain_chunks``)."""
    t, heads, d = 192, 2, 128
    rng = np.random.RandomState(51)
    q, k = (jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True),
                        jnp.float32)
            for x in (rng.randn(1, t, heads, d) for _ in range(2)))
    v, w = (jnp.asarray(rng.randn(1, t, heads, d), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.uniform(lo, hi, (1, t, heads)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.99, (1, t, heads)), jnp.float32)
    scale = d ** -0.5

    def run(g):
        o, kept = kda_ops._kernel_rule(q, k, v, g, beta, scale,
                                       interpret=True)
        return o, kept, kda_ops._kernel_rule_vjp(
            q, k, v, g, beta, kept, w, scale, interpret=True)

    with jax.default_matmul_precision("highest"):
        o, kept, grads = run(g)
        o_lane, kept_lane, lane = run(
            jnp.broadcast_to(g[..., None], q.shape))
    assert grads[3].shape == g.shape and lane[3].shape == q.shape
    lane = lane[:3] + (lane[3].sum(-1),) + lane[4:]
    assert np.abs(np.asarray(o - o_lane)).max() \
        <= 1e-4 * np.abs(np.asarray(o_lane)).max()
    # the states and the chunks' A, Bs, T: what the step keeps is what
    # it kept
    for x, y in zip(kept, kept_lane):
        assert x.shape == y.shape
        assert np.abs(np.asarray(x - y)).max() \
            <= 1e-4 * np.abs(np.asarray(y)).max()
    for x, y in zip(grads, lane):
        assert np.abs(np.asarray(y)).max() > 0
        assert np.abs(np.asarray(x - y)).max() \
            <= 5e-4 * np.abs(np.asarray(y)).max()


def test_the_kernels_are_handed_the_decay_a_head():
    """What the kernel lowering moves for the decay (ISSUE 51): the two
    ``pallas_call``s of ``gated_delta_net``'s forward and backward pass
    take it, and return its cotangent, as ``(B, Hv, N, 1, C)``, a chunk
    a row of 64 lanes (``(B, Hv, T, 1)``, beta's layout, lies on the
    chip with its last dimension padded to 128 lanes: the bytes of the
    decay on every key lane); of float32 arrays the size of ``(B, T, Hv,
    Dk)`` the forward kernel takes two (q and k) and the backward one
    takes two and returns two (theirs): none for the decay, where each
    held one more each way."""
    args = _gdn_inputs(128, -3.0, -0.01)
    args = args[:2] + (args[2].astype(jnp.bfloat16),) + args[3:]
    b, t, hv, d = args[2].shape

    def loss(*a):
        return kda_ops.gated_delta_net(*a, interpret=True).astype(
            jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(7))))(*args)
    calls = {c.params["name"]: c
             for c in jaxpr_eqns(jaxpr.jaxpr, "pallas_call")}
    assert set(calls) == {"kda_chunk_fwd", "kda_chunk_bwd"}

    def lane_wide(avals):
        return sum(1 for x in avals if x.dtype == jnp.float32
                   and x.size == b * t * hv * d)

    for name, taken, given in (("kda_chunk_fwd", 2, 0),
                               ("kda_chunk_bwd", 2, 2)):
        call = calls[name]
        ins = [x.aval for x in call.invars]
        outs = [x.aval for x in call.outvars]
        assert (lane_wide(ins), lane_wide(outs)) == (taken, given), name
        # the decay, one number a head and token, and beta
        assert [x.shape for x in ins[3:5]] == [(b, hv, t // 64, 1, 64),
                                               (b, hv, t, 1)], name
    assert [x.aval.shape for x in calls["kda_chunk_bwd"].outvars[3:]] \
        == [(b, hv, t // 64, 1, 64), (b, hv, t, 1)]


def test_the_backward_pass_keeps_the_ops_own_inputs():
    """What the kernel lowering's ``custom_vjp`` holds across the step:
    the op's seven inputs (q and k at the KEY's heads, a ``(B, T, Hv)``
    decay) and the kernels' kept arrays; nothing ``(B, T, Hv, Dk)`` in
    float32."""
    args = _gdn_inputs(128, -3.0, -0.01)
    args = args[:2] + (args[2].astype(jnp.bfloat16),) + args[3:]
    _, (kept_args, kept) = jax.eval_shape(
        lambda *a: kda_ops._two_lowerings_fwd(*a, True), *args)
    assert [x.shape for x in kept_args] == [x.shape for x in args]
    assert [x.shape for x in kept] == [(1, 2, 2, 128, 128), (1, 128, 384)]
    spread = (1, 128, 2, 128)
    assert not any(x.shape == spread and x.dtype == jnp.float32
                   for x in jax.tree_util.tree_leaves((kept_args, kept)))


def test_gdn_op_shapes_scope_and_counter():
    b, t, hk, hv, d = 1, 70, 2, 4, 8
    shapes = {"query": (b, t, hk, d), "key": (b, t, hk, d),
              "value": (b, t, hv, d), "decay": (b, t, hv),
              "beta": (b, t, hv), "a_log_bias": (hv,), "dt_bias": (hv,)}
    sym = mx.sym.GatedDeltaNet(*[mx.sym.Variable(n) for n in shapes],
                               layer=2)
    args, outs, _ = sym.infer_shape(query=(b, t, hk, d),
                                    value=(b, t, hv, d))
    assert dict(zip(sym.list_arguments(), args)) == shapes
    assert outs == [(b, t, hv, d)]
    with pytest.raises(mx.MXNetError):
        sym.infer_shape(query=(b, t, 3, d), value=(b, t, hv, d))
    rng = np.random.RandomState(1)
    x = [jnp.asarray(rng.randn(*s), jnp.float32) for s in shapes.values()]
    op = mx.ops.get_op("GatedDeltaNet")
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.jit(lambda *a: op.forward(
            op.parse_params({"layer": 2}), list(a), [], None)[0]).lower(
                *x).as_text(debug_info=True)
        events = mx.trace.counter_events(["gdn:lowering"], since_ns=mark)
        none = mx.trace.counter_events(["kda:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    # the rule's scope, whichever front end runs it
    assert "kda.l2" in text
    assert not none
    assert events[0]["args"] == {"chunked": 1, "chunk": 64, "kernel": 0,
                                 "plain": 1, "key_heads": hk,
                                 "value_heads": hv}
    assert events[0]["id"] == "float32%s/k%d" % ([b, t, hv, d], hk)


def test_three_gdn_layers_trace_each_kernel_once():
    """A TPU program of three GatedDeltaNet ops at the kernels' sizes
    holds both kernels, and the process traced each once for them
    (shapes no other test of this file's process uses)."""
    b, t, hk, hv, d = 1, 192, 1, 2, 128
    shapes = [(b, t, hk, d)] * 2 + [(b, t, hv, d)] + [(b, t, hv)] * 2 \
        + [(hv,)] * 2
    x = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]

    def three(*a):
        def loss(*a):
            o = a[2]
            for _ in range(3):
                o = kda_ops.gated_delta_net(a[0], a[1], o, *a[3:])
            return o.sum()
        return jax.grad(loss, argnums=tuple(range(7)))(*a)

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(jax.jit(three), platforms=["tpu"])(
            *x).mlir_module()
        kernel = mx.trace.counter_events(["kda:kernel_trace"],
                                         since_ns=mark)
        chosen = mx.trace.counter_events(["gdn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    assert [e["args"]["kernel"] for e in chosen] == [1, 1, 1]
    # each kernel once, in the form a head's decay admits: no level
    assert sorted((e["args"]["fwd"], e["args"]["bwd"]) for e in kernel) \
        == [(0, 1), (1, 0)]
    assert all((e["args"]["decay"], e["args"]["level_rows"],
                e["args"]["vpu_levels"]) == ("head", 0, 0) for e in kernel)
    assert {e["id"] for e in kernel} == {"float32%s" % [b, t, hv, d]}


# -- the gated attention -------------------------------------------------------
def _attention_program(**how):
    rows, H, Hkv, dh, D = 12, 4, 2, 16, 32
    h = mx.sym.Variable("h")
    net = decoder.gqa_attention(h, "a_", 0, rows, H, Hkv, dh, D, 1e-6,
                                **how)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(h=(2 * rows, D))[0]))
    rng = np.random.RandomState(3)
    vals = {n: jnp.asarray(
        (1 + 0.1 * rng.randn(*s)) if n.endswith("gamma")
        else 0.3 * rng.randn(*s), jnp.float32) for n, s in shapes.items()}
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    return prog, vals


def test_the_gate_half_of_q_proj_gets_gradient_only_through_the_gate():
    """``gated="query"``: ``q_proj`` is twice the heads wide, head by
    head ``[q | gate]``.  Against the own-projection form (Trinity's)
    fed the same two matrices: the same output, the query rows' gradient
    is that form's ``q_proj`` gradient and the gate rows' gradient is its
    ``attn_gate_proj`` gradient, which reaches the loss through the
    sigmoid's product alone."""
    prog, vals = _attention_program(gated="query")
    assert vals["a_q_proj_weight"].shape == (2 * 4 * 16, 32)
    assert "a_attn_gate_proj_weight" not in vals
    rows = np.arange(2 * 4 * 16).reshape(4, 2, 16)
    q_rows, gate_rows = rows[:, 0].ravel(), rows[:, 1].ravel()
    own, own_vals = _attention_program(gated=True)
    w = np.asarray(vals["a_q_proj_weight"])
    same = dict(vals, a_q_proj_weight=jnp.asarray(w[q_rows]),
                a_attn_gate_proj_weight=jnp.asarray(w[gate_rows]))
    assert set(same) == set(own_vals)

    def loss(prog, v):
        return jnp.square(
            prog.eval(v, {}, jax.random.PRNGKey(0), True)[0][0]).sum()

    with jax.default_matmul_precision("highest"):
        got, g = jax.value_and_grad(lambda v: loss(prog, v))(vals)
        want, g_own = jax.value_and_grad(lambda v: loss(own, v))(same)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)
    g = np.asarray(g["a_q_proj_weight"])
    for mine, theirs in ((q_rows, "a_q_proj_weight"),
                         (gate_rows, "a_attn_gate_proj_weight")):
        theirs = np.asarray(g_own[theirs])
        assert np.abs(theirs).max() > 0
        assert np.abs(g[mine] - theirs).max() <= 1e-5 * np.abs(theirs).max()
    for name in ("a_k_proj_weight", "a_v_proj_weight", "a_o_proj_weight",
                 "a_q_norm_gamma", "h"):
        assert np.abs(np.asarray(g_own[name])).max() > 0


def test_the_partial_rotation_leaves_the_other_lanes_bit_for_bit():
    """The builder's ``rotate``: the first ``rotary_dim`` lanes of every
    head as ``RotaryEmbedding`` of that slice alone turns them, the
    reference's angles, and lanes ``rotary_dim..`` the bits they were."""
    seen = []
    real = decoder.gqa_attention

    def spy(h, pre, layer, rows, *sizes, rotate=None, **how):
        seen.append(rotate)
        return real(h, pre, layer, rows, *sizes, rotate=rotate, **how)

    import mxnet_tpu.models.qwen3_next as builder
    was, builder.gqa_attention = builder.gqa_attention, spy
    try:
        qwen3_next_lm(**dict(TINY, head_dim=256, rotary_dim=64,
                             num_heads=2, num_kv_heads=1))
    finally:
        builder.gqa_attention = was
    net = seen[0](mx.sym.Variable("x"))
    x = np.random.RandomState(4).randn(2, 24, 3, 256).astype(np.float32)
    exe = net.simple_bind(mx.cpu(), grad_req="null", x=x.shape)
    exe.arg_dict["x"][:] = x
    exe.forward(is_train=False)
    got = exe.outputs[0].asnumpy()
    assert got.shape == x.shape
    assert np.array_equal(got[..., 64:], x[..., 64:])
    assert not np.array_equal(got[:, 1:, :, :64], x[:, 1:, :, :64])
    want = np.asarray(REF.rotate(jnp.asarray(x), 1e7, 64))
    assert np.abs(got - want).max() <= 1e-5
    # position 0 is not turned at all
    assert np.array_equal(got[:, 0], x[:, 0])


# -- the expert layer ----------------------------------------------------------
def test_shared_gate_unset_is_not_in_the_graph():
    def layer(**how):
        return MoEFeedForward(
            mx.sym.Variable("data"), num_hidden=8, num_experts=4, k=2,
            capacity_factor=0.0, name="moe", act_type="silu", gated=True,
            no_bias=True, renormalize=True, output_dim=6, shared_hidden=8,
            **how)
    with mx.name.NameManager():
        plain = layer().tojson()
    with mx.name.NameManager():
        unset = layer(shared_gate=False).tojson()
    with mx.name.NameManager():
        gated = layer(shared_gate=True)
    assert plain == unset and "shared_gate" not in plain
    assert "moe_shared_gate_weight" in gated.list_arguments()
    assert "moe_shared_gate_weight" not in layer().list_arguments()


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """64 experts over 16 ranks of 4, top-5 of a softmax over all 64,
    renormalised: each rank's output LESS the gated shared expert is the
    reference's routed part given the same share, and the sixteen routed
    parts plus the gated shared expert ONCE are the reference's layer
    with all experts held."""
    E, k, held = 64, 5, 4
    rng = np.random.RandomState(5)
    T, D, H = 40, 12, 10
    h = rng.randn(T, D).astype(np.float32)
    full = {"moe_gate_weight": rng.randn(E, D),
            "moe_experts_i2h_gate_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D),
            "moe_shared_i2h_gate_weight": 0.5 * rng.randn(H, D),
            "moe_shared_i2h_weight": 0.5 * rng.randn(H, D),
            "moe_shared_h2o_weight": 0.5 * rng.randn(D, H),
            "moe_shared_gate_weight": rng.randn(1, D)}
    full = {n: v.astype(np.float32) for n, v in full.items()}
    m = {"num_experts": E, "experts_per_tok": k}
    dev = {n: jnp.asarray(v) for n, v in full.items()}
    with jax.default_matmul_precision("highest"):
        whole, _, counts = REF.moe(dev, "", jnp.asarray(h), m)
        shared = jax.nn.sigmoid(
            jnp.asarray(h) @ dev["moe_shared_gate_weight"].T) * REF.swiglu(
                jnp.asarray(h), dev["moe_shared_i2h_gate_weight"],
                dev["moe_shared_i2h_weight"], dev["moe_shared_h2o_weight"])
    shared = np.asarray(shared)
    assert np.asarray(counts).sum() == T * k
    # the gate is one number a token and does gate
    assert np.abs(shared).max() > 0
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, held):
        mine = {n: (v[first:first + held] if "experts" in n else v)
                for n, v in full.items()}
        net = MoEFeedForward(
            mx.sym.Variable("data"), num_hidden=H, num_experts=E, k=k,
            capacity_factor=0.0, name="moe", act_type="silu", gated=True,
            no_bias=True, renormalize=True, output_dim=D, shared_hidden=H,
            shared_gate=True, experts_held=held, first_expert=first)
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        exe.arg_dict["data"][:] = h
        for n, v in mine.items():
            exe.arg_dict[n][:] = v
        exe.forward(is_train=False)
        out = exe.outputs[0].asnumpy()
        with jax.default_matmul_precision("highest"):
            want, _, _ = REF.moe(
                {n: jnp.asarray(v) for n, v in mine.items()}, "",
                jnp.asarray(h), dict(m, experts_held=held,
                                     first_expert=first))
        assert np.abs(out - np.asarray(want)).max() \
            <= 1e-4 * np.abs(np.asarray(want)).max()
        total += out - shared
    assert np.abs(total + shared - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()


# -- scopes and counters -------------------------------------------------------
# sha256 of the symbol's arguments, outputs and states (names and shapes,
# in order: ``common/symbol_signature.py``) at the parent of ISSUE 68,
# which made the mixers' output stage one node and meant to move nothing
# a checkpoint or the reference's weights map by
SIGNATURE_WAS = {
    "cell": "1bc64c778f1eab7245996e200419e75d8649e9ab79f02817d79b1d34242f8b68",
    "tiny": "364dc18e3c94c45b4f4d1f523528f78419dba0c23beb332c04f7b8b7d15745c5",
}


@pytest.mark.parametrize("case", sorted(SIGNATURE_WAS))
def test_the_output_stage_is_one_node_under_the_names_it_had(case):
    """Every Gated DeltaNet layer ends in ONE ``GatedRMSNorm`` on the
    rows as the rule writes them, under ``gdn_proj.l<i>``, fed the z
    lanes as the convolution hands them on; its weight is still
    ``l<i>_o_norm_gamma`` of a head's width, and the symbol's arguments,
    outputs and states are the parent's, name for name and shape for
    shape."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cell = json.load(f)["model"]["kwargs"]
    kwargs, batch = (cell, 1) if case == "cell" else (TINY, BATCH)
    net = qwen3_next_lm(**kwargs)
    shape = (batch, kwargs["seq_len"])
    assert signature(net, data=shape, softmax_label=shape) \
        == SIGNATURE_WAS[case]
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=shape, softmax_label=shape)[0]))
    mixers = [l for l in range(kwargs["num_layers"])
              if (l + 1) % kwargs["full_attention_interval"]]
    stages = nodes(net, "GatedRMSNorm")
    assert [n.name for n in stages] == ["l%d_o_norm" % l for l in mixers]
    for l, node in zip(mixers, stages):
        assert shapes["l%d_o_norm_gamma" % l] == (kwargs["gdn_head_dim"],)
        assert node.attrs["__scope__"] == "gdn_proj.l%d" % l
        assert node.params["act_type"] == "silu"
        assert [i[0].name for i in node.inputs][1:] == [
            "l%d_o_norm_gamma" % l, "l%d_conv" % l]
    # no other norm or activation is left of the stage
    assert not [n for n in nodes(net, "RMSNorm") if "o_norm" in n.name]
    assert not [n for n in nodes(net, "Activation")
                if n.attrs.get("__scope__", "").startswith("gdn_proj")]


def test_device_scopes_name_the_new_parts():
    net, kwargs, params, tokens, labels = _tiny(seed=1, num_layers=4)
    scopes = set()
    for node in mx.symbol._topo(net._heads):
        scope = (getattr(node, "attrs", None) or {}).get("__scope__")
        if scope:
            scopes.add(scope)
    assert {"gdn_proj.l%d" % l for l in range(3)} <= scopes
    assert {"attn_proj.l3", "attn_gate.l3"} <= scopes
    assert "gdn_proj.l3" not in scopes and "attn_proj.l0" not in scopes
    # the op itself is made outside its projections' scope: its own
    # ``kda.l<i>`` is the one its operations carry
    for node in mx.symbol._topo(net._heads):
        if not node.is_variable and node.op.name == "GatedDeltaNet":
            assert not (getattr(node, "attrs", None) or {}).get("__scope__")
