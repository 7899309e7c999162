"""Granite 4.0-H (granite-4.0-h-micro) through the Symbol graph (ISSUE
67, tier-1): the state-space scan's plain chunks and its kernel pair
(interpreted) against a token-by-token scan, forward and every gradient;
``CausalConv1D`` with a bias against the plain form and by finite
differences; the whole tiny model against
``benchmark/reference/granite-4.0-h-micro.py`` in float32 (loss, every
gradient, Adam's first step); the lowering rule, the counters and the
scopes of a traced step; the FLOP count by hand."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402
from jax import lax                                       # noqa: E402

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.executor import _GraphProgram              # noqa: E402
from mxnet_tpu.models import granite_hybrid_lm            # noqa: E402
from mxnet_tpu.ops import causal_conv as cc               # noqa: E402
from mxnet_tpu.ops import ssd                             # noqa: E402

import manifest                                           # noqa: E402

REF = manifest.load_module("reference", "granite-4.0-h-micro")

TINY = dict(num_layers=3, hidden_size=32,
            layer_types=["mamba", "attention", "mamba"],
            ssm_heads=4, ssm_head_dim=8, ssm_state=12, ssm_groups=1,
            conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=8,
            mlp_width=48, vocab_size=50, seq_len=24,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.125, logits_scaling=8.0, rms_eps=1e-5)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
F32, BF16 = jnp.float32, jnp.bfloat16
SCAN_INPUTS = ("x", "b", "c", "dt", "a_log", "dt_bias", "d")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


# -- the scan --------------------------------------------------------------------
def token_by_token(x, bm, cm, dt, a_log, dt_bias, d):
    """The rule as it is stated, one token a step of ``lax.scan``."""
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    dtf = jax.nn.softplus(dt.astype(F32) + dt_bias)
    decay = jnp.exp(-jnp.exp(a_log) * dtf)
    bh, ch = (jnp.repeat(v.astype(F32), h // g, axis=2) for v in (bm, cm))
    xf = x.astype(F32)

    def step(S, c):
        xt, bt, ct, at, dtt = c
        S = at[..., None, None] * S \
            + dtt[..., None, None] * bt[..., :, None] * xt[..., None, :]
        return S, jnp.einsum("bhn,bhnp->bhp", ct, S)

    _, y = lax.scan(step, jnp.zeros((b, h, n, p), F32),
                    tuple(jnp.moveaxis(v, 1, 0)
                          for v in (xf, bh, ch, decay, dtf)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * xf


def scan_inputs(b, t, h, p, g, n, dtype, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, t, h, p), dtype),
            jnp.asarray(0.5 * r.randn(b, t, g, n), dtype),
            jnp.asarray(0.5 * r.randn(b, t, g, n), dtype),
            jnp.asarray(r.randn(b, t, h), dtype),
            jnp.asarray(r.uniform(-1.0, 1.5, h), F32),
            jnp.asarray(0.5 * r.randn(h), F32), jnp.asarray(r.randn(h), F32))


def scan_errors(fn, args):
    """The output's and every gradient's distance from the token-by-token
    scan's, under one random cotangent."""
    w = jnp.asarray(np.random.RandomState(9).randn(*args[0].shape), F32)

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(F32) * w)

    errors = {"y": _rel(jax.jit(fn)(*args), jax.jit(token_by_token)(*args))}
    want = jax.jit(jax.grad(loss(token_by_token), argnums=range(7)))(*args)
    got = jax.jit(jax.grad(loss(fn), argnums=range(7)))(*args)
    errors.update({"d" + name: _rel(a, b)
                   for name, a, b in zip(SCAN_INPUTS, got, want)})
    return errors


@pytest.mark.parametrize("shape, chunk", [
    ((2, 40, 4, 8, 1, 12), 16),      # T no multiple of the chunk, N != P
    ((1, 37, 6, 8, 2, 16), 16),      # two groups of three heads
    ((1, 48, 8, 4, 1, 6), 16),       # one group under many heads
    ((1, 9, 2, 8, 1, 8), 16),        # shorter than a chunk
])
def test_the_plain_chunks_are_the_token_by_token_scan(monkeypatch, shape,
                                                      chunk):
    monkeypatch.setattr(ssd, "SSD_CHUNK", chunk)
    errors = scan_errors(ssd._plain_scan, scan_inputs(*shape, F32))
    assert max(errors.values()) <= 5e-5, errors


@pytest.mark.parametrize("dtype, heads, limit", [
    (F32, 4, 5e-4), (BF16, 4, 2e-2), (BF16, 2, 2e-2)])
def test_the_kernel_pair_interpreted_is_the_token_by_token_scan(dtype, heads,
                                                                limit):
    """Two chunks of 128 tokens, two 64-lane heads a tile, one group of
    128: forward and all seven gradients, ``A_log``, ``dt_bias``, ``D``
    and ``dt`` among them.  bfloat16 is held to the scan of the same
    rounded inputs, so what it reads is the kernels' own rounding."""
    args = scan_inputs(1, 256, heads, ssd.SSD_HEAD_DIM, 1, ssd.SSD_STATE,
                       dtype, seed=heads)
    errors = scan_errors(lambda *a: ssd._two_lowerings(*a, True), args)
    assert max(errors.values()) <= limit, errors


@pytest.mark.parametrize("shape, dtype, takes", [
    ((1, 4096, 64, 64, 1, 128), BF16, True),      # the cell's
    ((1, 4096, 64, 64, 1, 128), F32, False),      # float32: the plain chunks
    ((1, 4000, 64, 64, 1, 128), BF16, False),     # no whole chunks
    ((1, 4096, 64, 64, 2, 128), BF16, True),      # two groups (ISSUE 71)
    ((1, 4096, 64, 64, 64, 128), BF16, False),    # half a lane tile a group
    ((1, 4096, 63, 64, 1, 128), BF16, False),     # half a lane tile left
    ((1, 4096, 32, 128, 1, 128), BF16, False),    # another head
    ((1, 4096, 64, 64, 1, 64), BF16, False),      # another state
])
def test_one_rule_by_shape_and_dtype_chooses_the_lowering(shape, dtype, takes):
    b, t, h, p, g, n = shape
    x = jax.ShapeDtypeStruct((b, t, h, p), dtype)
    bm = jax.ShapeDtypeStruct((b, t, g, n), dtype)
    assert ssd._kernel_takes(x, bm) is takes


def test_a_tpu_program_holds_the_kernels_and_the_counters_say_so():
    """The op lowered for a TPU at a shape the kernels take holds both
    kernels by name; ``ssd:lowering`` records the choice a traced op and
    ``ssd:kernel_trace`` each kernel's trace with its tiling."""
    shape = (1, 384, 6, 64, 1, 128)       # no other test's: traced once
    args = scan_inputs(*shape, BF16)

    def loss(*a):
        return jnp.sum(ssd.ssd_scan(*a).astype(F32))

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(
            jax.jit(jax.grad(loss, argnums=tuple(range(7)))),
            platforms=["tpu"])(*args).mlir_module()
        chose = mx.trace.counter_events(["ssd:lowering"], since_ns=mark)
        traced = mx.trace.counter_events(["ssd:kernel_trace"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text
    assert [e["id"] for e in chose] == ["bfloat16[1, 384, 6, 64]/g1n128"]
    assert chose[0]["args"] == {"chunked": 1, "chunk": 128, "kernel": 1,
                                "plain": 0}
    assert [(e["args"]["fwd"], e["args"]["bwd"]) for e in traced] \
        == [(1, 0), (0, 1)]
    for e in traced:
        assert e["id"] == "bfloat16[1, 384, 6, 64]/g1n128"
        assert (e["args"]["chunk"], e["args"]["heads_a_tile"],
                e["args"]["heads_a_step"], e["args"]["lowering"]) \
            == (128, 2, 6, "kernel")


def test_the_references_scan_in_segments_is_the_token_by_token_scan(
        monkeypatch):
    """The reference's scan keeps a state a segment of ``SEGMENT`` tokens
    and walks a segment eight tokens a trip: the same numbers."""
    monkeypatch.setattr(REF, "SEGMENT", 16)
    x, bm, cm, dt, a_log, dt_bias, d = scan_inputs(2, 32, 6, 4, 2, 5, F32)
    got = REF.ssm_scan(x, bm, cm, jax.nn.softplus(dt + dt_bias),
                       -jnp.exp(a_log), d)
    assert _rel(got, token_by_token(x, bm, cm, dt, a_log, dt_bias, d)) <= 1e-6


# -- the convolution's bias ------------------------------------------------------
def conv_inputs(b, t, c, width, dtype, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, t, c), dtype),
            jnp.asarray(0.5 * r.randn(c, width), F32),
            jnp.asarray(0.5 * r.randn(c), F32))


def plain_biased(x, w, bias, act=jax.nn.silu):
    return act(cc.causal_conv1d(x.astype(F32), w) + bias)


@pytest.mark.parametrize("act", [None, "silu"])
def test_the_biased_convolution_is_the_plain_form_plus_the_bias(act):
    x, w, bias = conv_inputs(2, 24, 16, 4, F32)
    y, = cc.biased_conv(x, w, bias, act)
    want = plain_biased(x, w, bias, jax.nn.silu if act else lambda v: v)
    assert _rel(y, want) <= 1e-6
    # finite differences of the bias: one number a channel
    def total(bias):
        return jnp.sum(cc.biased_conv(x, w, bias, act)[0] ** 2)
    got = jax.grad(total)(bias)
    eps = 1e-2
    for ch in (0, 7, 15):
        e = jnp.zeros_like(bias).at[ch].set(eps)
        fd = (total(bias + e) - total(bias - e)) / (2 * eps)
        assert abs(float(got[ch]) - float(fd)) <= 2e-3 * abs(float(fd)) + 1e-3


@pytest.mark.parametrize("dtype, limit", [(F32, 1e-5), (BF16, 2e-2)])
def test_the_biased_kernel_pair_interpreted_is_the_plain_form(dtype, limit):
    """Two lane blocks, two row passes: forward, dx, dw and db."""
    x, w, bias = conv_inputs(2, 256, 256, 4, dtype, seed=3)
    g = jnp.asarray(np.random.RandomState(4).randn(2, 256, 256), F32)

    def loss(fn):
        return lambda x, w, bias: jnp.sum(fn(x, w, bias).astype(F32) * g)

    def kernels(x, w, bias):
        return cc.biased_conv(x, w, bias, "silu", interpret=True)[0]

    assert _rel(kernels(x, w, bias), plain_biased(x, w, bias)) <= limit
    got = jax.grad(loss(kernels), argnums=(0, 1, 2))(x, w, bias)
    want = jax.grad(loss(plain_biased), argnums=(0, 1, 2))(x, w, bias)
    for name, a, b in zip(("dx", "dw", "db"), got, want):
        assert _rel(a, b) <= limit, name


def test_the_op_has_a_bias_only_when_asked_and_records_its_lowering():
    data = mx.sym.Variable("data")
    plain = mx.sym.CausalConv1D(data, kernel=4, act_type="silu", name="conv")
    biased = mx.sym.CausalConv1D(data, kernel=4, act_type="silu",
                                 no_bias=False, name="conv")
    assert plain.list_arguments() == ["data", "conv_weight"]
    assert biased.list_arguments() == ["data", "conv_weight", "conv_bias"]
    assert "no_bias" not in plain.tojson()
    shapes, outs, _ = biased.infer_shape(data=(2, 24, 16))
    assert shapes == [(2, 24, 16), (16, 4), (16,)] and outs == [(2, 24, 16)]
    with pytest.raises(mx.MXNetError):
        mx.sym.CausalConv1D(data, kernel=3, gated=True, no_bias=False,
                            name="conv").infer_shape(data=(2, 24, 48))
    x, w, bias = conv_inputs(2, 24, 16, 4, F32, seed=5)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        exe = biased.simple_bind(mx.cpu(), grad_req="null", data=x.shape)
        for k, v in (("data", x), ("conv_weight", w), ("conv_bias", bias)):
            exe.arg_dict[k][:] = np.asarray(v)
        exe.forward(is_train=False)
        events = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    assert _rel(exe.outputs[0].asnumpy(), plain_biased(x, w, bias)) <= 1e-6
    assert events and all(e["id"] == "float32[2, 24, 16]/16+bias"
                          and e["args"] == {"kernel": 0, "plain": 1}
                          for e in events)


# -- the builder -----------------------------------------------------------------
def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = granite_hybrid_lm(**kwargs)
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
        elif name == "embed_weight":
            params[name] = (0.05 * rng.randn(*shape)).astype(np.float32)
        else:
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


def test_the_builder_names_its_parts_and_refuses_what_it_cannot_build():
    net = granite_hybrid_lm(**TINY)
    names = net.list_arguments()
    for name in ("l0_in_proj_weight", "l0_conv_weight", "l0_conv_bias",
                 "l0_ssm_a_log_bias", "l0_ssm_dt_bias", "l0_ssm_d_gamma",
                 "l0_ssm_norm_gamma", "l0_out_proj_weight",
                 "l0_input_linear_weight", "l0_output_linear_weight",
                 "l1_q_proj_weight", "l1_o_proj_weight", "l2_conv_bias",
                 "final_norm_gamma", "embed_weight"):
        assert name in names, name
    assert "lm_head_weight" not in names and names.count("embed_weight") == 1
    assert not any("q_norm" in n or "k_norm" in n for n in names)
    shapes = dict(zip(names, net.infer_shape(
        data=(BATCH, 24), softmax_label=(BATCH, 24))[0]))
    assert shapes["l0_in_proj_weight"] == (2 * 32 + 2 * 12 + 4, 32)
    assert shapes["l0_conv_weight"] == (32 + 24, 4)
    assert shapes["l0_conv_bias"] == (56,)
    assert shapes["l0_ssm_a_log_bias"] == shapes["l0_ssm_d_gamma"] == (4,)
    assert shapes["l0_ssm_norm_gamma"] == (32,)
    assert shapes["l0_input_linear_weight"] == (96, 32)
    assert net.list_outputs() == ["lm_output"]
    assert "force_mirroring" not in net.tojson()
    with pytest.raises(ValueError):
        granite_hybrid_lm(**dict(TINY, layer_types=["mamba", "conv", "mamba"]))
    with pytest.raises(ValueError):
        granite_hybrid_lm(**dict(TINY, ssm_groups=3))


def test_model_matches_reference_loss_gradients_and_adam_step(monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)
    lr = 8.0       # a step float32 can tell from the weight
    mod, batch = _bound(net, params, tokens, labels, "sgd", {
        "learning_rate": lr, "momentum": 0.0, "wd": 0.0, "rescale_grad": 1.0})
    mod.forward_backward(batch)
    mod.update()
    loss = float(mod.get_outputs()[0].asnumpy().mean())
    after, _ = mod.get_params()
    grads = {k: (params[k] - after[k].asnumpy()) / lr for k in params}
    assert abs(loss - ref["loss"]) <= 1e-5 * ref["loss"]
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= 2e-4, errors

    names = ["l0_in_proj_weight", "l0_conv_weight", "l0_conv_bias",
             "l0_ssm_a_log_bias", "l0_ssm_dt_bias", "l0_ssm_d_gamma",
             "l0_ssm_norm_gamma", "l0_out_proj_weight", "l1_q_proj_weight",
             "l1_o_proj_weight", "l2_input_linear_weight", "embed_weight"]
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
        # an element whose gradient is ~0 may flip sign: Adam's first
        # step is lr * sign(g); such elements are a sliver of the norm
        assert _rel(got, want["updates"][name]) <= 0.02, name


def test_reference_flops_are_the_hand_count():
    cfg = manifest._read_json(os.path.join(
        ROOT, "benchmark", "configs", "granite-4.0-h-micro.json"), "config")
    parts = REF.forward_flops_per_token(cfg)
    D, T = 2048, 4096
    assert parts["ssm_proj"] == 9 * (2 * D * 8512 + 2 * 4096 * D)
    assert parts["ssm_scan"] == 9 * 4 * 128 * 64 * 64
    assert parts["attn_proj"] == 2 * D * 64 * (2 * 32 + 2 * 8)
    assert parts["attn"] == 4 * 64 * 32 * (T + 1) / 2
    assert parts["mlp"] == 10 * 3 * 2 * D * 8192
    assert parts["head"] == 2 * D * 12544
    total = REF.train_flops_per_sample(cfg)
    assert total == 3.0 * sum(parts.values())
    assert 4.7e9 < total < 4.85e9                 # ISSUE 67: about 4.80 G
    assert 19.3e12 < total * T < 19.9e12          # about 19.7 TFLOP a step
    # the scan under 3 % of the FLOPs, the head 3.2 %
    assert 3 * parts["ssm_scan"] / total < 0.03
    assert abs(3 * parts["head"] / total - 0.032) < 0.002


def test_device_scopes_and_the_lowering_counters_name_the_mixers_parts():
    net, kwargs, params, tokens, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(tokens), softmax_label=jnp.asarray(labels))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.jit(lambda a: prog.eval(a, {}, jax.random.PRNGKey(0),
                                           True)[0]).lower(args) \
            .as_text(debug_info=True)
        scan = mx.trace.counter_events(["ssd:lowering"], since_ns=mark)
        conv = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    for scope in ("ssm_proj.l0", "ssm_conv.l0", "ssm_scan.l0", "ssm_norm.l0",
                  "ssm_scan.l2", "attn_proj.l1", "attn.l1", "lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope
    for absent in ("ssm_scan.l1", "attn.l0", "moe_"):
        assert absent not in text
    assert [e["id"] for e in scan] == ["float32[2, 24, 4, 8]/g1n12"] * 2
    assert all(e["args"]["plain"] == 1 and e["args"]["kernel"] == 0
               for e in scan)
    assert [e["id"] for e in conv] == ["float32[2, 24, 56]/56+bias"] * 2
    assert [e["id"] for e in attn] == ["float32[2, 24, 4, 8]/kv2"]
