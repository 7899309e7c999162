"""LFM2-MoE (LFM2-8B-A1B) through the Symbol graph (ISSUE 61, tier-1):
the whole tiny model against ``benchmark/reference/lfm2-8b-a1b.py`` in
float32 (loss, every gradient, Adam's first step, the selection bias's
first move) with both mixer kinds, a dense and routed layers and the
tied head; the tied weight's update as the sum of its two uses'; the
convolution mixer's causality and its two gates; the four ranks' shares
of one expert layer against the uncut layer; the TPU wrapper of
attention at 64-lane heads against the plain blocks, and its lowering
and counter at the cell's shape; the FLOP count by hand; the scopes and
the counters of a traced step."""
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
from mxnet_tpu.models import lfm2_moe_lm                  # noqa: E402
from mxnet_tpu.moe import find_load_heads                 # noqa: E402
from mxnet_tpu.moe.layer import MoEFeedForward            # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402

import manifest                                           # noqa: E402
from symbol_signature import nodes, placed_on_rows      # noqa: E402

REF = manifest.load_module("reference", "lfm2-8b-a1b")

TINY = dict(num_layers=5, hidden_size=32,
            layer_types=["conv", "full_attention", "conv", "conv", "conv"],
            dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
            conv_kernel=3, rope_theta=1e6, dense_width=48, num_experts=16,
            experts_per_tok=4, expert_width=24, vocab_size=50, seq_len=16,
            route_scale=1.0, experts_held=4, first_expert=4, bias_rate=1e-3,
            rms_eps=1e-5)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
BLOCKS = ["l%d_moe_dispatch" % l for l in (1, 2, 3, 4)]
F32, BF16 = jnp.float32, jnp.bfloat16


def _rel(got, want):
    want = np.asarray(want)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = lfm2_moe_lm(**kwargs)
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
            # wide enough that routing, gates and attention are not flat
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
    outs = [o.asnumpy() for o in mod.get_outputs()]
    after, _ = mod.get_params()
    return outs, {k: (params[k] - after[k].asnumpy()) / lr for k in params}


def _losses(net, params, tokens, labels):
    """The per-token loss head ``(B, T)`` of a forward pass."""
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=tokens.shape,
                          softmax_label=labels.shape)
    for k, v in dict(params, data=tokens, softmax_label=labels).items():
        exe.arg_dict[k][:] = v
    exe.forward(is_train=False)
    return exe.outputs[0].asnumpy().reshape(tokens.shape)


# -- the builder ---------------------------------------------------------------
def test_the_builder_names_its_parts_and_refuses_what_it_cannot_build():
    net, kwargs, _, _, _ = _tiny(seed=0)
    assert net.list_outputs() == ["lm_output", "moe_load_output"]
    assert find_load_heads(net) == (1, BLOCKS)
    assert sorted(net.list_auxiliary_states()) \
        == [b + "_select_bias" for b in BLOCKS]
    args = net.list_arguments()
    # ONE weight for the embedding and the head
    assert args.count("embed_weight") == 1 and "lm_head_weight" not in args
    assert "l0_gate_proj_weight" in args and "l0_moe_gate_weight" not in args
    assert "l1_moe_gate_weight" in args and "l1_gate_proj_weight" not in args
    assert not any("shared" in a for a in args)
    mine = {"conv": ("in_proj_weight", "conv_weight", "out_proj_weight"),
            "full_attention": ("q_proj_weight", "k_proj_weight",
                               "v_proj_weight", "o_proj_weight",
                               "q_norm_gamma", "k_norm_gamma")}
    for l, kind in enumerate(kwargs["layer_types"]):
        for part in ("operator_norm_gamma", "ffn_norm_gamma"):
            assert "l%d_%s" % (l, part) in args
        for other, parts in mine.items():
            for part in parts:
                assert ("l%d_%s" % (l, part) in args) == (other == kind)
    shapes = dict(zip(args, net.infer_shape(
        data=(BATCH, 16), softmax_label=(BATCH, 16))[0]))
    assert shapes["l0_in_proj_weight"] == (96, 32)
    assert shapes["l0_conv_weight"] == (32, 3)
    for bad in (dict(layer_types=["conv"] * 4),
                dict(layer_types=["conv", "full", "conv", "conv", "conv"]),
                dict(layer_types=["conv", "sliding", "conv", "conv",
                                  "conv"]),
                dict(num_kv_heads=3)):
        with pytest.raises(ValueError):
            lfm2_moe_lm(**dict(kwargs, **bad))


# -- the model -----------------------------------------------------------------
def test_model_matches_reference_loss_gradients_adam_step_and_bias_move(
        monkeypatch):
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    net, kwargs, params, tokens, labels = _tiny(seed=7)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)
    outs, grads = _sgd_gradients(net, params, tokens, labels)
    assert abs(float(outs[0].mean()) - ref["loss"]) <= 1e-5 * ref["loss"]
    for row, blk in zip(outs[1], BLOCKS):
        assert np.array_equal(row[:-1], np.asarray(ref["counts"][blk]))
        assert row[-1] == 0 and row[:-1].sum() == 16 * BATCH * 4
    errors = {k: _rel(grads[k], ref["grads"][k]) for k in params}
    assert set(errors) == set(ref["grads"])
    # float32 sums in another order; the program's router divides by
    # max(sum, 1e-9), the reference by sum + 1e-6: 5e-7 of a weight
    assert max(errors.values()) <= 2e-4, errors

    # the configuration's optimizer: Adam's first step and the bias
    names = ["l0_in_proj_weight", "l0_conv_weight", "l0_out_proj_weight",
             "l1_q_proj_weight", "l1_k_proj_weight", "l1_q_norm_gamma",
             "l2_moe_gate_weight", "l2_moe_experts_i2h_weight",
             "l4_conv_weight", "embed_weight"]
    want = REF.reference_step(cfg, params, {"data": tokens},
                              {"softmax_label": labels}, ADAM, names)
    assert want["loss"] == ref["loss"]
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
    for blk in BLOCKS:
        moved = aux[blk + "_select_bias"].asnumpy()
        assert np.allclose(moved, want["bias_moves"][blk], atol=1e-9)
        assert np.allclose(np.abs(moved)[moved != 0], 1e-3)


def test_the_tied_weights_update_is_the_sum_of_its_two_gradients():
    """``embed_weight`` is read by the lookup and by the head.  Its SGD
    step is the step of the SUM of the two uses' gradients: the head's
    (a row for every id, the softmax's) and the lookup's (rows of the
    batch's ids only), each taken from an untied twin of the symbol's
    arithmetic, the reference with two separate tables."""
    net, kwargs, params, tokens, labels = _tiny(seed=23)
    _, grads = _sgd_gradients(net, params, tokens, labels)
    cfg = {"model": {"kwargs": kwargs}}
    m = dict(kwargs)

    def untied_loss(table, head):
        p = {k: jnp.asarray(v) for k, v in params.items()}
        x = table[jnp.asarray(tokens)]
        for l, kind in enumerate(m["layer_types"]):
            x, _ = REF.block(p, "l%d_" % l, x, m, kind,
                             l < m["dense_layers"])
        return REF.head_loss((p["final_norm_gamma"], head), x,
                             jnp.asarray(labels), m)

    e = jnp.asarray(params["embed_weight"])
    with jax.default_matmul_precision("highest"):
        d_lookup, d_head = jax.grad(untied_loss, argnums=(0, 1))(e, e)
    d_lookup, d_head = np.asarray(d_lookup), np.asarray(d_head)
    # the two uses differ in kind: the lookup's touches the batch's rows
    touched = np.zeros(kwargs["vocab_size"], bool)
    touched[tokens.reshape(-1)] = True
    assert not d_lookup[~touched].any() and d_head[~touched].any()
    assert _rel(grads["embed_weight"], d_lookup + d_head) <= 2e-4
    assert _rel(grads["embed_weight"], d_head) > 0.05
    assert _rel(grads["embed_weight"], d_lookup) > 0.05
    want = REF.loss_and_grads(cfg, params, tokens, labels,
                              names=["embed_weight"])["grads"]
    assert _rel(want["embed_weight"], d_lookup + d_head) <= 1e-5


def test_the_convolution_mixer_is_causal_gated_twice_and_has_three_taps():
    """One convolution layer under a routed MLP left out of the picture
    (its output weights zero): a later token's change moves no earlier
    loss, a token three places back moves nothing either (3 taps: its
    own position and two before), and each gate's third of ``in_proj``
    at zero silences the mixer: the loss is the bare embedding's."""
    kwargs = dict(TINY, num_layers=1, layer_types=["conv"], dense_layers=1)
    net, _, params, tokens, labels = _tiny(seed=29, **kwargs)
    params["l0_down_proj_weight"][:] = 0
    base = _losses(net, params, tokens, labels)
    cfg = {"model": {"kwargs": kwargs}}
    assert abs(base.mean() - REF.loss_and_grads(
        cfg, params, tokens, labels, names=[])["loss"]) <= 1e-5 * base.mean()
    other = tokens.copy()
    other[:, 5] = (tokens[:, 5] + 1) % kwargs["vocab_size"]
    moved = _losses(net, params, other, labels)
    assert np.array_equal(moved[:, :5], base[:, :5])
    assert np.abs(moved[:, 5:8] - base[:, 5:8]).min() > 1e-6
    assert np.allclose(moved[:, 8:], base[:, 8:], rtol=1e-6, atol=1e-7)
    D = kwargs["hidden_size"]
    silent = dict(params, l0_out_proj_weight=np.zeros_like(
        params["l0_out_proj_weight"]))
    bare = _losses(net, silent, tokens, labels)
    assert np.abs(bare - base).max() > 1e-3
    for third in range(3):
        cut = dict(params, l0_in_proj_weight=params[
            "l0_in_proj_weight"].copy())
        cut["l0_in_proj_weight"][third * D:(third + 1) * D] = 0
        assert np.allclose(_losses(net, cut, tokens, labels), bare,
                           rtol=1e-6, atol=1e-7), third


# -- one rank's share ----------------------------------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """32 experts over 4 ranks of 8 (``first_expert`` 0, 8, 16, 24)
    under the sigmoid router with its selection bias, top-4, the weights
    normalized over all 4 chosen: the ranks' outputs summed are the
    reference's layer with all experts held, and each rank's output is
    the reference given the same share."""
    E, k, held = 32, 4, 8
    rng = np.random.RandomState(5)
    T, D, H = 40, 12, 10
    x = rng.randn(T, D).astype(np.float32)
    full = {"moe_gate_weight": rng.randn(E, D),
            "moe_experts_i2h_gate_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D)}
    full = {n: v.astype(np.float32) for n, v in full.items()}
    bias = (0.3 * rng.randn(E)).astype(np.float32)
    m = {"num_experts": E, "experts_per_tok": k, "route_scale": 1.0}
    state = {"moe_dispatch_select_bias": jnp.asarray(bias)}
    p = dict({n: jnp.asarray(v) for n, v in full.items()}, **state)
    with jax.default_matmul_precision("highest"):
        whole, counts = REF.moe(p, "", jnp.asarray(x), m)
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, held):
        mine = {n: (v[first:first + held] if "experts" in n else v)
                for n, v in full.items()}
        net = MoEFeedForward(
            mx.sym.Variable("data"), num_hidden=H, num_experts=E, k=k,
            capacity_factor=0.0, name="moe", act_type="silu", gated=True,
            no_bias=True, renormalize=True, output_dim=D, score="sigmoid",
            scale=1.0, bias_rate=1e-3, experts_held=held,
            first_expert=first)
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        exe.arg_dict["data"][:] = x
        for n, v in mine.items():
            exe.arg_dict[n][:] = v
        exe.aux_dict["moe_dispatch_select_bias"][:] = bias
        exe.forward(is_train=False)
        out = exe.outputs[0].asnumpy()
        with jax.default_matmul_precision("highest"):
            want = np.asarray(REF.moe(
                dict({n: jnp.asarray(v) for n, v in mine.items()}, **state),
                "", jnp.asarray(x), dict(m, experts_held=held,
                                         first_expert=first))[0])
        assert np.abs(out - want).max() <= 1e-4 * np.abs(want).max()
        assert np.abs(out).max() > 0
        total += out
    assert np.asarray(counts).sum() == T * k
    assert np.abs(total - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()


# -- attention at 64 lanes -----------------------------------------------------
def test_the_tpu_wrapper_at_64_lanes_interpreted(monkeypatch):
    """The form the TPU lowering runs at heads of 64 (zero lanes behind
    q, k and v up to 128, the output's lanes cut), with the library
    kernel interpreted on the CPU at tiles of 128 over 512 rows, 8 query
    heads over 2 key/value heads: output and the three input gradients
    against the plain blocks in float32, inside bfloat16's rounding; the
    kernel is handed 128-lane heads and hands back 64."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    seen = []
    make = sk.make_splash_mha_single_device

    def made(*args, **kwargs):
        attend = make(*args, interpret=True, **kwargs)

        def watched(q, k, v):
            seen.append((q.shape, k.shape, v.shape))
            return attend(q, k, v)
        return watched

    monkeypatch.setattr(sk, "make_splash_mha_single_device", made)
    monkeypatch.setattr(tf_ops, "ATTN_KERNEL_BLOCK", 128)
    rng = np.random.RandomState(61)
    q, k, v = (jnp.asarray(rng.randn(1, 512, h, 64), BF16)
               for h in (8, 2, 2))
    w = jnp.asarray(rng.randn(1, 512, 8, 64), F32)
    assert tf_ops._kernel_takes(q, k, v)
    kind, scale = ("causal", 0), 64 ** -0.5

    def run(fn, *args):
        out, vjp = jax.vjp(lambda *a: fn(*a, scale, kind).astype(F32),
                           *args)
        return [np.asarray(x, np.float32) for x in (out,) + vjp(w)]

    got = run(tf_ops._flash_attention, q, k, v)
    want = run(tf_ops._plain_attention, *(x.astype(F32) for x in (q, k, v)))
    assert seen and set(seen) == {((8, 512, 128), (2, 512, 128),
                                   (2, 512, 128))}
    assert [g.shape for g in got] == [(1, 512, 8, 64), (1, 512, 8, 64),
                                      (1, 512, 2, 64), (1, 512, 2, 64)]
    for g, r in zip(got, want):
        assert np.abs(g - r).max() <= 0.02 * np.abs(r).max()


@pytest.mark.parametrize("shape, hkv, dv, takes, pads", [
    ((1, 8192, 32, 64), 8, 64, True, 3),      # the cell's: q, k, v padded
    ((1, 4096, 32, 128), 4, 128, True, 0),    # every 128-lane cell's
    ((1, 1024, 4, 192), 4, 128, True, 2),     # latent attention: q and k
    ((1, 1024, 4, 32), 4, 32, False, 0),      # under 64 lanes: plain
    ((1, 1024, 4, 96), 4, 96, False, 0),      # a value of no whole 64
])
def test_which_heads_lower_to_the_kernel_on_a_tpu(shape, hkv, dv, takes,
                                                  pads):
    """The 64-lane case beside the ones ``attn:lowering`` already had:
    the lowered TPU text holds the two splash kernels and a ``pad`` for
    each of q, k, v the wrapper widens (each twice: the forward and the
    backward pass's forming of it again), or the plain blocks; the
    track prints the shape."""
    b, t, h, dh = shape
    q = jax.ShapeDtypeStruct(shape, BF16)
    k = jax.ShapeDtypeStruct((b, t, hkv, dh), BF16)
    v = jax.ShapeDtypeStruct((b, t, hkv, dv), BF16)
    fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, dh ** -0.5).astype(F32).sum(), argnums=(0, 1, 2)))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.export.export(fn, platforms=["tpu"])(q, k, v) \
            .mlir_module()
        events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    # the repo's own pair takes grouped 128-lane heads; 64-lane heads and
    # latent attention's widths are the library's
    pair = "none" if not takes else "rows" if dh == dv == 128 else "library"
    assert events[0]["args"] == {
        "kernel": int(takes), "plain": int(not takes), "pair": pair,
        "mask_form": "library" if takes else "none"}
    assert events[0]["id"] == "bfloat16%s%s%s" % (
        list(shape), "" if dv == dh else "x%d" % dv,
        "" if hkv == h else "/kv%d" % hkv)
    assert text.count("tpu_custom_call") == (2 if takes else 0)
    if takes:
        assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
        assert text.count("stablehlo.pad") >= pads
        assert (text.count("stablehlo.pad") == 0) == (pads == 0)


# -- counts --------------------------------------------------------------------
def test_reference_flops_are_the_hand_count():
    """ISSUE 61's arithmetic at the cell's sizes, a forward token: four
    convolution mixers' projections 4 x 16.78 M x 2 = 134.2 M FLOP, the
    attention layer's projections 2 x 10.49 M = 21.0 M and its causal
    pairs 4 x 64 x 32 x 4096.5 = 33.6 M, the dense MLP 88.1 M, four
    routers 0.52 M, the held routed share (4 x 8 / 32 = 1 expert a
    token) 4 x 22.0 M = 88.1 M, the head 67.1 M: 432.6 M, three times
    that a trained token, 10.6 TFLOP a step of 8192."""
    kwargs = dict(num_layers=5, hidden_size=2048,
                  layer_types=["conv", "full_attention", "conv", "conv",
                               "conv"],
                  dense_layers=1, num_heads=32, num_kv_heads=8, head_dim=64,
                  conv_kernel=3, dense_width=7168, num_experts=32,
                  experts_per_tok=4, expert_width=1792, vocab_size=16384,
                  seq_len=8192, experts_held=8)
    conv = 4 * 2 * 2048 * (3 * 2048 + 2048)
    proj = 2 * 2048 * 64 * (32 + 8 + 8 + 32)
    assert REF.causal_pairs(8192) == 8192 * 8193 // 2 == 33558528
    scores = 4 * 64 * 32 * 33558528 / 8192
    dense = 6 * 2048 * 7168
    routers = 4 * 2 * 2048 * 32
    held = 4 * 6 * 2048 * 1792
    head = 2 * 2048 * 16384
    forward = conv + proj + scores + dense + routers + held + head
    assert forward == pytest.approx(432.6e6, rel=1e-3)
    got = REF.train_flops_per_sample({"model": {"kwargs": kwargs}})
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert got == pytest.approx(1.298e9, rel=1e-3)
    assert got * 8192 == pytest.approx(10.63e12, rel=1e-3)
    # the shares ISSUE 61 names, of the whole
    assert conv / forward == pytest.approx(0.31, abs=0.005)
    assert dense / forward == pytest.approx(0.20, abs=0.005)
    assert held / forward == pytest.approx(0.20, abs=0.005)
    assert head / forward == pytest.approx(0.155, abs=0.005)
    assert (proj + scores) / forward == pytest.approx(0.126, abs=0.005)
    # all experts held: 4 experts a token, 3 more than the share's 1
    whole = REF.train_flops_per_sample(
        {"model": {"kwargs": dict(kwargs, experts_held=0)}})
    assert whole - got == pytest.approx(3 * 3 * held, rel=1e-12)


# -- scopes and the counters ---------------------------------------------------
def test_device_scopes_and_the_lowering_counters_name_both_mixers():
    net, kwargs, params, tokens, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(tokens), softmax_label=jnp.asarray(labels))
    aux = {name: jnp.zeros((kwargs["num_experts"],), F32)
           for name in net.list_auxiliary_states()}
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.jit(lambda a: prog.eval(a, aux, jax.random.PRNGKey(0),
                                           True)[0]).lower(args) \
            .as_text(debug_info=True)
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
        conv = mx.trace.counter_events(["conv:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    for scope in ("gsc_proj.l0", "gsc_conv.l0", "gsc_conv.l4", "gsc_proj.l2",
                  "attn_proj.l1", "attn.l1", "moe_experts.l1",
                  "moe_route.l2", "moe_combine.l4", "lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope
    for absent in ("moe_experts.l0", "gsc_conv.l1", "attn.l0"):
        assert absent not in text
    assert [e["id"] for e in attn] == ["float32[2, 16, 4, 8]/kv2"]
    assert [e["id"] for e in conv] == ["float32[2, 16, 96]/gated32"] * 4
    assert all(e["args"] == {"kernel": 0, "plain": 1} for e in conv)


# -- ISSUE 70: q's and k's norm and rotation, one node on the rows ---------
def test_q_and_k_are_placed_by_one_node_on_the_rows():
    """The attention layers' q and k are normed and rotated by ONE
    ``HeadNormRotary`` under ``attn_proj.l<i>`` (at the cell's 64-lane
    heads the op's plain lowering: ``rotary:lowering`` reads ``plain``
    for any head that is not 128 lanes)."""
    net = lfm2_moe_lm(**TINY)
    attends = [l for l, kind in enumerate(TINY["layer_types"])
               if kind == "full_attention"]
    placed = placed_on_rows(net)
    assert [(name, scope, ins) for name, scope, _, ins in placed] == [
        ("l%d_%s_norm" % (l, x), "attn_proj.l%d" % l,
         ["l%d_%s_proj" % (l, x), "l%d_%s_norm_gamma" % (l, x)])
        for l in attends for x in "qk"]
    for _, _, how, _ in placed:
        assert (how["head_dim"], how["norm"], how["seq_len"], how["theta"],
                how["eps"]) == (TINY["head_dim"], True, TINY["seq_len"],
                                TINY["rope_theta"], TINY["rms_eps"])
    assert not nodes(net, "RotaryEmbedding")
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        shape = (BATCH, TINY["seq_len"])
        args, _, _ = net.infer_shape(data=shape, softmax_label=shape)
        net.simple_bind(mx.cpu(), grad_req="null", data=shape,
                        softmax_label=shape).forward()
        chosen = mx.trace.counter_events(["rotary:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    rows = BATCH * TINY["seq_len"]
    assert [(e["id"], e["args"]) for e in chosen[:2]] == [
        ("float32[%d, %d]/%d" % (rows, n * TINY["head_dim"],
                                 TINY["head_dim"]),
         {"kernel": 0, "plain": 1})
        for n in (TINY["num_heads"], TINY["num_kv_heads"])]
