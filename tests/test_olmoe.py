"""OLMoE through the Symbol graph (ISSUE 26, tier-1): the transformer
block's ops against jnp and numeric gradients, the drop-free routed
experts against the plain reference's dense loop, and the whole model
(logits, loss, every gradient) against ``benchmark/reference/
olmoe-1b-7b.py`` in float32, at a tolerance bfloat16 compute fails."""
import functools
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
from mxnet_tpu.models import olmoe_lm                     # noqa: E402
from mxnet_tpu.moe import MoEFeedForward                  # noqa: E402
from mxnet_tpu.ops import transformer as tf_ops           # noqa: E402
from check_utils import (check_numeric_gradient,          # noqa: E402
                         check_symbolic_forward)

import manifest                                           # noqa: E402

REF = manifest.load_module("reference", "olmoe-1b-7b")


# -- the ops -----------------------------------------------------------------

def test_rmsnorm_forward_and_gradient():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 8).astype(np.float32)
    g = (1 + 0.1 * rng.randn(8)).astype(np.float32)
    sym = mx.sym.RMSNorm(mx.sym.Variable("data"), mx.sym.Variable("gamma"),
                         eps=1e-5)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    check_symbolic_forward(sym, {"data": x, "gamma": g}, [want], 1e-5)
    check_numeric_gradient(sym, {"data": x, "gamma": g}, numeric_eps=1e-3,
                           check_eps=0.02)
    # float32 statistics under bfloat16 data: the output keeps the dtype
    out = tf_ops.rms_norm(jnp.asarray(x, jnp.bfloat16),
                          jnp.asarray(g, jnp.bfloat16), 1e-5)
    assert out.dtype == jnp.bfloat16
    assert np.abs(np.asarray(out, np.float32) - want).max() < 0.05


def test_rotary_matches_reference_and_keeps_norm():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 3, 8).astype(np.float32)
    sym = mx.sym.RotaryEmbedding(mx.sym.Variable("data"), theta=10000.0)
    want = np.asarray(REF.rotary(jnp.asarray(x), 10000.0))
    check_symbolic_forward(sym, {"data": x}, [want], 1e-5)
    # position 0 is the identity; a rotation keeps each head's norm
    assert np.allclose(want[:, 0], x[:, 0], atol=1e-6)
    assert np.allclose(np.linalg.norm(want, axis=-1),
                       np.linalg.norm(x, axis=-1), rtol=1e-5)
    check_numeric_gradient(sym, {"data": x}, numeric_eps=1e-3,
                           check_eps=0.02)


def _dense_attention(q, k, v):
    t, dh = q.shape[1], q.shape[3]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    s = np.where(np.tril(np.ones((t, t), bool))[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("seq,block", [(6, 512), (12, 4), (10, 4)],
                         ids=["one-block", "blocks", "padded-blocks"])
def test_causal_attention_forward_and_gradient(seq, block, monkeypatch):
    """One block, whole blocks and a padded last block give the dense
    softmax(q k^T / sqrt(Dh) + mask) v, and the gradient of the
    recomputing (checkpointed) blocks is the dense one."""
    monkeypatch.setattr(tf_ops, "ATTN_BLOCK_Q", block)
    rng = np.random.RandomState(2)
    q, k, v = (rng.randn(2, seq, 2, 4).astype(np.float32) for _ in range(3))
    sym = mx.sym.CausalSelfAttention(
        mx.sym.Variable("query"), mx.sym.Variable("key"),
        mx.sym.Variable("value"), layer=3)
    loc = {"query": q, "key": k, "value": v}
    check_symbolic_forward(sym, loc, [_dense_attention(q, k, v)], 1e-5)
    if seq <= 10:
        check_numeric_gradient(sym, loc, numeric_eps=1e-3, check_eps=0.03)
    # the future does not leak: changing the last key/value moves only
    # the last query's output
    k2, v2 = k.copy(), v.copy()
    k2[:, -1] += 1.0
    v2[:, -1] -= 1.0
    a = tf_ops.causal_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), 0.5)
    b = tf_ops.causal_attention(jnp.asarray(q), jnp.asarray(k2),
                                jnp.asarray(v2), 0.5)
    assert np.array_equal(np.asarray(a)[:, :-1], np.asarray(b)[:, :-1])


def test_attention_scores_are_never_materialized():
    """No array of B*H*T*T elements in the forward or backward jaxpr
    beyond one query block's."""
    b, t, h, dh = 2, 2048, 2, 8
    q = jax.ShapeDtypeStruct((b, t, h, dh), jnp.float32)
    fn = jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, 0.35).sum(), argnums=(0, 1, 2))
    sizes = _jaxpr_sizes(jax.make_jaxpr(fn)(q, q, q).jaxpr)
    assert max(sizes) <= b * h * tf_ops.ATTN_BLOCK_Q * t


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("dtype,shape,platform,kernel_inputs", [
    (BF16, (1, 256, 2, 128), "tpu", True),
    (BF16, (1, 2048, 2, 128), "tpu", True),
    (BF16, (1, 256, 2, 128), "cpu", True),
    (F32, (1, 256, 2, 128), "tpu", False),
    (BF16, (1, 1536, 2, 128), "tpu", False),
    (BF16, (1, 768, 2, 128), "tpu", False),
    (BF16, (1, 200, 2, 128), "tpu", False),
    (BF16, (1, 256, 2, 64), "tpu", True),
    (BF16, (1, 256, 2, 32), "tpu", False),
    (BF16, (1, 256, 2, 96), "tpu", False),
], ids=["bf16-tpu", "bf16-2-tiles-tpu", "bf16-cpu", "float32-tpu",
        "seq-not-whole-tiles-tpu", "tile-not-whole-slices-tpu",
        "seq-not-128s-tpu", "head-64-tpu", "head-32-tpu", "head-96-tpu"])
def test_attention_lowering_is_chosen_from_platform_and_inputs(
        dtype, shape, platform, kernel_inputs):
    """bfloat16 at shapes the kernel's tiling takes, lowered for a TPU,
    is the Mosaic kernel, forward and the fused backward one (a 64-wide
    head too, since PR 61: the wrapper pads it to 128 lanes); float32, a
    sequence that is not whole tiles, a 32- or 96-wide head, and ANY CPU
    lowering are the plain blocks.  Read off the text lowered for the
    platform (no chip, no libtpu) and off ``attn:lowering``, which says
    what the op's TPU lowering is."""
    x = jax.ShapeDtypeStruct(shape, dtype)
    fn = jax.jit(jax.grad(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, 0.3).astype(F32).sum(), argnums=(0, 1, 2)))
    mark = time.perf_counter_ns()
    text = jax.export.export(fn, platforms=[platform])(x, x, x).mlir_module()
    want = 2 if kernel_inputs and platform == "tpu" else 0
    assert text.count("tpu_custom_call") == want
    events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    assert len(events) == 1, "once a trace of the op"
    assert events[0]["args"] == {
        "kernel": int(kernel_inputs), "plain": int(not kernel_inputs),
        "pair": "library" if kernel_inputs else "none",
        "mask_form": "library" if kernel_inputs else "none"}
    assert events[0]["id"] == "%s%s" % (np.dtype(dtype).name, list(shape))


def test_kernel_lowering_matches_dense_attention_interpreted(monkeypatch):
    """The library kernel the TPU lowering runs, interpreted on the CPU
    at tiles of 128 (so blocks above the diagonal are skipped and the
    online softmax spans two tiles): output and the three input
    gradients against dense float32 attention of the same bfloat16
    inputs, inside bfloat16's rounding; the future does not leak."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(sk, "make_splash_mha_single_device",
                        functools.partial(sk.make_splash_mha_single_device,
                                          interpret=True))
    monkeypatch.setattr(tf_ops, "ATTN_KERNEL_BLOCK", 128)
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(2, 256, 2, 128), BF16)
               for _ in range(3))
    w = jnp.asarray(rng.randn(2, 256, 2, 128), F32)
    assert tf_ops._kernel_takes(q, k, v)
    scale = 128 ** -0.5

    def run(fn, *args):
        out, vjp = jax.vjp(lambda *a: fn(*a, scale).astype(F32), *args)
        return [np.asarray(x, np.float32) for x in (out,) + vjp(w)]

    got = run(tf_ops._flash_attention, q, k, v)
    k2, v2 = k.at[:, -1].add(1.0), v.at[:, -1].add(-1.0)
    moved = np.asarray(tf_ops._flash_attention(q, k2, v2, scale), np.float32)
    # the parity twin on the same values in float32 is the dense result
    want = run(tf_ops._plain_attention, *(x.astype(F32) for x in (q, k, v)))
    assert np.allclose(want[0], _dense_attention(
        *(np.asarray(x, np.float32) for x in (q, k, v))), atol=1e-5)
    for g, r in zip(got, want):
        # bfloat16 probabilities and outputs: 2**-8 a rounding
        assert np.abs(g - r).max() <= 0.02 * np.abs(r).max()
    assert np.array_equal(moved[:, :-1], got[0][:, :-1])
    assert not np.array_equal(moved[:, -1], got[0][:, -1])


def test_silu_and_the_gated_product_inside_the_expert_op():
    """SiLU has no op of its own: it is ``_moe_expert_ffn``'s
    ``act_type="silu"``, and the gated product its ``gated`` form (here
    on a hand-built ``(E, C, D)`` bucket, counts unused)."""
    rng = np.random.RandomState(3)
    e, c, d, h = 2, 3, 4, 6
    x, wg, w1 = (rng.randn(*s).astype(np.float32)
                 for s in ((e, c, d), (e, d, h), (e, d, h)))
    w2 = rng.randn(e, h, d).astype(np.float32)
    args = {"data": x, "wg": wg, "w1": w1, "w2": w2,
            "counts": np.zeros(e, np.float32)}
    names = ["data", "wg", "w1", "w2", "counts"]

    def silu(a):
        return a / (1 + np.exp(-a))

    plain = mx.sym._moe_expert_ffn(
        *[mx.sym.Variable(n) for n in names if n != "wg"], num_hidden=h,
        act_type="silu", no_bias=True)
    check_symbolic_forward(
        plain, {n: v for n, v in args.items() if n != "wg"},
        [np.einsum("ech,eho->eco", silu(np.einsum("ecd,edh->ech", x, w1)),
                   w2)], 1e-4)
    gated = mx.sym._moe_expert_ffn(
        *[mx.sym.Variable(n) for n in names], num_hidden=h,
        act_type="silu", no_bias=True, gated=True)
    hid = silu(np.einsum("ecd,edh->ech", x, wg)) * \
        np.einsum("ecd,edh->ech", x, w1)
    check_symbolic_forward(gated, args,
                           [np.einsum("ech,eho->eco", hid, w2)], 1e-4)


def test_softmax_ce_loss_emits_loss_and_softmax_minus_onehot():
    rng = np.random.RandomState(4)
    n, v = 6, 11
    x = (3 * rng.randn(n, v)).astype(np.float32)
    y = rng.randint(0, v, n).astype(np.float32)
    loss = mx.sym.SoftmaxCELoss(mx.sym.Variable("data"),
                                mx.sym.Variable("label"))
    p = np.exp(x - x.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = -np.log(p[np.arange(n), y.astype(int)])
    assert loss.infer_shape(data=(n, v))[1] == [(n,)]
    check_symbolic_forward(loss, {"data": x, "label": y}, [want], 1e-5)
    # through MakeLoss(normalization="batch"): (softmax - onehot) / n
    net = mx.sym.MakeLoss(loss, normalization="batch")
    exe = net.simple_bind(mx.cpu(), data=(n, v), label=(n,),
                          grad_req={"data": "write", "label": "null"})
    exe.arg_dict["data"][:] = x
    exe.arg_dict["label"][:] = y
    exe.forward(is_train=True)
    exe.backward()
    onehot = np.eye(v, dtype=np.float32)[y.astype(int)]
    assert np.allclose(exe.grad_dict["data"].asnumpy(), (p - onehot) / n,
                       atol=1e-6)
    # bfloat16 logits: the loss is float32 and close
    out = tf_ops._softmax_ce(jnp.asarray(x, jnp.bfloat16),
                             jnp.asarray(y, jnp.int32))
    assert out.dtype == jnp.float32
    assert np.abs(np.asarray(out) - want).max() < 0.1


# -- drop-free routed experts ------------------------------------------------

def _jaxpr_sizes(jaxpr):
    """Element counts of every value a jaxpr (and its sub-jaxprs) makes."""
    sizes = [1]
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            sizes.append(int(np.prod(shape)) if shape else 1)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    sizes.extend(_jaxpr_sizes(inner))
    return sizes


def _moe_block(E, k, H, gated=True, no_bias=True, cf=0.0):
    return MoEFeedForward(mx.sym.Variable("data"), num_hidden=H,
                          num_experts=E, k=k, capacity_factor=cf,
                          name="l0_moe", act_type="silu", gated=gated,
                          no_bias=no_bias, layer=0)


def _gate_for(routing, E, D, rng):
    """Router weights (E, D) that, with non-negative features, route
    ``balanced`` (random), ``one-expert`` (k=1 territory: expert 2 far
    ahead, the rest in order) or leave the last expert ``empty``."""
    w = (0.5 * rng.randn(E, D)).astype(np.float32)
    if routing == "one-expert":
        w = np.abs(w) * 0.01
        w[2] += 5.0
    elif routing == "empty-expert":
        w[E - 1] = -5.0
    return w


@pytest.mark.parametrize("E,k", [(8, 2), (64, 8)], ids=["E8k2", "E64k8"])
@pytest.mark.parametrize("routing",
                         ["balanced", "one-expert", "empty-expert"])
def test_drop_free_experts_match_dense_loop(routing, E, k):
    """The sorted layout against the reference's dense loop over E with
    a mask: output, counts, aux, and the gradients of the input and all
    three stacked expert tensors; nothing dropped in any routing."""
    T, D, H = 48, 16, 8
    rng = np.random.RandomState(5)
    x = np.abs(rng.randn(T, D)).astype(np.float32)
    p = {"l0_moe_gate_weight": _gate_for(routing, E, D, rng),
         "l0_moe_experts_i2h_gate_weight":
             (0.3 * rng.randn(E, D, H)).astype(np.float32),
         "l0_moe_experts_i2h_weight":
             (0.3 * rng.randn(E, D, H)).astype(np.float32),
         "l0_moe_experts_h2o_weight":
             (0.3 * rng.randn(E, H, D)).astype(np.float32)}
    net = _moe_block(E, k, H)
    from mxnet_tpu.moe import (aux_loss_symbols, count_symbols,
                               dropped_symbols)
    group = mx.sym.Group([net, mx.sym.BlockGrad(aux_loss_symbols(net)[0]),
                          count_symbols(net)[0], dropped_symbols(net)[0]])
    exe = group.simple_bind(mx.cpu(), data=(T, D), grad_req="write")
    # the sorted layout: T*k rows, no (E, C, D) bucket anywhere
    shapes = dict(zip(net.get_internals().list_outputs(),
                      net.get_internals().infer_shape(data=(T, D))[1]))
    assert shapes["l0_moe_dispatch_dispatched"] == (T * k, D)
    assert shapes["l0_moe_experts_output"] == (T * k, D)
    exe.arg_dict["data"][:] = x
    for name, val in p.items():
        exe.arg_dict[name][:] = val
    exe.forward(is_train=True)
    out, aux, counts, dropped = (o.asnumpy() for o in exe.outputs)
    m = {"num_experts": E, "experts_per_tok": k}
    jp = {n: jnp.asarray(v) for n, v in p.items()}

    def ref_sum(xv, params):
        y, a, c = REF.moe(params, "l0_", xv, m)
        return y.sum(), (y, a, c)

    with jax.default_matmul_precision("highest"):
        (_, (want, want_aux, want_counts)), (gx, gp) = jax.value_and_grad(
            ref_sum, argnums=(0, 1), has_aux=True)(jnp.asarray(x), jp)
    assert np.allclose(out, np.asarray(want), rtol=1e-4, atol=1e-5)
    assert np.allclose(aux[0], float(want_aux), rtol=1e-5)
    assert np.array_equal(counts, np.asarray(want_counts))
    assert counts.sum() == T * k and float(dropped[0]) == 0.0
    if routing == "one-expert":
        assert counts[2] == T                    # every token chose it
    if routing == "empty-expert":
        assert counts[E - 1] == 0
    exe.backward()
    assert np.allclose(exe.grad_dict["data"].asnumpy(), np.asarray(gx),
                       rtol=1e-3, atol=1e-5)
    for name in p:
        if name.endswith("gate_weight") and "experts" not in name:
            continue          # the router's gradient: whole-model test
        got, ref = exe.grad_dict[name].asnumpy(), np.asarray(gp[name])
        assert np.allclose(got, ref, rtol=1e-3, atol=1e-5), name
        if routing == "empty-expert":
            assert not got[E - 1].any(), name    # no token, no gradient


@pytest.mark.parametrize("gated,no_bias", [(False, False), (True, False),
                                           (False, True)],
                         ids=["plain-bias", "gated-bias", "plain-nobias"])
def test_sorted_layout_equals_capacity_layout_when_nothing_drops(gated,
                                                                 no_bias):
    """capacity_factor 0 (sorted rows) and a capacity that holds every
    choice (buckets, cf large) are the same function, biases and the
    plain form included."""
    T, D, H, E, k = 32, 8, 6, 4, 2
    rng = np.random.RandomState(6)
    outs = []
    for cf in (0.0, 100.0):
        net = _moe_block(E, k, H, gated=gated, no_bias=no_bias, cf=cf)
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="write")
        rng = np.random.RandomState(6)
        for name in net.list_arguments():
            exe.arg_dict[name][:] = rng.randn(
                *exe.arg_dict[name].shape).astype(np.float32) * 0.4
        exe.forward(is_train=True)
        exe.backward()
        outs.append((exe.outputs[0].asnumpy(),
                     {n: g.asnumpy() for n, g in exe.grad_dict.items()}))
    (a, ga), (b, gb) = outs
    assert np.allclose(a, b, rtol=1e-5, atol=1e-6)
    for name in ga:
        assert np.allclose(ga[name], gb[name], rtol=1e-4, atol=1e-5), name


def test_no_drop_allocates_no_expert_by_token_buffer():
    """capacity_factor=0 at E=64, T=512, D=16: no value of E*T*D
    elements exists in the forward or backward jaxpr (the old no-drop
    bucket was exactly that), and the expert matmuls see T*k rows."""
    E, T, D, H, k = 64, 512, 16, 8, 8
    net = _moe_block(E, k, H)
    from mxnet_tpu.executor import _GraphProgram
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(T, D))[0]))

    def loss(args):
        outs, _ = prog.eval(args, {}, jax.random.PRNGKey(0), True)
        return outs[0].sum()

    args = {n: jax.ShapeDtypeStruct(s, jnp.float32)
            for n, s in shapes.items()}
    sizes = _jaxpr_sizes(jax.make_jaxpr(jax.grad(loss))(args).jaxpr)
    assert max(sizes) < E * T * D
    assert max(sizes) <= max(T * k * D, E * D * H, T * E * k)


def test_serve_parity_pass_repins_to_the_sorted_layout():
    """MoEServeParityPass rewrites only the dispatch node's capacity;
    the expert and combine nodes follow by the rank of their data."""
    from mxnet_tpu.passes import MoEServeParityPass
    net = _moe_block(4, 2, 6, gated=False, no_bias=False, cf=0.5)
    out, _ = MoEServeParityPass().apply(net, {})
    shapes = dict(zip(out.get_internals().list_outputs(),
                      out.get_internals().infer_shape(data=(16, 8))[1]))
    assert shapes["l0_moe_dispatch_dispatched"] == (16 * 2, 8)
    assert out.infer_shape(data=(16, 8))[1] == [(16, 8)]


# -- the whole model ---------------------------------------------------------

TINY = dict(num_layers=2, hidden_size=64, num_heads=4, expert_width=32,
            vocab_size=128, seq_len=32, rope_theta=10000.0, rms_eps=1e-5,
            aux_coef=0.01)
BATCH = 2
# float32 system against float32 reference.  The two differ in the order
# of float32 sums (grouped matmuls over sorted rows against a masked
# dense loop, blockwise against whole-row softmax) and in the gradient
# being read back as (w - w') / lr from float32 weights: measured 2e-6
# to 3e-5 relative per tensor on this machine.  bfloat16 compute (8 bits
# of mantissa, 4e-3 per rounding) lands at 1e-2 or more and must fail.
GRAD_RTOL = 2e-4
LOSS_RTOL = 1e-5
SGD_LR = 1024.0


def _tiny_params(kwargs, seed):
    rng = np.random.RandomState(seed)
    net = olmoe_lm(**kwargs)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(BATCH, kwargs["seq_len"]),
        softmax_label=(BATCH, kwargs["seq_len"]))[0]))
    params = {}
    for name, shape in shapes.items():
        if name in ("data", "softmax_label"):
            continue
        if name.endswith("gamma"):
            params[name] = (1 + 0.1 * rng.randn(*shape)).astype(np.float32)
        else:
            # wide enough that routing and attention are far from uniform
            params[name] = (0.15 * rng.randn(*shape)).astype(np.float32)
    tokens = rng.randint(0, kwargs["vocab_size"],
                         (BATCH, kwargs["seq_len"])).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    return net, params, tokens, labels


def _system_step(net, params, tokens, labels):
    """One SGD step (lr SGD_LR, no momentum, no decay, rescale 1) of the
    fused train step: -> (mean CE, aux per layer, counts per layer,
    {name: gradient = (before - after) / lr}).  The fused step is what
    honours MXNET_COMPUTE_DTYPE, so float32 and bfloat16 take this same
    path."""
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(data_shapes=[("data", tokens.shape)],
             label_shapes=[("softmax_label", labels.shape)])
    mod.init_params(arg_params={k: mx.nd.array(v) for k, v in params.items()},
                    aux_params={})
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": SGD_LR, "momentum": 0.0, "wd": 0.0,
        "rescale_grad": 1.0})
    assert mod._fused is not None
    batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                            label=[mx.nd.array(labels)], pad=0)
    mod.forward_backward(batch)
    mod.update()
    outs = [o.asnumpy() for o in mod.get_outputs()]
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    # outputs: the loss head, one aux head a block, the (L, E + 1) load
    grads = {k: (params[k] - after[k]) / SGD_LR for k in params}
    return (float(outs[0].mean()), [float(a[0]) for a in outs[1:-1]],
            outs[-1][:, :-1], grads)


def _grad_errors(grads, ref_grads):
    return {k: float(np.linalg.norm(grads[k] - np.asarray(ref_grads[k]))
                     / max(float(np.linalg.norm(ref_grads[k])), 1e-30))
            for k in grads}


@pytest.mark.parametrize("E,k", [(8, 2), (64, 8)], ids=["E8k2", "E64k8"])
def test_model_matches_reference_in_float32_and_bfloat16_does_not(
        E, k, monkeypatch):
    kwargs = dict(TINY, num_experts=E, experts_per_tok=k)
    net, params, tokens, labels = _tiny_params(kwargs, seed=11)
    cfg = {"model": {"kwargs": kwargs}}
    ref = REF.loss_and_grads(cfg, params, tokens, labels)

    # logits: the head's input to the loss, through a plain executor
    logits_sym = net.get_internals()["lm_head_output"]
    exe = logits_sym.simple_bind(mx.cpu(), data=tokens.shape,
                                 grad_req="null")
    exe.arg_dict["data"][:] = tokens
    for name, val in params.items():
        exe.arg_dict[name][:] = val
    exe.forward(is_train=False)
    logits = exe.outputs[0].asnumpy()
    ref_logits = np.asarray(ref["logits"])
    assert np.abs(logits - ref_logits).max() \
        <= 1e-4 * np.abs(ref_logits).max()

    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    loss, aux, counts, grads = _system_step(net, params, tokens, labels)
    assert abs(loss - ref["loss"]) <= LOSS_RTOL * ref["loss"]
    assert np.allclose(aux, ref["aux"], rtol=1e-5)
    for got, want in zip(counts, ref["counts"]):
        assert np.array_equal(got, np.asarray(want))
    errors = _grad_errors(grads, ref["grads"])
    assert set(errors) == set(ref["grads"])
    assert max(errors.values()) <= GRAD_RTOL, errors

    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    loss16, _, _, grads16 = _system_step(net, params, tokens, labels)
    errors16 = _grad_errors(grads16, ref["grads"])
    assert abs(loss16 - ref["loss"]) > LOSS_RTOL * ref["loss"]
    assert max(errors16.values()) > 10 * GRAD_RTOL, errors16
    # ... yet it is the same model: coarse agreement
    assert abs(loss16 - ref["loss"]) < 0.02 * ref["loss"]


def test_reference_flops_are_the_active_parameter_count():
    """3 x [L x (8 D^2 + 2 T D + 2 D E + 6 k D H) + 2 D V] at the
    published widths, depth 1, vocabulary 12576: 202.8 MFLOP a token."""
    cfg = {"model": {"kwargs": dict(
        num_layers=1, hidden_size=2048, num_heads=16, num_experts=64,
        experts_per_tok=8, expert_width=1024, vocab_size=12576,
        seq_len=4096)}}
    per_token = REF.train_flops_per_sample(cfg)
    layer = 8 * 2048 ** 2 + 2 * 4096 * 2048 + 2 * 2048 * 64 \
        + 6 * 8 * 2048 * 1024
    assert per_token == 3 * (layer + 2 * 2048 * 12576)
    assert abs(per_token / 3 - 202.8e6) < 0.1e6


def test_fit_feeds_moe_load_counter_and_stats():
    """Module.fit on the model: the fused step runs, the token embedding
    takes the sparse path, and every step feeds MoeStats and one
    ``moe:load`` counter sample a block, none dropped."""
    kwargs = dict(TINY, num_experts=8, experts_per_tok=2)
    net, _, _, _ = _tiny_params(kwargs, seed=3)
    rng = np.random.RandomState(0)
    X = rng.randint(0, 128, (16, 32)).astype(np.int32)
    it = mx.io.NDArrayIter(X, np.roll(X, -1, 1), batch_size=BATCH)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        since = time.perf_counter_ns()
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1, eval_metric=mx.metric.OutputMean(0),
                optimizer="adam", initializer=mx.init.Normal(0.02),
                optimizer_params={"learning_rate": 1e-3,
                                  "rescale_grad": 1.0})
        events = mx.trace.counter_events(["moe:load"], since_ns=since)
    finally:
        mx.trace.set_enabled(was)
    assert mod._fused is not None and "embed_weight" in \
        mod._fused.sparse_embeds
    head, blocks = mod._fused.head("moe_load")
    assert blocks == ["l0_moe_dispatch", "l1_moe_dispatch"]
    # ONE (blocks, E + 1) head, the symbol's last output
    assert net.list_outputs()[head] == "moe_load_output" and \
        head == len(net.list_outputs()) - 1
    assert mod.get_outputs()[head].shape == (2, 8 + 1)
    steps = 16 // BATCH
    assert len(events) == steps * len(blocks)
    for e in events:
        assert e["id"] in blocks
        assert e["args"]["dropped"] == 0.0
        assert e["args"]["routed"] == BATCH * 32 * 2
        assert e["args"]["max"] >= e["args"]["mean"] == BATCH * 32 * 2 / 8
    rep = mod._fused.moe_stats.report()["blocks"]
    assert rep["l0_moe_dispatch"]["steps"] == steps
    assert rep["l0_moe_dispatch"]["dropped"] == 0.0


def test_a_checkpoint_written_before_pr36_still_loads():
    """The combine node reads the dispatch node's ``order`` since PR 36:
    the parameters, the saved graph's arguments and the loss are the
    commit before's (``tests/common/old_checkpoint.py``)."""
    from old_checkpoint import check_checkpoint_written_before_pr36
    net, _, tokens, labels = _tiny_params(
        dict(TINY, num_experts=8, experts_per_tok=2), seed=36)
    check_checkpoint_written_before_pr36(
        "olmoe", net, tokens, labels,
        {"learning_rate": 1e-3, "rescale_grad": 1.0})
