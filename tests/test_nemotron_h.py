"""Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B) through the Symbol graph
(ISSUE 71, tier-1): the whole tiny model of one-branch layers against
``benchmark/reference/nemotron-3-nano-30b-a3b.py`` in float32 (loss, every
gradient, Adam's first step, the selection bias's first move); the
state-space scan's kernel pair (interpreted) over ``G`` > 1 groups against
a token-by-token scan, forward and all seven gradients; the grouped gated
norm; the squared ReLU; the grouped-matmul kernels at a width of 64 x odd
against ``ragged_dot``; all ranks' shares of the plain expert layer summed
against the uncut reference; the lowering rules, the counters and the
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

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu.executor import _GraphProgram              # noqa: E402
from mxnet_tpu.models import nemotron_h_lm                # noqa: E402
from mxnet_tpu.models.nemotron_h import PATTERN           # noqa: E402
from mxnet_tpu.moe import MoEFeedForward, gmm             # noqa: E402
from mxnet_tpu.ops import ssd                             # noqa: E402
from mxnet_tpu.ops.nn import ACTIVATIONS                  # noqa: E402
from mxnet_tpu.ops.transformer import grouped_rms_norm    # noqa: E402

import manifest                                           # noqa: E402
from test_granite_hybrid import scan_errors, scan_inputs  # noqa: E402
from test_moe_gmm import (ROWS as GMM_ROWS, _both_ways,    # noqa: E402
                          _lowerings)

REF = manifest.load_module("reference", "nemotron-3-nano-30b-a3b")

TINY = dict(num_layers=5, hidden_size=32,
            layer_types=["mamba", "moe", "attention", "moe", "mamba"],
            ssm_heads=4, ssm_head_dim=8, ssm_state=12, ssm_groups=2,
            conv_kernel=4, num_heads=4, num_kv_heads=2, head_dim=8,
            num_experts=8, experts_per_tok=3, expert_width=24,
            shared_width=40, route_scale=2.5, vocab_size=50, seq_len=24,
            rms_eps=1e-5, bias_rate=1e-3, experts_held=4, first_expert=0)
BATCH = 2
ADAM = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95,
        "epsilon": 1e-8, "wd": 0.0, "rescale_grad": 1.0}
F32, BF16 = jnp.float32, jnp.bfloat16


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


# -- the scan over groups --------------------------------------------------------
@pytest.mark.parametrize("dtype, heads, groups, limit", [
    (F32, 8, 2, 5e-4),        # two groups of four heads: one step a group
    (BF16, 16, 8, 2e-2),      # eight groups of one lane tile each
    (BF16, 32, 2, 2e-2),      # sixteen heads a group: two steps a group
])
def test_the_kernel_pair_over_groups_is_the_token_by_token_scan(dtype, heads,
                                                                groups, limit):
    """Two chunks of 128 tokens: forward and all seven gradients, ``dB``
    and ``dC`` summed over a group's heads (and over a group's grid
    steps).  bfloat16 is held to the scan of the same rounded inputs."""
    args = scan_inputs(1, 256, heads, ssd.SSD_HEAD_DIM, groups,
                       ssd.SSD_STATE, dtype, seed=heads + groups)
    assert ssd._step_heads(heads, groups) == min(8, heads // groups)
    errors = scan_errors(lambda *a: ssd._two_lowerings(*a, True), args)
    assert max(errors.values()) <= limit, errors


@pytest.mark.parametrize("shape, takes, step_heads", [
    ((1, 4096, 64, 64, 8, 128), True, 8),      # the cell's: a step a group
    ((1, 4096, 64, 64, 1, 128), True, 8),      # Granite's, as it was
    ((1, 4096, 64, 64, 2, 128), True, 8),      # four steps a group
    ((1, 4096, 64, 64, 16, 128), True, 4),     # a step cut to a group's 4
    ((1, 4096, 64, 64, 32, 128), True, 2),     # one lane tile a group
    ((1, 4096, 64, 64, 64, 128), False, 0),    # half a lane tile a group
    ((1, 4096, 24, 64, 4, 128), True, 6),      # six heads a group
    ((1, 4096, 64, 64, 8, 64), False, 0),      # another state
])
def test_the_lowering_rule_takes_groups_whose_heads_fill_lane_tiles(
        shape, takes, step_heads):
    b, t, h, p, g, n = shape
    x = jax.ShapeDtypeStruct((b, t, h, p), BF16)
    bm = jax.ShapeDtypeStruct((b, t, g, n), BF16)
    assert ssd._kernel_takes(x, bm) is takes
    if takes:
        assert ssd._step_heads(h, g) == step_heads
        assert (h // g) % step_heads == 0


def test_a_tpu_program_holds_the_grouped_kernels_and_the_tracks_say_so():
    shape = (1, 256, 16, 64, 4, 128)       # no other test's: traced once
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
    assert [e["id"] for e in chose] == ["bfloat16[1, 256, 16, 64]/g4n128"]
    assert chose[0]["args"]["kernel"] == 1 and chose[0]["args"]["plain"] == 0
    assert [e["id"] for e in traced] \
        == ["bfloat16[1, 256, 16, 64]/g4n128"] * 2
    assert all(e["args"]["heads_a_step"] == 4 for e in traced)


# -- the grouped gated norm ------------------------------------------------------
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_grouped_norm_is_a_statistic_a_group_and_one_gain(groups):
    rng = np.random.RandomState(groups)
    x = jnp.asarray(rng.randn(6, 32), F32)
    gamma = jnp.asarray(1 + 0.1 * rng.randn(32), F32)
    got = grouped_rms_norm(x, gamma, 1e-5, groups)
    parts = np.asarray(x).reshape(6, groups, -1)
    want = (parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(6, 32) * np.asarray(gamma)
    assert _rel(got, want) <= 1e-6
    assert _rel(REF.rms_norm(x, gamma, 1e-5, groups), want) <= 1e-6


def test_the_norm_op_says_its_groups_only_where_it_has_them():
    data = mx.sym.Variable("data")
    plain = mx.sym.RMSNorm(data, eps=1e-5, name="n")
    grouped = mx.sym.RMSNorm(data, eps=1e-5, groups=4, name="n")
    assert "groups" not in plain.tojson() and "groups" in grouped.tojson()
    assert grouped.infer_shape(data=(3, 32))[0] == [(3, 32), (32,)]
    rng = np.random.RandomState(0)
    x = rng.randn(3, 32).astype(np.float32)
    gamma = (1 + 0.1 * rng.randn(32)).astype(np.float32)
    exe = grouped.simple_bind(mx.cpu(), grad_req="null", data=x.shape)
    exe.arg_dict["data"][:] = x
    exe.arg_dict["n_gamma"][:] = gamma
    exe.forward(is_train=False)
    assert _rel(exe.outputs[0].asnumpy(),
                REF.rms_norm(jnp.asarray(x), jnp.asarray(gamma), 1e-5, 4)) \
        <= 1e-6
    with pytest.raises(mx.MXNetError):
        grouped_rms_norm(jnp.zeros((2, 30)), jnp.ones((30,)), 1e-5, 4)


# -- the squared ReLU ------------------------------------------------------------
def test_the_squared_relu_has_the_relus_zeros_and_twice_its_slope():
    x = jnp.asarray([-3.0, -1e-3, 0.0, 1e-3, 0.5, 2.0], F32)
    relu2 = ACTIVATIONS["relu2"]
    got = np.asarray(relu2(x))
    assert (got[:3] == 0.0).all() and np.allclose(got[3:], [1e-6, 0.25, 4.0])
    grad = np.asarray(jax.vmap(jax.grad(relu2))(x))
    assert (grad[:3] == 0.0).all() and np.allclose(grad[3:], [2e-3, 1.0, 4.0])
    bf = relu2(x.astype(BF16))
    assert bf.dtype == BF16 and (np.asarray(bf[:3], np.float32) == 0.0).all()
    assert np.allclose(np.asarray(REF.relu2(x)), got)
    # the op and the experts take it by name
    data = mx.sym.Variable("data")
    exe = mx.sym.Activation(data, act_type="relu2").simple_bind(
        mx.cpu(), grad_req="null", data=(6,))
    exe.arg_dict["data"][:] = np.asarray(x)
    exe.forward(is_train=False)
    assert np.array_equal(exe.outputs[0].asnumpy(), got)
    with pytest.raises(Exception):
        mx.sym.Activation(data, act_type="relu3")


# -- the grouped matmul at a width that is no whole number of lane tiles ---------
ODD_K, ODD_N = 192, 320                  # 64 x 3 and 64 x 5


@pytest.mark.parametrize("dtype, limit", [("float32", 2e-6),
                                          ("bfloat16", 1e-2)])
def test_the_kernels_at_a_width_of_64_times_odd_match_ragged_dot(dtype, limit):
    """``K`` = 192 and ``N`` = 320 are each ONE block, the array's whole
    dimension: the three products against ``ragged_dot`` and its
    autodiff, over groups that sum to fewer rows than the matrix has."""
    assert gmm.tiles_for(GMM_ROWS, ODD_K, ODD_N, 4, jnp.dtype(dtype)) \
        == (gmm.ROW_TILE, ODD_K, ODD_N)
    got, want = _both_ways("fewer_rows_than_m", dtype, kn=(ODD_K, ODD_N))
    for product in ("forward", "backward_data", "backward_weight"):
        a, b = (np.asarray(x[product], np.float32) for x in (got, want))
        assert a.shape == b.shape and np.isfinite(a).all()
        assert np.abs(a - b).max() <= limit * max(np.abs(b).max(), 1.0), \
            product


def test_every_traced_grouped_product_says_its_lowering():
    """``moe:gmm_lowering``: ``kernel`` 1 where the tile rule takes the
    product, ``turned`` 1 where the kernels read the weight through its
    transpose (a last dimension of one and a half lane tiles under a first
    of two) and on no other sample (the benchmark's tests hold a plain
    sample's fields to the two it had), ``plain`` 1 where ``ragged_dot``
    stays on every platform: a shape the rule would turn too, over rows
    that are no whole tile."""
    sizes = jnp.asarray([128, 128], jnp.int32)

    def all_four(rows, w, narrow, odd):
        return gmm.tiled_matmul(rows[:, :192], w, sizes).sum() \
            + gmm.tiled_matmul(rows[:, :96], narrow, sizes).sum() \
            + gmm.tiled_matmul(rows, odd, sizes).sum() \
            + gmm.tiled_matmul(rows[:100], odd, sizes).sum()

    assert _lowerings(all_four, jnp.zeros((256, 256), BF16),
                      jnp.zeros((2, 192, 64), BF16),
                      jnp.zeros((2, 96, 64), BF16),
                      jnp.zeros((2, 256, 192), BF16)) == [
        ("bfloat16[256] x [2, 192, 64]", {"kernel": 1, "plain": 0}),
        ("bfloat16[256] x [2, 96, 64]", {"kernel": 0, "plain": 1}),
        ("bfloat16[256] x [2, 256, 192]",
         {"kernel": 1, "plain": 0, "turned": 1}),
        ("bfloat16[100] x [2, 256, 192]", {"kernel": 0, "plain": 1})]


@pytest.mark.parametrize("name, held", [("_moe_expert_ffn", 0),
                                        ("_moe_expert_ffn", 8),
                                        ("_moe_share_ffn", 8)])
def test_the_stacked_weights_are_declared_as_the_reference_reads_them(
        name, held):
    """Reading the up projection through its transpose is the kernels'
    business: the two nodes declare ``(E, D, W)`` and ``(E, W, D)`` at the
    cell's widths as before (the reference computes ``x @ w_up[e]``,
    checkpoints and initializers see these shapes)."""
    op = mx.ops.get_op(name)
    p = op.parse_params({"num_hidden": 1856, "act_type": "relu2",
                         "no_bias": True, "experts_held": held})
    known = {"data": (4096, 2688), "weight": (4096, 6), "counts": (128,)}
    shapes = op.infer_shape(p, [known.get(n) for n in op.list_arguments(p)])[0]
    assert [s for n, s in zip(op.list_arguments(p), shapes)
            if n.endswith("_weight") and n != "weight"] == [
        (held or 128, 2688, 1856), (held or 128, 1856, 2688)]


def test_the_cell_declares_its_expert_weights_in_the_shapes_it_had():
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        kwargs = json.load(f)["model"]["kwargs"]
    net = nemotron_h_lm(**kwargs)
    inputs = {n: (1, kwargs["seq_len"]) for n in ("data", "softmax_label")}
    shapes = dict(zip(net.list_arguments(), net.infer_shape(**inputs)[0]))
    up = {n: s for n, s in shapes.items() if n.endswith("experts_i2h_weight")}
    down = {n: s for n, s in shapes.items()
            if n.endswith("experts_h2o_weight")}
    assert len(up) == len(down) == 4
    assert set(up.values()) == {(8, 2688, 1856)}
    assert set(down.values()) == {(8, 1856, 2688)}
    assert all(gmm.reads_turned(*s[1:]) for s in up.values())
    assert not any(gmm.reads_turned(*s[1:]) for s in down.values())


# -- the builder -----------------------------------------------------------------
def _tiny(seed, **over):
    kwargs = dict(TINY, **over)
    net = nemotron_h_lm(**kwargs)
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
            params[name] = rng.randn(*shape).astype(np.float32)
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
    net = nemotron_h_lm(**TINY)
    names = net.list_arguments()
    for name in ("l0_norm_gamma", "l0_in_proj_weight", "l0_conv_weight",
                 "l0_conv_bias", "l0_ssm_a_log_bias", "l0_ssm_dt_bias",
                 "l0_ssm_d_gamma", "l0_ssm_norm_gamma", "l0_out_proj_weight",
                 "l1_norm_gamma", "l1_moe_gate_weight",
                 "l1_moe_experts_i2h_weight", "l1_moe_experts_h2o_weight",
                 "l1_moe_shared_i2h_weight", "l1_moe_shared_h2o_weight",
                 "l2_q_proj_weight", "l2_o_proj_weight", "l4_conv_bias",
                 "final_norm_gamma", "embed_weight", "lm_head_weight"):
        assert name in names, name
    # one branch a layer: a mixer has no MLP behind it, an expert layer no
    # mixer before it, and no expert has a gate projection
    assert not any("i2h_gate" in n or "ffn_norm" in n or "q_norm" in n
                   for n in names)
    assert not any(n.startswith("l1_") and ("proj" in n or "conv" in n)
                   for n in names)
    assert sum(n.endswith("norm_gamma") and "ssm" not in n
               for n in names) == 5 + 1
    shapes = dict(zip(names, net.infer_shape(
        data=(BATCH, 24), softmax_label=(BATCH, 24))[0]))
    assert shapes["l0_in_proj_weight"] == (2 * 32 + 2 * 24 + 4, 32)
    assert shapes["l0_conv_weight"] == (32 + 48, 4)
    assert shapes["l0_ssm_norm_gamma"] == (32,)
    assert shapes["l1_moe_gate_weight"] == (8, 32)
    assert shapes["l1_moe_experts_i2h_weight"] == (4, 32, 24)
    assert shapes["l1_moe_shared_i2h_weight"] == (40, 32)
    assert shapes["l2_k_proj_weight"] == (16, 32)
    assert net.list_outputs() == ["lm_output", "moe_load_output"]
    assert net.list_auxiliary_states() == ["l1_moe_dispatch_select_bias",
                                           "l3_moe_dispatch_select_bias"]
    text = net.tojson()
    assert "force_mirroring" not in text and "RotaryEmbedding" not in text \
        and "HeadNormRotary" not in text
    assert [PATTERN[c] for c in "MEMEM*EME"] == [
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe", "mamba",
        "moe"]
    with pytest.raises(ValueError):
        nemotron_h_lm(**dict(TINY, layer_types=["mamba", "mlp", "attention",
                                                "moe", "mamba"]))
    with pytest.raises(ValueError):
        nemotron_h_lm(**dict(TINY, ssm_groups=3))


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
    # the held experts got rows and a gradient; the load head counts every
    # choice over all experts
    assert np.abs(ref["grads"]["l1_moe_experts_i2h_weight"]).max() > 0
    load = mod.get_outputs()[1].asnumpy()
    assert load.shape == (2, 8 + 1)
    assert (load[:, :8].sum(axis=1) == BATCH * 24 * 3).all()
    assert (load[:, 8] == 0).all()
    for row, block in zip(load, ("l1_moe_dispatch", "l3_moe_dispatch")):
        assert np.array_equal(row[:8], np.asarray(ref["counts"][block]))

    names = ["l0_in_proj_weight", "l0_conv_weight", "l0_conv_bias",
             "l0_ssm_a_log_bias", "l0_ssm_dt_bias", "l0_ssm_d_gamma",
             "l0_ssm_norm_gamma", "l0_out_proj_weight", "l1_moe_gate_weight",
             "l1_moe_experts_i2h_weight", "l1_moe_experts_h2o_weight",
             "l1_moe_shared_i2h_weight", "l1_moe_shared_h2o_weight",
             "l2_q_proj_weight", "l2_o_proj_weight", "embed_weight",
             "lm_head_weight"]
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
    # the selection bias started at zero and made its first move
    for block, move in want["bias_moves"].items():
        assert np.array_equal(aux[block + "_select_bias"].asnumpy(),
                              np.asarray(move, np.float32)), block


def test_the_selection_bias_enters_the_choice_and_not_the_weights():
    """A bias that lifts one expert into every token's choice changes
    WHO is chosen; the weights stay the chosen experts' own sigmoids over
    their sum times the scale, whatever the bias is."""
    E, k, scale = 8, 3, 2.5
    rng = np.random.RandomState(3)
    T, D, H = 32, 12, 10
    x = rng.randn(T, D).astype(np.float32)
    weights = {"moe_gate_weight": rng.randn(E, D),
               "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
               "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D),
               "moe_shared_i2h_weight": 0.5 * rng.randn(H, D),
               "moe_shared_h2o_weight": 0.5 * rng.randn(D, H)}
    weights = {n: v.astype(np.float32) for n, v in weights.items()}
    m = {"num_experts": E, "experts_per_tok": k, "route_scale": scale}
    net = MoEFeedForward(
        mx.sym.Variable("data"), num_hidden=H, num_experts=E, k=k,
        capacity_factor=0.0, name="moe", act_type="relu2", gated=False,
        no_bias=True, renormalize=True, output_dim=D, score="sigmoid",
        scale=scale, bias_rate=1e-3, shared_hidden=H)
    exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
    exe.arg_dict["data"][:] = x
    for n, v in weights.items():
        exe.arg_dict[n][:] = v
    outs = {}
    for lifted in (0.0, 10.0):
        bias = np.zeros(E, np.float32)
        bias[5] = lifted
        exe.aux_dict["moe_dispatch_select_bias"][:] = bias
        exe.forward(is_train=False)
        outs[lifted] = exe.outputs[0].asnumpy()
        p = dict({n: jnp.asarray(v) for n, v in weights.items()},
                 moe_dispatch_select_bias=jnp.asarray(bias))
        with jax.default_matmul_precision("highest"):
            want, counts = REF.moe(p, "", jnp.asarray(x), m)
        assert np.abs(outs[lifted] - np.asarray(want)).max() \
            <= 1e-4 * np.abs(np.asarray(want)).max()
        if lifted:
            assert float(counts[5]) == T         # chosen by every token
    assert np.abs(outs[10.0] - outs[0.0]).max() > 1e-3


# -- all ranks' shares -----------------------------------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 ranks of 4 (``first_expert`` 0, 4, 8, 12) under
    the sigmoid router with its selection bias, the weights normalized
    over all 6 chosen and scaled by 2.5, PLAIN squared-ReLU experts: each
    rank's output (its held experts' part plus the shared expert), summed
    with the shared expert counted once, is the reference's layer with
    all experts held; and each rank's output is the reference given the
    same share."""
    E, k, held, scale = 16, 6, 4, 2.5
    rng = np.random.RandomState(5)
    T, D, H, S = 40, 12, 10, 20
    x = rng.randn(T, D).astype(np.float32)
    full = {"moe_gate_weight": rng.randn(E, D),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D),
            "moe_shared_i2h_weight": 0.5 * rng.randn(S, D),
            "moe_shared_h2o_weight": 0.5 * rng.randn(D, S)}
    full = {n: v.astype(np.float32) for n, v in full.items()}
    bias = (0.3 * rng.randn(E)).astype(np.float32)
    m = {"num_experts": E, "experts_per_tok": k, "route_scale": scale}
    state = {"moe_dispatch_select_bias": jnp.asarray(bias)}
    p = dict({n: jnp.asarray(v) for n, v in full.items()}, **state)
    with jax.default_matmul_precision("highest"):
        whole, counts = REF.moe(p, "", jnp.asarray(x), m)
        shared = np.asarray(REF.relu2(
            jnp.asarray(x) @ p["moe_shared_i2h_weight"].T)
            @ p["moe_shared_h2o_weight"].T)
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, held):
        mine = {n: (v[first:first + held] if "experts" in n else v)
                for n, v in full.items()}
        net = MoEFeedForward(
            mx.sym.Variable("data"), num_hidden=H, num_experts=E, k=k,
            capacity_factor=0.0, name="moe", act_type="relu2", gated=False,
            no_bias=True, renormalize=True, output_dim=D, score="sigmoid",
            scale=scale, bias_rate=1e-3, shared_hidden=S,
            experts_held=held, first_expert=first)
        assert "moe_experts_i2h_gate_weight" not in net.list_arguments()
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
        total += out - shared
    total += shared
    assert np.asarray(counts).sum() == T * k
    assert np.abs(total - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()


# -- counts ----------------------------------------------------------------------
def test_reference_flops_are_the_hand_count():
    """ISSUE 71's arithmetic, a forward token: four Mamba layers 4 x (55.4
    + 22.0 M) and the scan 4 x 2.1 M, four expert layers 4 x (0.7 router +
    39.9 shared + 7.5 held routed at 0.375 expected held choices), the
    attention layer 46.8 + 33.6, the head 88.1: about 679 M, three times
    that a trained token."""
    cfg = manifest._read_json(os.path.join(
        ROOT, "benchmark", "configs", "nemotron-3-nano-30b-a3b.json"),
        "config")
    parts = REF.forward_flops_per_token(cfg)
    D, T = 2688, 4096
    assert parts["ssm_proj"] == 4 * (2 * D * 10304 + 2 * 4096 * D)
    assert parts["ssm_scan"] == 4 * 4 * 128 * 64 * 64
    assert parts["moe_route"] == 4 * 2 * D * 128
    assert parts["moe_shared"] == 4 * 2 * 2 * D * 3712
    assert parts["moe_experts"] == 4 * 6 * 8 / 128 * 2 * 2 * D * 1856
    assert parts["attn_proj"] == 2 * D * 128 * (2 * 32 + 2 * 2)
    assert parts["attn"] == 4 * 128 * 32 * (T + 1) / 2
    assert parts["head"] == 2 * D * 16384
    total = REF.train_flops_per_sample(cfg)
    assert total == 3.0 * sum(parts.values())
    assert 2.0e9 < total < 2.08e9                 # about 2.04 G
    assert 8.2e12 < total * T < 8.5e12            # about 8.3 TFLOP a step
    forward = total / 3.0
    assert abs((parts["ssm_proj"] + parts["ssm_scan"]) / forward - 0.47) < 0.01
    assert abs(parts["head"] / forward - 0.13) < 0.005


def test_device_scopes_and_the_lowering_counters_name_every_branch():
    net, kwargs, params, tokens, labels = _tiny(seed=5)
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    args = {k: jnp.asarray(v) for k, v in params.items()}
    args.update(data=jnp.asarray(tokens), softmax_label=jnp.asarray(labels))
    aux = {n: jnp.zeros((8,), F32) for n in net.list_auxiliary_states()}
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        text = jax.jit(lambda a: prog.eval(a, aux, jax.random.PRNGKey(0),
                                           True)[0]).lower(args) \
            .as_text(debug_info=True)
        scan = mx.trace.counter_events(["ssd:lowering"], since_ns=mark)
        attn = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
        grouped = mx.trace.counter_events(["moe:gmm_lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)
    for scope in ("ssm_proj.l0", "ssm_conv.l0", "ssm_scan.l0", "ssm_norm.l0",
                  "ssm_scan.l4", "moe_route.l1", "moe_experts.l1", "mlp.l1",
                  "mlp.l3", "moe_combine.l3", "attn_proj.l2", "attn.l2",
                  "block_norm.l3",
                  "residual.l4", "lm_head", "lm_loss"):
        assert scope + "/" in text or scope + '"' in text, scope
    for absent in ("ssm_scan.l1", "attn.l0", "mlp.l0", "mlp.l2", "ffn_norm"):
        assert absent not in text
    assert [e["id"] for e in scan] == ["float32[2, 24, 4, 8]/g2n12"] * 2
    assert all(e["args"]["plain"] == 1 for e in scan)
    assert [e["id"] for e in attn] == ["float32[2, 24, 4, 8]/kv2"]
    # the plain form: two grouped products forward, traced once for both
    # layers (the share node's parts are module-level jits); 144 rows are
    # no whole row tile: ragged_dot, and the counter says so
    assert len(grouped) >= 2 and all(e["args"]["plain"] == 1
                                     for e in grouped)
    assert {e["id"] for e in grouped} == {
        "float32[144] x [4, 32, 24]", "float32[144] x [4, 24, 32]"}
