"""One expert-parallel rank's share sized by a static bound on its held
rows (``_moe_share_ffn``, ``moe.dispatch.held_rows_bound``): the one
node against the three-node share it replaces, forward and every
gradient, where the held rows lie under the bound, fill it exactly, and
overflow it (the full-size fallback), and where the geometry has no
bound and the node is the window of every row; the shares of all ranks
still sum to the uncut layer; the rule's values at the benchmark's
shapes; which graph ``MoEFeedForward`` builds; the ``moe:load`` sample's
``bound``."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.moe import MoEFeedForward
from mxnet_tpu.moe.dispatch import (BOUND_WORTH_ROWS, HELD_ROWS_SLACK,
                                    held_rows_bound)
from mxnet_tpu.moe.gmm import ROW_TILE

# ``mxnet_tpu.moe.dispatch`` the attribute is the function of that name
share_rule = sys.modules["mxnet_tpu.moe.dispatch"]
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
import manifest  # noqa: E402

T, K, E, HELD, FIRST, D, H = 512, 4, 32, 4, 5, 32, 48
ROWS = T * K                                     # 2048 routed choices
BOUND = 1024                                     # 4 x (2048 x 4 / 32)


class _Train:
    is_train = True


@pytest.fixture
def small_bounds(monkeypatch):
    """The bound at the tests' sizes: it is taken however few rows it
    saves (at the cells' sizes it has to save ``BOUND_WORTH_ROWS``)."""
    monkeypatch.setattr(share_rule, "BOUND_WORTH_ROWS", 0)


def _ops(score, bias, held=HELD):
    get = mx.ops.get_op
    share = dict(experts_held=held, first_expert=FIRST)
    ffn = dict(num_hidden=H, output_dim=D, act_type="silu", no_bias=True,
               gated=True, layer=1, **share)
    dispatch = get("_moe_dispatch")
    return {
        "dispatch": (dispatch, dispatch.parse_params(dict(
            num_experts=E, k=K, capacity_factor=0.0, renormalize=True,
            score=score, scale=1.5 if score == "sigmoid" else 1.0,
            bias_rate=1e-3 if bias else 0.0, layer=1, **share))),
        "experts": (get("_moe_expert_ffn"),
                    get("_moe_expert_ffn").parse_params(ffn)),
        "combine": (get("_moe_combine"),
                    get("_moe_combine").parse_params(dict(layer=1))),
        "share": (get("_moe_share_ffn"),
                  get("_moe_share_ffn").parse_params(ffn))}


def _run(ops, which, *inputs, aux=()):
    op, p = ops[which]
    out = op.forward(p, list(inputs), list(aux), _Train)
    return out[0] if isinstance(out, tuple) else out


def _inputs(held_rows, score, bias, held=HELD):
    """Logits under which exactly ``held_rows`` of the ``T*k`` choices
    fall on the rank's ``held`` experts: the first ``held_rows / k``
    tokens choose among them alone, the others none of them."""
    rng = np.random.RandomState(held_rows)
    logits = rng.randn(T, E).astype(np.float32)
    logits[:, FIRST:FIRST + held] *= 0.1
    logits[:, FIRST:FIRST + held] -= 12.0
    logits[:held_rows // K, FIRST:FIRST + held] += 24.0
    x = rng.randn(T, D).astype(np.float32)
    ws = [(rng.randn(held, *s) / 6).astype(np.float32)
          for s in ((D, H), (D, H), (H, D))]
    ct = rng.randn(T, D).astype(np.float32)
    aux = [jnp.asarray(1e-3 * rng.randn(E), jnp.float32)] if bias else []
    return [jnp.asarray(a) for a in [x, logits] + ws], jnp.asarray(ct), aux


def _one_against_three(held_rows, score, bias, held):
    """``_moe_share_ffn`` against ``_moe_expert_ffn`` between the dispatch
    node and ``_moe_combine`` for a rank of ``held`` experts: forward and
    the gradients of the data, of the router's logits (through
    ``weight``) and of the three stacked weights; an absent choice's
    weight gets a gradient of exactly 0 from both."""
    ops = _ops(score, bias, held)
    args, ct, aux = _inputs(held_rows, score, bias, held)

    def plan(x, logits):
        return _run(ops, "dispatch", x, logits, aux=aux)

    def three(x, weight, ws, d):
        rows = _run(ops, "experts", d[0], *ws, d[4])[0]
        return _run(ops, "combine", rows, weight, d[2], d[7])[0]

    def one(x, weight, ws, d):
        return _run(ops, "share", x, weight, d[2], d[7], d[4], *ws)[0]

    def graded(layer):
        def loss(x, logits, *ws):
            d = plan(x, logits)
            out = layer(x, d[1], ws, d)
            return (out * ct).sum(), (out, d[4], d[2])
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    (_, (want, counts, slot)), want_grads = graded(three)(*args)
    (_, (got, _, _)), got_grads = graded(one)(*args)
    assert int(np.asarray(counts)[FIRST:FIRST + held].sum()) == held_rows
    assert np.asarray(want).any()
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-6 * scale
    for name, a, b in zip(("data", "logits", "gate", "up", "down"),
                          got_grads, want_grads):
        a, b = np.asarray(a), np.asarray(b)
        assert b.any(), name
        assert np.abs(a - b).max() <= 2e-6 * np.abs(b).max(), name

    # the weights' own gradient, with the plan held fixed
    d = plan(*args[:2])
    absent = np.asarray(slot) >= held_rows
    assert absent.sum() == ROWS - held_rows
    for layer in (three, one):
        d_weight = np.asarray(jax.jit(jax.grad(
            lambda w: (layer(args[0], w, args[2:], d) * ct).sum()))(d[1]))
        assert not d_weight[absent].any()
        assert d_weight[~absent].all()


CASES = [("under", 400), ("exactly_full", BOUND), ("overflow", 1600)]


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("case,held_rows", CASES,
                         ids=[c for c, _ in CASES])
def test_the_one_node_is_the_three_node_share(case, held_rows, score, bias,
                                              small_bounds):
    assert held_rows_bound(ROWS, E, HELD) == BOUND
    _one_against_three(held_rows, score, bias, HELD)


# the LFM2 cell's geometry scaled down (8 of 32 experts, top-4: four
# balanced shares are all T*k rows) with the held rows a few per cent of
# the choices, the balanced quarter, and every one
NO_BOUND = [("a_few_per_cent", 128), ("a_quarter", ROWS // 4),
            ("every_row", ROWS)]


@pytest.mark.parametrize("score,bias", [("softmax", False),
                                        ("sigmoid", True)],
                         ids=["softmax", "sigmoid-bias"])
@pytest.mark.parametrize("case,held_rows", NO_BOUND,
                         ids=[c for c, _ in NO_BOUND])
def test_the_one_node_with_no_bound_is_the_three_node_share(case, held_rows,
                                                            score, bias):
    """A quarter held is no bound, whatever a bound would have to save:
    the node is the window ``(0, T*k)``."""
    assert held_rows_bound(ROWS, E, 2 * HELD) == ROWS
    _one_against_three(held_rows, score, bias, 2 * HELD)


def _share_block(held, first, scale):
    router = dict(score="softmax") if scale is None else dict(
        score="sigmoid", scale=scale, bias_rate=1e-3, shared_hidden=H)
    return MoEFeedForward(mx.sym.Variable("data"), num_hidden=H,
                          num_experts=E, k=K, capacity_factor=0.0,
                          name="moe", act_type="silu", gated=True,
                          no_bias=True, renormalize=True, output_dim=D,
                          experts_held=held, first_expert=first, **router)


@pytest.mark.parametrize("config,scale", [
    ("kimi-linear-48b-a3b", 2.446), ("sdar-30b-a3b", None)],
    ids=["sigmoid-bias-shared", "softmax"])
def test_the_shares_add_up_to_the_uncut_layer_under_the_bound(config, scale,
                                                              small_bounds):
    """32 experts over 8 ranks of 4 at 2048 routed choices: every rank's
    layout is bounded at 1024 rows, the logits load rank 1 past it (the
    fallback) and leave the others under it, and the ranks' outputs
    still sum to the configuration's reference layer with all experts
    held."""
    REF = manifest.load_module("reference", config)
    rng = np.random.RandomState(11)
    x = rng.randn(T, D).astype(np.float32)
    x[:, 0] = 2.0
    gate = (0.2 * rng.randn(E, D)).astype(np.float32)
    gate[4:8, 0] = 3.0                     # rank 1 draws the load
    full = {"moe_gate_weight": gate,
            "moe_experts_i2h_gate_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_i2h_weight": 0.5 * rng.randn(E, D, H),
            "moe_experts_h2o_weight": 0.5 * rng.randn(E, H, D)}
    if scale is not None:
        full.update({"moe_shared_i2h_gate_weight": 0.5 * rng.randn(H, D),
                     "moe_shared_i2h_weight": 0.5 * rng.randn(H, D),
                     "moe_shared_h2o_weight": 0.5 * rng.randn(D, H)})
    full = {n: v.astype(np.float32) for n, v in full.items()}
    bias = (0.05 * rng.randn(E)).astype(np.float32)
    m = {"num_experts": E, "experts_per_tok": K, "routed_scale": scale}
    state = {} if scale is None else \
        {"moe_dispatch_select_bias": jnp.asarray(bias)}
    p = dict({n: jnp.asarray(v) for n, v in full.items()}, **state)
    shared = np.zeros((T, D), np.float32)
    with jax.default_matmul_precision("highest"):
        whole, *_, counts = REF.moe(p, "", jnp.asarray(x), m)
        if scale is not None:
            shared = np.asarray(REF.swiglu(jnp.asarray(x), *(
                p["moe_shared_%s_weight" % n]
                for n in ("i2h_gate", "i2h", "h2o"))))
    counts = np.asarray(counts)
    held_rows = counts.reshape(E // HELD, HELD).sum(axis=1)
    assert (held_rows > BOUND).any() and (held_rows < BOUND).any()
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, HELD):
        net = _share_block(HELD, first, scale)
        assert [n.op.name for n in mx.symbol._topo(net._heads)
                if not n.is_variable and n.op.name.startswith("_moe")] \
            == ["_moe_dispatch", "_moe_share_ffn"]
        exe = net.simple_bind(mx.cpu(), data=(T, D), grad_req="null")
        exe.arg_dict["data"][:] = x
        for n, v in full.items():
            exe.arg_dict[n][:] = v[first:first + HELD] \
                if "experts" in n else v
        if scale is not None:
            exe.aux_dict["moe_dispatch_select_bias"][:] = bias
        exe.forward(is_train=False)
        total += exe.outputs[0].asnumpy() - shared
    total += shared
    assert np.abs(total - np.asarray(whole)).max() \
        <= 1e-4 * np.abs(np.asarray(whole)).max()


@pytest.mark.parametrize("cell,tokens,k,experts,held,want", [
    ("sdar-30b-a3b-train-4k", 8192, 8, 128, 16, 32768),
    ("kimi-linear-48b-a3b-train-4k", 4096, 8, 256, 8, 4096),
    # 8192 rows would be saved of 16 384: not worth a conditional
    ("glm-4.7-flash-train-4k", 4096, 4, 64, 8, 16384),
    # four balanced shares ARE all 32 768 rows
    ("lfm2-8b-a1b-train-8k", 8192, 4, 32, 8, 32768),
    ("a_quarter_held_is_all_rows", 4096, 8, 64, 16, 4096 * 8),
    ("rounded_up_to_a_tile", 16384, 2, 128, 3, 3072),
    ("too_few_rows_to_save", 512, 4, 32, 4, 2048),
    ("every_expert_held", 16384, 8, 64, 0, 16384 * 8)])
def test_the_bound_at_the_cells_shapes(cell, tokens, k, experts, held, want):
    """``HELD_ROWS_SLACK`` times the balanced share in whole row tiles of
    the grouped-matmul kernels, and all ``T*k`` rows where that is no
    fewer.  The four
    cells' numbers are read from their configuration files."""
    if "-train-" in cell:
        kw = manifest.Manifest().cell(cell).config["model"]["kwargs"]
        rows = 2 * kw["seq_len"] if cell.startswith("sdar") \
            else kw["seq_len"]
        assert (rows, kw["experts_per_tok"], kw["num_experts"],
                kw["experts_held"]) == (tokens, k, experts, held)
    got = held_rows_bound(tokens * k, experts, held)
    assert got == want
    balanced = tokens * k * (held or experts) / experts
    tiles = -(-HELD_ROWS_SLACK * balanced // ROW_TILE)
    if tokens * k - tiles * ROW_TILE < BOUND_WORTH_ROWS or not held:
        assert got == tokens * k
    else:
        assert got % ROW_TILE == 0
        assert 0 <= got - HELD_ROWS_SLACK * balanced < ROW_TILE


def test_every_expert_held_builds_the_three_nodes():
    """``experts_held == 0`` is the graph it always was (the tiny OLMoE
    step's lowered text is held by ``tests/test_sdar_moe.py``)."""
    net = MoEFeedForward(mx.sym.Variable("data"), num_hidden=H,
                         num_experts=E, k=K, capacity_factor=0.0,
                         name="moe", no_bias=True, output_dim=D)
    assert [n.op.name for n in mx.symbol._topo(net._heads)
            if not n.is_variable and n.op.name.startswith("_moe")] == \
        ["_moe_dispatch", "_moe_expert_ffn", "_moe_combine"]
    with pytest.raises(mx.base.MXNetError, match="experts_held > 0"):
        mx.sym._moe_share_ffn(
            mx.sym.Variable("data"), num_hidden=H, no_bias=True,
            name="share").infer_shape(
                data=(T, D), share_weight=(T, K), share_slot=(T, K))


def _share_layer(ops):
    """The dispatch node and the share node over its plan."""
    def layer(x, logits, *ws):
        d = _run(ops, "dispatch", x, logits)
        return _run(ops, "share", x, d[1], d[2], d[7], d[4], *ws)[0]
    return layer


def _two_devices():
    from mxnet_tpu.parallel import mesh as _mesh
    return _mesh.tracing_over(_mesh.make_mesh([("ep", 2)],
                                              jax.devices()[:2]))


def test_a_program_over_two_devices_runs_no_cond(small_bounds):
    """The bound is one device's: under a mesh the body runs over all
    rows, today's statements in today's order."""
    layer = _share_layer(_ops("softmax", False))
    args, ct, aux = _inputs(200, "softmax", False)

    def conds(f):
        return str(jax.make_jaxpr(f)(*args)).count(" cond[")

    assert conds(lambda *a: layer(*a)) == 1
    with _two_devices():
        assert conds(lambda *a: layer(*a)) == 0


def test_a_share_with_no_bound_is_one_window_and_no_cond():
    """Where the bound is all ``T*k`` rows the one-device program is the
    window of every row: the module's three jits, ``held_sum`` both ways
    (``_combine_held``, not the picked rows' ``_combine_sorted``), no
    conditional and no second pass.  A program over two devices keeps the
    three nodes' statements."""
    layer = _share_layer(_ops("softmax", False, 2 * HELD))
    args, ct, aux = _inputs(ROWS // 4, "softmax", False, 2 * HELD)

    def texts():
        # new functions each time: jax.make_jaxpr caches by the function
        return [str(jax.make_jaxpr(f)(*args)) for f in (
            lambda *a: layer(*a),
            jax.grad(lambda *a: (layer(*a) * ct).sum(), argnums=(0, 1, 2)))]

    forward, both = texts()
    assert " cond[" not in forward + both
    for part in ("_window_rows", "_window_ffn", "_window_sum"):
        assert "name=%s" % part in forward, part
    assert "name=_combine_held" in forward
    assert "name=_combine_sorted" not in forward
    with _two_devices():
        forward, both = texts()
    assert " cond[" not in forward + both
    assert "name=_window_" not in forward
    assert "name=_combine_sorted" in forward
    assert "name=_combine_held" not in forward


def test_a_second_program_finds_the_bounded_body_traced(small_bounds,
                                                        monkeypatch):
    """The bounded body is a jit of the module: a process traces a
    node's two passes once, and a second program over the same node (a
    run's checking module, then its training one) traces none anew; a
    node with another layer index is another body (its scopes' names)."""
    from mxnet_tpu.ops import moe as moe_ops
    args, ct, aux = _inputs(200, "softmax", False)

    def program(layer):
        ops = _ops("softmax", False)
        ops["share"][1]["layer"] = layer

        def loss(x, logits, *ws):
            d = _run(ops, "dispatch", x, logits)
            out = _run(ops, "share", x, d[1], d[2], d[7], d[4], *ws)[0]
            return (out * ct).sum()
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)

    bodies = []
    window = moe_ops._share_window
    monkeypatch.setattr(moe_ops, "_share_window",
                        lambda p, w: bodies.append(w[0]) or window(p, w))
    program(7)
    assert bodies == [0, BOUND]
    assert " cond[" in str(program(7)) and bodies == [0, BOUND]
    program(8)
    assert bodies == [0, BOUND] * 2


def test_fit_records_the_bound_with_the_load(small_bounds):
    """Every ``moe:load`` sample of a rank's share carries ``bound``, the
    op's own rule at the block's geometry, beside ``held``."""
    from mxnet_tpu.models.sdar_moe import sdar_moe_lm
    kwargs = dict(num_layers=2, hidden_size=32, num_heads=4, num_kv_heads=2,
                  head_dim=8, num_experts=16, experts_per_tok=4,
                  expert_width=24, vocab_size=40, seq_len=72, block_len=4,
                  experts_held=2, first_expert=4)
    net = sdar_moe_lm(**kwargs)
    rng = np.random.RandomState(0)
    L = kwargs["seq_len"]
    X = rng.randint(0, 39, (8, 2 * L)).astype(np.int32)
    Y = np.stack([rng.randint(0, 39, (8, L)).astype(np.float32),
                  np.ones((8, L), np.float32)], axis=1)
    it = mx.io.NDArrayIter(X, Y, batch_size=2)
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        since = time.perf_counter_ns()
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.fit(it, num_epoch=1, eval_metric=mx.metric.OutputMean(0),
                optimizer="adam", initializer=mx.init.Normal(0.02),
                optimizer_params={"learning_rate": 1e-3})
        events = mx.trace.counter_events(["moe:load"], since_ns=since)
    finally:
        mx.trace.set_enabled(was)
    routed = 2 * 2 * L * kwargs["experts_per_tok"]          # 1152
    assert len(events) == 4 * 2
    for e in events:
        a = e["args"]
        assert a["routed"] == routed
        assert a["bound"] == held_rows_bound(routed, 16, 2) == 768
        assert 0 < a["held"] < routed
