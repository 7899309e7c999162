"""The grouped matmul's tiled Pallas kernel pair (ISSUE 38, tier-1).

``moe/gmm.py``'s ``ragged-dot-gmm`` / ``ragged-dot-tgmm`` run here in
the Pallas interpreter, held to ``lax.ragged_dot`` and its autodiff: the
forward, the backward-data and the backward-weight product, in float32
and bfloat16, over balanced groups, one group holding every row, several
empty groups, and groups that sum to fewer rows than the matrix has.
Which lowering a call gets (shape, platform, devices of the program) and
how often a process traces the kernels are read from the jaxpr, the
exported text and the counter ``moe:gmm_trace``.  Times and the chip's
own numerics: ``tests/tpu/test_olmoe_tpu.py``."""
import functools
import importlib
import json
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.moe import MoEFeedForward, gmm
from mxnet_tpu.parallel.mesh import make_mesh, tracing_over

# the package's name ``dispatch`` is the bucket scatter, not the module
dispatch = importlib.import_module("mxnet_tpu.moe.dispatch")

ROWS, K, N = 1024, 256, 128       # four row tiles; one k and one n tile

LAYOUTS = {
    "balanced": [256, 256, 256, 256],
    "one_holds_all": [0, 1024, 0, 0],
    "empty_groups": [0, 100, 0, 300, 0, 0, 624, 0],
    # a rank's share: the groups end inside the second tile, the last
    # two tiles are nobody's and are never visited
    "fewer_rows_than_m": [100, 0, 200, 17],
}
TOLERANCE = {"float32": 2e-6, "bfloat16": 1e-2}


@functools.lru_cache(maxsize=None)
def _products(matmul, layout, dtype_name, k, n):
    """{product: array}: the output and both gradients of
    ``own(matmul(own(rows), w, sizes))`` against a fixed cotangent, ``own``
    being ``_moe_expert_ffn``'s select of the rows that belong to a group."""
    dtype = jnp.dtype(dtype_name)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    rng = np.random.RandomState(len(layout))
    rows = jnp.asarray(rng.randn(ROWS, k), dtype)
    w = jnp.asarray(rng.randn(len(sizes), k, n) / np.sqrt(k), dtype)
    ct = jnp.asarray(rng.randn(ROWS, n), dtype)
    mine = (jnp.arange(ROWS) < sizes.sum())[:, None]

    def own(x):
        return jnp.where(mine, x, jnp.zeros((), x.dtype))

    def loss(rows, w):
        out = own(matmul(own(rows), w, sizes))
        return (out.astype(jnp.float32) * ct.astype(jnp.float32)).sum(), out
    (_, out), (d_rows, d_w) = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(rows, w)
    return {"forward": out, "backward_data": d_rows, "backward_weight": d_w}


_interpreted = functools.partial(gmm.tiled_matmul, interpret=True)


def _both_ways(layout, dtype_name, wide=False, kn=None):
    """(kernels, ragged_dot) each as ``_products`` gives them.  ``kn``:
    another ``(K, N)``."""
    k, n = kn or ((1280, 1280) if wide else (K, N))
    return (_products(_interpreted, layout, dtype_name, k, n),
            _products(gmm.ragged_matmul, layout, dtype_name, k, n))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("product", ["forward", "backward_data",
                                     "backward_weight"])
def test_kernels_match_ragged_dot(product, dtype, layout):
    got, want = (x[product] for x in _both_ways(layout, dtype))
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * max(
        np.abs(want).max(), 1.0)
    if product == "backward_weight":
        empty = [i for i, s in enumerate(LAYOUTS[layout]) if s == 0]
        assert not got[empty].any(), "an expert without rows writes zeros"
    elif layout == "fewer_rows_than_m":
        assert not got[sum(LAYOUTS[layout]):].any()


@pytest.mark.parametrize("product", ["forward", "backward_data",
                                     "backward_weight"])
def test_kernels_match_ragged_dot_over_several_k_and_n_tiles(product):
    """``K`` and ``N`` of two tiles each (1280 = 2 x 640 in float32): the
    accumulator across k steps, the output's n tiles, both read
    transposed in the backward-data product."""
    got, want = (np.asarray(x[product], np.float32)
                 for x in _both_ways("empty_groups", "float32", wide=True))
    assert gmm.tiles_for(ROWS, 1280, 1280, 8, jnp.float32) == (
        gmm.ROW_TILE, 640, 640)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


# (cell, M, K, N, E) of the two stacked shapes (gate / up ``D x W``, down
# ``W x D``) of every expert cell: M the rows its grouped products run over
# (``T k``, or a rank's static bound ``held_rows_bound``), E the experts the
# stacked weights hold; and the tiles the rule gave them before ISSUE 71
# took widths of 64 x odd and ``K`` = 2688, in bfloat16 (what the cells
# compute in) and in float32 (what their parity tests do)
CELL_SHAPES = [
    ("olmoe-1b-7b", 131072, 2048, 1024, 64, (256, 2048, 1024), (256, 1024, 1024)),
    ("olmoe-1b-7b", 131072, 1024, 2048, 64, (256, 1024, 2048), (256, 1024, 1024)),
    ("kimi-linear-48b-a3b", 4096, 2304, 1024, 8, (256, 1152, 1024), (256, 768, 1024)),
    ("kimi-linear-48b-a3b", 4096, 1024, 2304, 8, (256, 1024, 1152), (256, 1024, 768)),
    ("glm-4.7-flash", 16384, 2048, 1536, 8, (256, 2048, 1536), (256, 1024, 768)),
    ("glm-4.7-flash", 16384, 1536, 2048, 8, (256, 1536, 2048), (256, 768, 1024)),
    ("sdar-30b-a3b", 32768, 2048, 768, 16, (256, 2048, 768), (256, 1024, 768)),
    ("sdar-30b-a3b", 32768, 768, 2048, 16, (256, 768, 2048), (256, 768, 1024)),
    ("trinity-mini", 8192, 2048, 1024, 8, (256, 2048, 1024), (256, 1024, 1024)),
    ("trinity-mini", 8192, 1024, 2048, 8, (256, 1024, 2048), (256, 1024, 1024)),
    ("smallthinker-21b-a3b", 24576, 2560, 768, 8, (256, 1280, 768), (256, 640, 768)),
    ("smallthinker-21b-a3b", 24576, 768, 2560, 8, (256, 768, 1280), (256, 768, 640)),
    ("qwen3-next-80b-a3b", 10240, 2048, 512, 32, (256, 2048, 512), (256, 1024, 512)),
    ("qwen3-next-80b-a3b", 10240, 512, 2048, 32, (256, 512, 2048), (256, 512, 1024)),
    ("keye-vl-2.0-30b-a3b", 32768, 2048, 768, 16, (256, 2048, 768), (256, 1024, 768)),
    ("keye-vl-2.0-30b-a3b", 32768, 768, 2048, 16, (256, 768, 2048), (256, 768, 1024)),
    ("lfm2-8b-a1b", 32768, 2048, 1792, 8, (256, 2048, 1792), (256, 1024, 896)),
    ("lfm2-8b-a1b", 32768, 1792, 2048, 8, (256, 1792, 2048), (256, 896, 1024)),
]


def _cell_kwargs(cell):
    """The builder's arguments in ``benchmark/configs/<cell>.json``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", cell + ".json")) as f:
        return json.load(f)["model"]["kwargs"]


@pytest.mark.parametrize("cell,m,k,n,e,bf16,f32", CELL_SHAPES, ids=[
    "%s-%dx%d" % (c[0], c[2], c[3]) for c in CELL_SHAPES])
def test_the_tile_rule_gives_the_nine_expert_cells_the_tiles_they_had(
        cell, m, k, n, e, bf16, f32):
    """A later change of the rule for one width cannot move another cell's
    tiles unseen.  The shapes are read back from the configuration files:
    ``M`` is the rows the cell's grouped products run over."""
    kw = _cell_kwargs(cell)
    rows = kw["seq_len"] * (2 if cell.startswith("sdar") else 1) \
        * kw["experts_per_tok"] * (4 if cell.startswith("olmoe") else 1)
    held = kw.get("experts_held", 0)
    assert m == dispatch.held_rows_bound(rows, kw["num_experts"], held)
    assert e == (held or kw["num_experts"])
    assert {k, n} == {kw["hidden_size"], kw["expert_width"]}
    assert gmm.tiles_for(m, k, n, e, jnp.bfloat16) == bf16
    assert gmm.tiles_for(m, k, n, e, jnp.float32) == f32
    # what every cell's tiles hold: at most two steps along K and along N
    assert k // bf16[1] <= 2 and n // bf16[2] <= 2


@pytest.mark.parametrize("m,k,n,dtype,want", [
    # Nemotron-H's experts: 2688 = 3 x 896 and 1856 = 14.5 x 128, ONE block
    # each (42.5 MiB of VMEM in tgmm, under the limit less an eighth)
    (6144, 2688, 1856, "bfloat16", (256, 2688, 1856)),
    (6144, 1856, 2688, "bfloat16", (256, 1856, 2688)),
    # float32's tiles are twice the bytes: 66 MiB do not fit, K keeps 896
    (6144, 2688, 1856, "float32", (256, 896, 1856)),
    # a width of 64 x odd is one block at any size that fits
    (1024, 192, 320, "float32", (256, 192, 320)),
    # 32 lanes over a half tile: no block
    (1024, 160, 128, "bfloat16", None),
    # 29 x 128 against 2688 fits in no one block: of the pairs that fit the
    # fewest steps, 3 along K and not 29 along N
    (1024, 2688, 3712, "bfloat16", (256, 896, 3712)),
    (1024, 3712, 2688, "bfloat16", (256, 3712, 896)),
    (1000, 2688, 1856, "bfloat16", None),
])
def test_the_tile_rule_at_widths_that_are_no_whole_lane_tiles(m, k, n, dtype,
                                                              want):
    assert gmm.tiles_for(m, k, n, 8, jnp.dtype(dtype)) == want
    # the backward-data product asks with K and N swapped: refused alike
    assert (gmm.tiles_for(m, n, k, 8, jnp.dtype(dtype)) is None) \
        == (want is None)


TURNED_K, TURNED_N = 256, 192            # two lane tiles against one and a half


def _kernels_as_the_weight_lies(rows, w, sizes):
    """The kernel pair in the plain orientation, whatever ``reads_turned``
    says: what ``tiled_matmul`` ran at such a shape until ISSUE 72."""
    return gmm._two_lowerings(rows, w, gmm.group_tiles(sizes, rows.shape[0]),
                              True)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("product", ["forward", "backward_data",
                                     "backward_weight"])
def test_a_weight_read_through_its_transpose_gives_the_same_products(
        product, dtype, layout):
    """``N`` = 192 is no whole number of lane tiles and ``K`` = 256 is:
    ``tiled_matmul`` hands the kernels ``swapaxes(w, 1, 2)``.  Output,
    the rows' gradient and the weight's, in ``w``'s own ``(E, K, N)``, are
    the plain orientation's bit for bit (the same float32 sums over the
    same tiles) and ``ragged_dot``'s within its tolerance, over empty
    groups and a window whose groups end before its rows do (rows behind
    the last group are selected away: the kernels leave them unwritten)."""
    assert gmm.reads_turned(TURNED_K, TURNED_N)
    assert not gmm.reads_turned(TURNED_N, TURNED_K)
    got, want = (x[product] for x in _both_ways(
        layout, dtype, kn=(TURNED_K, TURNED_N)))
    plain = _products(_kernels_as_the_weight_lies, layout, dtype,
                      TURNED_K, TURNED_N)[product]
    assert got.dtype == want.dtype == plain.dtype
    assert got.shape == want.shape == plain.shape
    got, want, plain = (np.asarray(x, np.float32)
                        for x in (got, want, plain))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, plain)
    assert np.abs(got - want).max() <= TOLERANCE[dtype] * max(
        np.abs(want).max(), 1.0)
    if product == "backward_weight":
        empty = [i for i, s in enumerate(LAYOUTS[layout]) if s == 0]
        assert not got[empty].any(), "an expert without rows writes zeros"
    elif layout == "fewer_rows_than_m":
        assert not got[sum(LAYOUTS[layout]):].any()


def _lowerings(fn, *args):
    """``moe:gmm_lowering``'s samples of one trace of ``fn``:
    ``[(track, fields)]``; a sample that is not turned carries no
    ``turned`` field."""
    return [(e["id"], e["args"]) for e in _counter_events(
        "moe:gmm_lowering", lambda: jax.make_jaxpr(fn)(*args))]


@pytest.mark.parametrize("cell", sorted(
    {c[0] for c in CELL_SHAPES} | {"nemotron-3-nano-30b-a3b"}))
def test_of_the_ten_expert_cells_only_nemotrons_up_projection_turns(cell):
    """The orientation is chosen from the stacked weight's shape alone.
    Of the ten configurations' ``(E, D, W)`` (gate / up) and ``(E, W, D)``
    (down), read from the configuration files, ``(8, 2688, 1856)`` is the
    only one whose last dimension is no whole number of lane tiles; the
    counter says ``turned`` there and for no other product, a kernel's or
    not."""
    kw = _cell_kwargs(cell)
    e = kw.get("experts_held") or kw["num_experts"]
    d, w = kw["hidden_size"], kw["expert_width"]
    turns = {(e, d, w): gmm.reads_turned(d, w),
             (e, w, d): gmm.reads_turned(w, d)}
    assert [s for s, t in turns.items() if t] == (
        [(8, 2688, 1856)] if cell.startswith("nemotron") else [])
    sizes = jnp.zeros((e,), jnp.int32)
    for (e, k, n), turned in turns.items():
        for rows in (gmm.ROW_TILE, 100):      # the kernels; no whole row tile
            got = _lowerings(
                lambda x, w: dispatch.grouped_matmul(x, w, sizes),
                jax.ShapeDtypeStruct((rows, k), jnp.bfloat16),
                jax.ShapeDtypeStruct((e, k, n), jnp.bfloat16))
            kernel = rows == gmm.ROW_TILE
            assert got == [("bfloat16[%d] x [%d, %d, %d]" % (rows, e, k, n),
                            dict({"kernel": int(kernel),
                                  "plain": int(not kernel)},
                                 **({"turned": 1} if turned and kernel
                                    else {})))]


@pytest.mark.parametrize("experts,tiles", [(1, 1), (4, 2), (8, 16), (64, 4)])
def test_visits_cover_every_groups_rows_once_and_in_order(experts, tiles):
    """The tile -> group map over random group sizes (balanced, skewed,
    tile-aligned, summing to all, some or none of the rows): every row of
    every group lies in exactly one visit's tile and group, no row behind
    the groups in any; a tile's visits and a group's are consecutive;
    every group, empty or not, has a visit; none beyond
    ``m / tm + E - 1``."""
    tm, rng = gmm.ROW_TILE, np.random.RandomState(experts + tiles)
    m = tm * tiles
    for trial in range(40):
        p = np.exp(rng.randn(experts) * rng.choice([0.0, 1.0, 3.0]))
        sizes = rng.multinomial(int(m * rng.choice([1.0, 1.0, 0.3, 0.0])),
                                p / p.sum()).astype(np.int32)
        if trial % 3 == 0:
            sizes = sizes // tm * tm
        t = gmm.group_tiles(jnp.asarray(sizes), m)
        n = int(t.visits[0])
        group, tile, off = (np.asarray(x) for x in (
            t.group_of[:n], t.tile_of[:n], t.offsets))
        assert n <= tiles + experts - 1
        assert (np.diff(group) >= 0).all() and (np.diff(tile) >= 0).all()
        assert sorted(set(group)) == list(range(experts))
        assert tile.min() >= 0 and tile.max() < tiles
        covered = np.zeros(m, int)
        for g, i in zip(group, tile):
            lo, hi = max(off[g], i * tm), min(off[g + 1], (i + 1) * tm)
            assert hi > lo or sizes[g] == 0, (sizes, g, i)
            covered[lo:max(lo, hi)] += 1
        held = sizes.sum()
        assert (covered[:held] == 1).all() and not covered[held:].any()


def _expert_ffn(**params):
    op = mx.ops.get_op("_moe_expert_ffn")
    p = op.parse_params(dict({"num_hidden": 2 * N, "act_type": "silu",
                              "gated": True, "no_bias": True}, **params))
    return lambda *inputs: op.forward(p, list(inputs), [], None)[0]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _primitives(fn, *args):
    names = [e.primitive.name for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)]
    return names.count("pallas_call"), names.count("ragged_dot_general")


@pytest.fixture
def interpreted(monkeypatch):
    """``grouped_matmul`` with its kernels in the Pallas interpreter."""
    monkeypatch.setattr(dispatch, "grouped_matmul", functools.partial(
        gmm.tiled_matmul, interpret=True))
    return monkeypatch


def test_a_ranks_share_reads_zero_behind_its_groups(interpreted):
    """``_moe_expert_ffn`` holding experts 2..5 of 8 over 1024 sorted
    rows of which 600 are its own: through the kernels the output and
    the rows' gradient behind the groups are exactly zero, and all of it
    is what ``ragged_dot`` gives."""
    rng = np.random.RandomState(3)
    counts = jnp.asarray([50, 70, 150, 0, 250, 200, 204, 100], jnp.float32)
    x = jnp.asarray(rng.randn(ROWS, K), jnp.float32)
    ws = [jnp.asarray(rng.randn(4, *s) / 16, jnp.float32)
          for s in ((K, 2 * N), (K, 2 * N), (2 * N, K))]
    ffn = _expert_ffn(experts_held=4, first_expert=2)

    def grad():
        """A new function each time: jax caches a function's trace."""
        def loss(x, *ws):
            out = ffn(x, *ws, counts)
            return jnp.square(out).sum(), out
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)

    assert _primitives(grad(), x, *ws) == (9, 0)
    (_, out), grads = jax.jit(grad())(x, *ws)
    assert not np.asarray(out[600:]).any()
    assert not np.asarray(grads[0][600:]).any()
    assert np.asarray(out[:600]).any() and np.asarray(grads[0][:600]).any()
    interpreted.setattr(dispatch, "grouped_matmul", lambda rows, w, groups:
                        gmm.ragged_matmul(rows, w, groups.sizes))
    assert _primitives(grad(), x, *ws) == (0, 9)
    (_, want), want_grads = jax.jit(grad())(x, *ws)
    for a, b in zip((out,) + grads, (want,) + want_grads):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() <= 2e-5 * max(
            np.abs(np.asarray(b)).max(), 1.0)


@pytest.mark.parametrize("why,rows,k,n,dtype,devices", [
    ("rows_not_whole_tiles", 640, K, N, "float32", 1),
    ("k_not_whole_half_tiles", ROWS, 160, N, "float32", 1),
    ("n_not_whole_half_tiles", ROWS, K, 96, "float32", 1),
    ("a_dtype_without_a_kernel", ROWS, K, N, "float16", 1),
    ("a_program_over_two_devices", ROWS, K, N, "float32", 2),
    ("the_kernels", ROWS, K, N, "float32", 1),
    ("a_turned_weight_over_two_devices", ROWS, TURNED_K, TURNED_N,
     "float32", 2),
    ("the_kernels_turned", ROWS, TURNED_K, TURNED_N, "float32", 1),
])
def test_what_the_kernels_refuse_keeps_ragged_dot(why, rows, k, n, dtype,
                                                  devices):
    """The choice is static and made while the op is traced: a refused
    call is ``ragged_dot_general`` in the jaxpr, forward and backward,
    with no kernel beside it.  The last case is a call the kernels take:
    three kernels, with ``ragged_dot`` and its autodiff as the other
    branch of a choice by platform that the lowering makes."""
    x = jnp.zeros((rows, k), dtype)
    w = jnp.zeros((4, k, n), dtype)
    sizes = jnp.asarray([rows // 4] * 4, jnp.int32)

    def both(x, w):
        with tracing_over(make_mesh([("dp", devices)])):
            groups = dispatch.group_tiles(sizes, rows)
            grad = jax.grad(lambda x, w: dispatch.grouped_matmul(
                x, w, groups).astype(jnp.float32).sum(), argnums=(0, 1))
            return grad(x, w)

    want = (3, 4) if why.startswith("the_kernels") else (0, 3)
    assert _primitives(both, x, w) == want


@pytest.mark.parametrize("k,n", [(K, N), (TURNED_K, TURNED_N)],
                         ids=["as_it_lies", "turned"])
@pytest.mark.parametrize("platform,custom_calls", [("cpu", 0), ("tpu", 3)])
def test_the_platform_chooses_when_the_program_is_lowered(platform,
                                                           custom_calls, k, n):
    """Shapes the kernels take, exported for a CPU: no Mosaic call
    (``ragged_dot``, which that platform spells out in plain operations);
    for a TPU: the three kernels under the names the benchmark's
    roofline reader sums (``ragged-dot*``), whichever way they read the
    weight."""
    x = jnp.zeros((ROWS, k), jnp.bfloat16)
    w = jnp.zeros((4, k, n), jnp.bfloat16)
    sizes = jnp.asarray([256] * 4, jnp.int32)
    text = jax.export.export(jax.jit(jax.grad(
        lambda x, w: jnp.square(dispatch.grouped_matmul(x, w, sizes).astype(
            jnp.float32)).sum(), argnums=(0, 1))),
        platforms=[platform])(x, w).mlir_module()
    assert text.count("tpu_custom_call") == custom_calls
    if platform == "tpu":
        names = re.findall(r'kernel_name = "([^"]+)"', text)
        assert sorted(names) == ["ragged-dot-gmm", "ragged-dot-gmm",
                                 "ragged-dot-tgmm"], names


def _routed_net(layers, tokens, dim, hidden, experts, k):
    net = mx.sym.Variable("data")
    for i in range(layers):
        net = net + MoEFeedForward(
            net, num_hidden=hidden, num_experts=experts, k=k,
            capacity_factor=0.0, name="l%d_moe" % i, act_type="silu",
            gated=True, no_bias=True, layer=i)
    return mx.sym.LinearRegressionOutput(net, name="lro")


def _one_fused_step(net, tokens, dim, seed):
    rng = np.random.RandomState(seed)
    mod = mx.mod.Module(net, context=mx.cpu(0), label_names=["lro_label"])
    mod.bind(data_shapes=[("data", (tokens, dim))],
             label_shapes=[("lro_label", (tokens, dim))])
    mod.init_params(initializer=mx.init.Normal(0.05))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    assert mod._fused is not None
    mod.forward_backward(mx.io.DataBatch(
        data=[mx.nd.array(rng.randn(tokens, dim).astype(np.float32))],
        label=[mx.nd.array(rng.randn(tokens, dim).astype(np.float32))],
        pad=0))
    mod.update()
    assert np.isfinite(mod.get_outputs()[0].asnumpy()).all()


def _counter_events(counter, run):
    """The samples of ``counter`` that ``run()`` leaves on the trace ring."""
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        run()
        return mx.trace.counter_events([counter], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)


_gmm_traces = functools.partial(_counter_events, "moe:gmm_trace")


def test_kernels_are_traced_once_a_signature_whatever_the_layers(
        interpreted):
    """Two fused modules (as the harness's reference check and ``fit``
    are) of three routed layers each: 54 grouped matmuls, six kernel
    signatures (gate and up share theirs), each traced once.  The shapes
    (384 rows of 640) are no other test's of this process."""
    net = _routed_net(3, 128, 640, 384, 4, 4)
    # 128 tokens x 4 choices = 512 rows: two row tiles
    traces = _gmm_traces(lambda: [_one_fused_step(net, 128, 640, seed)
                                  for seed in (1, 2)])
    assert len(traces) == 6, [e["id"] for e in traces]
    assert sorted(k for e in traces for k in ("gmm", "gmm_t", "tgmm")
                  if e["args"][k]) == ["gmm"] * 2 + ["gmm_t"] * 2 \
        + ["tgmm"] * 2
    assert {(e["args"]["tm"], e["args"]["tk"], e["args"]["tn"])
            for e in traces} == {(gmm.ROW_TILE, 640, 384),
                                 (gmm.ROW_TILE, 384, 640)}
    assert len({e["id"] for e in traces}) == 6


def test_turned_kernels_are_traced_once_a_signature_too(interpreted):
    """Three routed layers whose gate and up projections ``(4, 640, 320)``
    are read turned and whose down projection ``(4, 320, 640)`` is not, in
    two fused modules: again six kernel traces.  The turned forward is
    ``gmm_t`` and its backward-data product ``gmm``: shape for shape the
    down projection's three products, so every track's name comes twice."""
    net = _routed_net(3, 128, 640, 320, 4, 4)
    traces = _gmm_traces(lambda: [_one_fused_step(net, 128, 640, seed)
                                  for seed in (3, 4)])
    assert sorted(e["id"] for e in traces) == sorted(
        ["gmm_t float32[512, 640] x [4, 320, 640]",       # up, forward
         "gmm float32[512, 320] x [4, 320, 640]",         # up, backward-data
         "tgmm float32[512, 320] x [512, 640]",           # up, backward-weight
         "gmm float32[512, 320] x [4, 320, 640]",         # down, forward
         "gmm_t float32[512, 640] x [4, 320, 640]",       # down, backward-data
         "tgmm float32[512, 320] x [512, 640]"]), [e["id"] for e in traces]
    assert {(e["args"]["tm"], e["args"]["tk"], e["args"]["tn"])
            for e in traces} == {(gmm.ROW_TILE, 640, 320),
                                 (gmm.ROW_TILE, 320, 640)}


def test_a_graph_without_routed_experts_traces_no_kernel():
    def dense():
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=640,
                                    name="fc")
        _one_fused_step(mx.sym.LinearRegressionOutput(net, name="lro"),
                        128, 640, 5)
    assert _gmm_traces(dense) == []
