"""A rank's share sums its tokens' rows on the sorted side
(``moe.dispatch.held_sum``, ``moe.gmm.token_sums``): the kernel form in the
Pallas interpreter against the token-side gathers it replaces on a TPU --
the sum itself (the combine's forward, ``sort_rows``' backward) and, through
``_moe_share_ffn``, the forward and every gradient -- for held rows under,
exactly at and over the bound, in bfloat16 and float32, with a token tile
that holds no row and a row tile that is half sentinel; which form a
program gets, by platform and dtype (the exported text), and the counter
``moe:gmm_trace``'s ``tsum`` sample.  Times are the chip's (tests/tpu)."""
import contextlib
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.moe import gmm
from mxnet_tpu.moe.dispatch import held_rows_bound
from mxnet_tpu.ops import moe as moe_ops

# ``mxnet_tpu.moe.dispatch`` the attribute is the function of that name
layout = sys.modules["mxnet_tpu.moe.dispatch"]

T, K, E, HELD, FIRST, D, H = 512, 4, 32, 4, 5, 128, 256
ROWS = T * K                                     # 2048 routed choices
BOUND = 1024                                     # 4 x (2048 x 4 / 32)


def _plan(held_rows, seed, among=ROWS):
    """A drop-free plan of ``T*k`` choices whose first ``held_rows``
    sorted rows are the rank's, drawn from the first ``among`` choices
    (so from the first ``among / k`` tokens) and dealt to ``HELD``
    experts, each expert's in token order: ``order``, ``slot``, float32
    weights that are 0 on every other choice, and the experts' sizes."""
    rng = np.random.RandomState(seed)
    mine = rng.permutation(among)[:held_rows]
    expert = rng.randint(0, HELD, held_rows)
    # the third may be empty
    expert[:2] = (0, HELD - 1)[:held_rows]
    expert[expert == 2] = 1
    mine = mine[np.lexsort((mine, expert))]
    order = np.concatenate([mine, np.setdiff1d(np.arange(ROWS), mine)]
                           ).astype(np.int32)
    slot = np.empty_like(order)
    slot[order] = np.arange(ROWS, dtype=np.int32)
    weight = rng.rand(ROWS).astype(np.float32) + 0.1
    weight[order[held_rows:]] = 0.0
    sizes = np.bincount(expert, minlength=HELD).astype(np.int32)
    return order, slot.reshape(T, K), weight.reshape(T, K), sizes


def _oracle(rows, order, held, weight, lo, dtype):
    """Each held row of the window times its weight rounded to ``dtype``,
    the product exact, added to its token, all in float64."""
    out = np.zeros((T, rows.shape[1]), np.float64)
    for r in range(max(min(held - lo, rows.shape[0]), 0)):
        t, j = divmod(int(order[lo + r]), K)
        w = 1.0 if weight is None else float(
            jnp.asarray(weight[t, j]).astype(dtype).astype(jnp.float32))
        out[t] += w * np.asarray(rows[r].astype(jnp.float32), np.float64)
    return out


@pytest.fixture
def any_dtype(monkeypatch):
    """The kernel form for float32 rows too: the interpreter's float32
    product is exact, the chip's is not known to be (``token_sum_tiles``
    keeps float32 on the token side there)."""
    rule = gmm.token_sum_tiles
    monkeypatch.setattr(gmm, "token_sum_tiles", lambda m, n, tokens, dtype:
                        rule(m, n, tokens, jnp.bfloat16))


# held rows, window (lo, n): under the bound with the second token tile
# empty; the last visited row tile half sentinel; exactly full; the rows
# behind the bound of an overflowing step; all rows of a whole plan; and
# the window of a share with no bound, every row of the plan (``lo = 0, n =
# T*k``), of which none, a few per cent, a quarter (the balanced share of
# such a rank) and all are held
WINDOWS = [("under_one_token_tile_empty", 400, 0, BOUND),
           ("half_a_row_tile_sentinel", 640, 0, BOUND),
           ("exactly_full", BOUND, 0, BOUND),
           ("behind_the_bound", 1600, BOUND, ROWS - BOUND),
           ("nothing_held_in_the_window", 700, BOUND, ROWS - BOUND),
           ("every_row_none_held", 0, 0, ROWS),
           ("every_row_a_few_per_cent_held", 132, 0, ROWS),
           ("every_row_a_quarter_held", ROWS // 4, 0, ROWS),
           ("every_row_all_held", ROWS, 0, ROWS)]


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["combine_forward", "sort_rows_backward"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case,held,lo,n", WINDOWS,
                         ids=[w[0] for w in WINDOWS])
def test_the_sorted_side_sum_is_the_token_side_sum(case, held, lo, n, dtype,
                                                   weighted, any_dtype):
    order, slot, weight, sizes = _plan(
        held, held + lo, 256 * K if case.startswith("under") else ROWS)
    assert sizes.sum() == held and sizes[2] == 0
    rng = np.random.RandomState(7)
    rows = rng.randn(n, D).astype(np.float32)
    rows[max(held - lo, 0):] = 0.0            # behind the held rows: zeros
    rows = jnp.asarray(rows, dtype)
    w = jnp.asarray(weight) if weighted else None
    tokens = order[lo:max(held, lo)] // K
    if case.startswith("under"):
        assert tokens.max() < 256              # no row for tokens 256..511
    if case.startswith("half"):
        assert held % gmm.ROW_TILE == gmm.ROW_TILE // 2

    def run(**form):
        return np.asarray(jax.jit(lambda rows: layout.held_sum(
            rows, jnp.asarray(order[lo:lo + n]), jnp.asarray(slot),
            jnp.asarray(sizes), w, lo, **form))(rows)
            .astype(jnp.float32))

    want = _oracle(rows, order, held, weight if weighted else None, lo,
                   dtype)
    token_side, sorted_side = run(), run(interpret=True)
    assert token_side.shape == sorted_side.shape == (T, D)
    scale = max(float(np.abs(want).max()), 1.0)
    if dtype == jnp.float32:
        assert np.abs(sorted_side - want).max() <= 1e-6 * scale
        assert np.abs(token_side - want).max() <= 1e-6 * scale
    else:
        # exact products summed in float32, rounded once: the oracle's
        # sum to the nearest bfloat16, bit for bit; the token side (here,
        # on the CPU) rounds each product and adds k rows in bfloat16
        nearest = np.asarray(jnp.asarray(want, jnp.float32)
                             .astype(jnp.bfloat16).astype(jnp.float32))
        assert np.array_equal(sorted_side, nearest)
        assert np.abs(token_side - want).max() <= 2.0 ** -6 * scale
    if held <= lo:
        assert not sorted_side.any() and not token_side.any()


class _Train:
    is_train = True


def _ops(dtype, held=HELD):
    get = mx.ops.get_op
    share = dict(experts_held=held, first_expert=FIRST)
    ffn = dict(num_hidden=H, output_dim=D, act_type="silu", no_bias=True,
               gated=True, layer=1, **share)
    dispatch = get("_moe_dispatch")
    return {"dispatch": (dispatch, dispatch.parse_params(dict(
                num_experts=E, k=K, capacity_factor=0.0, renormalize=True,
                score="softmax", layer=1, **share))),
            "share": (get("_moe_share_ffn"),
                      get("_moe_share_ffn").parse_params(ffn))}


def _run(ops, which, *inputs):
    op, p = ops[which]
    out = op.forward(p, list(inputs), [], _Train)
    return out[0] if isinstance(out, tuple) else out


def _inputs(held_rows, dtype, held=HELD):
    """Logits under which exactly ``held_rows`` of the ``T*k`` choices
    fall on the rank's ``held`` experts: the first ``held_rows / k``
    tokens choose among them alone, the others none of them."""
    rng = np.random.RandomState(held_rows)
    logits = rng.randn(T, E).astype(np.float32)
    logits[:, FIRST:FIRST + held] *= 0.1
    logits[:, FIRST:FIRST + held] -= 12.0
    logits[:held_rows // K, FIRST:FIRST + held] += 24.0
    x = rng.randn(T, D).astype(np.float32)
    ws = [(rng.randn(held, *s) / 12).astype(np.float32)
          for s in ((D, H), (D, H), (H, D))]
    ct = rng.randn(T, D).astype(np.float32)
    return [jnp.asarray(x, dtype), jnp.asarray(logits)] \
        + [jnp.asarray(w, dtype) for w in ws], jnp.asarray(ct, dtype)


def _forget_the_share():
    """The bounded node and its window's parts are jits of the module,
    traced once a process: a test that changes the form they hold has
    them traced again, before and after."""
    for part in moe_ops._WINDOW_PARTS + (moe_ops._share_bounded,):
        part.clear_cache()


@contextlib.contextmanager
def _sorted_side(monkeypatch):
    """``held_sum`` with its kernel in the Pallas interpreter."""
    with monkeypatch.context() as m:
        m.setattr(layout, "held_sum",
                  functools.partial(layout.held_sum, interpret=True))
        _forget_the_share()
        try:
            yield
        finally:
            _forget_the_share()


def _tsum_traces(since):
    return [e for e in mx.trace.counter_events(["moe:gmm_trace"],
                                               since_ns=since)
            if e["args"]["tsum"]]


# four held experts of 32: a bound of 1024 rows, the held rows under, at
# and over it; eight: four balanced shares are all 2048 rows, no bound, and
# the node's one window is every row
CASES = [("under", 400, HELD), ("exactly_full", BOUND, HELD),
         ("overflow", 1600, HELD),
         ("no_bound_a_few_per_cent", 128, 2 * HELD),
         ("no_bound_a_quarter", ROWS // 4, 2 * HELD)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case,held_rows,held", CASES,
                         ids=[c[0] for c in CASES])
def test_the_share_on_the_sorted_side_is_the_share_through_slot(
        case, held_rows, held, dtype, monkeypatch, any_dtype):
    """``_moe_share_ffn`` with ``held_sum``'s kernel form against the same
    node with the gathers: the forward, the gradients of the data, of the
    router's logits (through ``weight``) and of the three stacked weights;
    an absent choice's weight gets a gradient of exactly 0 from both."""
    monkeypatch.setattr(layout, "BOUND_WORTH_ROWS", 0)
    bound = held_rows_bound(ROWS, E, held)
    assert bound == (BOUND if held == HELD else ROWS)
    ops = _ops(dtype, held)
    args, ct = _inputs(held_rows, dtype, held)

    def plan(x, logits):
        return _run(ops, "dispatch", x, logits)

    def share(x, weight, ws, d):
        return _run(ops, "share", x, weight, d[2], d[7], d[4], *ws)[0]

    def loss(x, logits, *ws):
        d = plan(x, logits)
        out = share(x, d[1], ws, d)
        return (out.astype(jnp.float32) * ct.astype(jnp.float32)).sum(), \
            (out, d[4], d[2])

    def weights_grad(d):
        return np.asarray(jax.jit(jax.grad(lambda w: (
            share(args[0], w, args[2:], d).astype(jnp.float32)
            * ct.astype(jnp.float32)).sum()))(d[1]))

    def both():
        # new functions each time: jax.jit caches by the function
        got = jax.jit(jax.value_and_grad(
            lambda *a: loss(*a), argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return got, weights_grad(plan(*args[:2]))

    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        _forget_the_share()
        since = time.perf_counter_ns()
        ((_, (want, counts, slot)), want_grads), want_dw = both()
        # both forms are traced wherever the shapes allow the kernel; a
        # CPU program holds the gathers (test_which_form_a_program_holds)
        assert _tsum_traces(since)
        with _sorted_side(monkeypatch):
            since = time.perf_counter_ns()
            ((_, (got, _, _)), got_grads), got_dw = both()
            tracks = {e["id"] for e in _tsum_traces(since)}
    finally:
        mx.trace.set_enabled(was)
    # the first window's sum, and the window's behind the bound; with no
    # bound the one window's
    name = jnp.dtype(dtype).name
    assert tracks == {
        "tsum %s[%d, 256] x [%d, %d]" % (name, n, n, D)
        for n in (bound, ROWS - bound) if n}, tracks
    assert int(np.asarray(counts)[FIRST:FIRST + held].sum()) == held_rows

    f32 = lambda a: np.asarray(a.astype(jnp.float32))       # noqa: E731
    # float32: the same sums in another order; bfloat16: k rows added in
    # float32 and rounded once, against added in bfloat16
    tol = 2e-6 if dtype == jnp.float32 else 2.0 ** -6
    want, got = f32(want), f32(got)
    assert want.any()
    assert np.abs(got - want).max() <= tol * np.abs(want).max()
    for which, a, b in zip(("data", "logits", "gate", "up", "down"),
                           got_grads, want_grads):
        a, b = f32(a), f32(b)
        assert b.any(), which
        assert np.abs(a - b).max() <= 2 * tol * np.abs(b).max(), which
    absent = np.asarray(slot) >= held_rows
    assert absent.sum() == ROWS - held_rows
    for d_weight in (want_dw, got_dw):
        assert not d_weight[absent].any()
        assert d_weight[~absent].all()


def _exported(platform, dtype):
    """The text of one bounded window's combine and row gradient, lowered
    for ``platform`` with no device of it."""
    order, slot, weight, sizes = _plan(400, 3)

    def f(rows, x):
        def through(rows, x):
            sorted_x = layout.sort_rows(x, jnp.asarray(order),
                                        jnp.asarray(slot),
                                        jnp.asarray(sizes), (0, BOUND))
            return layout.combine_sorted(
                rows + sorted_x, jnp.asarray(order), jnp.asarray(slot),
                jnp.asarray(weight), share_from=0, held=jnp.asarray(sizes)
            ).astype(jnp.float32).sum()
        return jax.value_and_grad(through, argnums=1)(rows, x)

    shapes = (jax.ShapeDtypeStruct((BOUND, D), dtype),
              jax.ShapeDtypeStruct((T, D), dtype))
    return jax.export.export(jax.jit(f), platforms=[platform])(
        *shapes).mlir_module()


@pytest.mark.parametrize("platform,dtype,kernels", [
    ("cpu", jnp.bfloat16, 0), ("cpu", jnp.float32, 0),
    ("tpu", jnp.bfloat16, 2), ("tpu", jnp.float32, 0)],
    ids=["cpu-bfloat16", "cpu-float32", "tpu-bfloat16", "tpu-float32"])
def test_which_form_a_program_holds(platform, dtype, kernels):
    """A CPU program holds no kernel, whatever the dtype: its statements
    are the gathers through ``slot``.  A TPU program holds the sorted-side
    sum twice (the combine's forward, the rows' gradient) in bfloat16, and
    the gathers in float32: the rule is the operand's dtype."""
    text = _exported(platform, dtype)
    assert text.count("tpu_custom_call") == kernels
    assert ("token-sum" in text) == bool(kernels)
    assert "ragged-dot" not in text


def test_a_shape_the_tiles_refuse_keeps_the_gathers():
    """Tokens that are no whole number of 256-token tiles, a window that
    is no whole number of row tiles, a width that is no whole number of
    lanes: ``held_sum`` is the gathers, interpreter or not."""
    rng = np.random.RandomState(0)
    for t, n, d in ((384, 512, 128), (512, 384, 128), (512, 512, 96)):
        order = rng.permutation(t * K).astype(np.int32)
        slot = np.empty_like(order)
        slot[order] = np.arange(t * K, dtype=np.int32)
        rows = jnp.asarray(rng.randn(n, d), jnp.bfloat16)
        jaxpr = str(jax.make_jaxpr(lambda rows: layout.held_sum(
            rows, jnp.asarray(order[:n]), jnp.asarray(slot.reshape(t, K)),
            jnp.asarray([n // 2, n // 2]), interpret=True))(rows))
        assert "pallas_call" not in jaxpr, (t, n, d)
