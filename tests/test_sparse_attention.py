"""``IndexedSelfAttention`` (``mxnet_tpu/ops/sparse_attention.py``), tier-1:
the op against the layer's equations written out by hand with dense ``(T,
T)`` arrays (outputs, cotangents both ways), the selection's count and
tie rule by both k-th-value methods, the isolation of the two losses as
EXACT zeros, ``topk >= T`` against ``CausalSelfAttention``, the TPU
kernels (attend and target) interpreted at small shapes, and the two small ops the model
brings with it (``LayerNorm``, ``RotaryEmbedding(sections=)``)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import selected_attention as sel
from mxnet_tpu.ops import sparse_attention as sa
from mxnet_tpu.ops import transformer as tr

DH, HI, DI = 16, 4, 8


def dense(q, k, v, qi, ki, w, topk, scale):
    """The equations with every ``(T, T)`` array whole: ``(outputs, a
    sequence's mean row loss, the selection)``."""
    b, t, h, _ = q.shape
    hkv = k.shape[2]
    z = jnp.einsum("btjd,bsd->btjs", qi, ki[:, :, 0])
    scores = (jax.nn.relu(z) * w[..., None]).sum(2) \
        * qi.shape[3] ** -0.5 * qi.shape[2] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                            min(topk, t))
    chosen = jax.vmap(jax.vmap(lambda row, i: row.at[i].set(True)))(
        jnp.zeros((b, t, t), bool), best) & causal
    chosen = jax.lax.stop_gradient(chosen)
    kk, vv = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, kk) * scale
    a = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", a, vv)
    p = jax.lax.stop_gradient(a.mean(1))
    log_index = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
    kl = jnp.where(chosen, jax.scipy.special.xlogy(p, p)
                   - p * jnp.where(chosen, log_index, 0.0), 0.0).sum(-1)
    return out, kl.mean(1), chosen


def inputs(seed, b, t, h, hkv, dtype=np.float32, dh=DH):
    rng = np.random.RandomState(seed)

    def make(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)

    return (make(b, t, h, dh), make(b, t, hkv, dh), make(b, t, hkv, dh),
            make(b, t, HI, DI), make(b, t, 1, DI), make(b, t, HI))


def op(topk, scale=DH ** -0.5):
    return lambda *a: sa.indexed_attention(*a, topk=topk, scale=scale,
                                           layer=0)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several blocks of rows at these lengths: 64 = 2 x 32, 200 = 8 x 25."""
    monkeypatch.setattr(sa, "DSA_BLOCK_Q", 32)


@pytest.mark.parametrize("t", [64, 200])
@pytest.mark.parametrize("topk", [16, 256])
@pytest.mark.parametrize("h,hkv", [(8, 1), (4, 4)], ids=["groups-of-8",
                                                         "groups-of-1"])
def test_the_op_is_the_equations_written_out(t, topk, h, hkv):
    """Two sequences a batch (nothing is selected across them): the
    outputs, the row losses, the counter's numbers, and the cotangents of
    q, k, v from the first output and of qI, kI, w from the second."""
    args = inputs(t + topk + h, 2, t, h, hkv)
    scale = DH ** -0.5
    out, loss, stats = op(topk)(*args)
    want_out, want_loss, chosen = dense(*args, topk, scale)
    assert np.abs(out - want_out).max() < 2e-5
    assert np.allclose(loss, want_loss, rtol=1e-5, atol=1e-6)
    kept = sum(min(i + 1, topk) for i in range(t))
    assert np.array_equal(np.asarray(chosen.sum(-1)),
                          np.tile(np.minimum(np.arange(t) + 1, topk), (2, 1)))
    tiles = -(-t // min(sa.DSA_TILE, t))
    assert np.array_equal(np.asarray(stats), np.tile(np.float32(
        [t, kept, t * (t + 1) // 2, tiles * (tiles + 1) // 2,
         tiles * (tiles + 1) // 2]), (2, 1)))
    rng = np.random.RandomState(1)
    g_out = jnp.asarray(rng.randn(*out.shape).astype(np.float32))
    g_loss = jnp.asarray(rng.randn(2).astype(np.float32))
    for pick, cot, live in ((0, g_out, (0, 1, 2)), (1, g_loss, (3, 4, 5))):
        got = jax.grad(lambda *a: (op(topk)(*a)[pick] * cot).sum(),
                       argnums=tuple(range(6)))(*args)
        want = jax.grad(
            lambda *a: (dense(*a, topk, scale)[pick] * cot).sum(),
            argnums=tuple(range(6)))(*args)
        for i, (a, b) in enumerate(zip(got, want)):
            if i in live:
                assert np.abs(b).max() > 1e-4
                assert np.abs(a - b).max() <= 2e-5 * max(1.0,
                                                         np.abs(b).max())
            else:
                # the isolation is EXACT, both ways
                assert not np.asarray(a).any() and not np.asarray(b).any()


def test_a_selection_of_every_key_is_causal_attention():
    args = inputs(3, 2, 64, 8, 2)
    scale = DH ** -0.5
    out, _, stats = op(64)(*args)
    want = tr.causal_attention(*args[:3], scale)
    assert np.abs(out - want).max() < 2e-6
    assert np.array_equal(np.asarray(stats[:, 1]), np.asarray(stats[:, 2]))
    got = jax.grad(lambda q, k, v: op(1000)(q, k, v, *args[3:])[0].sum(),
                   argnums=(0, 1, 2))(*args[:3])
    ref = jax.grad(lambda q, k, v: tr.causal_attention(q, k, v,
                                                       scale).sum(),
                   argnums=(0, 1, 2))(*args[:3])
    for a, b in zip(got, ref):
        assert np.abs(a - b).max() < 2e-5


def test_every_row_selects_its_count_and_ties_go_to_the_earlier_key():
    """Scores on a grid of nine values, so that most rows have keys level
    with their k-th: exactly ``min(t + 1, topk)`` selected, and among
    level keys the earliest, as ``lax.top_k`` orders them.  ``w = 0``
    (every score 0.0 or -0.0) selects the first ``topk`` keys."""
    t, topk = 96, 20
    rng = np.random.RandomState(7)
    scores = jnp.asarray(rng.randint(-4, 5, (t, t)).astype(np.float32) / 4)
    scores = scores.at[5].set(-0.0).at[6, ::2].set(-0.0)
    pos = jnp.arange(t)
    got = np.asarray(sa.select_keys(scores, pos, topk))
    causal = np.tril(np.ones((t, t), bool))
    _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    want = np.zeros((t, t), bool)
    want[np.arange(t)[:, None], np.asarray(best)] = True
    want &= causal
    assert np.array_equal(got, want)
    assert np.array_equal(got.sum(1), np.minimum(np.arange(t) + 1, topk))
    # a block of rows from the middle of a sequence, no tie at all
    smooth = jnp.asarray(rng.randn(32, t).astype(np.float32))
    part = np.asarray(sa.select_keys(smooth, 40 + jnp.arange(32), topk))
    assert np.array_equal(part.sum(1), np.full(32, topk))
    assert not (part & ~causal[40:72]).any()
    args = inputs(11, 1, 64, 4, 2)
    zero_w = args[:5] + (jnp.zeros_like(args[5]),)
    out, _, _ = op(16)(*zero_w)
    want_out, _, chosen = dense(*zero_w, 16, DH ** -0.5)
    assert np.abs(out - want_out).max() < 2e-5
    first = np.tril(np.ones((64, 64), bool)) & (np.arange(64)[None] < 16)
    assert np.array_equal(np.asarray(chosen[0]), first)


def test_the_ordered_image_keeps_the_order_of_floats():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0,
                     jnp.inf], jnp.float32)
    image = np.asarray(sa._ordered(x)).astype(np.int64)
    assert image[3] == image[4] and image.min() > 0
    assert (np.diff(np.delete(image, 3)) > 0).all()
    keys = jnp.asarray(np.random.RandomState(0).randint(
        1, 2 ** 32 - 1, (5, 40), dtype=np.int64).astype(np.uint32))
    want = jnp.asarray([1, 7, 40, 13, 2])
    got = np.asarray(sa._kth_largest(keys, want))
    ranked = -np.sort(-np.asarray(keys).astype(np.int64), axis=1)
    assert np.array_equal(got, ranked[np.arange(5), np.asarray(want) - 1])


def _rel(a, b):
    a, b = (jnp.asarray(x, jnp.float32).ravel() for x in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _both_kernels_against_the_plain_blocks(seed, t, h, hkv, lone_row=None,
                                           dv=128, early_rows=0):
    """This repo's forward kernel and, fed ITS output and log-sum-exp,
    its backward kernel under a top-48 selection, both interpreted on
    the CPU: outputs, log-sum-exp and the three cotangents against the
    plain blocks on the same bfloat16 inputs of 128-lane heads (values
    of ``dv`` lanes).  ``lone_row`` selects key 3 alone, each of the
    first ``early_rows`` rows key 0 alone."""
    q, k, v, qi, ki, w = (x[0] for x in inputs(seed, 1, t, h, hkv,
                                               jnp.bfloat16, dh=128))
    if dv != 128:
        v = jnp.asarray(np.random.RandomState(seed).randn(t, hkv, dv),
                        jnp.bfloat16)
    assert tr._kernel_takes(q[None], k[None], v[None])
    mask = sa._select(qi, ki[:, 0], w, 48, None)
    assert int(mask.sum()) == sum(min(i + 1, 48) for i in range(t))
    if lone_row is not None:
        mask = mask.at[lone_row].set(False).at[lone_row, 3].set(True)
    mask = mask.at[:early_rows].set(False).at[:early_rows, 0].set(True)
    qs = q * jnp.bfloat16(128 ** -0.5)
    out, lse = sa._attend_kernel(qs, k, v, mask, interpret=True)
    want, want_lse = sa._attend_plain(qs, k, v, mask)
    assert out.dtype == want.dtype and out.shape == want.shape == (t, h, dv)
    assert lse.dtype == jnp.float32 and lse.shape == (h, t)
    f32 = jnp.float32
    assert np.abs(out.astype(f32) - want.astype(f32)).max() < 0.03
    assert np.abs(lse - want_lse).max() < 1e-3
    if lone_row is not None:
        # a row of one key gives that key's value row, whatever its score
        assert np.array_equal(
            np.asarray(out[lone_row].astype(f32)),
            np.asarray(jnp.repeat(v[3], h // hkv, axis=0).astype(f32)))
    g = inputs(6, 1, t, h, hkv, jnp.bfloat16, dh=dv)[0][0]
    got = sa._attend_kernel_bwd(qs, k, v, mask, out, lse, g, interpret=True)
    ref = sa._attend_plain_bwd(qs, k, v, mask, want, want_lse, g)
    for a, b in zip(got, ref):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert _rel(a, b) < 0.02


def _small_tiles(monkeypatch, rows, block_kv=256, piece=128):
    """Both attend kernels in tiles a few hundred rows fill."""
    for name, n in (("ROWS", rows), ("BLOCK_KV", block_kv), ("PIECE", piece)):
        monkeypatch.setattr(sel, name, n)
        monkeypatch.setattr(sel, "BACKWARD_" + name, n)
    monkeypatch.setattr(tr, "ATTN_KERNEL_BLOCK", block_kv)
    monkeypatch.setattr(tr, "ATTN_KERNEL_SLICE", piece)


@pytest.mark.parametrize("h,hkv,rows,tiles", [
    (2, 2, 1024, (1, 256, 256, 128)), (4, 2, 1024, (2, 256, 256, 128)),
    (8, 1, 1024, (8, 128, 256, 128)), (8, 1, 512, (4, 128, 256, 128))],
    ids=["groups-of-1", "groups-of-2", "groups-of-8",
         "groups-of-8-four-a-step"])
def test_the_forward_kernel_interpreted_is_the_plain_blocks(
        monkeypatch, h, hkv, rows, tiles):
    """``selected_attention_fwd`` at 768 rows: three key tiles of 256 read
    128 at a time (the diagonal tile runs only the pieces its rows
    reach), a step's heads under ONE int8 tile of the selection, one row
    that selects a single key of the first tile."""
    _small_tiles(monkeypatch, rows)
    assert sel.forward_tiles(768, h // hkv, 128) == tiles
    _both_kernels_against_the_plain_blocks(h, 768, h, hkv, lone_row=700)


@pytest.mark.parametrize("t,h,hkv,dv,rows,tiles", [
    (768, 2, 2, 128, 256, (1, 256, 256, 128)),
    (768, 4, 2, 128, 256, (2, 128, 256, 128)),
    (768, 8, 1, 128, 1024, (8, 128, 256, 128)),
    (768, 8, 1, 128, 512, (4, 128, 256, 128)),
    (512, 4, 2, 256, 1024, (2, 256, 256, 128)),
    (128, 4, 2, 128, 1024, (2, 128, 128, 128))],
    ids=["groups-of-1", "groups-of-2", "groups-of-8",
         "groups-of-8-two-steps-a-key-head", "values-of-256-lanes",
         "a-single-tile"])
def test_the_backward_kernel_interpreted_is_the_plain_blocks(
        monkeypatch, t, h, hkv, dv, rows, tiles):
    """``selected_attention_bwd`` against ``_attend_plain_bwd``: ``dq``
    summed over a query tile's causal key tiles in VMEM, ``dk`` and
    ``dv`` of a key/value head over all its query tiles AND its group's
    heads (in two steps where the group is wider than a step), a step's
    heads under ONE int8 tile of the selection, a diagonal tile that runs
    only the pieces its rows reach; ``Dv != Dh``; one tile in all; the
    first 40 rows each keep key 0 alone."""
    _small_tiles(monkeypatch, rows)
    assert sel.backward_tiles(t, h // hkv, max(128, dv)) == tiles
    _both_kernels_against_the_plain_blocks(t + h, t, h, hkv, dv=dv,
                                           early_rows=40)


def test_the_backward_kernels_tiles_follow_the_shapes():
    """At the Keye cell's shape a step holds the 8 heads of a key/value
    head under one mask tile; the rows a head follow the group and the
    head widths, every tile is a whole divisor in whole 128s, and a
    sequence whose float32 ``dk`` and ``dv`` of one key/value head pass
    BACKWARD_KEPT, or one not in whole 128s, is not taken."""
    rows, bkv, piece = (sel.BACKWARD_ROWS, sel.BACKWARD_BLOCK_KV,
                        sel.BACKWARD_PIECE)
    assert sel.backward_tiles(8192, 8, 128) == (8, rows // 8, bkv, piece)
    assert sel.backward_tiles(8192, 1, 128) == (1, min(rows, bkv), bkv, piece)
    assert sel.backward_tiles(8192, 128, 128)[0] == min(128, rows // 128)
    assert sel.backward_tiles(8192, 8, 256)[:2] == (8, rows // 16)
    assert sel.backward_tiles(384, 4, 128) == (4, 384 if rows >= 1536
                                               else 128, 384, 384 if piece
                                               >= 384 else 128)
    assert sel.backward_tiles(2048, 3, 128)[0] == 3
    assert sel.backward_tiles(32768, 8, 128) is not None
    assert sel.backward_tiles(65536, 8, 128) is None
    assert sel.backward_tiles(32768, 8, 256) is None
    assert sel.backward_tiles(8192 + 64, 8, 128) is None
    for t, group, lanes in ((8192, 8, 128), (1536, 6, 128), (640, 2, 256)):
        heads, bq, bkv, piece = sel.backward_tiles(t, group, lanes)
        assert group % heads == 0 and t % bq == 0 and t % bkv == 0
        assert bkv % piece == 0 and bq % 128 == 0 and piece % 128 == 0
        assert bq <= bkv and heads * bq <= rows * 128 // lanes


def test_the_forward_kernels_tiles_follow_the_shapes():
    """At the Keye cell's shape a step holds the 8 heads of a key/value
    head, 512 rows each, against 512 keys read 256 at a time; a group
    wider than a step's rows is split, wider heads take fewer rows, and
    every tile is a whole divisor in whole 128s."""
    assert sel.forward_tiles(8192, 8, 128) == (8, 512, 512, 256)
    assert sel.forward_tiles(8192, 1, 128) == (1, 512, 512, 256)
    assert sel.forward_tiles(8192, 128, 128) == (32, 128, 512, 256)
    assert sel.forward_tiles(8192, 8, 256) == (8, 256, 512, 256)
    assert sel.forward_tiles(384, 4, 128) == (4, 384, 384, 128)
    assert sel.forward_tiles(2048, 3, 128) == (3, 512, 512, 256)


def test_the_kernel_lowering_interpreted_is_the_plain_blocks(monkeypatch):
    """Both kernels at (256, 4 heads over 2, 128) in tiles of 128, two
    heads a mask tile."""
    _small_tiles(monkeypatch, 256, 128, 128)
    assert sel.forward_tiles(256, 2, 128) == (2, 128, 128, 128)
    assert sel.backward_tiles(256, 2, 128) == (2, 128, 128, 128)
    _both_kernels_against_the_plain_blocks(5, 256, 4, 2)


def _target_inputs(t, h, hkv, topk, tied):
    """bfloat16 operands of 128-lane heads under a 4 x 64 indexer, the
    selection and the plain attend pass's log-sum-exp.  ``tied``: every
    indexer key stands twice, so every score has its equal."""
    q, k, v, qi, ki, w = (x[0] for x in inputs(t + h, 1, t, h, hkv,
                                               jnp.bfloat16, dh=128))
    rng = np.random.RandomState(t + hkv)
    qi = jnp.asarray(rng.randn(t, HI, 64), jnp.bfloat16)
    ki = jnp.asarray(rng.randn(t, 64), jnp.bfloat16)
    if tied:
        ki = jnp.concatenate([ki[:t // 2], ki[:t // 2]])
    mask = sa._select(qi, ki, w, topk, None)
    assert int(mask.sum()) == sum(min(i + 1, topk) for i in range(t))
    qs = q * jnp.bfloat16(128 ** -0.5)
    return (qi, ki, w, qs, k, sa._attend_plain(qs, k, v, mask)[1], mask)


@pytest.mark.parametrize("with_grads", [False, True],
                         ids=["loss-only", "with-gradient"])
@pytest.mark.parametrize("h,hkv,topk,tied,tiles", [
    (8, 1, 48, False, (128, 128)), (16, 4, 48, False, (128, 128)),
    (4, 2, 1000, False, (128, 128)), (4, 2, 48, True, (128, 128)),
    (4, 2, 48, False, (128, 256))],
    ids=["groups-of-8-over-1", "groups-of-4-over-4", "topk-above-T",
         "tied-kth-scores", "half-a-key-tile-behind-the-diagonal"])
def test_the_target_kernel_interpreted_is_the_plain_blocks(
        monkeypatch, with_grads, h, hkv, topk, tied, tiles):
    """``selected_target`` at 384 rows (three query tiles of 128: with
    key tiles of 256 the first and the third end half-way into one)
    against ``_target`` on the same selection and log-sum-exp: the mean
    row loss and the three unit gradients, cast as the op casts them."""
    monkeypatch.setattr(sel, "TARGET_BQ", tiles[0])
    monkeypatch.setattr(sel, "TARGET_BK", tiles[1])
    monkeypatch.setattr(sel, "TARGET_HEADS", 2)
    t = 384 if tiles[1] == 128 else 512 + 256
    assert sel.target_tiles(t, HI) == tiles + (2,)
    args = _target_inputs(t, h, hkv, topk, tied)
    loss, unit = sa._target_kernel(*args, None, with_grads, interpret=True)
    want_loss, want = sa._target(*args, None, with_grads)
    assert loss.dtype == want_loss.dtype == jnp.float32
    assert float(want_loss) > 1e-3
    assert abs(float(loss) - float(want_loss)) <= 1e-3 * float(want_loss)
    if not with_grads:
        assert unit is None and want is None
        return
    for a, b, x in zip(unit, want, args[:3]):
        assert a.dtype == b.dtype == x.dtype and a.shape == b.shape == x.shape
        assert _rel(a, b) < 0.02


def test_the_target_kernels_tiles_follow_the_shapes():
    """256 rows against 512 keys and the sixteen indexer heads under one
    matmul at the Keye cell's shape; fewer rows where a row's kept pairs
    are longer, none beyond what 128 rows may keep; the stacked heads
    divide Hi."""
    assert sel.target_tiles(8192, 16) == (256, 512, 16)
    assert sel.target_tiles(16384, 64) == (128, 512, 16)
    assert sel.target_tiles(32768, 16) is None
    assert sel.target_tiles(8192 + 64, 16) is None
    assert sel.target_tiles(384, 6) == (128, 384, 6)
    assert sel.target_tiles(2048, 24) == (256, 512, 12)


def test_the_op_with_both_flags_off_the_chip_is_the_plain_blocks():
    """A program lowered for the CPU runs the plain blocks under either
    flag: outputs and cotangents bit for bit."""
    args = inputs(9, 1, 128, 4, 2, jnp.bfloat16, dh=128)
    args = args[:3] + tuple(jnp.asarray(
        np.random.RandomState(i).randn(*shape), jnp.bfloat16)
        for i, shape in enumerate([(1, 128, HI, 64), (1, 128, 1, 64)])) \
        + args[5:]

    def both(kernel):
        def run(*a):
            outs, vjp = jax.vjp(lambda *x: sa._indexed_attention(
                *x, 16, 128 ** -0.5, 0, kernel, kernel)[:2], *a)
            return outs + vjp((jnp.ones_like(outs[0]),
                               jnp.ones((1,), jnp.float32)))
        return jax.jit(run)(*args)

    for a, b in zip(both(True), both(False)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    out = jax.jit(lambda *a: sa._indexed_attention(
        *a, 16, 128 ** -0.5, 0, True, True))(*args)
    assert np.isfinite(float(out[1][0]))


def test_the_program_lowered_for_a_tpu_holds_the_repos_three_kernels(
        monkeypatch):
    """The op and its backward at the Keye cell's shapes (32 heads over 4,
    8192 rows, a 16 x 64 indexer's top-2048), lowered for a TPU from
    here: this repo's three kernels by their device names, none of the
    library's, no int32 ``MaskInfo`` tiling of the selection and no
    partial ``dq`` planes ``(key tiles, H, T, Dh)``."""
    import re
    monkeypatch.setattr(sa, "DSA_BLOCK_Q", 256)
    t, h, hkv, dh, hi, di, topk = 8192, 32, 4, 128, 16, 64, 2048
    shapes = [(1, t, h, dh), (1, t, hkv, dh), (1, t, hkv, dh),
              (1, t, hi, di), (1, t, 1, di), (1, t, hi)]

    def run(*args):
        outs, vjp = jax.vjp(lambda *a: sa._indexed_attention(
            *a, topk, dh ** -0.5, 0, True, True)[:2], *args)
        return outs + vjp((jnp.ones_like(outs[0]),
                           jnp.ones((1,), jnp.float32)))

    # lint: allow(raw-jit) — one-off lowering inspection
    text = jax.jit(run).trace(*(jax.ShapeDtypeStruct(s, jnp.bfloat16)
                                for s in shapes)).lower(
        lowering_platforms=("tpu",)).as_text()
    for name in ("splash_mha_fwd_selected", "splash_mha_dkv_selected",
                 "dsa_target_grads"):
        assert text.count("tpu_custom_call") >= 3 and name in text
    assert "splash_mha_dkv_no_residuals" not in text
    assert "splash_mha_fwd_residuals" not in text
    assert not re.search(r"x1024x1024xi32", text)
    assert not re.search(r"tensor<\d+x%dx%dx%dx" % (h, t, dh), text)
    # dq leaves the kernel as the op's rows, once
    assert re.search(r"tensor<%dx%dxbf16>" % (t, h * dh), text)


def test_the_op_node_its_shapes_and_its_counter():
    q, k, v, qi, ki, w = (mx.sym.Variable(n) for n in
                          ("q", "k", "v", "qi", "ki", "w"))
    node = mx.sym.IndexedSelfAttention(q, k, v, qi, ki, w, topk=8, layer=3,
                                       name="attn")
    assert node.list_outputs() == ["attn_output", "attn_index_loss",
                                   "attn_selection"]
    args, outs, _ = node.infer_shape(q=(2, 32, 4, 8), k=(2, 32, 2, 8),
                                     v=(2, 32, 2, 8), qi=(2, 32, 3, 4))
    assert args[4:] == [(2, 32, 1, 4), (2, 32, 3)]
    assert outs == [(2, 32, 4, 8), (2,), (2, 5)]
    with pytest.raises(mx.base.MXNetError):
        mx.sym.IndexedSelfAttention(q, k, v, qi, ki, w, topk=0).infer_shape(
            q=(2, 32, 4, 8))
    with pytest.raises(mx.base.MXNetError):
        node.infer_shape(q=(2, 32, 4, 8), k=(2, 32, 3, 8), v=(2, 32, 3, 8),
                         qi=(2, 32, 3, 4))
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        op(8)(*inputs(2, 1, 32, 4, 2))
        events = [e for e in mx.trace.counter_events(["dsa:lowering"])
                  if e["id"] == "float32[1, 32, 4, 16]/kv2/top8"]
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert events and events[-1]["args"] == {
        "kernel": 0, "plain": 1, "heads_a_mask_tile": 0, "target_kernel": 0,
        "backward_kernel": 0}
    # what the kernels take: the group a mask tile serves beside them;
    # the target kernel an indexer of 64-lane heads in the same dtype; the
    # backward kernel whatever the forward kernel takes, up to the length
    # whose float32 ``dk`` and ``dv`` of a key/value head fit its VMEM
    took = inputs(2, 1, 256, 8, 2, jnp.bfloat16, dh=128)
    wide = tuple(jax.ShapeDtypeStruct(x.shape[:3] + (64,), x.dtype)
                 for x in took[3:5])
    mx.trace.set_enabled(True)
    try:
        jax.eval_shape(op(8, 128 ** -0.5), *took)
        jax.eval_shape(op(8, 128 ** -0.5), *took[:3], *wide, took[5])
        jax.eval_shape(op(8, 128 ** -0.5), *took[:3], *wide,
                       took[5].astype(jnp.float32))
        long = tuple(jax.ShapeDtypeStruct((1, 65536) + x.shape[2:], x.dtype)
                     for x in took)
        jax.eval_shape(op(8, 128 ** -0.5), *long)
        events = mx.trace.counter_events(["dsa:lowering"])
    finally:
        mx.trace.reset()
        mx.trace.set_enabled(was)
    assert [(e["id"], e["args"]) for e in events] == [
        ("bfloat16[1, %d, 8, 128]/kv2/top8" % t,
         {"kernel": 1, "plain": 0, "heads_a_mask_tile": 4,
          "target_kernel": n, "backward_kernel": b})
        for t, n, b in ((256, 0, 1), (256, 1, 1), (256, 0, 1),
                        (65536, 0, 0))]


def test_layer_norm_is_its_equation():
    rng = np.random.RandomState(2)
    x = rng.randn(6, 10).astype(np.float32)
    gamma, beta = (rng.randn(10).astype(np.float32) for _ in range(2))
    net = mx.sym.LayerNorm(mx.sym.Variable("x"), eps=1e-6, name="ln")
    assert net.list_arguments() == ["x", "ln_gamma", "ln_beta"]
    exe = net.simple_bind(mx.cpu(), x=(6, 10))
    for n, a in (("x", x), ("ln_gamma", gamma), ("ln_beta", beta)):
        exe.arg_dict[n][:] = a
    exe.forward(is_train=False)
    c = x - x.mean(-1, keepdims=True)
    want = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-6) * gamma + beta
    assert np.abs(exe.outputs[0].asnumpy() - want).max() < 1e-5


def test_rotary_sections_with_equal_axes_are_todays_op():
    """No positions: the op as it was, whatever the sections; equal axes
    as an input: the same numbers; sections that are not the head's
    pairs, or positions without sections, are refused."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 24, 3, 16).astype(np.float32))
    plain = tr.rotary_embedding(x, 1e4)
    assert np.array_equal(tr.sectioned_rotary(x, theta=1e4,
                                              sections=(2, 3, 3)), plain)
    rows = jnp.broadcast_to(jnp.arange(24.0), (2, 3, 24))
    assert np.abs(tr.sectioned_rotary(x, rows, theta=1e4,
                                      sections=(2, 3, 3)) - plain).max() < 1e-6
    data, pos = mx.sym.Variable("data"), mx.sym.Variable("pos")
    old = mx.sym.RotaryEmbedding(data, theta=1e4, name="r")
    assert "sections" not in old.tojson() and "positions" not in old.tojson()
    assert old.list_arguments() == ["data"]
    new = mx.sym.RotaryEmbedding(data, positions=pos, with_positions=True,
                                 theta=1e4, sections=(2, 3, 3), name="r")
    assert new.list_arguments() == ["data", "pos"]
    assert new.infer_shape(data=(2, 24, 3, 16))[0] == [(2, 24, 3, 16),
                                                       (2, 3, 24)]
    for bad in (dict(sections=(2, 3, 4)), dict(sections=(8,), period=4)):
        with pytest.raises(mx.base.MXNetError):
            mx.sym.RotaryEmbedding(data, theta=1e4, **bad).infer_shape(
                data=(2, 24, 3, 16))
    with pytest.raises(mx.base.MXNetError):
        mx.sym.RotaryEmbedding(data, positions=pos, with_positions=True,
                               theta=1e4).infer_shape(data=(2, 24, 3, 16))
