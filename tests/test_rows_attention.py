"""``causal_attention``'s own TPU kernel pair (``ops/selected_attention.py``
``computed_attention_fwd`` / ``computed_attention_bwd`` behind
``ops/transformer.py`` ``_rows_attention``), tier-1: the pair interpreted
on the CPU against the plain blocks under every mask kind, the visit plan
against the mask pair by pair, the rule that chooses the pair, the
counter's field, the program exported for a TPU at the SDAR cell's shape,
and the Keye kernels' jaxprs where the parent had them."""
import hashlib
import re
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import selected_attention as sel
from mxnet_tpu.ops import transformer as tr

KINDS = {"causal": ("causal", 0), "window": ("sliding_window", 200),
         "block": ("block_diffusion", 4),
         # 768 rows: a copy of 384 is no power of two, the mask goes as the
         # function and not as row codes
         "block-function": ("block_diffusion", 128)}


def _small_tiles(monkeypatch, rows, block_kv=256, piece=128):
    """Both kernels in tiles a few hundred rows fill."""
    for name, n in (("ROWS", rows), ("BLOCK_KV", block_kv), ("PIECE", piece)):
        monkeypatch.setattr(sel, name, n)
        monkeypatch.setattr(sel, "BACKWARD_" + name, n)
    tr._rows_forward.clear_cache()
    tr._rows_backward.clear_cache()


def _rel(a, b):
    a, b = (jnp.asarray(x, jnp.float32).ravel() for x in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("kind,b,t,h,hkv,dh,dv,rows,tiles", [
    ("causal", 1, 768, 2, 2, 128, 128, 1024, (1, 256, 256, 128)),
    ("causal", 1, 768, 7, 1, 128, 128, 1024, (7, 128, 256, 128)),
    ("causal", 1, 768, 8, 1, 128, 128, 1024, (8, 128, 256, 128)),
    ("window", 1, 768, 2, 2, 128, 128, 1024, (1, 256, 256, 128)),
    ("window", 1, 768, 7, 1, 128, 128, 1024, (7, 128, 256, 128)),
    ("window", 2, 768, 8, 1, 128, 128, 1024, (8, 128, 256, 128)),
    ("block", 1, 1024, 2, 2, 128, 128, 1024, (1, 256, 256, 128)),
    ("block", 1, 1024, 7, 1, 128, 128, 1024, (7, 128, 256, 128)),
    ("block", 1, 1024, 8, 1, 128, 128, 1024, (8, 128, 256, 128)),
    ("block-function", 1, 768, 4, 2, 128, 128, 1024, (2, 256, 256, 128)),
    ("block", 1, 1024, 8, 1, 128, 128, 512, (4, 128, 256, 128)),
    ("window", 1, 768, 4, 2, 128, 256, 1024, (2, 256, 256, 128)),
    ("block", 1, 512, 4, 2, 256, 128, 1024, (2, 256, 256, 128)),
    ("causal", 1, 512, 4, 2, 256, 256, 1024, (2, 256, 256, 128)),
    ("causal", 2, 128, 4, 2, 128, 128, 1024, (2, 128, 128, 128))],
    ids=lambda x: str(x) if isinstance(x, (str, int)) else "x".join(
        map(str, x)))
def test_the_computed_mask_pair_interpreted_is_the_plain_blocks(
        monkeypatch, kind, b, t, h, hkv, dh, dv, rows, tiles):
    """Forward and all three gradients against ``_plain_attention`` on the
    same bfloat16 inputs: every mask kind under groups of 1, 7 and 8 query
    heads a key/value head (the group in one step, and in two), heads of
    128 and 256 lanes, value lanes unlike the query's both ways, a batch
    of 2, one tile in all."""
    _small_tiles(monkeypatch, rows)
    kind = KINDS[kind]
    lanes = max(dh, dv)
    assert sel.forward_tiles(t, h // hkv, lanes) == tiles
    assert sel.backward_tiles(t, h // hkv, lanes) == tiles
    rng = np.random.RandomState(t + h + dv)
    q, k, v, g = (jnp.asarray(rng.randn(b, t, n, d), jnp.bfloat16)
                  for n, d in ((h, dh), (hkv, dh), (hkv, dv), (h, dv)))
    scale = dh ** -0.5
    out, vjp = jax.vjp(lambda *a: tr._rows_attention(*a, scale, kind, True),
                       q, k, v)
    want, plain_vjp = jax.vjp(
        lambda *a: tr._plain_attention(*a, scale, kind), q, k, v)
    assert out.dtype == want.dtype and out.shape == want.shape
    f32 = jnp.float32
    assert np.abs(out.astype(f32) - want.astype(f32)).max() < 0.03
    for got, ref in zip(vjp(g), plain_vjp(g)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert _rel(got, ref) < 0.02


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("bq,bkv,piece", [(128, 256, 128), (256, 256, 256),
                                          (128, 128, 128)])
def test_the_visit_plan_is_the_mask_pair_by_pair(kind, bq, bkv, piece):
    """At 768 (1024 under the block mask's codes) rows the plan runs
    exactly the pieces that hold an allowed pair, each query tile's in
    key order; marks a query tile's last visit once; closes every key
    tile once, at the last step of the grid that reads it; and the key
    tile whose ``dk`` block is current at a step is always one that has
    closed, or the first that will, so no block leaves unwritten."""
    t = 1024 if kind == "block" else 768
    rows, allowed = tr._rows_mask(KINDS[kind], t)
    dense = np.asarray(tr._mask_function(KINDS[kind], t)(
        np.arange(t)[:, None], np.arange(t)[None, :]))
    assert np.array_equal(dense, np.asarray(allowed(
        rows[:, None], np.arange(t, dtype=np.int32)[None, :])))
    plan = sel.visit_plan(rows, allowed, bq, bkv, piece)
    nq, visits = plan.shape[1:]
    ran, closed, current = set(), [], plan[3, 0, 0]
    for i in range(nq):
        tiles = [plan[0, i, j] for j in range(visits) if plan[1, i, j]]
        assert tiles == sorted(set(tiles))
        last = [j for j in range(visits) if plan[2, i, j] & sel.LAST_VISIT]
        assert last == [len(tiles) - 1]
        for j in range(visits):
            if j >= len(tiles):
                # nothing runs and nothing new is fetched
                assert plan[0, i, j] == tiles[-1] and plan[1, i, j] == 0
            for n in range(bkv // piece):
                if plan[1, i, j] >> n & 1:
                    ran.add((i, plan[0, i, j] * (bkv // piece) + n))
            if plan[2, i, j] & sel.CLOSES:
                closed.append(plan[0, i, j])
                # the block that becomes current is the one written now
                assert plan[3, i, j] == plan[0, i, j]
            else:
                assert plan[3, i, j] == current
            current = plan[3, i, j]
    assert plan[3, 0, 0] == closed[0]
    assert sorted(closed) == list(range(t // bkv))
    some = dense.reshape(nq, bq, t // piece, piece).any(axis=(1, 3))
    assert ran == {(i, n) for i, n in zip(*np.nonzero(some))}
    # a key tile closes at its last reader
    for tile in range(t // bkv):
        readers = [i for i in range(nq) if tile in plan[0, i][plan[1, i] != 0]]
        at = [(i, j) for i in range(nq) for j in range(visits)
              if plan[2, i, j] & sel.CLOSES and plan[0, i, j] == tile]
        assert [i for i, _ in at] == [readers[-1]]


@pytest.mark.parametrize("t,group,kind,visits", [
    (8192, 8, ("block_diffusion", 4), 80), (8192, 7, ("causal", 0), 136),
    (8192, 7, ("sliding_window", 4096), 108),
    (4096, 8, ("sliding_window", 2048), 30), (4096, 8, ("causal", 0), 36)],
    ids=["sdar", "smallthinker-causal", "smallthinker-window",
         "trinity-window", "trinity-causal"])
def test_the_visit_plans_counts_at_the_cells_shapes(t, group, kind, visits):
    """Tiles of 512 x 512 a step of the group's heads: under the block
    mask at 8192 rows 8 band tiles, 36 noised-by-clean and the clean
    quadrant's 36 (the causal triangle has 136); a window of half the
    rows visits 108 of those; both passes visit the same tiles."""
    for tiles in (sel.forward_tiles(t, group, 128),
                  sel.backward_tiles(t, group, 128)):
        assert tiles[:3] == (group, 512, 512)
        plan = sel.visit_plan(*tr._rows_mask(kind, t), *tiles[1:])
        assert int((plan[1] != 0).sum()) == visits
        assert int((plan[2] & sel.CLOSES != 0).sum()) == t // 512


def test_the_pair_is_chosen_from_the_inputs():
    """``kernel_pair``: groups of 2 and more over 128-lane q, k and v
    whose backward sums fit VMEM run the repo's pair; equal heads, 64-lane
    and 256-lane heads, value lanes unlike the query's and sequences too
    long for the backward kernel's kept ``dk`` and ``dv`` the library's."""
    def pair(t, h, hkv, dh, dv=None, b=1):
        q, k, v = (jax.ShapeDtypeStruct((b, t, n, d), jnp.bfloat16)
                   for n, d in ((h, dh), (hkv, dh), (hkv, dv or dh)))
        assert tr._kernel_takes(q, k, v)
        return tr.kernel_pair(q, k, v)

    assert pair(8192, 32, 4, 128) == "rows"         # SDAR
    assert pair(8192, 28, 4, 128) == "rows"         # SmallThinker
    assert pair(4096, 32, 4, 128) == "rows"         # Trinity
    assert pair(4096, 4, 2, 128, b=4) == "rows"
    assert pair(4096, 16, 16, 128, b=4) == "library"    # OLMoE, Ouro
    assert pair(8192, 32, 8, 64) == "library"           # LFM2
    assert pair(4096, 16, 2, 256) == "library"          # Qwen3-Next
    assert pair(4096, 20, 20, 256) == "library"         # GLM
    assert pair(4096, 32, 32, 192, 128) == "library"    # Kimi
    assert pair(4096, 8, 4, 128, 256) == "library"
    assert pair(32768, 8, 4, 128) == "rows"
    assert pair(65536, 8, 4, 128) == "library"


def _lowering_events(fn, *shapes):
    was = mx.trace.enabled()
    mx.trace.set_enabled(True)
    try:
        mark = time.perf_counter_ns()
        jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, d) for s, d in shapes))
        return mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    finally:
        mx.trace.set_enabled(was)


def test_the_counter_says_which_pair():
    """``attn:lowering`` gains ``pair``: ``rows`` where the TPU lowering
    is the repo's kernels, ``library`` where it is splash attention,
    ``none`` on the plain path; the other fields as they were."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    for h, hkv, dtype, kind, want, track in (
            (8, 2, bf16, ("block_diffusion", 4),
             {"kernel": 1, "plain": 0, "pair": "rows", "mask_form": "codes"},
             "bfloat16[1, 256, 8, 128]/kv2/block_diffusion4"),
            (4, 4, bf16, ("causal", 0),
             {"kernel": 1, "plain": 0, "pair": "library",
              "mask_form": "library"}, "bfloat16[1, 256, 4, 128]"),
            (8, 2, f32, ("sliding_window", 64),
             {"kernel": 0, "plain": 1, "pair": "none", "mask_form": "none"},
             "float32[1, 256, 8, 128]/kv2/sliding_window64")):
        shapes = [((1, 256, n, 128), dtype) for n in (h, hkv, hkv)]
        events = _lowering_events(
            lambda q, k, v: tr.causal_attention(
                q, k, v, 0.1, kind[0], block=kind[1], window=kind[1]),
            *shapes)
        assert len(events) == 1
        assert events[0]["args"] == want and events[0]["id"] == track


def test_the_program_exported_for_a_tpu_holds_the_computed_pair():
    """``causal_attention`` and its backward at the SDAR cell's shape,
    ``[1, 8192, 32, 128]/kv4/block_diffusion4``, lowered for a TPU from
    here: the two ``*_computed`` kernels and none of the library's, q,
    the output, its cotangent and ``dq`` as ``(T, H * Dh)`` rows, no
    transpose to a head-major ``(.., H, T, Dh)`` array and no partial
    ``dq`` planes ``(key tiles, H, T, Dh)``."""
    t, h, hkv, dh = 8192, 32, 4, 128

    def run(q, k, v, g):
        out, vjp = jax.vjp(lambda *a: tr.causal_attention(
            *a, dh ** -0.5, "block_diffusion", block=4), q, k, v)
        return (out,) + vjp(g)

    # lint: allow(raw-jit) — one-off lowering inspection
    text = jax.jit(run).trace(*(
        jax.ShapeDtypeStruct((1, t, n, dh), jnp.bfloat16)
        for n in (h, hkv, hkv, h))).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert "splash_mha_fwd_computed" in text
    assert "splash_mha_dkv_computed" in text
    assert "splash_mha_fwd_residuals" not in text
    assert "splash_mha_dkv_no_residuals" not in text
    assert not re.search(r"tensor<(\d+x)*%dx%dx%dx" % (h, t, dh), text)
    assert not re.search(r"tensor<(\d+x)*%dx%dx%dx" % (hkv, t, dh), text)
    assert "stablehlo.transpose" not in text and "stablehlo.pad" not in text
    assert re.search(r"tensor<1x%dx%dxbf16>" % (t, h * dh), text)
    assert re.search(r"tensor<1x%dx%dxbf16>" % (t, hkv * dh), text)


# sha256 of ``str(jax.make_jaxpr(..))`` of the Keye cell's two attend
# kernels, taken at 7b48475 (the commit before the step bodies took the
# source of a tile's pairs as a parameter): (T, H, Hkv, Dh, Dv) -> (fwd, bwd)
KEYE_JAXPRS = {
    (8192, 32, 4, 128, 128): (
        "c49d85f5816358438680e0a40256a5e049a6efec6aaf3605512da4892b2f09da",
        "e12b5c3b2055967bc55dcdb83a9121ef241748dc5bb3ab66b0c52930e6e3f5a4"),
    (768, 4, 2, 128, 256): (
        "73d3969197ed3c030fb226cb4fe9376faefe70172fb3b978516a65ab66178255",
        "81af0397fe8208621f15299802020d0b298a08aef7d13b9054443f342e61f106"),
}


@pytest.mark.parametrize("shape", sorted(KEYE_JAXPRS),
                         ids=["a-small-shape", "the-keye-cells"])
def test_the_selected_kernels_are_the_parents(shape):
    """The kernels under a selection that is data (``_Loaded``) trace to
    the jaxprs they had before the computed mask shared their bodies:
    equation for equation, so Mosaic is handed the same program."""
    t, h, hkv, dh, dv = shape

    def sds(*s, d=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, d)

    q, k, v = sds(t, h, dh), sds(t, hkv, dh), sds(t, hkv, dv)
    mask, out, lse = sds(t, t, d=jnp.bool_), sds(t, h, dv), \
        sds(h, t, d=jnp.float32)
    texts = (
        str(jax.make_jaxpr(lambda *a: sel.selected_attention_fwd(*a))(
            q, k, v, mask)),
        str(jax.make_jaxpr(lambda *a: sel.selected_attention_bwd(*a))(
            q, k, v, mask, out, lse, out)))
    assert tuple(hashlib.sha256(x.encode()).hexdigest()
                 for x in texts) == KEYE_JAXPRS[shape]
