"""``moe.dispatch.held_sum`` on the chip at the shapes of the cells whose
rank's share runs it, four bounded windows and the two windows of every row
(no bound): the sorted-side kernel ``token-sum`` compiled by Mosaic against
the token-side gathers it replaces.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_moe_held_sum_tpu.py -s -q

bfloat16: the kernel's sums are the rows' exact products with their
bfloat16 weights summed in float32 and rounded once (bit for bit a float32
``segment_sum`` of the same products, rounded), a token with one held row
reads what the gather reads bit for bit, and the gathers' fused
``(rows * w).sum(axis=1)`` lies within its own rounding of it (on the chip
XLA keeps that product in float32 too, and the two forms are equal); both
forms' times are printed
(``chiprun_out/held_sum_parity.jsonl``).  float32: the rule is the
operand's dtype, the compiled program holds the gathers and no kernel.
"""
import json
import os
import time

import numpy as np
import pytest

from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# cell, tokens, k, experts, held experts, the window's rows (the bound), D
SHAPES = [("sdar-30b-a3b-train-4k", 8192, 8, 128, 16, 32768, 2048),
          ("smallthinker-21b-a3b-train-8k", 8192, 6, 64, 8, 24576, 2560),
          ("trinity-mini-train-4k", 4096, 8, 128, 8, 8192, 2048),
          ("kimi-linear-48b-a3b-train-4k", 4096, 8, 256, 8, 4096, 2304),
          # no bound: the window is all T*k rows
          ("lfm2-8b-a1b-train-8k", 8192, 4, 32, 8, 32768, 2048),
          ("glm-4.7-flash-train-4k", 4096, 4, 64, 8, 16384, 2048)]


def _plan(tokens, k, experts, held_experts, seed):
    """``route_sorted``'s layout of a random top-k: the held experts'
    pairs first, by expert, an expert's in token order."""
    rng = np.random.RandomState(seed)
    choice = np.argsort(-rng.randn(tokens, experts), axis=1)[:, :k].reshape(-1)
    key = np.where(choice < held_experts, choice, experts)
    order = np.argsort(key, kind="stable").astype(np.int32)
    slot = np.empty_like(order)
    slot[order] = np.arange(tokens * k, dtype=np.int32)
    sizes = np.bincount(choice[choice < held_experts],
                        minlength=held_experts).astype(np.int32)
    weight = np.where(choice < held_experts, rng.rand(tokens * k) + 0.1, 0.0)
    return order, slot.reshape(tokens, k), sizes, \
        weight.reshape(tokens, k).astype(np.float32)


def _ms(fn, *args):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(20)]
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) * 50.0


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["combine_forward", "sort_rows_backward"])
@pytest.mark.parametrize("cell,tokens,k,experts,held_experts,n,d", SHAPES,
                         ids=[s[0] for s in SHAPES])
def test_the_token_sum_kernel_at_the_cells_shapes(cell, tokens, k, experts,
                                                  held_experts, n, d,
                                                  weighted):
    import importlib
    import jax
    import jax.numpy as jnp
    layout = importlib.import_module("mxnet_tpu.moe.dispatch")
    order, slot, sizes, weight = _plan(tokens, k, experts, held_experts, 49)
    held = int(sizes.sum())
    assert 0 < held < n
    rows = np.random.RandomState(50).randn(n, d).astype(np.float32)
    rows[held:] = 0.0
    args = (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(order[:n]),
            jnp.asarray(slot), jnp.asarray(weight))

    def form(held):
        return jax.jit(lambda rows, order, slot, w: layout.held_sum(
            rows, order, slot, held, w if weighted else None))

    sorted_side, token_side = form(jnp.asarray(sizes)), form(held)
    text = sorted_side.lower(*args).compile().as_text()
    assert "token-sum" in text
    assert "token-sum" not in token_side.lower(*args).compile().as_text()

    @jax.jit
    def oracle(rows, order, slot, w):
        # a bfloat16 weight times a bfloat16 row: exact in float32
        exact = rows.astype(jnp.float32)
        if weighted:
            w_sorted = w.reshape(-1)[order].astype(rows.dtype)
            exact = exact * w_sorted[:, None].astype(jnp.float32)
        tok = jnp.where(jnp.arange(n) < held, order // k, tokens)
        return jax.ops.segment_sum(exact, tok,
                                   tokens + 1)[:tokens].astype(rows.dtype)

    f32 = lambda a: np.asarray(a.astype(jnp.float32))       # noqa: E731
    got, gathered, want = (f32(f(*args))
                           for f in (sorted_side, token_side, oracle))
    held_rows_of = np.bincount(order[:held] // k, minlength=tokens)
    scale = float(np.abs(want).max())
    report = {
        "cell": cell, "pass": "combine_fwd" if weighted else "rowgrad_bwd",
        "shape": [tokens, k, n, d], "held": held,
        "tokens_with_rows": int((held_rows_of > 0).sum()),
        "tokens_with_two_or_more": int((held_rows_of > 1).sum()),
        "bits_off_the_float32_sum": int((got != want).sum()),
        "gathers_max_err_share": float(np.abs(gathered - want).max() / scale),
        "token_sum_ms": _ms(sorted_side, *args),
        "gathers_ms": _ms(token_side, *args)}
    print("\nHELD_SUM_PARITY " + json.dumps(report), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "held_sum_parity.jsonl"),
              "a") as f:
        f.write(json.dumps(report) + "\n")
    assert np.isfinite(got).all()
    assert np.array_equal(got, want)
    assert not got[held_rows_of == 0].any()
    single = held_rows_of == 1
    assert single.any() and np.array_equal(got[single], gathered[single])
    assert report["gathers_max_err_share"] <= 2.0 ** -6


def test_float32_keeps_the_gathers():
    """The dtype rule: a float32 window compiles to the token-side
    gathers on the chip too, and the two dtypes' programs differ in
    nothing else the caller chooses."""
    import importlib
    import jax
    import jax.numpy as jnp
    layout = importlib.import_module("mxnet_tpu.moe.dispatch")
    _, tokens, k, experts, held_experts, n, d = SHAPES[2]
    order, slot, sizes, weight = _plan(tokens, k, experts, held_experts, 49)

    def text(dtype):
        return jax.jit(lambda rows: layout.held_sum(
            rows, jnp.asarray(order[:n]), jnp.asarray(slot),
            jnp.asarray(sizes), jnp.asarray(weight))).lower(
                jax.ShapeDtypeStruct((n, d), dtype)).compile().as_text()

    assert "token-sum" in text(jnp.bfloat16)
    assert "token-sum" not in text(jnp.float32)
