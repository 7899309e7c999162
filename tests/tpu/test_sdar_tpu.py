"""SDAR-30B-A3B-Chat at its published widths on the chip (as the
``sdar-30b-a3b`` configuration is cut: its layers, 16 of 128 experts, an
eighth of the vocabulary), against the plain reference
``benchmark/reference/sdar-30b-a3b.py`` computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_sdar_tpu.py -s -q

The first test has phases that each release what they held (the chip
holds one 0.65 G-parameter module at a time): the reference's loss,
gradients and first Adam step at one sequence of 4096 clean tokens (8192
rows), and the same with its weights rounded to float8 (what the
configuration's limits have to refuse); the configuration's own Adam
step in bfloat16 at the default matmul precision, as the cell's
reference check runs it, with the ``attn:lowering`` samples of the bind
and the noise head's three numbers; and the Adam step in float32 compute
against the reference at one sequence of 1024 (2048 rows).  The numbers
go to ``chiprun_out/sdar_parity.json`` after every phase, before
anything is asserted.

The second holds ``causal_attention``'s TPU kernel under the block mask
against its plain blocks at the cell's shape, ``(1, 8192, 32, 128)``
over 4 key/value heads in blocks of 4, with both lowerings' times.

The third (ISSUE 70) holds q's and k's norm and rotation as the kernel
pair ``head_rotary_fwd`` / ``head_rotary_bwd`` alone at this cell's
``(8192, 4096)`` and ``(8192, 512)`` rows, both copies at positions ``n
mod 4096``, against the plain form and against its bytes, and the cell's
step compiled for the chip, whose ``attn_proj`` part holds no relayout
of a ``[.., 32, 128]`` or ``[.., 4, 128]`` array
(``chiprun_out/sdar_rotary_parity.json``).
"""
import gc
import json
import os
import sys
import time

import numpy as np

import _head_rotary
from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# as tests/tpu/test_olmoe_tpu.py: each side rounds its probabilities and
# results to 8 bits of mantissa
ATTN_MAX_ERR_SHARE = 0.02
ATTN_L2_ERR = 0.01
SEED = 3900000039


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adam_step(net, params, data, labels, opt_params, compute_dtype, names):
    """One step of the fused train step on the chip.  -> (the weighted
    loss, the noise head's three numbers, held rows a block, {name: after
    - before})."""
    import mxnet_tpu as mx
    if compute_dtype:
        os.environ["MXNET_COMPUTE_DTYPE"] = compute_dtype
    else:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    try:
        mod = mx.mod.Module(net, context=mx.tpu(0))
        mod.bind(data_shapes=[("data", data.shape)],
                 label_shapes=[("softmax_label", labels.shape)])
        mod.init_params(mx.init.Zero(), allow_missing=True, arg_params={
            k: mx.nd.array(v) for k, v in params.items()})
        gc.collect()
        mod.init_optimizer(optimizer="adam",
                           optimizer_params=dict(opt_params))
        assert mod._fused is not None
        batch = mx.io.DataBatch(data=[mx.nd.array(data, dtype=np.int32)],
                                label=[mx.nd.array(labels)], pad=0)
        mod.forward_backward(batch)
        mod.update()
        assert mod._exec_group.execs == []
        outs = [o.asnumpy() for o in mod.get_outputs()]
        load, noise = (mod._fused.head("moe_load")[0],
                       mod._fused.head("diffusion_noise"))
        after, _ = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    return (float(outs[0].mean()), [float(x) for x in outs[noise]],
            outs[load][:, :-1], delta)


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import sdar_moe_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "sdar-30b-a3b")
    gen = manifest.load_module("generators", "token_block_noised")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "block-noised-4k-b1.json")) as f:
        traffic = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq = kw["seq_len"]
    net = sdar_moe_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, 2 * seq), softmax_label=(1, 2, seq))[0]))
    rng = np.random.RandomState(39)
    params = {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                  (0.02 * rng.standard_normal(s)).astype(np.float32))
              for n, s in shapes.items()
              if n not in ("data", "softmax_label")}
    batches = gen.build(dict(traffic, distinct_batches=1), cfg, SEED,
                        [mx.cpu(0)], None)
    (data,), (labels,) = (list(d.values()) for d in
                          batches.reference_batch(1)[:2])
    report = {"device": jax.devices()[0].device_kind,
              "params_M": sum(v.size for v in params.values()) / 1e6,
              "masked": int((labels[:, 0] >= 0).sum())}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "sdar_parity.json"), "w") as f:
            json.dump(report, f, indent=1)
        print("\nSDAR_PARITY " + json.dumps(report), flush=True)

    def reference(p, d, lb, config=cfg):
        out = ref.reference_step(config, p, {"data": d},
                                 {"softmax_label": lb}, adam, names)
        gc.collect()
        return out

    def loss_of(got, want):
        return {"loss": got, "reference_loss": want["loss"],
                "loss_rel_err": abs(got - want["loss"]) / want["loss"]}

    # A. the reference on this chip, and with float8 weights (e4m3, the
    # nearest format under bfloat16; arithmetic stays float32)
    want = reference(params, data, labels)
    coarse = {n: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(jnp.float32))
              for n, v in params.items()}
    out = reference(coarse, data, labels)
    report["reference_fp8_weights"] = dict(
        loss_of(out["loss"], want),
        adam_update_rel_err={n: _rel(out["updates"][n], want["updates"][n])
                             for n in names})
    del out, coarse
    gc.collect()
    save()

    # B. the configuration's step, bfloat16 at the default precision
    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    with jax.default_matmul_precision("default"):
        loss, noise, counts, delta = _adam_step(
            net, params, data, labels, adam, "bfloat16", names)
    lowered = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    report["adam_bf16"] = dict(
        loss_of(loss, want),
        update_rel_err={n: _rel(delta[n], want["updates"][n])
                        for n in names},
        noise=noise,
        held_rows=[float(c[:kw["experts_held"]].sum()) for c in counts],
        attn_lowering=[[e["id"], e["args"]] for e in lowered])
    save()
    del want
    gc.collect()

    # C. float32 compute against the reference, one sequence of 1024
    short = dict(kw, seq_len=1024)
    cfg_short = dict(cfg, model=dict(cfg["model"], kwargs=short),
                     input=dict(cfg["input"], seq_len=1024))
    small = gen.build(dict(traffic, distinct_batches=1), cfg_short, SEED,
                      [mx.cpu(0)], None)
    (d32,), (l32,) = (list(d.values()) for d in
                      small.reference_batch(1)[:2])
    want = reference(params, d32, l32, cfg_short)
    loss32, _, _, delta32 = _adam_step(
        sdar_moe_lm(**short), params, d32, l32, adam, None, names)
    report["adam_f32_t1024"] = dict(
        loss_of(loss32, want),
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names})
    save()

    fp8 = report["reference_fp8_weights"]
    bf16 = report["adam_bf16"]
    assert bf16["loss_rel_err"] <= limits["loss_rtol"]
    for n in names:
        assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], n
    masked = labels[:, 0] >= 0
    assert np.allclose(bf16["noise"], [masked.sum(), masked.size,
                                       labels[:, 1][masked].sum()],
                       rtol=1e-5)
    # one attention call a layer, every one the kernel under the block
    # mask over 4 key/value heads
    assert len(bf16["attn_lowering"]) == kw["num_layers"]
    for track, args in bf16["attn_lowering"]:
        assert args == {"kernel": 1, "plain": 0, "pair": "rows",
                        "mask_form": "codes"}, \
            (track, args)
        assert track == "bfloat16[1, 8192, 32, 128]/kv4/block_diffusion4"
    # float8 weights are refused by at least one limit
    assert fp8["loss_rel_err"] > limits["loss_rtol"] or any(
        fp8["adam_update_rel_err"][n] > limits["update_rtol"][n]
        for n in names)
    f32 = report["adam_f32_t1024"]
    assert f32["loss_rel_err"] <= 1e-4
    assert max(f32["update_rel_err"].values()) <= 0.1, f32


def test_attention_kernel_matches_plain_blocks_under_the_block_mask():
    """``causal_attention`` at the cell's ``(1, 8192, 32, 128)`` bfloat16
    q over 4 key/value heads under ``block_diffusion`` in blocks of 4
    compiles to the Mosaic kernels on the chip; output and all three
    input gradients agree with the plain blocks', and a block's clean
    rows do not reach its noised rows.  The kernel gets the mask as row
    codes (``mask_form`` ``codes``); the forward and the forward +
    backward ms of the layer's pair are printed and kept."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import transformer as tf_ops
    scale = 128 ** -0.5
    rng = np.random.RandomState(39)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 8192, h, 128)),
                           jnp.bfloat16) for h in (32, 4, 4))
    w = jnp.asarray(rng.standard_normal((1, 8192, 32, 128)), jnp.float32)
    kind = ("block_diffusion", 4)

    def both_passes(attend, *mask):
        def run(q, k, v):
            out, vjp = jax.vjp(lambda *a: attend(*a, scale, *mask), q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(run)

    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    kernel = both_passes(tf_ops.causal_attention, *kind)
    plain = both_passes(tf_ops._plain_attention, kind)
    text = kernel.lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text and "splash_mha" in text
    # the repo's own pair in row form (PR 64), none of the library's
    assert "splash_mha_fwd_computed" in text
    assert "splash_mha_dkv_computed" in text
    assert "no_residuals" not in text and "fwd_residuals" not in text
    assert "tpu_custom_call" not in plain.lower(q, k, v).compile().as_text()
    event = mx.trace.counter_events(["attn:lowering"], since_ns=mark)[-1]
    assert event["args"] == {"kernel": 1, "plain": 0, "pair": "rows",
                             "mask_form": "codes"}
    assert event["id"] == "bfloat16[1, 8192, 32, 128]/kv4/block_diffusion4"
    got = [np.asarray(x, np.float32) for x in kernel(q, k, v)]
    want = [np.asarray(x, np.float32) for x in plain(q, k, v)]
    report = {"max_err_share": [], "l2_err": []}
    for g, r in zip(got, want):
        report["max_err_share"].append(
            float(np.abs(g - r).max() / np.abs(r).max()))
        report["l2_err"].append(_rel(g, r))

    def ms(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 5 * 1e3

    # one layer's kernel pair in isolation, from head-major-free inputs
    # as the test makes them (XLA lays them out for the kernels here; in
    # the step the projections give that layout), and the library's pair
    # with its transposes and partial dq planes beside it: the figures to
    # hold beside the traced cell's splash_mha* operations (PERF.md
    # section 5)
    forward = jax.jit(lambda q, k, v: tf_ops.causal_attention(
        q, k, v, scale, *kind))
    report["mask_form"] = event["args"]["mask_form"]
    report["ms_a_layer"] = {"kernel_forward": ms(forward, q, k, v),
                            "kernel_forward_backward": ms(kernel, q, k, v),
                            "library_forward_backward": ms(both_passes(
                                tf_ops._flash_attention, kind), q, k, v),
                            "plain_forward_backward": ms(plain, q, k, v)}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "sdar_attn_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("\nSDAR_ATTN_PARITY " + json.dumps(report), flush=True)
    assert max(report["max_err_share"]) <= ATTN_MAX_ERR_SHARE, report
    assert max(report["l2_err"]) <= ATTN_L2_ERR, report
    # clean rows 4096 + 8 .. 4096 + 11 are block 2's: noised rows 0..11
    # do not move, the next block's noised rows and those clean rows do
    at = slice(4096 + 8, 4096 + 12)
    moved = np.asarray(jax.jit(tf_ops.causal_attention,
                               static_argnums=(3, 4, 5))(
        q, k.at[:, at].add(1.0), v.at[:, at].add(-1.0), scale, *kind),
        np.float32)
    assert np.array_equal(moved[:, :12], got[0][:, :12])
    assert not np.array_equal(moved[:, 12:16], got[0][:, 12:16])
    assert not np.array_equal(moved[:, at], got[0][:, at])


def test_head_rotary_kernels_at_the_cells_rows_and_the_steps_relayouts():
    """``head_norm_rotary`` at the cell's q and k rows, ``(8192, 4096)``
    and ``(8192, 512)`` bfloat16, the noised and the clean copy at the
    same positions: the kernel pair compiled by Mosaic is no further from
    the plain form in float32 than the plain form in bfloat16 is, each
    pass under ``BYTES_TIMES`` its bytes' time and under the plain
    form's.  Then the cell's step compiled for the chip: four layers' q
    and k are eight calls of each kernel, and no ``copy`` or ``reshape``
    under ``attn_proj`` writes a head-form array, nor does any float32
    result of the entry computation have q's head form (23 of ``[1, 8192,
    32, 128]`` in the parent's step compiled for a described v5e)."""
    import jax
    report = {"device": jax.devices()[0].device_kind, "pairs": [
        _head_rotary.pair_against_the_plain_form(
            width, 1e-6, theta=1e6, period=4096)
        for width in _head_rotary.WIDTHS]}
    print("\nROTARY_KERNEL_PARITY " + json.dumps(report["pairs"]),
          flush=True)
    report["step"] = _head_rotary.head_form_in_attn_proj(
        "sdar-30b-a3b", {"data": (1, 8192), "softmax_label": (1, 2, 4096)})
    print("ROTARY_STEP " + json.dumps(report["step"]), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "sdar_rotary_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    for pair in report["pairs"]:
        _head_rotary.check_pair(pair)
    assert report["step"]["calls"] == [8, 8]
    assert report["step"]["lowering"] == [
        ["bfloat16[8192, %d]/128" % width, 1]
        for width in _head_rotary.WIDTHS] * 4
    assert not report["step"]["head_layout_copies"]
    assert not report["step"]["float32_head_form"]
