"""GLM-4.7-Flash at its published widths on the chip (five layers and the
prediction module, 8 of 64 experts, as the ``glm-4.7-flash`` configuration
is cut), against the plain reference ``benchmark/reference/glm-4.7-flash.py``
computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_glm_flash_tpu.py -s -q

The first test has phases that each release what they held (the chip
holds one 0.7 G-parameter module at a time): the reference's two losses,
gradients and first Adam step at one sequence of 4096, and the same with
its weights rounded to float8 (what the configuration's limits have to
refuse); the configuration's own Adam step in bfloat16 at the default
matmul precision, as the cell's reference check runs it, with the second
head's loss, the selection bias's first move and the six
``attn:lowering`` samples of the bind; and the Adam step in float32
compute against the reference at one sequence of 1024.  The numbers go
to ``chiprun_out/glm_parity.json`` after every phase, before anything is
asserted.

The second holds ``causal_attention``'s TPU kernel against its plain
blocks at the cell's shape, ``(1, 4096, 20, 256)`` against values of 256:
nothing is padded there, and both kernels run at the 1024-token tile.
"""
import gc
import json
import os
import sys
import time

import numpy as np

from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# as tests/tpu/test_olmoe_tpu.py: each side rounds its probabilities and
# results to 8 bits of mantissa
ATTN_MAX_ERR_SHARE = 0.02
ATTN_L2_ERR = 0.01


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adam_step(net, params, tokens, labels, opt_params, compute_dtype,
               names):
    """One step of the fused train step on the chip.  -> (mean CE, the
    second head's mean over its positions, counts per block, {name:
    after - before}, {aux: value})."""
    import mxnet_tpu as mx
    if compute_dtype:
        os.environ["MXNET_COMPUTE_DTYPE"] = compute_dtype
    else:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    try:
        mod = mx.mod.Module(net, context=mx.tpu(0))
        mod.bind(data_shapes=[("data", tokens.shape)],
                 label_shapes=[("softmax_label", labels.shape)])
        mod.init_params(mx.init.Zero(), allow_missing=True, arg_params={
            k: mx.nd.array(v) for k, v in params.items()})
        gc.collect()
        mod.init_optimizer(optimizer="adam",
                           optimizer_params=dict(opt_params))
        assert mod._fused is not None
        batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)], pad=0)
        mod.forward_backward(batch)
        mod.update()
        assert mod._exec_group.execs == []
        outs = [o.asnumpy() for o in mod.get_outputs()]
        main, extra = mod._fused.head("mtp_loss")[:2]
        after, aux = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        aux = {n: v.asnumpy() for n, v in aux.items()}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    second = outs[extra].reshape(tokens.shape)
    assert not second[:, -1].any()
    return (float(outs[main].mean()), float(second[:, :-1].mean()),
            outs[-1][:, :-1], delta, aux)


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import glm_moe_lite_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "glm-4.7-flash")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        cfg = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq, vocab = kw["seq_len"], kw["vocab_size"]
    net = glm_moe_lite_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))
    rng = np.random.RandomState(35)
    params = {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                  (0.02 * rng.standard_normal(s)).astype(np.float32))
              for n, s in shapes.items()
              if n not in ("data", "softmax_label")}
    stream = gen.markov_stream(np.random.RandomState(3500000035 % 2 ** 32),
                               seq + 1, vocab, 0.85, 1.2, 600.0)
    tokens, labels = stream[:-1].reshape(1, seq), stream[1:].reshape(1, seq)
    blocks = ["l%d_moe_dispatch" % l for l in range(1, kw["num_layers"])] \
        + ["mtp_moe_dispatch"]
    report = {"device": jax.devices()[0].device_kind,
              "params_M": sum(v.size for v in params.values()) / 1e6}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "glm_parity.json"), "w") as f:
            json.dump(report, f, indent=1)
        print("\nGLM_PARITY " + json.dumps(report), flush=True)

    def reference(p, tk, lb, config=cfg):
        out = ref.reference_step(config, p, {"data": tk},
                                 {"softmax_label": lb}, adam, names)
        gc.collect()
        return out

    def losses(got, want):
        return {"loss": got[0], "reference_loss": want["loss"],
                "loss_rel_err": abs(got[0] - want["loss"]) / want["loss"],
                "mtp_loss": got[1], "reference_mtp_loss": want["mtp_loss"],
                "mtp_loss_rel_err": abs(got[1] - want["mtp_loss"])
                / want["mtp_loss"]}

    # A. the reference on this chip, and with float8 weights (e4m3, the
    # nearest format under bfloat16; arithmetic stays float32)
    want = reference(params, tokens, labels)
    coarse = {n: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(jnp.float32))
              for n, v in params.items()}
    out = reference(coarse, tokens, labels)
    report["reference_fp8_weights"] = dict(
        losses((out["loss"], out["mtp_loss"]), want),
        adam_update_rel_err={n: _rel(out["updates"][n], want["updates"][n])
                             for n in names})
    del out, coarse
    gc.collect()
    save()

    # B. the configuration's step, bfloat16 at the default precision
    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    with jax.default_matmul_precision("default"):
        loss, mtp, counts, delta, aux = _adam_step(
            net, params, tokens, labels, adam, "bfloat16", names)
    lowered = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    moves = {b: np.asarray(want["bias_moves"][b]) for b in blocks}
    report["adam_bf16"] = dict(
        losses((loss, mtp), want),
        update_rel_err={n: _rel(delta[n], want["updates"][n])
                        for n in names},
        held_rows=[float(c[:kw["experts_held"]].sum()) for c in counts],
        bias_signs_agreed={b: float(np.mean(
            np.sign(aux[b + "_select_bias"]) == np.sign(moves[b])))
            for b in blocks},
        attn_lowering=[[e["id"], e["args"]] for e in lowered])
    save()
    del want
    gc.collect()

    # C. float32 compute against the reference, one sequence of 1024
    short = dict(kw, seq_len=1024)
    cfg_short = dict(cfg, model=dict(cfg["model"], kwargs=short))
    tk, lb = tokens[:, :1024], labels[:, :1024]
    want = reference(params, tk, lb, cfg_short)
    loss32, mtp32, counts32, delta32, aux32 = _adam_step(
        glm_moe_lite_lm(**short), params, tk, lb, adam, None, names)
    report["adam_f32_t1024"] = dict(
        losses((loss32, mtp32), want),
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names},
        bias_moves_equal={b: bool(np.array_equal(
            aux32[b + "_select_bias"],
            np.asarray(want["bias_moves"][b], np.float32)))
            for b in blocks})
    save()

    fp8 = report["reference_fp8_weights"]
    bf16 = report["adam_bf16"]
    assert bf16["loss_rel_err"] <= limits["loss_rtol"]
    assert bf16["mtp_loss_rel_err"] <= limits["loss_rtol"]
    for n in names:
        assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], n
    # six attention calls, every one the kernel at 256 against 256
    assert len(bf16["attn_lowering"]) == 6, bf16["attn_lowering"]
    for track, args in bf16["attn_lowering"]:
        assert args == {"kernel": 1, "plain": 0, "pair": "library",
                        "mask_form": "library"}, (track, args)
        assert track == "bfloat16[1, 4096, 20, 256]", track
    # float8 weights are refused by at least one limit
    assert fp8["loss_rel_err"] > limits["loss_rtol"] or any(
        fp8["adam_update_rel_err"][n] > limits["update_rtol"][n]
        for n in names)
    f32 = report["adam_f32_t1024"]
    assert f32["loss_rel_err"] <= 1e-4 and f32["mtp_loss_rel_err"] <= 1e-4
    assert max(f32["update_rel_err"].values()) <= 0.1, f32


def test_attention_kernel_matches_plain_blocks_at_256_against_256():
    """``causal_attention`` at the cell's ``(1, 4096, 20, 256)`` bfloat16
    q, k and v compiles to the Mosaic kernels on the chip at the
    1024-token tile with nothing padded; output and all three input
    gradients agree with the plain blocks', and the future does not
    leak."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import transformer as tf_ops
    shape, scale = (1, 4096, 20, 256), 256 ** -0.5
    rng = np.random.RandomState(35)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def both_passes(attend):
        def run(q, k, v):
            out, vjp = jax.vjp(lambda *a: attend(*a, scale), q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(run)

    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    kernel = both_passes(tf_ops.causal_attention)
    plain = both_passes(tf_ops._plain_attention)
    text = kernel.lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text
    assert "tpu_custom_call" not in plain.lower(q, k, v).compile().as_text()
    event = mx.trace.counter_events(["attn:lowering"], since_ns=mark)[-1]
    assert event["args"] == {"kernel": 1, "plain": 0, "pair": "library",
                             "mask_form": "library"}
    assert event["id"] == "bfloat16[1, 4096, 20, 256]"
    assert tf_ops._kernel_tiles(4096) == (1024, 512)
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

    report["ms_a_layer"] = {"kernel_forward_backward": ms(kernel, q, k, v),
                            "plain_forward_backward": ms(plain, q, k, v)}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "glm_attn_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("\nGLM_ATTN_PARITY " + json.dumps(report), flush=True)
    assert max(report["max_err_share"]) <= ATTN_MAX_ERR_SHARE, report
    assert max(report["l2_err"]) <= ATTN_L2_ERR, report
    moved = np.asarray(jax.jit(tf_ops.causal_attention, static_argnums=3)(
        q, k.at[:, -1].add(1.0), v.at[:, -1].add(-1.0), scale), np.float32)
    assert np.array_equal(moved[:, :-1], got[0][:, :-1])
    assert not np.array_equal(moved[:, -1], got[0][:, -1])
