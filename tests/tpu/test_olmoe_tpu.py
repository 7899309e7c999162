"""OLMoE-1B-7B at its published widths on the chip (one layer, as the
``olmoe-1b-7b`` configuration is cut), against the plain reference
``benchmark/reference/olmoe-1b-7b.py`` computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_olmoe_tpu.py -s -q

One test, six phases that each release what they held (the chip holds
one 0.47 G-parameter module at a time): the logits of the last 256
positions of two seeded sequences through a plain executor in float32;
the reference's loss, logits and gradients (and the same with its
weights rounded to float8); one SGD step of the fused
train step in float32 and one in bfloat16 (the gradient read back as
``(w - w') / lr``); and the configuration's own Adam step in bfloat16,
where the token embedding takes the lazy sparse path.  The numbers go to
``chiprun_out/olmoe_parity.json`` after every phase, before anything is
asserted.  Beside it: the attention kernel and the grouped-matmul kernel
pair, each against its plain lowering at the cells' shapes.
tests/tpu/conftest.py pins "highest" matmul precision, so float32 is
float32 on both sides; bfloat16 operands are exact under it.
"""
import gc
import json
import os
import sys
import time

import numpy as np
import pytest

from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMED = ["l0_moe_gate_weight", "l0_moe_experts_i2h_weight",
         "l0_q_proj_weight", "embed_weight"]
LAST = 256
SGD_LR = 1024.0
# float32 against float32 on the same chip: the order of the sums only
# (tests/test_olmoe.py reads 2e-6..3e-5 on the CPU at tiny widths; 4096
# keys and 2048-wide sums make it a few times that)
F32_LOGIT_ATOL_SHARE = 2e-4      # of the largest |logit|
F32_LOSS_RTOL = 1e-5
F32_GRAD_RTOL = 1e-3
# bfloat16 compute against the float32 reference: 8 bits of mantissa a
# rounding, through ~10 roundings a layer; a token near the top-8's edge
# changes expert, which moves the router's and the experts' gradients
BF16_LOSS_RTOL = 2e-3
BF16_GRAD_RTOL = {"l0_moe_gate_weight": 0.5, "l0_moe_experts_i2h_weight":
                  0.2, "l0_q_proj_weight": 0.2, "embed_weight": 0.2}


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _module_step(net, params, tokens, labels, optimizer, opt_params,
                 compute_dtype):
    """One step of the fused train step on the chip.  -> (mean CE, aux,
    counts, {name: after - before for NAMED}, sparse embeds engaged)."""
    import mxnet_tpu as mx
    if compute_dtype:
        os.environ["MXNET_COMPUTE_DTYPE"] = compute_dtype
    else:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    try:
        mod = mx.mod.Module(net, context=mx.tpu(0))
        mod.bind(data_shapes=[("data", tokens.shape)],
                 label_shapes=[("softmax_label", labels.shape)])
        mod.init_params(arg_params={k: mx.nd.array(v)
                                    for k, v in params.items()},
                        aux_params={})
        gc.collect()
        mod.init_optimizer(optimizer=optimizer,
                           optimizer_params=dict(opt_params))
        assert mod._fused is not None
        sparse = sorted(mod._fused.sparse_embeds)
        batch = mx.io.DataBatch(data=[mx.nd.array(tokens)],
                                label=[mx.nd.array(labels)], pad=0)
        mod.forward_backward(batch)
        mod.update()
        outs = [o.asnumpy() for o in mod.get_outputs()]
        after = mod.get_params()[0]
        delta = {n: after[n].asnumpy() - params[n] for n in NAMED}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    # one layer: the loss head, its aux head, the (1, E + 1) load head
    return (float(outs[0].mean()), float(outs[1][0]), outs[2][0, :-1], delta,
            sparse)


def test_published_width_step_matches_reference():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import olmoe_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "olmoe-1b-7b")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    kw = cfg["model"]["kwargs"]
    seq, vocab = kw["seq_len"], kw["vocab_size"]
    net = olmoe_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))
    rng = np.random.RandomState(26)
    params = {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                  (0.02 * rng.standard_normal(s)).astype(np.float32))
              for n, s in shapes.items()
              if n not in ("data", "softmax_label")}
    stream = gen.markov_stream(np.random.RandomState(2600000026 % 2 ** 32),
                               2 * seq + 1, vocab, 0.85, 1.2, 600.0)
    tokens = stream[:-1].reshape(2, seq)
    labels = stream[1:].reshape(2, seq)
    report = {"device": jax.devices()[0].device_kind,
              "params_M": sum(v.size for v in params.values()) / 1e6}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "olmoe_parity.json"), "w") as f:
            json.dump(report, f, indent=1)
        print("\nOLMOE_PARITY " + json.dumps(report), flush=True)

    # A. float32 logits of the last LAST positions, two sequences
    logits_sym = net.get_internals()["lm_head_output"]
    exe = logits_sym.simple_bind(mx.tpu(0), data=(1, seq), grad_req="null")
    for name, val in params.items():
        exe.arg_dict[name][:] = val
    got_logits = []
    for s in range(2):
        exe.arg_dict["data"][:] = tokens[s:s + 1]
        exe.forward(is_train=False)
        got_logits.append(exe.outputs[0].asnumpy()[-LAST:])
    del exe
    gc.collect()

    # B. the reference, on this chip, float32 at highest precision
    want_logits = []
    for s in range(2):
        out = ref.loss_and_grads(cfg, params, tokens[s:s + 1],
                                 labels[s:s + 1], NAMED)
        want_logits.append(np.asarray(out["logits"][-LAST:]))
        if s == 0:
            want = {"loss": out["loss"], "aux": out["aux"][0],
                    "counts": np.asarray(out["counts"][0]),
                    "grads": {n: np.asarray(out["grads"][n])
                              for n in NAMED}}
            adam = {n: np.asarray(ref.adam_first_step(
                out["grads"][n], cfg["optimizer"]["params"]))
                for n in NAMED}
        del out
        gc.collect()
    # B2. the same reference with its weights rounded to float8 (e4m3,
    # the nearest format under the configuration's bfloat16; the
    # arithmetic stays float32, so this reads low): what the limits of
    # the configuration's reference check have to refuse
    import jax.numpy as jnp
    coarse = {n: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(jnp.float32))
              for n, v in params.items()}
    out = ref.loss_and_grads(cfg, coarse, tokens[:1], labels[:1], NAMED)
    report["reference_fp8_weights"] = {
        "loss": out["loss"], "reference_loss": want["loss"],
        "loss_rel_err": abs(out["loss"] - want["loss"]) / want["loss"],
        "adam_update_rel_err": {n: _rel(ref.adam_first_step(
            out["grads"][n], cfg["optimizer"]["params"]), adam[n])
            for n in NAMED}}
    del out, coarse
    gc.collect()
    peak = max(np.abs(w).max() for w in want_logits)
    report["logits_f32"] = {
        "max_abs_err": [float(np.abs(g - w).max())
                        for g, w in zip(got_logits, want_logits)],
        "max_abs_logit": float(peak)}
    save()

    # C, D. one SGD step of the fused train step: float32, bfloat16
    sgd = {"learning_rate": SGD_LR, "momentum": 0.0, "wd": 0.0,
           "rescale_grad": 1.0}
    for tag, dtype in (("f32", None), ("bf16", "bfloat16")):
        loss, aux, counts, delta, _ = _module_step(
            net, params, tokens[:1], labels[:1], "sgd", sgd, dtype)
        report["step_" + tag] = {
            "loss": loss, "reference_loss": want["loss"],
            "aux": aux, "reference_aux": want["aux"],
            "counts_equal": bool(np.array_equal(counts, want["counts"])),
            "choices_moved": float(np.abs(counts - want["counts"]).sum()
                                   / 2),
            "grad_rel_err": {n: _rel(-delta[n] / SGD_LR, want["grads"][n])
                             for n in NAMED}}
        save()

    # E. the configuration's Adam step in bfloat16: what the cell's own
    # reference check compares
    loss, _, _, delta, sparse = _module_step(
        net, params, tokens[:1], labels[:1], cfg["optimizer"]["name"],
        cfg["optimizer"]["params"], "bfloat16")
    touched = np.zeros(vocab, bool)
    touched[np.unique(tokens[0])] = True
    emb, emb_ref = delta["embed_weight"], adam["embed_weight"]
    report["adam_bf16"] = {
        "loss": loss, "sparse_embeds": sparse,
        "update_rel_err": {n: _rel(delta[n], adam[n]) for n in NAMED},
        "embed_rows_touched": int(touched.sum()),
        "embed_untouched_max_abs_update": float(np.abs(emb[~touched]).max()),
        "reference_untouched_max_abs_update":
            float(np.abs(emb_ref[~touched]).max()),
        "embed_touched_rel_err": _rel(emb[touched], emb_ref[touched]),
        "sign_agreement": {n: float(np.mean(np.sign(delta[n])
                                            == np.sign(adam[n])))
                           for n in NAMED}}

    save()

    for err in report["logits_f32"]["max_abs_err"]:
        assert err <= F32_LOGIT_ATOL_SHARE * peak
    f32, bf16 = report["step_f32"], report["step_bf16"]
    assert abs(f32["loss"] - want["loss"]) <= F32_LOSS_RTOL * want["loss"]
    assert f32["counts_equal"]
    assert max(f32["grad_rel_err"].values()) <= F32_GRAD_RTOL, f32
    assert abs(bf16["loss"] - want["loss"]) <= BF16_LOSS_RTOL * want["loss"]
    for n, bound in BF16_GRAD_RTOL.items():
        assert bf16["grad_rel_err"][n] <= bound, (n, bf16)
    # the float32 bound is one bfloat16 compute does not meet
    assert max(bf16["grad_rel_err"].values()) > F32_GRAD_RTOL
    assert sparse == ["embed_weight"]
    # rows the batch did not touch: the lazy sparse update leaves them,
    # and so does dense Adam's first step (zero gradient, zero state)
    assert report["adam_bf16"]["embed_untouched_max_abs_update"] == 0.0
    assert report["adam_bf16"]["reference_untouched_max_abs_update"] == 0.0


# the kernel against the plain blocks, both bfloat16 on the same chip:
# each side rounds its probabilities and its results to 8 bits of
# mantissa (2**-8 = 0.0039 a rounding), so two correct results differ by
# a few roundings of the largest element (0.0036-0.0085 measured, PR 27)
ATTN_MAX_ERR_SHARE = 0.02        # of the largest |element| of the plain side
ATTN_L2_ERR = 0.01               # of the plain side's norm


def test_attention_kernel_matches_plain_blocks_at_the_cell_shape():
    """``causal_attention`` at the cell's ``(4, 4096, 16, 128)`` bfloat16
    compiles to the Mosaic kernel on the chip; its output and its three
    input gradients agree with the plain blocks', and the last key and
    value move only the last query's output."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import transformer as tf_ops
    shape, scale = (4, 4096, 16, 128), 128 ** -0.5
    rng = np.random.RandomState(27)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def both_passes(attend):
        def run(q, k, v):
            out, vjp = jax.vjp(lambda *a: attend(*a, scale), q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(run)

    mark = time.perf_counter_ns()
    kernel = both_passes(tf_ops.causal_attention)
    plain = both_passes(tf_ops._plain_attention)
    assert "tpu_custom_call" in kernel.lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" not in plain.lower(q, k, v).compile().as_text()
    event = mx.trace.counter_events(["attn:lowering"], since_ns=mark)[-1]
    assert event["args"] == {"kernel": 1, "plain": 0, "pair": "library",
                             "mask_form": "library"}
    assert event["id"] == "bfloat16[4, 4096, 16, 128]"
    got = [np.asarray(x, np.float32) for x in kernel(q, k, v)]
    want = [np.asarray(x, np.float32) for x in plain(q, k, v)]
    report = {"max_err_share": [], "l2_err": []}
    for g, r in zip(got, want):
        report["max_err_share"].append(
            float(np.abs(g - r).max() / np.abs(r).max()))
        report["l2_err"].append(_rel(g, r))
    print("\nATTN_KERNEL_PARITY " + json.dumps(report), flush=True)
    assert max(report["max_err_share"]) <= ATTN_MAX_ERR_SHARE, report
    assert max(report["l2_err"]) <= ATTN_L2_ERR, report
    # the future does not leak
    moved = np.asarray(jax.jit(tf_ops.causal_attention, static_argnums=3)(
        q, k.at[:, -1].add(1.0), v.at[:, -1].add(-1.0), scale), np.float32)
    assert np.array_equal(moved[:, :-1], got[0][:, :-1])
    assert not np.array_equal(moved[:, -1], got[0][:, -1])


# bfloat16 outputs of float32 sums taken in another order: a rounding or
# two of the largest element (0.0016-0.0036 measured, PR 38)
GMM_MAX_ERR_SHARE = 0.02


def _group_sizes(rows, experts, spread, seed):
    rng = np.random.RandomState(seed)
    p = np.exp(spread * rng.standard_normal(experts))
    return rng.multinomial(rows, p / p.sum()).astype(np.int32)


@pytest.mark.parametrize("cell,m,k,n,experts,held,spread", [
    # the cell's gate / up and down products, a router as uneven as the
    # cell's (fullest expert 3.5 x the mean)
    ("olmoe_gate_up", 131072, 2048, 1024, 64, 131072, 0.7),
    ("olmoe_down", 131072, 1024, 2048, 64, 131072, 0.7),
    # one expert-parallel rank's share: 3 % / 17 % of the rows in groups
    ("kimi_gate_up", 32768, 2304, 1024, 8, 1024, 0.3),
    ("glm_gate_up", 16384, 2048, 1536, 8, 2800, 1.0),
])
def test_grouped_matmul_kernels_match_ragged_dot_at_the_cells_shapes(
        cell, m, k, n, experts, held, spread):
    """``moe.dispatch.grouped_matmul`` at a cell's published shapes in
    bfloat16 compiles to ``ragged-dot-gmm`` / ``ragged-dot-tgmm`` on the
    chip; its output and both gradients over the rows that belong to a
    group agree with ``lax.ragged_dot``'s on the same chip, and both
    sides' times (forward; forward and backward) are printed."""
    import importlib
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.moe import gmm
    dispatch = importlib.import_module("mxnet_tpu.moe.dispatch")
    sizes = jnp.asarray(_group_sizes(held, experts, spread, 38))
    key = jax.random.PRNGKey(38)
    rows = jax.random.normal(key, (m, k), jnp.bfloat16)
    w = (jax.random.normal(key, (experts, k, n), jnp.float32)
         / np.sqrt(k)).astype(jnp.bfloat16)
    ct = jax.random.normal(jax.random.PRNGKey(39), (m, n), jnp.bfloat16)
    mine = (jnp.arange(m) < held)[:, None]

    def own(x):
        return jnp.where(mine, x, jnp.zeros((), x.dtype))

    def passes(matmul):
        def both(rows, w):
            out, vjp = jax.vjp(
                lambda rows, w: own(matmul(own(rows), w, sizes)), rows, w)
            return (out,) + vjp(ct)
        return (jax.jit(lambda rows, w: own(matmul(own(rows), w, sizes))),
                jax.jit(both))

    def ms(fn):
        jax.block_until_ready(fn(rows, w))
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(rows, w)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) * 100.0

    mark = time.perf_counter_ns()
    kernel = passes(dispatch.grouped_matmul)
    plain = passes(gmm.ragged_matmul)
    text = kernel[1].lower(rows, w).compile().as_text()
    assert text.count("ragged-dot-gmm") >= 2 and "ragged-dot-tgmm" in text
    assert "ragged-dot-none" not in text
    assert "ragged-dot-gmm" not in plain[1].lower(rows, w).compile().as_text()
    traced = mx.trace.counter_events(["moe:gmm_trace"], since_ns=mark)
    got = [np.asarray(x, np.float32) for x in kernel[1](rows, w)]
    want = [np.asarray(x, np.float32) for x in plain[1](rows, w)]
    report = {
        "cell": cell, "shape": [m, k, n, experts], "rows_in_groups": held,
        "tiles": sorted({(e["args"]["tm"], e["args"]["tk"], e["args"]["tn"])
                         for e in traced}),
        "max_err_share": [float(np.abs(g - r).max() / np.abs(r).max())
                          for g, r in zip(got, want)],
        "kernel_ms": {"forward": ms(kernel[0]), "both": ms(kernel[1])},
        "ragged_dot_ms": {"forward": ms(plain[0]), "both": ms(plain[1])}}
    print("\nGMM_KERNEL_PARITY " + json.dumps(report), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gmm_kernel_parity.jsonl"),
              "a") as f:
        f.write(json.dumps(report) + "\n")
    assert all(np.isfinite(g).all() for g in got)
    assert max(report["max_err_share"]) <= GMM_MAX_ERR_SHARE, report


def test_the_cell_s_bound_module_runs_the_kernel():
    """The cell's module (the configuration's model, bfloat16 compute,
    4 sequences of 4096) traces its attention op once, and the op hands
    the chip the kernel: ``attn:lowering`` reads ``kernel``."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import olmoe_lm
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    kw = cfg["model"]["kwargs"]
    seq, vocab = kw["seq_len"], kw["vocab_size"]
    net = olmoe_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(4, seq), softmax_label=(4, seq))[0]))
    rng = np.random.RandomState(27)
    params = {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                  (0.02 * rng.standard_normal(s)).astype(np.float32))
              for n, s in shapes.items()
              if n not in ("data", "softmax_label")}
    tokens = rng.randint(0, vocab, (4, seq + 1))
    mark = time.perf_counter_ns()
    loss = _module_step(net, params, tokens[:, :-1], tokens[:, 1:],
                        cfg["optimizer"]["name"], cfg["optimizer"]["params"],
                        cfg["compute_dtype"])[0]
    assert np.isfinite(loss)
    events = mx.trace.counter_events(["attn:lowering"], since_ns=mark)
    assert events, "the step traced no attention op"
    for e in events:
        assert e["args"] == {"kernel": 1, "plain": 0, "pair": "library",
                             "mask_form": "library"}, e
        assert e["id"] == "bfloat16[4, 4096, 16, 128]", e
