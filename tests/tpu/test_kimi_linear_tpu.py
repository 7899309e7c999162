"""Kimi-Linear-48B-A3B at its published widths on the chip (five layers,
8 of 256 experts, as the ``kimi-linear-48b-a3b`` configuration is cut),
against the plain reference ``benchmark/reference/kimi-linear-48b-a3b.py``
computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_kimi_linear_tpu.py -s -q

One test, phases that each release what they held (the chip holds one
0.6 G-parameter module at a time): the reference's loss, gradients and
first Adam step at one sequence of 4096, and the same with its weights
rounded to float8 (what the configuration's limits have to refuse); the
configuration's own Adam step in bfloat16 at the default matmul
precision, as the cell's reference check runs it, with the selection
bias's first move; and the Adam step in float32 compute against the
reference at one sequence of 1024 (float32 activations of 4096 tokens do
not fit beside the state).  The numbers go to
``chiprun_out/kimi_parity.json`` after every phase, before anything is
asserted.

A second test (ISSUE 33) holds ``gated_delta_rule``'s two lowerings
against each other at the published shape ``(1, 4096, 32, 128)``: the
Pallas kernels at the default matmul precision may be no further from
the plain chunks at HIGHEST than the plain chunks at the default
precision are, in the output and in all five gradients, in four decay
bands; the numbers and both lowerings' times go to
``chiprun_out/kda_kernel_parity.json``.  The third holds the mixers'
output stage, ``gated_rms_norm`` with a sigmoid gate: its kernel pair
alone at ``(1, 4096, 4096)`` against the plain form and against its
bytes, and the relayouts of float32 ``[.., 32, 128]`` arrays the cell's
step compiled for the chip still holds
(``chiprun_out/kimi_norm_parity.json``).
"""
import gc
import json
import os
import re
import sys

import numpy as np

import _gated_norm
from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adam_step(net, params, tokens, labels, opt_params, compute_dtype,
               names):
    """One step of the fused train step on the chip.  -> (mean CE,
    counts per block, {name: after - before}, {aux: value})."""
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
        after, aux = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        aux = {n: v.asnumpy() for n, v in aux.items()}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    return float(outs[0].mean()), outs[-1][:, :-1], delta, aux


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import kimi_linear_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "kimi-linear-48b-a3b")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq, vocab = kw["seq_len"], kw["vocab_size"]
    net = kimi_linear_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))
    rng = np.random.RandomState(31)
    params = {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                  np.zeros(s, np.float32) if n.endswith("bias") else
                  (0.02 * rng.standard_normal(s)).astype(np.float32))
              for n, s in shapes.items()
              if n not in ("data", "softmax_label")}
    stream = gen.markov_stream(np.random.RandomState(3100000031 % 2 ** 32),
                               seq + 1, vocab, 0.85, 1.2, 600.0)
    tokens, labels = stream[:-1].reshape(1, seq), stream[1:].reshape(1, seq)
    blocks = ["l%d_moe_dispatch" % l for l in range(2, kw["num_layers"] + 1)]
    report = {"device": jax.devices()[0].device_kind,
              "params_M": sum(v.size for v in params.values()) / 1e6}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "kimi_parity.json"), "w") as f:
            json.dump(report, f, indent=1)
        print("\nKIMI_PARITY " + json.dumps(report), flush=True)

    def reference(p, tk, lb, config=cfg):
        out = ref.reference_step(config, p, {"data": tk},
                                 {"softmax_label": lb}, adam, names)
        gc.collect()
        return out

    # A. the reference on this chip, and with float8 weights (e4m3, the
    # nearest format under bfloat16; arithmetic stays float32)
    want = reference(params, tokens, labels)
    coarse = {n: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                            .astype(jnp.float32))
              for n, v in params.items()}
    out = reference(coarse, tokens, labels)
    report["reference_fp8_weights"] = {
        "loss": out["loss"], "reference_loss": want["loss"],
        "loss_rel_err": abs(out["loss"] - want["loss"]) / want["loss"],
        "adam_update_rel_err": {n: _rel(out["updates"][n],
                                        want["updates"][n])
                                for n in names}}
    del out, coarse
    gc.collect()
    save()

    # B. the configuration's step, bfloat16 at the default precision
    with jax.default_matmul_precision("default"):
        loss, counts, delta, aux = _adam_step(
            net, params, tokens, labels, adam, "bfloat16", names)
    moves = {b: np.asarray(want["bias_moves"][b]) for b in blocks}
    report["adam_bf16"] = {
        "loss": loss, "reference_loss": want["loss"],
        "loss_rel_err": abs(loss - want["loss"]) / want["loss"],
        "update_rel_err": {n: _rel(delta[n], want["updates"][n])
                           for n in names},
        "held_rows": [float(c[:kw["experts_held"]].sum()) for c in counts],
        "bias_signs_agreed": {b: float(np.mean(
            np.sign(aux[b + "_select_bias"]) == np.sign(moves[b])))
            for b in blocks}}
    save()
    del want
    gc.collect()

    # C. float32 compute against the reference, one sequence of 1024
    short = dict(kw, seq_len=1024)
    cfg_short = dict(cfg, model=dict(cfg["model"], kwargs=short))
    tk, lb = tokens[:, :1024], labels[:, :1024]
    want = reference(params, tk, lb, cfg_short)
    loss32, counts32, delta32, aux32 = _adam_step(
        kimi_linear_lm(**short), params, tk, lb, adam, None, names)
    report["adam_f32_t1024"] = {
        "loss": loss32, "reference_loss": want["loss"],
        "loss_rel_err": abs(loss32 - want["loss"]) / want["loss"],
        "update_rel_err": {n: _rel(delta32[n], want["updates"][n])
                           for n in names},
        "bias_moves_equal": {b: bool(np.array_equal(
            aux32[b + "_select_bias"],
            np.asarray(want["bias_moves"][b], np.float32)))
            for b in blocks}}
    save()

    fp8 = report["reference_fp8_weights"]
    bf16 = report["adam_bf16"]
    assert bf16["loss_rel_err"] <= limits["loss_rtol"]
    for n in names:
        assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], n
    # float8 weights are refused by at least one limit
    assert fp8["loss_rel_err"] > limits["loss_rtol"] or any(
        fp8["adam_update_rel_err"][n] > limits["update_rtol"][n]
        for n in names)
    f32 = report["adam_f32_t1024"]
    assert f32["loss_rel_err"] <= 1e-4
    assert max(f32["update_rel_err"].values()) <= 0.1, f32


BANDS = {"near-1": (-0.01, -1e-4), "mixed": (-3.0, -0.01),
         "near-0": (-40.0, -5.0), "wide": (-40.0, -1e-4)}


def test_kda_kernels_against_the_plain_chunks_at_the_published_shape():
    import time
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import linear_attention as kda
    b, t, h, d = 1, 4096, 32, 128
    scale = d ** -0.5
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")

    @jax.jit
    def kernel(q, k, v, g, beta, w):
        o, states = kda._kernel_rule(q, k, v, g, beta, scale)
        return (o,) + kda._kernel_rule_vjp(q, k, v, g, beta, states,
                                           w.astype(o.dtype), scale)

    @jax.jit
    def plain(q, k, v, g, beta, w):
        o, vjp = jax.vjp(lambda *a: kda.gated_delta_rule(*a, scale),
                         q, k, v, g, beta)
        return (o,) + vjp(w.astype(o.dtype))

    forward = {"kernel": jax.jit(
        lambda *a: kda._kernel_rule(*a, scale)[0]),
        "plain": jax.jit(lambda *a: kda.gated_delta_rule(*a, scale))}
    # what the lowering keeps of a layer's forward pass beside the op's
    # inputs: the entry states and the chunks' A, Bs, T, float32
    kept = jax.eval_shape(
        lambda *a: kda._kernel_rule(*a, scale)[1],
        *(jax.ShapeDtypeStruct((b, t, h, d), jnp.float32),) * 4,
        jax.ShapeDtypeStruct((b, t, h), jnp.float32))
    kept_bytes = [x.size * x.dtype.itemsize for x in kept]
    assert kept_bytes == [b * h * (t // 64) * d * d * 4,
                          b * h * (t // 64) * 3 * 64 * 64 * 4] \
        == [134217728, 100663296]
    report = {"device": jax.devices()[0].device_kind, "shape": [b, t, h, d],
              "kept_bytes_a_layer": kept_bytes, "bands": {}}

    def rels(got, want):
        return {n: _rel(x, y) for n, x, y in zip(names, got, want)}

    for band, (lo, hi) in BANDS.items():
        rng = np.random.RandomState(33)
        q, k = (rng.standard_normal((b, t, h, d)).astype(np.float32)
                for _ in range(2))
        q, k = (jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True))
                for x in (q, k))
        v = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
        g = jnp.asarray(rng.uniform(lo, hi, (b, t, h, d)), jnp.float32)
        beta = jnp.asarray(rng.uniform(0.05, 0.99, (b, t, h)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
        args = (q, k, v, g, beta, w)
        with jax.default_matmul_precision("highest"):
            want = plain(*args)
            exact = rels(kernel(*args), want)
        with jax.default_matmul_precision("default"):
            got = kernel(*args)
            report["bands"][band] = {
                "kernel_vs_plain_highest": exact,
                "kernel_default_vs_plain_highest": rels(got, want),
                "plain_default_vs_plain_highest": rels(plain(*args), want),
                "finite": bool(all(np.isfinite(np.asarray(x, np.float32))
                                   .all() for x in got))}
        del want, got

    # times at the configuration's dtypes and precision, a layer
    v16 = v.astype(jnp.bfloat16)
    args = (q, k, v16, g, beta, w)

    def ms(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 5 * 1e3

    with jax.default_matmul_precision("default"):
        report["ms_a_layer"] = {
            "kernel_forward": ms(forward["kernel"], *args[:5]),
            "plain_forward": ms(forward["plain"], *args[:5]),
            "kernel_forward_backward": ms(kernel, *args),
            "plain_forward_backward": ms(plain, *args)}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kda_kernel_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("\nKDA_KERNEL_PARITY " + json.dumps(report), flush=True)

    for band, r in report["bands"].items():
        assert r["finite"], band
        for n in names:
            assert r["kernel_vs_plain_highest"][n] <= 2e-4, (band, n, r)
            # no further off than the plain chunks at this precision are
            assert r["kernel_default_vs_plain_highest"][n] <= \
                1.05 * r["plain_default_vs_plain_highest"][n] + 1e-5, \
                (band, n, r)


def test_gated_norm_kernels_at_the_cells_shape_and_the_steps_relayouts():
    """``gated_rms_norm`` with a sigmoid gate at ``(1, 4096, 4096)``
    bfloat16, a head 128 lanes: the kernel pair compiled by Mosaic is no
    further from the plain form in float32 than the plain form in
    bfloat16 is, output and all three cotangents; forward under 0.2 ms
    and backward under 0.35, each under the plain form's.  Then the
    cell's step compiled for the chip: the four stages are the pair, and
    the float32 ``[.., 32, 128]`` arrays it still copies are the delta
    rule's own, three a layer behind ``kda_chunk_bwd`` (four a layer
    until ISSUE 68: the stage's went)."""
    import jax
    report = {"device": jax.devices()[0].device_kind,
              "pair": _gated_norm.pair_against_the_plain_form("sigmoid",
                                                              1e-5)}
    print("\nNORM_KERNEL_PARITY " + json.dumps(report["pair"]), flush=True)
    text = _gated_norm.compiled_step_text("kimi-linear-48b-a3b")
    report["step"] = {
        "f32_head_layout_copies": len(_gated_norm.head_layout_copies(text)),
        "bf16_head_layout_copies": len(
            _gated_norm.head_layout_copies(text, "bf16")),
        "stages": [len(set(re.findall(r"%%gated_norm_%s\.\d+ = " % which,
                                      text)))
                   for which in ("fwd", "bwd")]}
    print("NORM_STEP " + json.dumps(report["step"]), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kimi_norm_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    _gated_norm.check_pair(report["pair"])
    assert report["step"]["stages"] == [4, 4]
    assert report["step"]["f32_head_layout_copies"] <= 12
