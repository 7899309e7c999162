"""Qwen3-Next-80B-A3B at its published widths on the chip (as the
``qwen3-next-80b-a3b`` configuration is cut: its four layers, the held
share of the 512 experts, an eighth of the vocabulary), against the plain
reference ``benchmark/reference/qwen3-next-80b-a3b.py`` computed on the
same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_qwen3_next_tpu.py -s -q

The first test has phases that each release what they held (the chip
holds one module of this size at a time): the reference's loss,
gradients and first Adam step at one sequence of 4096, and the same with
its weights rounded to float8 (what the configuration's limits have to
refuse); the configuration's own Adam step in bfloat16 at the default
matmul precision, as the cell's reference check runs it, on
``QWEN3_NEXT_PARITY_SEEDS`` seeds (8; weights and batch both from the
seed), with the ``gdn:lowering``, ``attn:lowering`` and
``kda:kernel_trace`` samples of the bind; and the Adam step in float32
compute against the reference at one sequence of 1024.  The numbers go to
``chiprun_out/qwen3_next_parity.json`` after every phase, before
anything is asserted.

The second holds ``gated_delta_net``'s two lowerings against each other
at the cell's shape, ``(1, 4096, 32, 128)`` values under 16 key heads
with a head's decay in four bands: forward and all seven gradients, with
both lowerings' times a layer beside the 7.98 ms before ISSUE 51, the
two kernels alone in the head form and in the lane form fed the same
decay on every lane, and the head form without its score products (the
ablation ISSUE 51's prediction was rewritten from), in
``chiprun_out/gdn_kernel_parity.json`` before anything is asserted.  The
third holds ``causal_attention``'s TPU
kernel against its plain blocks at ``(1, 4096, 16, 256)`` over 2
key/value heads.  The fourth holds ``causal_conv``'s kernel pair
``causal_conv_fwd`` / ``causal_conv_bwd`` against the plain form at the
two cells' shapes, a key head's q, k, v lanes out of ``(1, 4096, 16,
768)`` and Kimi's contiguous ``(1, 4096, 4096)``, with both lowerings'
times (``chiprun_out/conv_kernel_parity.json``).  The fifth holds the
mixers' output stage, ``gated_rms_norm`` with SiLU: its kernel pair
``gated_norm_fwd`` / ``gated_norm_bwd`` alone at ``(1, 4096, 4096)``
against the plain form and against its bytes, and the cell's step
compiled for the chip, which holds no relayout of a float32 ``[.., 32,
128]`` array (``chiprun_out/qwen3_next_norm_parity.json``).
"""
import gc
import json
import os
import re
import sys
import time

import numpy as np

import _gated_norm
from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# as tests/tpu/test_olmoe_tpu.py: each side rounds its probabilities and
# results to 8 bits of mantissa
ATTN_MAX_ERR_SHARE = 0.02
ATTN_L2_ERR = 0.01
SEED = 5000000050
GDN_TRACK = "bfloat16[1, 4096, 32, 128]/k16"
ATTN_TRACK = "bfloat16[1, 4096, 16, 256]/kv2"
CONV_TRACK = "bfloat16[1, 4096, 12288]/8192"


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adam_step(net, params, data, labels, opt_params, compute_dtype, names):
    """One step of the fused train step on the chip.  -> (the loss,
    choices per expert a block, {name: after - before})."""
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
        batch = mx.io.DataBatch(
            data=[mx.nd.array(data, dtype=np.int32)],
            label=[mx.nd.array(labels, dtype=np.int32)], pad=0)
        mod.forward_backward(batch)
        mod.update()
        assert mod._exec_group.execs == []
        outs = [o.asnumpy() for o in mod.get_outputs()]
        load = mod._fused.head("moe_load")[0]
        after, _ = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    return float(outs[0].mean()), outs[load][:, :-1], delta


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import qwen3_next_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "qwen3-next-80b-a3b")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "packed-4k-b1.json")) as f:
        traffic = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq = kw["seq_len"]
    seeds = int(os.environ.get("QWEN3_NEXT_PARITY_SEEDS", "8"))
    net = qwen3_next_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))

    def weights(seed):
        rng = np.random.default_rng(seed)
        return {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                    np.zeros(s, np.float32) if n.endswith("bias") else
                    0.02 * rng.standard_normal(s, dtype=np.float32))
                for n, s in shapes.items()
                if n not in ("data", "softmax_label")}

    def batch_of(seed, config=cfg):
        batches = gen.build(dict(traffic, distinct_batches=1), config, seed,
                            [mx.cpu(0)], None)
        (data,), (labels,) = (list(d.values()) for d in
                              batches.reference_batch(1)[:2])
        return data, labels

    params = weights(SEED)
    data, labels = batch_of(SEED)
    report = {"device": jax.devices()[0].device_kind,
              "params_M": sum(v.size for v in params.values()) / 1e6,
              "experts_held": kw["experts_held"], "adam_bf16": {}}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "qwen3_next_parity.json"),
                  "w") as f:
            json.dump(report, f, indent=1)

    def reference(p, d, lb, config=cfg):
        t0 = time.perf_counter()
        out = ref.reference_step(config, p, {"data": d},
                                 {"softmax_label": lb}, adam, names)
        gc.collect()
        report.setdefault("reference_s", []).append(
            round(time.perf_counter() - t0, 1))
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
    print("\nQWEN3_NEXT_PARITY fp8 " + json.dumps(
        report["reference_fp8_weights"]), flush=True)

    # B. the configuration's step, bfloat16 at the default precision,
    # weights and batch from each seed
    mx.trace.set_enabled(True)
    for i in range(seeds):
        seed = SEED + i
        if i:
            params, (data, labels) = weights(seed), batch_of(seed)
            want = reference(params, data, labels)
        mark = time.perf_counter_ns()
        with jax.default_matmul_precision("default"):
            loss, counts, delta = _adam_step(
                net, params, data, labels, adam, "bfloat16", names)
        report.setdefault("module_step_s", []).append(
            round((time.perf_counter_ns() - mark) / 1e9, 1))
        counters = {c: [[e["id"], e["args"]] for e in
                        mx.trace.counter_events([c], since_ns=mark)]
                    for c in ("gdn:lowering", "attn:lowering",
                              "kda:kernel_trace", "conv:lowering")}
        report["adam_bf16"][str(seed)] = dict(
            loss_of(loss, want),
            update_rel_err={n: _rel(delta[n], want["updates"][n])
                            for n in names},
            held_rows=[float(c[:kw["experts_held"]].sum()) for c in counts],
            **counters)
        save()
        print("\nQWEN3_NEXT_PARITY bf16 %d " % seed + json.dumps(
            report["adam_bf16"][str(seed)]), flush=True)
        del want, delta
        gc.collect()

    # C. float32 compute against the reference, one sequence of 1024
    short = dict(kw, seq_len=1024)
    cfg_short = dict(cfg, model=dict(cfg["model"], kwargs=short),
                     input=dict(cfg["input"], seq_len=1024))
    params = weights(SEED)
    d32, l32 = batch_of(SEED, cfg_short)
    want = reference(params, d32, l32, cfg_short)
    loss32, _, delta32 = _adam_step(
        qwen3_next_lm(**short), params, d32, l32, adam, None, names)
    report["adam_f32_t1024"] = dict(
        loss_of(loss32, want),
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names})
    save()
    print("\nQWEN3_NEXT_PARITY f32 " + json.dumps(report["adam_f32_t1024"]),
          flush=True)

    fp8 = report["reference_fp8_weights"]
    traces = []
    for seed, bf16 in report["adam_bf16"].items():
        assert bf16["loss_rel_err"] <= limits["loss_rtol"], seed
        for n in names:
            assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], \
                (seed, n)
        # one op a layer, every one the kernels: three of the rule with a
        # head's decay, then attention under the causal mask
        assert [t for t, _ in bf16["gdn:lowering"]] == [GDN_TRACK] * 3
        assert all(a["kernel"] == 1 and a["plain"] == 0
                   and (a["key_heads"], a["value_heads"]) == (16, 32)
                   for _, a in bf16["gdn:lowering"])
        assert bf16["attn:lowering"] == [[ATTN_TRACK, {
            "kernel": 1, "plain": 0, "pair": "library",
            "mask_form": "library"}]]
        # the three mixers' convolutions read the projection where it lies
        assert bf16["conv:lowering"] == [[CONV_TRACK, {
            "kernel": 1, "plain": 0}]] * 3
        traces += bf16["kda:kernel_trace"]
    # the process traced each kernel once for all the steps' GDN layers,
    # in the form a head's decay admits: no halving level
    assert sorted((a["fwd"], a["bwd"], a["decay"], a["level_rows"])
                  for _, a in traces) \
        == [(0, 1, "head", 0), (1, 0, "head", 0)]
    # float8 weights are refused by at least one limit
    assert fp8["loss_rel_err"] > limits["loss_rtol"] or any(
        fp8["adam_update_rel_err"][n] > limits["update_rtol"][n]
        for n in names)
    f32 = report["adam_f32_t1024"]
    assert f32["loss_rel_err"] <= 1e-4
    assert max(f32["update_rel_err"].values()) <= 0.1, f32


# the log-decay a token of each band, as tests/tpu/test_kimi_linear_tpu.py
# has them for a lane's decay; a head's projection reaches exp(-20)
GDN_BANDS = {"near-1": (-0.01, -1e-4), "mixed": (-3.0, -0.01),
             "near-0": (-20.0, -5.0), "wide": (-20.0, -1e-4)}
# gated_delta_net's kernel lowering, forward + backward a layer at this
# shape, with the head's decay spread over the lanes for the six halving
# levels (my chip run, PR 50: the commit before ISSUE 51)
GDN_MS_A_LAYER_BEFORE_PR51 = 7.98


def _ms(fn, *a, reps=5):
    import jax
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def test_gdn_kernels_match_the_plain_chunks_at_the_cells_shape(monkeypatch):
    """``gated_delta_net`` at ``(1, 4096, 32, 128)`` bfloat16 values under
    16 key heads with a head's decay, in four decay bands: the kernel
    lowering (compiled by Mosaic, the head form: ``kda:kernel_trace``
    says so) against the plain chunks, forward and all seven gradients,
    at HIGHEST precision; at the default precision the kernels are no
    further from that than the plain chunks are.  Then the times: the op
    a layer in both lowerings beside the 7.98 ms before ISSUE 51, the
    two kernels alone in the head form and in the lane form fed the same
    decay on every lane (what ran until then), and the ablation: the
    head form without its score products (a stand-in that reads ``A``
    and ``Bs`` off k and q by the VPU: not the rule), which says what
    the two products under the ``(C, C)`` decay cost of a call."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import linear_attention as kda
    b, t, hk, hv, d = 1, 4096, 16, 32, 128
    names = ("o", "dq", "dk", "dv", "ddecay", "dbeta", "da_log", "ddt_bias")

    def inputs(lo, hi):
        rng = np.random.RandomState(51)
        q, k = (jnp.asarray(rng.standard_normal((b, t, hk, d)), jnp.bfloat16)
                for _ in range(2))
        v = jnp.asarray(rng.standard_normal((b, t, hv, d)), jnp.bfloat16)
        g = rng.uniform(lo, hi, (b, t, hv))
        a_log = rng.uniform(-1, 1, (hv,))
        dt_bias = rng.uniform(-1, 1, (hv,))
        # softplus(decay + dt_bias) = -g / exp(a_log)
        decay = np.log(np.expm1(-g / np.exp(a_log))) - dt_bias
        beta = rng.uniform(-3, 3, (b, t, hv))
        w = jnp.asarray(rng.standard_normal((b, t, hv, d)), jnp.bfloat16)
        return (q, k, v, jnp.asarray(decay, jnp.float32),
                jnp.asarray(beta, jnp.bfloat16),
                jnp.asarray(a_log, jnp.float32),
                jnp.asarray(dt_bias, jnp.float32)), w

    def both_passes(fn):
        def run(w, *a):
            out, vjp = jax.vjp(fn, *a)
            return (out,) + vjp(w)
        return jax.jit(run)

    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    kernel = both_passes(kda.gated_delta_net)
    plain = both_passes(kda._plain_attention)
    args, w = inputs(*GDN_BANDS["mixed"])
    assert kda._kernel_takes(args[0], args[2])
    text = kernel.lower(w, *args).compile().as_text()
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    assert "kda_chunk" not in plain.lower(w, *args).compile().as_text()
    traces = [e["args"] for e in mx.trace.counter_events(
        ["kda:kernel_trace"], since_ns=mark)]

    def rels(got, want):
        return {n: _rel(x, y) for n, x, y in zip(names, got, want)}

    report = {"shape": [b, t, hv, d], "key_heads": hk,
              "kernel_traces": traces, "bands": {}}
    for band, (lo, hi) in GDN_BANDS.items():
        args, w = inputs(lo, hi)
        with jax.default_matmul_precision("highest"):
            want = plain(w, *args)
            exact = rels(kernel(w, *args), want)
        with jax.default_matmul_precision("default"):
            got = kernel(w, *args)
            report["bands"][band] = {
                "kernel_vs_plain_highest": exact,
                "kernel_default_vs_plain_highest": rels(got, want),
                "plain_default_vs_plain_highest": rels(plain(w, *args),
                                                       want),
                "finite": bool(all(np.isfinite(np.asarray(x, np.float32))
                                   .all() for x in got))}
        del want, got

    # the times, at the configuration's dtypes and precision
    args, w = inputs(*GDN_BANDS["mixed"])
    scale = d ** -0.5
    qn, kn, g, beta = jax.jit(kda._normalized_and_gated)(
        *args[:2], *args[3:])
    head = kda._kernel_layout(qn, kn, args[2], g, beta)
    lane = kda._kernel_layout(
        qn, kn, args[2], jnp.broadcast_to(g[..., None], qn.shape), beta)
    do = w.reshape(b, t, -1)

    def kernels_alone(lay):
        """ms a call of the two kernels, traced anew with the module's
        statements as they stand (a stand-in's too)."""
        fwd = jax.jit(lambda *a: kda._kda_fwd.__wrapped__(
            *a, scale=scale, interpret=False))
        bwd = jax.jit(lambda *a: kda._kda_bwd.__wrapped__(
            *a, scale=scale, interpret=False))
        _, states, kept = fwd(*lay)
        return {"forward": _ms(fwd, *lay),
                "backward": _ms(bwd, *lay, states, kept, do)}

    def scores_off_the_vpu(q, k, G, scale, masks):
        row, col, _ = masks
        D = kda._head_decays(G, row, col)
        s = jnp.sum(k + q, axis=2, keepdims=True)
        return jnp.where(row > col, s * D, 0.0), s * D * scale

    def cotangents_off_the_vpu(q, k, G, A, Bs, dA, dBs, scale, row, col):
        D = kda._head_decays(G, row, col)
        P = jnp.where(row > col, dA * A + dBs * Bs, 0.0)
        s = jnp.sum((dA + dBs) * D, axis=2, keepdims=True)
        dG = jnp.sum(P, axis=2, keepdims=True) - kda._as_col(
            jnp.sum(P, axis=1, keepdims=True), row, col)
        return s * k, s * (k + q), dG

    with jax.default_matmul_precision("default"):
        report["ms_a_layer"] = {
            "kernel_forward_backward": _ms(kernel, w, *args),
            "kernel_forward_backward_before_pr51": GDN_MS_A_LAYER_BEFORE_PR51,
            "plain_forward_backward": _ms(plain, w, *args)}
        report["ms_a_call"] = {"head_form": kernels_alone(head),
                               "lane_form_same_decay": kernels_alone(lane)}
        monkeypatch.setattr(kda, "_head_scores", scores_off_the_vpu)
        monkeypatch.setattr(kda, "_head_cotangents", cotangents_off_the_vpu)
        report["ms_a_call"]["head_form_without_score_products"] = \
            kernels_alone(head)
        monkeypatch.undo()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "gdn_kernel_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("\nGDN_KERNEL_PARITY " + json.dumps(report), flush=True)
    # the head form, where this test is the process's first to trace the
    # kernels at this shape (a jitted kernel function is traced once)
    assert all((a["decay"], a["level_rows"], a["vpu_levels"])
               == ("head", 0, 0) for a in traces), traces
    for band, r in report["bands"].items():
        assert r["finite"], band
        for n in names:
            # outputs and gradients are rounded to bfloat16 on both sides
            assert r["kernel_vs_plain_highest"][n] <= 1e-2, (band, n, r)
            assert r["kernel_default_vs_plain_highest"][n] <= \
                1.05 * r["plain_default_vs_plain_highest"][n] + 1e-2, \
                (band, n, r)


def test_attention_kernel_matches_plain_blocks_at_256_over_two():
    """``causal_attention`` at the cell's ``(1, 4096, 16, 256)`` bfloat16
    q over 2 key/value heads (groups of 8) compiles to the Mosaic
    kernels on the chip; output and all three input gradients agree with
    the plain blocks'."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import transformer as tf_ops
    scale = 256 ** -0.5
    rng = np.random.RandomState(50)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 4096, h, 256)),
                           jnp.bfloat16) for h in (16, 2, 2))
    w = jnp.asarray(rng.standard_normal((1, 4096, 16, 256)), jnp.float32)

    def both_passes(attend, *mask):
        def run(q, k, v):
            out, vjp = jax.vjp(lambda *a: attend(*a, scale, *mask), q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(run)

    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    kernel = both_passes(tf_ops.causal_attention)
    plain = both_passes(tf_ops._plain_attention)
    text = kernel.lower(q, k, v).compile().as_text()
    assert "tpu_custom_call" in text and "splash_mha" in text
    assert "tpu_custom_call" not in plain.lower(q, k, v).compile().as_text()
    event = mx.trace.counter_events(["attn:lowering"], since_ns=mark)[-1]
    assert event["args"] == {"kernel": 1, "plain": 0, "pair": "library",
                             "mask_form": "library"}
    assert event["id"] == ATTN_TRACK
    got = [np.asarray(x, np.float32) for x in kernel(q, k, v)]
    want = [np.asarray(x, np.float32) for x in plain(q, k, v)]
    report = {"max_err_share": [], "l2_err": []}
    for g, r in zip(got, want):
        report["max_err_share"].append(
            float(np.abs(g - r).max() / np.abs(r).max()))
        report["l2_err"].append(_rel(g, r))

    report["ms_a_layer"] = {
        "kernel_forward_backward": _ms(kernel, q, k, v, reps=10),
        "plain_forward_backward": _ms(plain, q, k, v, reps=10)}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "qwen3_next_attn_parity.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    print("\nQWEN3_NEXT_ATTN_PARITY " + json.dumps(report), flush=True)
    assert max(report["max_err_share"]) <= ATTN_MAX_ERR_SHARE, report
    assert max(report["l2_err"]) <= ATTN_L2_ERR, report


def test_conv_kernels_match_the_plain_form_at_the_cells_shapes():
    """``causal_conv`` with SiLU, the kernel pair compiled by Mosaic
    against the plain ``causal_conv1d`` + SiLU computed in float32 from
    the same bfloat16 inputs, forward and both cotangents, at the
    Qwen3-Next mixer's shape (q, k, v lanes of 16 key heads of 768, the
    z lanes left where they lie) and at Kimi's (4096 contiguous lanes);
    the kernels are no further from that than the plain form in
    bfloat16 is.  Then both lowerings' times, forward and forward +
    backward."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import causal_conv as cc
    report = {"device": jax.devices()[0].device_kind}

    def sides(shape, parts):
        rng = np.random.RandomState(53)
        g = shape[2] if len(shape) == 4 else 1
        c = g * sum(parts)
        x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        w = jnp.asarray(0.5 * rng.standard_normal((c, 4)), jnp.bfloat16)
        x4 = x.reshape(shape[:2] + (g, -1))
        cts = tuple(jnp.asarray(rng.standard_normal(shape[:2] + (n,)),
                                jnp.bfloat16)
                    for n in (c, x4.size // (shape[0] * shape[1]) - c))

        def both(fn):
            def run(x, w, cts):
                out, vjp = jax.vjp(fn, x, w)
                return out + vjp(cts)
            return jax.jit(run), jax.jit(fn)

        kernel = both(lambda x, w: cc._two_lowerings(x, w, parts, False))
        plain = both(lambda x, w: cc._plain(x, w, parts, "silu"))
        f32 = jnp.float32
        want = plain[0](x4.astype(f32), w.astype(f32),
                        tuple(c.astype(f32) for c in cts))
        out = {}
        for name, (step, fwd) in (("kernel", kernel), ("plain", plain)):
            got = step(x4, w, cts)
            out[name] = {
                "rel_err": {n: _rel(a, b) for n, a, b in
                            zip(("y", "rest", "dx", "dw"), got, want)
                            if a.size},
                "fwd_ms": _ms(fwd, x4, w),
                "fwd_bwd_ms": _ms(step, x4, w, cts)}
        text = kernel[0].lower(x4, w, cts).compile().as_text()
        out["kernel"]["custom_calls"] = [
            n for n in ("causal_conv_fwd", "causal_conv_bwd") if n in text]
        return out

    for name, shape, parts in (
            ("qwen3_next", (1, 4096, 16, 768), (128, 128, 256)),
            ("kimi", (1, 4096, 4096), (4096,))):
        report[name] = sides(shape, parts)
        print("\nCONV_KERNEL_PARITY %s " % name + json.dumps(report[name]),
              flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "conv_kernel_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    for name in ("qwen3_next", "kimi"):
        kernel, plain = report[name]["kernel"], report[name]["plain"]
        assert kernel["custom_calls"] == ["causal_conv_fwd",
                                          "causal_conv_bwd"]
        for n, err in kernel["rel_err"].items():
            assert err <= max(plain["rel_err"][n], 4e-3), (name, n)


def test_gated_norm_kernels_at_the_cells_shape_and_the_steps_relayouts():
    """``gated_rms_norm`` with SiLU at ``(1, 4096, 4096)`` bfloat16, a
    head 128 lanes: the kernel pair compiled by Mosaic is no further from
    the plain form in float32 than the plain form in bfloat16 is, output
    and all three cotangents; forward under 0.2 ms and backward under
    0.35 (their bytes take 0.12 and 0.20), each under the plain form's.
    Then the cell's step compiled for the chip: the three stages are the
    pair, and between ``kda_chunk_fwd`` and ``o_proj`` no float32 ``[..,
    32, 128]`` array is copied or reshaped (three a step until ISSUE 68);
    what is left in bfloat16 is the rule's own, v through its barrier."""
    import jax
    report = {"device": jax.devices()[0].device_kind,
              "pair": _gated_norm.pair_against_the_plain_form("silu", 1e-6)}
    print("\nNORM_KERNEL_PARITY " + json.dumps(report["pair"]), flush=True)
    text = _gated_norm.compiled_step_text("qwen3-next-80b-a3b")
    report["step"] = {
        "f32_head_layout_copies": _gated_norm.head_layout_copies(text),
        "bf16_head_layout_copies": len(
            _gated_norm.head_layout_copies(text, "bf16")),
        "stages": [len(set(re.findall(r"%%gated_norm_%s\.\d+ = " % which,
                                      text)))
                   for which in ("fwd", "bwd")]}
    print("NORM_STEP " + json.dumps(report["step"]), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "qwen3_next_norm_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    _gated_norm.check_pair(report["pair"])
    assert report["step"]["stages"] == [3, 3]
    assert not report["step"]["f32_head_layout_copies"]
    assert report["step"]["bf16_head_layout_copies"] <= 3
