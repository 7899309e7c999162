"""Granite-4.0-H-Micro at its published widths on the chip (as the
``granite-4.0-h-micro`` configuration is cut: one period of ten layers,
an eighth of the vocabulary under ONE weight for embedding and head),
against the plain reference ``benchmark/reference/granite-4.0-h-micro.py``
(a token-by-token scan) computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_granite_tpu.py -s -q

The first test has phases that each release what they held (the chip
holds one module of this size at a time): on ``GRANITE_PARITY_SEEDS``
seeds (4, from ``GRANITE_PARITY_FIRST_SEED`` on; weights and batch both
from the seed) the reference's loss and
first Adam step at one sequence of 4096, the same reference with its
weights rounded to float8 (which at least one of the configuration's
update limits has to refuse, on every seed) and the configuration's own
Adam step in bfloat16 at the default matmul precision, as the cell's
reference check runs it, with the ``ssd:lowering``, ``conv:lowering``
and ``attn:lowering`` samples of the bind; and the Adam step in float32
compute against the reference at one sequence of 1024 (the plain
chunks).  The numbers go to ``chiprun_out/granite_parity.json`` after
every phase, before anything is asserted.

The second holds the two lowerings this model brought against their
plain forms at the cell's shapes, with both sides' times in isolation:
the state-space scan's kernel pair at ``(1, 4096, 64, 64)`` over one
group of 128 and the biased convolution's at ``(1, 4096, 4352)``;
``chiprun_out/granite_kernel_parity.json``.
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

# each side rounds its operands and results to 8 bits of mantissa
SCAN_L2_ERR = 0.02
CONV_L2_ERR = 0.01
SEED = int(os.environ.get("GRANITE_PARITY_FIRST_SEED", "6700000067"))
SCAN_TRACK = "bfloat16[1, 4096, 64, 64]/g1n128"
CONV_TRACK = "bfloat16[1, 4096, 4352]/4352+bias"
ATTN_TRACK = "bfloat16[1, 4096, 32, 64]/kv8"


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adam_step(net, params, data, labels, opt_params, compute_dtype, names):
    """One step of the fused train step on the chip.  -> (the loss,
    {name: after - before})."""
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
        loss = float(mod.get_outputs()[0].asnumpy().mean())
        after, _ = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    return loss, delta


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import granite_hybrid_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "granite-4.0-h-micro")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "packed-4k-b1.json")) as f:
        traffic = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq = kw["seq_len"]
    seeds = int(os.environ.get("GRANITE_PARITY_SEEDS", "4"))
    net = granite_hybrid_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))
    sigma = cfg["initializer"]["kwargs"]["sigma"]

    def weights(seed):
        """As the configuration's initializer leaves them: Normal(sigma)
        matrices and taps, gains (and D) one, biases (the convolution's,
        A_log, dt_bias) zero."""
        rng = np.random.default_rng(seed)
        return {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                    np.zeros(s, np.float32) if n.endswith("bias") else
                    sigma * rng.standard_normal(s, dtype=np.float32))
                for n, s in shapes.items()
                if n not in ("data", "softmax_label")}

    def batch_of(seed, config=cfg):
        batches = gen.build(dict(traffic, distinct_batches=1), config, seed,
                            [mx.cpu(0)], None)
        (data,), (labels,) = (list(d.values()) for d in
                              batches.reference_batch(1)[:2])
        return data, labels

    report = {"device": jax.devices()[0].device_kind, "adam_bf16": {},
              "reference_fp8_weights": {}}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "granite_parity.json"), "w") as f:
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

    def coarse_control(p, d, lb, want):
        """The reference with its weights rounded to float8 (e4m3, the
        nearest format under bfloat16; arithmetic stays float32), read as
        the harness reads a step."""
        coarse = {n: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                                .astype(jnp.float32))
                  for n, v in p.items()}
        out = reference(coarse, d, lb)
        return dict(
            loss_of(out["loss"], want),
            adam_update_rel_err={n: _rel(out["updates"][n],
                                         want["updates"][n])
                                 for n in names})

    # A. the configuration's step, bfloat16 at the default precision,
    # beside the reference and its float8 control, a seed at a time
    mx.trace.set_enabled(True)
    for i in range(seeds):
        seed = SEED + i
        params, (data, labels) = weights(seed), batch_of(seed)
        report["params_M"] = sum(v.size for v in params.values()) / 1e6
        want = reference(params, data, labels)
        report["reference_fp8_weights"][str(seed)] = coarse_control(
            params, data, labels, want)
        print("\nGRANITE_PARITY fp8 %d " % seed + json.dumps(
            report["reference_fp8_weights"][str(seed)]), flush=True)
        mark = time.perf_counter_ns()
        with jax.default_matmul_precision("default"):
            loss, delta = _adam_step(net, params, data, labels, adam,
                                     "bfloat16", names)
        report.setdefault("module_step_s", []).append(
            round((time.perf_counter_ns() - mark) / 1e9, 1))
        lowered = {c: [[e["id"], e["args"]] for e in mx.trace.counter_events(
            [c + ":lowering"], since_ns=mark)] for c in ("ssd", "conv",
                                                         "attn")}
        report["adam_bf16"][str(seed)] = dict(
            loss_of(loss, want),
            update_rel_err={n: _rel(delta[n], want["updates"][n])
                            for n in names}, lowering=lowered)
        save()
        print("\nGRANITE_PARITY bf16 %d " % seed + json.dumps(
            report["adam_bf16"][str(seed)]), flush=True)
        del want, delta, params
        gc.collect()

    # B. float32 compute against the reference, one sequence of 1024: the
    # plain chunks and the plain convolution at published widths
    short = dict(kw, seq_len=1024)
    cfg_short = dict(cfg, model=dict(cfg["model"], kwargs=short),
                     input=dict(cfg["input"], seq_len=1024))
    params = weights(SEED)
    d32, l32 = batch_of(SEED, cfg_short)
    want = reference(params, d32, l32, cfg_short)
    loss32, delta32 = _adam_step(granite_hybrid_lm(**short), params, d32,
                                 l32, adam, None, names)
    report["adam_f32_t1024"] = dict(
        loss_of(loss32, want),
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names})
    save()
    print("\nGRANITE_PARITY f32 " + json.dumps(report["adam_f32_t1024"]),
          flush=True)

    for seed, bf16 in report["adam_bf16"].items():
        assert bf16["loss_rel_err"] <= limits["loss_rtol"], seed
        for n in names:
            assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], \
                (seed, n)
        low = bf16["lowering"]
        assert [t for t, _ in low["ssd"]] == [SCAN_TRACK] * 9
        assert [t for t, _ in low["conv"]] == [CONV_TRACK] * 9
        assert [t for t, _ in low["attn"]] == [ATTN_TRACK]
        assert all(a["kernel"] == 1 and a["plain"] == 0
                   for kind in low.values() for _, a in kind)
    # float8 weights are refused by at least one update limit on every
    # seed; the harness's own comparison never runs this control
    for seed, fp8 in report["reference_fp8_weights"].items():
        refused = [n for n in names if fp8["adam_update_rel_err"][n]
                   > limits["update_rtol"][n]]
        assert refused, seed
    f32 = report["adam_f32_t1024"]
    assert f32["loss_rel_err"] <= 1e-4
    assert max(f32["update_rel_err"].values()) <= 0.1, f32


def _ms(fn, *a, n=10):
    import jax
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def test_the_two_new_lowerings_match_their_plain_forms_at_the_cells_shapes():
    """The scan at ``(1, 4096, 64, 64)`` bfloat16 over one group of 128
    and the biased convolution at ``(1, 4096, 4352)`` under ``(4352, 4)``
    taps compile to Mosaic kernels on the chip; outputs and every input
    gradient agree with the plain forms at float32, and both sides'
    times go to the report."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import causal_conv as cc
    from mxnet_tpu.ops import ssd
    rng = np.random.RandomState(67)
    report = {}
    mx.trace.set_enabled(True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "granite_kernel_parity.json"),
                  "w") as f:
            json.dump(report, f, indent=1)

    # -- the scan -------------------------------------------------------------
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (jnp.asarray(rng.standard_normal((1, 4096, 64, 64)), bf16),
            jnp.asarray(0.5 * rng.standard_normal((1, 4096, 1, 128)), bf16),
            jnp.asarray(0.5 * rng.standard_normal((1, 4096, 1, 128)), bf16),
            jnp.asarray(rng.standard_normal((1, 4096, 64)), bf16),
            jnp.asarray(rng.uniform(-1.0, 1.5, 64), f32),
            jnp.asarray(0.5 * rng.standard_normal(64), f32),
            jnp.asarray(rng.standard_normal(64), f32))
    dy = jnp.asarray(rng.standard_normal((1, 4096, 64, 64)), f32)

    def scan_passes(fn, dtype):
        def run(*a):
            a = tuple(v.astype(dtype) for v in a[:4]) + a[4:]
            out, vjp = jax.vjp(fn, *a)
            return (out,) + vjp(dy.astype(out.dtype))
        return jax.jit(run)

    mark = time.perf_counter_ns()
    kernels = scan_passes(ssd.ssd_scan, bf16)
    plain = scan_passes(ssd._plain_scan, bf16)
    exact = scan_passes(ssd._plain_scan, f32)
    text = kernels.lower(*args).compile().as_text()
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text
    event = mx.trace.counter_events(["ssd:lowering"], since_ns=mark)[0]
    assert event["id"] == SCAN_TRACK and event["args"]["kernel"] == 1
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(a, np.float32) for a in exact(*args)]
    forward = jax.jit(lambda *a: ssd.ssd_scan(*a))
    report["ssd_scan"] = {
        "l2_err_of_the_kernels": [_rel(np.asarray(a, np.float32), r)
                                  for a, r in zip(kernels(*args), want)],
        "l2_err_of_the_plain_chunks": [_rel(np.asarray(a, np.float32), r)
                                       for a, r in zip(plain(*args), want)],
        "ms_a_layer": {
            "kernel_forward": _ms(forward, *args),
            "kernel_forward_backward": _ms(kernels, *args),
            "plain_forward_backward": _ms(plain, *args, n=3)}}
    save()
    print("\nGRANITE_KERNEL_PARITY scan " + json.dumps(report["ssd_scan"]),
          flush=True)
    del args, dy, want
    gc.collect()

    # -- the biased convolution -------------------------------------------------
    x = jnp.asarray(rng.standard_normal((1, 4096, 4352)), bf16)
    w = jnp.asarray(0.5 * rng.standard_normal((4352, 4)), f32)
    bias = jnp.asarray(0.5 * rng.standard_normal(4352), f32)
    dy = jnp.asarray(rng.standard_normal((1, 4096, 4352)), f32)

    def conv_passes(fn, dtype):
        def run(x, w, bias):
            out, vjp = jax.vjp(lambda *a: fn(*a, "silu")[0],
                               x.astype(dtype), w, bias)
            return (out,) + vjp(dy.astype(dtype))
        return jax.jit(run)

    def plain_biased(x, w, bias, act):
        return cc._plain_biased(x[:, :, None], w, bias, (x.shape[2],), act)

    mark = time.perf_counter_ns()
    kernels = conv_passes(cc.biased_conv, bf16)
    plain = conv_passes(plain_biased, bf16)
    exact = conv_passes(plain_biased, f32)
    text = kernels.lower(x, w, bias).compile().as_text()
    assert "causal_conv_bias_fwd" in text and "causal_conv_bias_bwd" in text
    event = mx.trace.counter_events(["conv:lowering"], since_ns=mark)[0]
    assert event["id"] == CONV_TRACK and event["args"]["kernel"] == 1
    want = [np.asarray(a, np.float32) for a in exact(x, w, bias)]
    report["biased_conv"] = {
        "l2_err_of_the_kernels": [_rel(np.asarray(a, np.float32), r)
                                  for a, r in zip(kernels(x, w, bias), want)],
        "l2_err_of_the_plain_form": [_rel(np.asarray(a, np.float32), r)
                                     for a, r in zip(plain(x, w, bias), want)],
        "ms_a_layer": {
            "kernel_forward_backward": _ms(kernels, x, w, bias),
            "plain_forward_backward": _ms(plain, x, w, bias)}}
    save()
    print("\nGRANITE_KERNEL_PARITY conv " + json.dumps(report["biased_conv"]),
          flush=True)
    scan, conv = report["ssd_scan"], report["biased_conv"]
    # the kernels are no further from float32 than the plain forms are
    for mine, theirs in zip(scan["l2_err_of_the_kernels"],
                            scan["l2_err_of_the_plain_chunks"]):
        assert mine <= max(SCAN_L2_ERR, 1.5 * theirs), scan
    for mine, theirs in zip(conv["l2_err_of_the_kernels"],
                            conv["l2_err_of_the_plain_form"]):
        assert mine <= max(CONV_L2_ERR, 1.5 * theirs), conv
