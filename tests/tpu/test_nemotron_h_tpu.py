"""NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths on the chip (as
the ``nemotron-3-nano-30b-a3b`` configuration is cut: published layers 0-8,
``MEMEM*EME``, 8 of 128 experts held, an eighth of the vocabulary), against
the plain reference ``benchmark/reference/nemotron-3-nano-30b-a3b.py`` (a
token-by-token scan with ``B`` and ``C`` indexed by group) computed on the
same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_nemotron_h_tpu.py -s -q

The first test has phases that each release what they held (the chip
holds one module of this size at a time): on ``NEMOTRON_PARITY_SEEDS``
seeds (3, from ``NEMOTRON_PARITY_FIRST_SEED`` on; weights and batch both
from the seed) the reference's loss and first Adam step at one sequence
of 4096, the same reference with its weights rounded to float8 (which at
least one of the configuration's update limits has to refuse, on every
seed) and the configuration's own Adam step in bfloat16 at the default
matmul precision, as the cell's reference check runs it, with the
``ssd:lowering``, ``conv:lowering``, ``attn:lowering`` and
``moe:gmm_lowering`` samples of the bind; and the Adam step in float32
compute against the reference at one sequence of 1024 (the plain chunks,
``ragged_dot``).  The numbers go to ``chiprun_out/nemotron_parity.json``
after every phase, before anything is asserted.

The second holds the two lowerings this model forced against their plain
forms at the cell's shapes, with both sides' times in isolation: the
state-space scan's kernel pair at ``(1, 4096, 64, 64)`` over EIGHT groups
of 128, and the grouped-matmul kernels at ``K, N`` = 2688, 1856 (one block
each) over 8 groups of ~190 rows in a window of 6144, the up and the down
projection, against ``lax.ragged_dot``;
``chiprun_out/nemotron_kernel_parity.json``.

The third (PR 72) holds the up projection where the state holds it: the
kernel pair reads ``(8, 2688, 1856)`` through its transpose
(``gmm.reads_turned``), output and both gradients ``ragged_dot``'s bit for
bit with its time beside the orientation it had, and the cell's step
compiled for the chip from shapes alone holds no ``copy`` that writes an
``f32[8,2688,1856]`` in any layout (PR 71's held 24: weight, ``m``, ``v``
and their updates, four layers); ``chiprun_out/nemotron_turned.json``.
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
GMM_L2_ERR = 0.01
SEED = int(os.environ.get("NEMOTRON_PARITY_FIRST_SEED", "7100000071"))
SCAN_TRACK = "bfloat16[1, 4096, 64, 64]/g8n128"
CONV_TRACK = "bfloat16[1, 4096, 6144]/6144+bias"
ATTN_TRACK = "bfloat16[1, 4096, 32, 128]/kv2"
GMM_TRACKS = {"bfloat16[6144] x [8, 2688, 1856]",
              "bfloat16[6144] x [8, 1856, 2688]"}
TURNED = "x [8, 2688, 1856]"


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
    from mxnet_tpu.models import nemotron_h_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "nemotron-3-nano-30b-a3b")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "packed-4k-b1.json")) as f:
        traffic = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq = kw["seq_len"]
    seeds = int(os.environ.get("NEMOTRON_PARITY_SEEDS", "3"))
    net = nemotron_h_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))
    sigma = cfg["initializer"]["kwargs"]["sigma"]

    def weights(seed):
        """As the configuration's initializer leaves them: Normal(sigma)
        matrices, taps and stacked experts, gains (and D) one, biases (the
        convolution's, A_log, dt_bias) zero; the selection bias, an aux
        state, starts at zero in the module and in the reference."""
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
        with open(os.path.join(out_dir, "nemotron_parity.json"), "w") as f:
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
        print("\nNEMOTRON_PARITY fp8 %d " % seed + json.dumps(
            report["reference_fp8_weights"][str(seed)]), flush=True)
        mark = time.perf_counter_ns()
        with jax.default_matmul_precision("default"):
            loss, delta = _adam_step(net, params, data, labels, adam,
                                     "bfloat16", names)
        report.setdefault("module_step_s", []).append(
            round((time.perf_counter_ns() - mark) / 1e9, 1))
        lowered = {c: [[e["id"], e["args"]] for e in mx.trace.counter_events(
            [c + "lowering"], since_ns=mark)] for c in (
                "ssd:", "conv:", "attn:", "moe:gmm_")}
        report["adam_bf16"][str(seed)] = dict(
            loss_of(loss, want),
            update_rel_err={n: _rel(delta[n], want["updates"][n])
                            for n in names}, lowering=lowered)
        save()
        print("\nNEMOTRON_PARITY bf16 %d " % seed + json.dumps(
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
    loss32, delta32 = _adam_step(nemotron_h_lm(**short), params, d32,
                                 l32, adam, None, names)
    report["adam_f32_t1024"] = dict(
        loss_of(loss32, want),
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names})
    save()
    print("\nNEMOTRON_PARITY f32 " + json.dumps(report["adam_f32_t1024"]),
          flush=True)

    for seed, bf16 in report["adam_bf16"].items():
        assert bf16["loss_rel_err"] <= limits["loss_rtol"], seed
        for n in names:
            assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], \
                (seed, n)
        low = bf16["lowering"]
        assert [t for t, _ in low["ssd:"]] == [SCAN_TRACK] * 4
        assert [t for t, _ in low["conv:"]] == [CONV_TRACK] * 4
        assert [t for t, _ in low["attn:"]] == [ATTN_TRACK]
        # the share node's parts are traced once a process: the first
        # seed's bind holds the grouped products' samples (the bound's
        # window of 6144 rows, and the overflow pass's rows behind it)
        assert all(a["kernel"] == 1 and a["plain"] == 0
                   for kind in low.values() for _, a in kind)
        # the up projection, and nothing else, is read through its transpose
        assert all(a.get("turned", 0) == int(t.endswith(TURNED))
                   for t, a in low["moe:gmm_"])
    first = report["adam_bf16"][str(SEED)]["lowering"]["moe:gmm_"]
    assert {t for t, _ in first} >= GMM_TRACKS
    # float8 weights are refused by at least one update limit on every
    # seed; a cell run never computes this control (the harness compares the
    # program's step with the float32 reference only), and tier-1 puts it
    # through the harness's own reference_check at tiny widths
    # (tests/benchmark/test_cell_nemotron.py)
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


def test_the_grouped_lowerings_match_their_plain_forms_at_the_cells_shapes():
    """The scan at ``(1, 4096, 64, 64)`` bfloat16 over eight groups of 128
    compiles to the Mosaic kernel pair on the chip, and the grouped
    products at 2688 x 1856 to ``ragged-dot-gmm`` / ``ragged-dot-tgmm``
    with ONE k and ONE n block; outputs and every input gradient agree
    with the plain forms at float32, and both sides' times go to the
    report."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.moe import gmm
    from mxnet_tpu.ops import ssd
    rng = np.random.RandomState(71)
    report = {}
    mx.trace.set_enabled(True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "nemotron_kernel_parity.json"),
                  "w") as f:
            json.dump(report, f, indent=1)

    # -- the scan over eight groups -------------------------------------------
    bf16, f32 = jnp.bfloat16, jnp.float32
    args = (jnp.asarray(rng.standard_normal((1, 4096, 64, 64)), bf16),
            jnp.asarray(0.5 * rng.standard_normal((1, 4096, 8, 128)), bf16),
            jnp.asarray(0.5 * rng.standard_normal((1, 4096, 8, 128)), bf16),
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
    report["ssd_scan_g8"] = {
        "l2_err_of_the_kernels": [_rel(np.asarray(a, np.float32), r)
                                  for a, r in zip(kernels(*args), want)],
        "l2_err_of_the_plain_chunks": [_rel(np.asarray(a, np.float32), r)
                                       for a, r in zip(plain(*args), want)],
        "ms_a_layer": {
            "kernel_forward": _ms(forward, *args),
            "kernel_forward_backward": _ms(kernels, *args),
            "plain_forward_backward": _ms(plain, *args, n=3)}}
    save()
    print("\nNEMOTRON_KERNEL_PARITY scan " + json.dumps(
        report["ssd_scan_g8"]), flush=True)
    del args, dy, want
    gc.collect()

    # -- the grouped products at 2688 x 1856 ----------------------------------
    m, e = 6144, 8
    sizes = jnp.asarray([192, 170, 230, 188, 201, 150, 214, 191], jnp.int32)
    mine = (jnp.arange(m) < sizes.sum())[:, None]
    report["gmm"] = {}
    for name, (k, n) in (("up", (2688, 1856)), ("down", (1856, 2688))):
        assert gmm.tiles_for(m, k, n, e, bf16) == (gmm.ROW_TILE, k, n)
        rows = jnp.where(mine, jnp.asarray(rng.standard_normal((m, k)),
                                           bf16), 0)
        w = jnp.asarray(rng.standard_normal((e, k, n)) / np.sqrt(k), bf16)
        ct = jnp.where(mine, jnp.asarray(rng.standard_normal((m, n)),
                                         bf16), 0)

        def passes(matmul, dtype):
            def run(rows, w, ct):
                out, vjp = jax.vjp(lambda r, w: matmul(r, w, sizes),
                                   rows.astype(dtype), w.astype(dtype))
                d_rows, d_w = vjp(ct.astype(dtype))
                keep = lambda a: jnp.where(mine, a, 0)
                return keep(out), keep(d_rows), d_w
            return jax.jit(run)

        kernels, plain = passes(gmm.tiled_matmul, bf16), \
            passes(gmm.ragged_matmul, bf16)
        text = kernels.lower(rows, w, ct).compile().as_text()
        assert "ragged-dot-gmm" in text and "ragged-dot-tgmm" in text
        with jax.default_matmul_precision("highest"):
            want = [np.asarray(a, np.float32)
                    for a in passes(gmm.ragged_matmul, f32)(rows, w, ct)]
        fwd = {which: jax.jit(lambda r, w, f=f: f(r, w, sizes))
               for which, f in (("kernel", gmm.tiled_matmul),
                                ("ragged_dot", gmm.ragged_matmul))}
        report["gmm"][name] = {
            "l2_err_of_the_kernels": [_rel(np.asarray(a, np.float32), r)
                                      for a, r in zip(kernels(rows, w, ct),
                                                      want)],
            "l2_err_of_ragged_dot": [_rel(np.asarray(a, np.float32), r)
                                     for a, r in zip(plain(rows, w, ct),
                                                     want)],
            "ms": {"kernel_forward": _ms(fwd["kernel"], rows, w),
                   "ragged_dot_forward": _ms(fwd["ragged_dot"], rows, w),
                   "kernel_three_products": _ms(kernels, rows, w, ct),
                   "ragged_dot_three_products": _ms(plain, rows, w, ct)}}
        save()
        print("\nNEMOTRON_KERNEL_PARITY gmm %s " % name + json.dumps(
            report["gmm"][name]), flush=True)
        del rows, w, ct, want
        gc.collect()
    scan = report["ssd_scan_g8"]
    # the kernels are no further from float32 than the plain forms are
    for mine_err, theirs in zip(scan["l2_err_of_the_kernels"],
                                scan["l2_err_of_the_plain_chunks"]):
        assert mine_err <= max(SCAN_L2_ERR, 1.5 * theirs), scan
    for side in report["gmm"].values():
        for mine_err, theirs in zip(side["l2_err_of_the_kernels"],
                                    side["l2_err_of_ragged_dot"]):
            assert mine_err <= max(GMM_L2_ERR, 1.5 * theirs), side


def test_the_up_projection_is_read_where_the_state_holds_it():
    """``(8, 2688, 1856)``: 1856 is 14.5 lane tiles, so the chip holds the
    float32 weight and Adam's moments with 2688 on the lanes, and since
    PR 72 the kernels read it so.  The three products over 8 groups of
    150-230 rows in a window of 6144 are ``ragged_dot``'s bit for bit and
    the orientation the weight lies in is no slower than the one PR 71
    ran (1.51 ms; the down projection 0.90); the cell's compiled step
    turns no float32 array of that shape."""
    import re
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.moe import gmm
    import _gated_norm
    rng = np.random.RandomState(72)
    bf16 = jnp.bfloat16
    m, e, k, n = 6144, 8, 2688, 1856
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    report = {}

    def save():
        with open(os.path.join(out_dir, "nemotron_turned.json"), "w") as f:
            json.dump(report, f, indent=1)

    assert gmm.reads_turned(k, n) and not gmm.reads_turned(n, k)
    sizes = jnp.asarray([192, 170, 230, 188, 201, 150, 214, 191], jnp.int32)
    mine = (jnp.arange(m) < sizes.sum())[:, None]
    rows = jnp.where(mine, jnp.asarray(rng.standard_normal((m, k)), bf16), 0)
    w = jnp.asarray(rng.standard_normal((e, k, n)) / np.sqrt(k), bf16)
    ct = jnp.where(mine, jnp.asarray(rng.standard_normal((m, n)), bf16), 0)

    def as_it_lay(rows, w, sizes):
        return gmm._two_lowerings(rows, w, gmm.group_tiles(sizes, m), False)

    def passes(matmul):
        def run(rows, w, ct):
            out, vjp = jax.vjp(lambda r, w: matmul(r, w, sizes), rows, w)
            d_rows, d_w = vjp(ct)
            keep = lambda a: jnp.where(mine, a, 0)
            return keep(out), keep(d_rows), d_w
        return jax.jit(run)

    mx.trace.set_enabled(True)
    mark = time.perf_counter_ns()
    turned, lay, plain = (passes(f) for f in (
        gmm.tiled_matmul, as_it_lay, gmm.ragged_matmul))
    text = turned.lower(rows, w, ct).compile().as_text()
    assert text.count("ragged-dot-gmm") and "ragged-dot-tgmm" in text
    which = [e_["id"].split()[0] for e_ in mx.trace.counter_events(
        ["moe:gmm_trace"], since_ns=mark)]
    want = [np.asarray(a, np.float32) for a in plain(rows, w, ct)]
    report["products"] = {
        "traced": which,
        "max_abs_diff_from_ragged_dot": [
            float(np.abs(np.asarray(a, np.float32) - b).max())
            for a, b in zip(turned(rows, w, ct), want)],
        "ms_three_products": {
            "turned": _ms(turned, rows, w, ct),
            "as_the_weight_lay": _ms(lay, rows, w, ct),
            "ragged_dot": _ms(plain, rows, w, ct)}}
    save()
    print("\nNEMOTRON_TURNED products " + json.dumps(report["products"]),
          flush=True)
    del rows, w, ct, want
    gc.collect()

    # -- the cell's step, compiled for the chip from shapes alone ---------------
    mark = time.perf_counter_ns()
    text = _gated_norm.compiled_step_text("nemotron-3-nano-30b-a3b")
    lowered = [[e_["id"], e_["args"]] for e_ in mx.trace.counter_events(
        ["moe:gmm_lowering"], since_ns=mark)]
    copies = re.findall(r"= (\w+\[8,2688,1856\]\{[0-9,]*)[^ ]* copy\(", text)
    report["step"] = {
        "copies": text.count(" copy("),
        "copies_of_the_up_projection": sorted(copies),
        "layouts_of_f32_8_2688_1856": sorted(set(re.findall(
            r"f32\[8,2688,1856\]\{([0-9,]*)", text))),
        "mosaic_kernels": text.count("tpu_custom_call"),
        "gmm_lowering": lowered}
    save()
    print("\nNEMOTRON_TURNED step " + json.dumps(report["step"]), flush=True)

    products = report["products"]
    assert sorted(which) == ["gmm", "gmm_t", "tgmm"], which
    assert products["max_abs_diff_from_ragged_dot"] == [0.0, 0.0, 0.0]
    ms = products["ms_three_products"]
    assert ms["turned"] <= 1.1 * ms["as_the_weight_lay"] < ms["ragged_dot"]
    assert not [c for c in copies if c.startswith("f32")], copies
    # the share node's jits are traced once a process: samples only where
    # no earlier test of this process bound the model
    assert all(a["kernel"] == 1
               and a.get("turned", 0) == int(t.endswith(TURNED))
               for t, a in lowered), lowered
