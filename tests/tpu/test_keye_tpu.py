"""Keye-VL-2.0-30B-A3B's language model at its published widths on the chip
(as the ``keye-vl-2.0-30b-a3b`` configuration is cut: four layers, 16 of
128 experts, an eighth of the vocabulary), against the plain reference
``benchmark/reference/keye-vl-2.0-30b-a3b.py`` computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_keye_tpu.py -s -q

The first test has phases that each release what they held: the
reference's two losses, gradients and first Adam step at one sequence of
8192 tokens, and the same with its weights rounded to float8 (what the
configuration's limits have to refuse); the configuration's own Adam step
in bfloat16 at the default matmul precision, as the cell's reference
check runs it, on ``KEYE_PARITY_SEEDS`` seeds (default 4) with the
``dsa:lowering`` samples of the bind and the selection head; and the Adam
step in float32 compute against the reference at one sequence of 2048
under a top-512.  The numbers go to ``chiprun_out/keye_parity.json``
after every phase, before anything is asserted.

The second holds ``IndexedSelfAttention``'s TPU lowering against its
plain lowering at ``(1, 2048, 32, 128)`` over 4 key/value heads under a
16 x 64 indexer's top-512 (the forward, the index loss and both
cotangents), and at the cell's ``(1, 8192, 32, 128)`` under the top-2048
the forward kernel against the plain blocks, the three passes' times in
isolation with the k-th value by either method (the target pass by both
lowerings: the plain blocks and, since PR 60, the kernel ``dsa_target_*``,
whose name and the absence of the blocks' float32 head products the
compiled text is held to), and three forward
attend passes side by side (PR 58): the library's splash attention under
the selection as a dynamic mask (the op's forward until PR 58), the
library's under a static causal mask (the floor of a kernel that visits
every causal tile), and the op's own forward kernel, each as a jitted
call and as its ``splash_mha*`` operation's time in a device trace; and
two backward attend passes (PR 62): the library's fused kernel under the
selection as a dynamic mask with what the op put around it until PR 62
(the ``MaskInfo`` tiling, head-major copies, the partial ``dq`` planes'
sum), and the op's own backward kernel ``splash_mha_dkv_selected``, the
three cotangents of each against the other's and the plain blocks'.
"""
import gc
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

from _mirror import tpu_gate

pytestmark = [tpu_gate()]

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "keye-vl-2.0-30b-a3b"
SEED = 3900000057
ATTN_MAX_ERR_SHARE = 0.02
ATTN_L2_ERR = 0.01


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _adam_step(net, params, data, labels, opt_params, compute_dtype, names):
    """One step of the fused train step on the chip.  -> (the cross
    entropy, each block's index loss, the selection head, {name: after -
    before})."""
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
                                label=[mx.nd.array(labels, dtype=np.int32)],
                                pad=0)
        mod.forward_backward(batch)
        mod.update()
        outs = dict(zip(net.list_outputs(),
                        (o.asnumpy() for o in mod.get_outputs())))
        after, _ = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    index = [float(v.mean()) for k, v in sorted(outs.items())
             if k.endswith("index_loss_output")]
    return (float(outs["lm_output"].mean()), index,
            outs["dsa_select_output"], delta)


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import keye_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", CONFIG)
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "packed-8k-b1.json")) as f:
        traffic = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq = kw["seq_len"]
    net = keye_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))
    seeds = int(os.environ.get("KEYE_PARITY_SEEDS", "4"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    report = {"device": jax.devices()[0].device_kind, "adam_bf16": []}

    def save():
        with open(os.path.join(out_dir, "keye_parity.json"), "w") as f:
            json.dump(report, f, indent=1)
        print("\nKEYE_PARITY " + json.dumps(report), flush=True)

    def weights(seed):
        """The configuration's start: Normal(0.02), the embedding at its
        own ``embed_sigma``, gains 1, the LayerNorm's bias 0."""
        rng = np.random.RandomState(seed)
        sigma = {"embed_weight": kw.get("embed_sigma") or 0.02}
        return {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                    np.zeros(s, np.float32) if n.endswith("beta") else
                    (sigma.get(n, 0.02) * rng.standard_normal(s)).astype(
                        np.float32))
                for n, s in shapes.items()
                if n not in ("data", "softmax_label")}

    def batch(seed, config=cfg):
        batches = gen.build(dict(traffic, distinct_batches=1), config,
                            seed, [mx.cpu(0)], None)
        (data,), (labels,) = (list(d.values()) for d in
                              batches.reference_batch(1)[:2])
        return data, labels

    def reference(p, d, lb, config=cfg):
        out = ref.reference_step(config, p, {"data": d},
                                 {"softmax_label": lb}, adam, names)
        gc.collect()
        return out

    def loss_of(got, want):
        return {"loss": got, "reference_loss": want["loss"],
                "loss_rel_err": abs(got - want["loss"]) / want["loss"]}

    mx.trace.set_enabled(True)
    for i in range(seeds):
        params = weights(57 + i)
        data, labels = batch(SEED + i)
        report["params_M"] = sum(v.size for v in params.values()) / 1e6
        want = reference(params, data, labels)
        if i == 0:
            # A. the reference with float8 weights (e4m3, the nearest
            # format under bfloat16; arithmetic stays float32)
            coarse = {n: np.asarray(jnp.asarray(v).astype(
                jnp.float8_e4m3fn).astype(jnp.float32))
                for n, v in params.items()}
            out = reference(coarse, data, labels)
            report["reference_fp8_weights"] = dict(
                loss_of(out["loss"], want),
                index_loss=out["index_loss"],
                adam_update_rel_err={
                    n: _rel(out["updates"][n], want["updates"][n])
                    for n in names})
            del out, coarse
            gc.collect()
            save()
        # B. the configuration's step, bfloat16 at the default precision
        mark = time.perf_counter_ns()
        with jax.default_matmul_precision("default"):
            loss, index, selection, delta = _adam_step(
                net, params, data, labels, adam, "bfloat16", names)
        lowered = mx.trace.counter_events(["dsa:lowering"], since_ns=mark)
        report["adam_bf16"].append(dict(
            loss_of(loss, want), seed=SEED + i,
            update_rel_err={n: _rel(delta[n], want["updates"][n])
                            for n in names},
            index_loss=index, reference_index_loss=want["index_loss"],
            selection=selection[:, 0].tolist(),     # one sequence
            dsa_lowering=[[e["id"], e["args"]] for e in lowered]))
        save()
        del want, params, delta
        gc.collect()

    # C. float32 compute against the reference, one sequence of 2048
    # under a top-512
    short = dict(kw, seq_len=2048, topk=512)
    cfg_short = dict(cfg, model=dict(cfg["model"], kwargs=short),
                     input=dict(cfg["input"], seq_len=2048))
    params = weights(57)
    d32, l32 = batch(SEED, cfg_short)
    want = reference(params, d32, l32, cfg_short)
    loss32, index32, _, delta32 = _adam_step(
        keye_lm(**short), params, d32, l32, adam, None, names)
    report["adam_f32_t2048"] = dict(
        loss_of(loss32, want), index_loss=index32,
        reference_index_loss=want["index_loss"],
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names})
    save()

    fp8 = report["reference_fp8_weights"]
    kept = float(ref.selected_pairs(seq, kw["topk"]))
    for bf16 in report["adam_bf16"]:
        assert bf16["loss_rel_err"] <= limits["loss_rtol"]
        for n in names:
            assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], n
        # every block keeps exactly its count, whatever the scores
        for row, loss in zip(bf16["selection"], bf16["index_loss"]):
            assert row[:3] == [float(seq), kept, seq * (seq + 1) / 2.0]
            assert abs(row[5] - loss) <= 1e-5 * abs(loss)
        assert np.allclose(bf16["index_loss"], bf16["reference_index_loss"],
                           rtol=0.02)
        # one op a layer, every one the kernel lowering
        assert len(bf16["dsa_lowering"]) == kw["num_layers"]
        for track, args in bf16["dsa_lowering"]:
            assert args == {"kernel": 1, "plain": 0, "heads_a_mask_tile": 8,
                            "target_kernel": 1, "backward_kernel": 1}
            assert track == "bfloat16[1, 8192, 32, 128]/kv4/top2048"
    # float8 weights are refused by at least one limit
    assert fp8["loss_rel_err"] > limits["loss_rtol"] or any(
        fp8["adam_update_rel_err"][n] > limits["update_rtol"][n]
        for n in names)
    f32 = report["adam_f32_t2048"]
    assert f32["loss_rel_err"] <= 1e-4
    assert np.allclose(f32["index_loss"], f32["reference_index_loss"],
                       rtol=1e-3)
    assert max(f32["update_rel_err"].values()) <= 0.1, f32


def _library_sizes(t):
    """The tiles ``causal_attention`` gives the library's kernels."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    from mxnet_tpu.ops import transformer as tr
    tile, piece = tr._kernel_tiles(t)
    return tile, sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=piece,
        block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=piece,
        use_fused_bwd_kernel=True)


def _kernel_ms(fn, *args):
    """ms a call of the ``splash_mha*`` operations in a device trace."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import trace_reduce
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(3):
                out = fn(*args)
            jax.block_until_ready(out)
        devices, _ = trace_reduce.load_xplane(trace_reduce.find_xplane(d))
    return sum(ns for name, _, ns in sorted(devices.items())[0][1]
               if name.startswith("splash_mha")) / 3e6


def _library_backward(q, k, v, mask, out, lse, g):
    """The op's backward attend pass until PR 62: the library's fused
    kernel under the selection as a dynamic mask, one head's ``MaskInfo``
    for every head, head-major operands, the partial ``dq`` planes
    summed."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask_info as mi)
    tile, sizes = _library_sizes(q.shape[0])
    info = mi.process_dynamic_mask_dkv(mask[None], (tile, tile),
                                       shrink_grid=False)[0]
    info = info._replace(partial_mask_blocks=info.partial_mask_blocks
                         .reshape(-1, tile, tile))
    heads = tuple(x.transpose(1, 0, 2) for x in (q, k, v))
    res = heads + (None, None, out.transpose(1, 0, 2), lse, None, info)
    with jax.default_matmul_precision("default"):
        grads = sk._splash_attention_bwd(
            False, sk.DEFAULT_MASK_VALUE, False, sizes, None, None, None,
            False, res, g.transpose(1, 0, 2))
    return tuple(x.transpose(1, 0, 2).astype(y.dtype)
                 for x, y in zip(grads[3:6], (q, k, v)))


def _two_backwards(ms, operands):
    """``({name: {"call", "kernel"}}, {pair: [l2 errors of dq, dk, dv]})``
    of the two backward attend passes at the cell's shape and the plain
    blocks'."""
    import jax
    from mxnet_tpu.ops import sparse_attention as sa
    passes = {"library_dynamic_mask": jax.jit(_library_backward),
              "selected": jax.jit(sa._attend_kernel_bwd)}
    times = {name: {"call": ms(fn, *operands),
                    "kernel": _kernel_ms(fn, *operands)}
             for name, fn in passes.items()}
    got = {name: [np.asarray(x, np.float32) for x in fn(*operands)]
           for name, fn in passes.items()}
    got["plain"] = [np.asarray(x, np.float32) for x in
                    jax.jit(sa._attend_plain_bwd)(*operands)]
    errors = {"%s_against_%s" % (a, b): [_rel(x, y) for x, y in
                                         zip(got[a], got[b])]
              for a, b in (("selected", "plain"),
                           ("library_dynamic_mask", "plain"),
                           ("selected", "library_dynamic_mask"))}
    return times, errors


def _three_forwards(ms, attend, qs, k, v, mask):
    """ms a layer of three forward attend passes at the cell's shape,
    ``{name: {"call": the jitted call, "kernel": its splash_mha*
    operations in a device trace}}``: the op's forward kernel
    (``attend``), and the library's splash attention under the selection
    as a dynamic mask and under a static causal mask."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm,
        splash_attention_mask_info as mi)
    t, h = qs.shape[:2]
    tile, sizes = _library_sizes(t)

    def heads_major(fn, *operands):
        with jax.default_matmul_precision("default"):
            out, (lse,) = fn(*(x.transpose(1, 0, 2) for x in operands))
        return out.transpose(1, 0, 2), lse

    def dynamic(q, k, v, mask):
        info = mi.process_dynamic_mask(mask[None], (tile, tile))[0]
        info = info._replace(partial_mask_blocks=info.partial_mask_blocks
                             .reshape(-1, tile, tile))
        return heads_major(lambda q, k, v: sk._splash_attention_forward(
            info, q, k, v, None, None, mask_value=sk.DEFAULT_MASK_VALUE,
            is_mqa=False, block_sizes=sizes, residual_checkpoint_name=None,
            save_residuals=True, mask_function=None), q, k, v)

    causal = sk.make_splash_mha_single_device(
        sm.MultiHeadMask([sm.CausalMask((t, t))] * h), block_sizes=sizes,
        save_residuals=True)

    forwards = {}
    for name, fn, args in (
            ("library_dynamic_mask", jax.jit(dynamic), (qs, k, v, mask)),
            ("library_causal_mask",
             jax.jit(lambda q, k, v: heads_major(causal, q, k, v)),
             (qs, k, v)),
            ("selected", attend, (qs, k, v, mask))):
        forwards[name] = {"call": ms(fn, *args),
                          "kernel": _kernel_ms(fn, *args)}
    return forwards


def test_the_op_alone_both_lowerings_and_the_passes_times():
    """``indexed_attention`` compiles to the Mosaic kernels on the chip;
    at ``(1, 2048, 32, 128)`` over 4 under a top-512 the heads' outputs,
    the index loss and the cotangents of all six inputs agree with the
    plain lowering's on the same selection; at the cell's ``(1, 8192, 32,
    128)`` under the top-2048 each pass's time in isolation, the k-th
    value by the op's count passes and by ``lax.top_k``."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.ops import sparse_attention as sa
    t, h, hkv, dh, hi, di, topk = 8192, 32, 4, 128, 16, 64, 2048
    scale = dh ** -0.5
    rng = np.random.RandomState(57)

    def make(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    q, k, v = make(1, t, h, dh), make(1, t, hkv, dh), make(1, t, hkv, dh)
    qi, ki, w = make(1, t, hi, di), make(1, t, 1, di), make(1, t, hi)
    g = jnp.asarray(rng.standard_normal((1, t, h, dh)), jnp.float32)

    def both_passes(kernel, topk):
        def run(*args):
            outs, vjp = jax.vjp(
                lambda *a: sa._indexed_attention(*a, topk, scale, 0,
                                                 kernel, kernel)[:2], *args)
            return outs + vjp((g[:, :args[0].shape[1]].astype(outs[0].dtype),
                               jnp.ones((1,), jnp.float32)))
        return jax.jit(run)

    mx.trace.set_enabled(True)
    short = [x[:, :2048] for x in (q, k, v, qi, ki, w)]
    kernel, plain = both_passes(True, 512), both_passes(False, 512)
    text = kernel.lower(*short).compile().as_text()
    assert "tpu_custom_call" in text and "splash_mha_fwd_selected" in text
    assert "splash_mha_dkv_selected" in text
    assert "splash_mha_fwd_residuals" not in text
    # the backward attend pass is this repo's kernel too (PR 62): none of
    # the library's, no int32 tiling of the selection, no partial dq planes
    assert "splash_mha_dkv_no_residuals" not in text
    assert not re.search(r"s32\[[\d,]*1024,1024\]", text)
    assert not re.search(r"bf16\[\d+,%d,2048,%d\]" % (h, dh), text)
    # the target pass: this repo's kernel (no ``splash_mha`` in its name:
    # dsa_attn_roofline's reader goes by that prefix) and none of the plain
    # blocks' float32 head products ``(Hkv, H / Hkv, 256, keys)``
    blocks = re.compile(r"f32\[%d,(%d,256,\d+|\d+,256,%d)\]"
                        % (hkv, h // hkv, h // hkv))
    assert "dsa_target_grads" in text and not blocks.search(text)
    assert "splash_mha_dsa" not in text and "splash_mha_target" not in text
    plain_text = plain.lower(*short).compile().as_text()
    assert "tpu_custom_call" not in plain_text and blocks.search(plain_text)
    del text, plain_text
    got = [np.asarray(x, np.float32) for x in kernel(*short)]
    want = [np.asarray(x, np.float32) for x in plain(*short)]
    names = ["output", "index_loss", "d_q", "d_k", "d_v", "d_qi", "d_ki",
             "d_w"]
    report = {"max_err_share": {}, "l2_err": {}}
    for n, a, b in zip(names, got, want):
        report["max_err_share"][n] = float(
            np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
        report["l2_err"][n] = _rel(a, b)

    def ms(fn, *a):
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 5 * 1e3

    one = [x[0] for x in (q, k, v, qi, ki[:, :, 0], w)]
    def select_by_sort(qi, ki, w):
        """The form that lost (PERF.md §5): a row's k-th value from
        ``lax.top_k`` of every block of 256 rows' scores and every key
        that reaches it: the op's selection and the keys level with a
        row's k-th (no tie rule here)."""
        def one_block(args):
            i, qi_b, w_b = args
            pos = i * 256 + jnp.arange(256)
            scores = jnp.where(jnp.arange(t)[None, :] <= pos[:, None],
                               sa.indexer_scores(qi_b, ki, w_b), -jnp.inf)
            best = jax.lax.top_k(scores, topk)[0]
            want = jnp.minimum(pos + 1, topk)
            return scores >= jnp.take_along_axis(best, want[:, None] - 1, 1)
        return jax.lax.map(one_block, (
            jnp.arange(t // 256), qi.reshape(t // 256, 256, hi, di),
            w.reshape(t // 256, 256, hi))).reshape(t, t)

    times = {}
    select = jax.jit(lambda qi, ki, w: sa._select(qi, ki, w, topk, 0))
    times["select_bisect"] = ms(select, *one[3:])
    mask = select(*one[3:])
    by_sort = jax.jit(select_by_sort)
    times["select_top_k"] = ms(by_sort, *one[3:])
    level = by_sort(*one[3:])
    assert not bool((mask & ~level).any())
    assert int(level.sum()) - int(mask.sum()) < t
    assert int(mask.sum()) == topk * (topk + 1) // 2 + (t - topk) * topk
    qs = one[0] * jnp.bfloat16(scale)
    attend = jax.jit(sa._attend_kernel)
    out, lse = attend(qs, one[1], one[2], mask)
    plain_out, plain_lse = jax.jit(sa._attend_plain)(qs, one[1], one[2], mask)
    for n, a, b in (("output", out, plain_out), ("lse", lse, plain_lse)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        report["max_err_share"]["forward_8192_" + n] = float(
            np.abs(a - b).max() / np.abs(b).max())
        report["l2_err"]["forward_8192_" + n] = _rel(a, b)
    del plain_out, plain_lse
    times["attend_kernel_forward"] = ms(attend, qs, one[1], one[2], mask)
    report["forward_ms_a_layer"] = _three_forwards(
        ms, attend, qs, one[1], one[2], mask)
    backwards, report["backward_8192_l2_err"] = _two_backwards(
        ms, (qs, one[1], one[2], mask, out, lse, g[0].astype(jnp.bfloat16)))
    report["backward_ms_a_layer"] = backwards
    times["attend_kernel_backward"] = backwards["selected"]["call"]
    # the target pass in isolation, both lowerings (PR 60)
    for grads in (False, True):
        name = "target_with_gradient" if grads else "target_loss_only"
        passes = [jax.jit(lambda *a, fn=fn: fn(*a, 0, grads))
                  for fn in (sa._target, sa._target_kernel)]
        operands = (one[3], one[4], one[5], qs, one[1], lse, mask)
        times[name], times[name + "_kernel"] = (ms(fn, *operands)
                                                for fn in passes)
        (want_loss, want), (loss, unit) = (fn(*operands) for fn in passes)
        report["l2_err"][name + "_8192_loss"] = _rel(loss, want_loss)
        for n, a, b in zip(("d_qi", "d_ki", "d_w"), unit or (), want or ()):
            report["l2_err"]["target_8192_" + n] = _rel(a, b)
        del want, unit
    times["op_forward_backward_kernel"] = ms(both_passes(True, topk),
                                             q, k, v, qi, ki, w)
    report["ms_a_layer"] = times
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "keye_op_parity.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("\nKEYE_OP_PARITY " + json.dumps(report), flush=True)
    for n in ("output", "d_q", "d_k", "d_v", "forward_8192_output"):
        assert report["max_err_share"][n] <= ATTN_MAX_ERR_SHARE, report
        assert report["l2_err"][n] <= ATTN_L2_ERR, report
    assert report["l2_err"]["forward_8192_lse"] <= 1e-5, report
    # the op's own forward kernel against the library's two: under the
    # dynamic mask's by 2 ms a layer, as a call and as an operation
    forwards = report["forward_ms_a_layer"]
    for kind in ("call", "kernel"):
        assert forwards["selected"][kind] + 2.0 \
            <= forwards["library_dynamic_mask"][kind], forwards
    # the op's own backward kernel: the plain blocks' cotangents as closely
    # as the library's kernel gave them, in less time as a call (what the
    # library's form put around its kernel is gone)
    errors = report["backward_8192_l2_err"]
    for n in ("selected_against_plain", "library_dynamic_mask_against_plain"):
        assert max(errors[n]) <= ATTN_L2_ERR, errors
    assert max(errors["selected_against_plain"]) \
        <= 1.25 * max(errors["library_dynamic_mask_against_plain"]), errors
    assert backwards["selected"]["call"] + 1.0 \
        <= backwards["library_dynamic_mask"]["call"], backwards
    # the indexer's side reads the log-sum-exp of whichever lowering ran
    assert report["l2_err"]["index_loss"] <= 1e-3, report
    for n in ("d_qi", "d_ki", "d_w"):
        assert report["l2_err"][n] <= 0.02, report
        assert report["l2_err"]["target_8192_" + n] <= 0.02, report
    # the target kernel on the same selection and log-sum-exp: the plain
    # blocks' loss, in under two thirds of their time with the gradient
    for n in ("target_loss_only", "target_with_gradient"):
        assert report["l2_err"][n + "_8192_loss"] <= 1e-3, report
    assert times["target_with_gradient_kernel"] * 1.5 \
        <= times["target_with_gradient"], times
