"""Ouro-2.6B at its published widths on the chip (as the ``ouro-2.6b``
configuration is cut: eight of its 48 layers, the whole vocabulary, all
four passes as one loop node), against the plain reference
``benchmark/reference/ouro-2.6b.py`` computed on the same chip.

    MXNET_TPU_TESTS=1 python -m pytest tests/tpu/test_ouro_tpu.py -s -q

One test, in phases that each release what they held (the chip holds one
module of this size at a time): the reference's objective, gradients and
first Adam step at one sequence of 4096, and the same with its weights
rounded to float8 (what the configuration's limits have to refuse); the
configuration's own Adam step in bfloat16 at the default matmul
precision, as the cell's reference check runs it, on
``OURO_PARITY_SEEDS`` seeds (8; weights and batch both from the seed),
with the ``loop:body`` and ``attn:lowering`` samples of the bind and the
step's exit head beside the reference's exit distribution, and of the
first seed's step program what its backward ``while`` forms again
(ISSUE 56: three earlier passes from their stacked carries, the last
pass's forward in the program once, and a toy loop of two passes, where
the loop's barrier alone keeps the first pass formed again); and the Adam
step in float32 compute against the reference at one sequence of 1024.
The numbers go to ``chiprun_out/ouro_parity.json`` after every phase,
before anything is asserted.
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

SEED = 5400000054
ATTN_TRACK = "bfloat16[1, 4096, 16, 128]"


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _formed_again():
    """Of the step program that has just run: what lies under
    ``rematted_computation`` (what a checkpoint forms again), read from
    its optimized HLO."""
    import re
    import mxnet_tpu as mx
    from mxnet_tpu.trace import scopes
    names = mx.trace.program_op_names("fused:step")
    again = [n for n in names.values() if "/rematted_computation/" in n]
    kernels = [n for n in names.values() if "splash_mha" in n]
    text = scopes._programs["fused:step"][0].optimized_hlo()
    head = re.compile(r"loop_head\)*/dot_general")
    # an instruction's text runs to the next one's start (a kernel's
    # custom call holds its payload and metadata on further lines)
    starts = [m.start() for m in scopes._INSTRUCTION.finditer(text)]
    instructions = [text[a:b] for a, b in zip(starts, starts[1:] + [None])]

    def calls(kernel, among=instructions):
        return [i for i in among
                if "custom-call(" in i and "tpu_custom_call" in i
                and kernel in i]

    # the entry computation is the text's last: what stands between the
    # two ``while``s
    entry_at = text.index("\nENTRY ")
    entry = instructions[sum(a < entry_at for a in starts):]

    return {
        "instructions": len(again),
        "splash_mha_fwd": sum("splash_mha_fwd" in n for n in again),
        "head_products": sum(bool(head.search(n)) for n in again),
        "head_products_forward_while": sum(
            bool(head.search(n)) for n in names.values()
            if "/jvp(loop)/while/body/" in n),
        "kernel_instructions": len(kernels),
        # the kernels' calls in the whole program, every computation
        "fwd_kernel_calls": len(calls("splash_mha_fwd")),
        "fwd_kernel_calls_in_entry": len(calls("splash_mha_fwd", entry)),
        "bwd_kernel_calls": len(calls("splash_mha_dkv")),
        # what the two ``while``s carry: the earlier passes' carries,
        # stacked a pass
        "stacked_carries": sorted(set(re.findall(
            r"bf16\[(\d+),4096,2048\]", text)))}


def _adam_step(net, params, data, labels, opt_params, compute_dtype, names,
               program=None):
    """One step of the fused train step on the chip.  -> (the objective,
    the exit head, {name: after - before}); ``program``, a dict, takes
    ``_formed_again`` of the step's program."""
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
        if program is not None:
            program.update(_formed_again())
        exits = outs[mod._fused.head("loop_exit")]
        after, _ = mod.get_params()
        delta = {n: after[n].asnumpy() - params[n] for n in names}
        del mod, after, batch
    finally:
        os.environ.pop("MXNET_COMPUTE_DTYPE", None)
    gc.collect()
    return float(outs[0].mean()), exits / outs[0].shape[0], delta


def test_two_passes_form_the_first_again_on_the_chip():
    """At two passes the ``while`` of one trip is unrolled and the first
    pass stands in one computation with the last: the loop's barrier is
    what keeps it formed again there and not merged with its own forward
    (XLA's CPU pipeline drops the barrier and keeps both passes,
    ``tests/test_loop_node.py``).  Read off the chip's compiled program by
    the one ``tanh`` of a pass: the first pass, the last ONCE, the first
    again."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import sym
    from mxnet_tpu.executor import _GraphProgram
    x = sym.Variable("x")
    body = x + sym.Activation(sym.FullyConnected(
        x, num_hidden=128, no_bias=True, name="fc"), act_type="tanh")
    net = sym.MakeLoss(sym.sum_axis(
        sym.Repeat(body, {"x": sym.Variable("data")}, 2, name="loop"),
        axis=1))
    prog = _GraphProgram(net, {}, None, do_mirror=False)
    data, w = jnp.ones((256, 128)), jnp.full((128, 128), 0.01)

    def step(w):
        outs, vjp = jax.vjp(lambda w: prog.eval(
            {"data": data, "fc_weight": w}, {}, None, True)[0], w)
        return vjp([jnp.ones_like(o) for o in outs])[0]

    text = jax.jit(step).lower(w).compile().as_text()
    assert len(re.findall(r" while\(", text)) == 0
    assert len(re.findall(r" tanh\(", text)) == 3, text


def test_published_width_step_matches_reference():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import ouro_lm
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import manifest
    ref = manifest.load_module("reference", "ouro-2.6b")
    gen = manifest.load_module("generators", "token_packed")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "packed-4k-b1.json")) as f:
        traffic = json.load(f)
    kw = cfg["model"]["kwargs"]
    names = cfg["reference"]["weights"]
    limits = cfg["reference"]
    adam = cfg["optimizer"]["params"]
    seq = kw["seq_len"]
    seeds = int(os.environ.get("OURO_PARITY_SEEDS", "8"))
    net = ouro_lm(**kw)
    shapes = dict(zip(net.list_arguments(), net.infer_shape(
        data=(1, seq), softmax_label=(1, seq))[0]))

    def weights(seed):
        # the configuration's start: Normal(0.02), the embedding's own
        # Normal(embed_sigma), gains 1, biases 0
        rng = np.random.default_rng(seed)
        sigma = {"embed_weight": kw["embed_sigma"]}
        return {n: (np.ones(s, np.float32) if n.endswith("gamma") else
                    np.zeros(s, np.float32) if n.endswith("bias") else
                    np.float32(sigma.get(n, 0.02))
                    * rng.standard_normal(s, dtype=np.float32))
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
              "adam_bf16": {}}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def save():
        with open(os.path.join(out_dir, "ouro_parity.json"), "w") as f:
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
    report["reference_exit"] = want["exit"]
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
    print("\nOURO_PARITY fp8 " + json.dumps(
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
            loss, exits, delta = _adam_step(
                net, params, data, labels, adam, "bfloat16", names,
                program=None if i else report.setdefault("formed_again", {}))
        report.setdefault("module_step_s", []).append(
            round((time.perf_counter_ns() - mark) / 1e9, 1))
        counters = {c: [[e["id"], e["args"]] for e in
                        mx.trace.counter_events([c], since_ns=mark)]
                    for c in ("loop:body", "attn:lowering")}
        report["adam_bf16"][str(seed)] = dict(
            loss_of(loss, want),
            update_rel_err={n: _rel(delta[n], want["updates"][n])
                            for n in names},
            exit_p=[float(x) for x in exits[:-1]],
            reference_exit_p=want["exit"]["p"],
            ce_last=float(exits[-1]),
            reference_ce_last=want["exit"]["ce"][-1], **counters)
        save()
        print("\nOURO_PARITY bf16 %d " % seed + json.dumps(
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
        ouro_lm(**short), params, d32, l32, adam, None, names)
    report["adam_f32_t1024"] = dict(
        loss_of(loss32, want),
        update_rel_err={n: _rel(delta32[n], want["updates"][n])
                        for n in names})
    save()
    print("\nOURO_PARITY f32 " + json.dumps(report["adam_f32_t1024"]),
          flush=True)

    fp8 = report["reference_fp8_weights"]
    for seed, bf16 in report["adam_bf16"].items():
        assert bf16["loss_rel_err"] <= limits["loss_rtol"], seed
        for n in names:
            assert bf16["update_rel_err"][n] <= limits["update_rtol"][n], \
                (seed, n)
        # one loop node of four passes, its body recomputed; the carry is
        # one (4096, 2048) bfloat16 array; the last pass is kept whole, of
        # the earlier ones their carries and nothing else
        assert bf16["loop:body"] and all(
            a["num_steps"] == 4 and a["recompute"] == 1
            and a["carry_bytes"] == 4096 * 2048 * 2
            and a["kept_passes"] == 1
            for _, a in bf16["loop:body"])
        # every trace of the body names eight layers, every one the kernel
        assert bf16["attn:lowering"] and all(
            t == ATTN_TRACK and a["kernel"] == 1 and a["plain"] == 0
            for t, a in bf16["attn:lowering"])
        assert abs(sum(bf16["exit_p"]) - 1.0) < 1e-3
        np.testing.assert_allclose(bf16["exit_p"], bf16["reference_exit_p"],
                                   atol=5e-3)
    # the backward ``while`` forms three passes again (its carries are
    # stacked by three, nothing by four), the head's products among them.
    # Eight layers' forward kernel in the forward ``while``, ONCE more for
    # the last pass, which the compiler does not form again (its backward
    # half's copy is merged with its forward), and once in the backward
    # ``while``; the backward kernel there and for the last pass
    again = report["formed_again"]
    assert again["instructions"] > 0 and again["kernel_instructions"] > 0
    assert again["head_products"] >= 1
    # (a leading 1 is the batch's: one sequence)
    assert again["stacked_carries"] == ["1", "3"], again
    assert again["fwd_kernel_calls"] == 24, again
    assert again["fwd_kernel_calls_in_entry"] == 8, again
    assert again["bwd_kernel_calls"] == 16, again
    # float8 weights are refused by at least one limit
    assert fp8["loss_rel_err"] > limits["loss_rtol"] or any(
        fp8["adam_update_rel_err"][n] > limits["update_rtol"][n]
        for n in names)
    f32 = report["adam_f32_t1024"]
    assert f32["loss_rel_err"] <= 1e-4
    assert max(f32["update_rel_err"].values()) <= 0.1, f32
