"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one process over a host's four chips

Drives the main path once at full width, through the entry points a user
calls: ResNet-50 @224 (``mxnet_tpu.models.get_resnet50``), bf16 compute,
``mx.io.NDArrayIter`` -> ``mx.mod.Module(context=mx.tpu(..))`` ->
``mod.fit`` for a few steps on one repeated batch (128 images a chip),
then ``mod.score`` and ``mod.predict``; then the two Pallas kernels that
are on by default for TPU serving (paged attention, fused FC epilogue),
compiled by Mosaic, against the jnp twin that ships beside each.

It checks, and fails on the first miss (no phase is wrapped in a ``try``):
the platform is ``tpu``; the fused train step engaged; parameters and
batch live on TPU devices; the loss is finite and falls; no program is
compiled after the first two steps; every device in the mesh reports
memory in use (its share, under ``--chips 4``); predictions are finite
softmax rows of the expected shape; for a few images the logits of the
trained weights, run on the chip, agree with a float32 run on the host
CPU (an explicit ``mx.cpu()`` reference on a small input, not a
fallback), whose top class ``mod.predict`` ranks in its top five; each
kernel matches its twin.

One process, no children, no network, no git.  JAX's persistent cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/
.jax_cache``, so a second run compiles nothing.  The last line of
standard output is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

With no TPU (``JAX_PLATFORMS=cpu``, or a machine without one) it says what
JAX found and exits 1 without a result.
"""
import argparse
import json
import math
import os
import sys
import time

import numpy as np

PER_CHIP_BATCH = 128
STEPS = 8
REF_IMAGES = 4
# the chip's default matmul precision (one bf16 pass) against the float32
# host reference through 50 layers, as a share of the logits' norm
REF_LOGITS_RTOL = 0.02
# bf16 probabilities carry 8 bits each
SOFTMAX_SUM_ATOL = 0.01


class SmokeFailure(Exception):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)
    print("  ok: " + what, flush=True)


def phase(name):
    print("[%7.1fs] %s" % (time.perf_counter() - _T0, name), flush=True)


_T0 = time.perf_counter()


def train_phase(chips, batch=None, steps=STEPS, image=224, classes=1000,
                ctx=None):
    """Module.fit on one repeated batch -> (module, X, y, iterator,
    compile counter)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.compile_cache import count_backend_compiles
    from mxnet_tpu.models import get_resnet50

    batch = batch or PER_CHIP_BATCH * chips
    mx.random.seed(0)                  # the initializer draws from it
    rng = np.random.RandomState(0)
    X = rng.rand(batch, 3, image, image).astype(np.float32)
    y = rng.randint(0, classes, batch).astype(np.float32)
    # the iterator holds exactly one batch: every epoch is one step on it
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    if ctx is None:
        ctx = [mx.tpu(i) for i in range(chips)]
    mod = mx.mod.Module(get_resnet50(classes), context=ctx)
    losses, compiles = [], []

    def on_batch(param):
        losses.append(float(param.eval_metric.get()[1]))
        compiles.append(counter.count)

    with count_backend_compiles() as counter:
        mod.fit(it, eval_metric="ce", num_epoch=steps,
                batch_end_callback=on_batch,
                initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
                optimizer_params={"learning_rate": 0.005, "momentum": 0.9})
    print("  loss per step: %s" % " ".join("%.4f" % v for v in losses))
    print("  compile requests by step: %s (%d served by the persistent "
          "cache, %d compiled)" % (compiles, counter.cache_hits,
                                   counter.compiled))

    check(mod._fused is not None, "fused train step engaged")
    mesh = mod._fused.mesh
    mesh_devs = list(mesh.devices.ravel())
    want = ctx if isinstance(ctx, list) else [ctx]
    check(dict(mesh.shape) == {"dp": len(want)}
          and len({d.id for d in mesh_devs}) == len(want)
          and mesh_devs == [c.jax_device() for c in want],
          "mesh is dp=%d over distinct devices %s"
          % (len(want), [d.id for d in mesh_devs]))
    platform = mesh_devs[0].platform
    params = jax.tree_util.tree_leaves(mod._fused_state["params"])
    check(params and all(d in mesh_devs for p in params
                         for d in p.devices()),
          "%d parameter arrays live on the %s mesh" % (len(params),
                                                       platform))
    staged = mod._fused.make_batch(next(iter(it)))
    check(all(set(a.devices()) == set(mesh_devs) for a in staged.values()),
          "a staged batch lives on the %s mesh, split over dp" % platform)
    check(len(losses) == steps and all(math.isfinite(v) for v in losses),
          "loss finite on all %d steps" % steps)
    check(losses[-1] < losses[0],
          "loss fell on the repeated batch (%.4f -> %.4f)"
          % (losses[0], losses[-1]))
    check(compiles[-1] == compiles[1],
          "zero compile requests after the first two steps")
    return mod, X, y, it, counter


def memory_phase(mesh_devs):
    used = []
    for d in mesh_devs:
        stats = d.memory_stats()
        check(stats is not None and stats.get("bytes_in_use", 0) > 0,
              "device %d reports %.2f GiB in use (peak %.2f GiB)"
              % (d.id, stats["bytes_in_use"] / 2 ** 30,
                 stats.get("peak_bytes_in_use", 0) / 2 ** 30))
        used.append(stats["bytes_in_use"])
    if len(used) > 1:
        # the first device also holds what the default context holds (the
        # NDArrayIter's copy of the batch), about as much again
        check(max(used) < 4 * min(used),
              "each device holds its share (max/min bytes in use %.2f < 4)"
              % (max(used) / min(used)))


def eval_phase(mod, X, y, it, classes=1000, image=224):
    import mxnet_tpu as mx
    batch = X.shape[0]
    score = dict(mod.score(it, "acc"))
    check(0.0 <= score["accuracy"] <= 1.0,
          "score on one batch: accuracy %.3f" % score["accuracy"])
    # the fused module answers in the compute dtype (bf16): widen before
    # any host arithmetic — a bf16 running sum of 1000 terms stalls
    probs = mod.predict(it).asnumpy().astype(np.float32)
    check(probs.shape == (batch, classes), "predict shape %s"
          % (probs.shape,))
    check(bool(np.isfinite(probs).all()), "predictions finite")
    off = float(np.abs(probs.sum(axis=1) - 1.0).max())
    check(off < SOFTMAX_SUM_ATOL,
          "softmax rows sum to 1 (max |sum - 1| %.4f < %g)"
          % (off, SOFTMAX_SUM_ATOL))
    # the reference: the same trained weights on the host CPU, float32.
    # Compared on LOGITS (the softmax saturates once the net has
    # memorized a batch, and probabilities then say nothing)
    arg, aux = mod.get_params()
    n = REF_IMAGES
    logits_sym = mod.symbol.get_internals()["fc1_output"]

    def logits_on(ctx):
        m = mx.mod.Module(logits_sym, context=ctx, label_names=None)
        m.bind(data_shapes=[("data", (n, 3, image, image))],
               for_training=False)
        m.set_params(arg, aux)
        return m.predict(mx.io.NDArrayIter(X[:n], batch_size=n)).asnumpy()

    on_chip, want = logits_on(mod._context[0]), logits_on(mx.cpu(0))
    rel = float(np.linalg.norm(on_chip - want) / np.linalg.norm(want))
    check(bool(np.isfinite(on_chip).all()) and rel < REF_LOGITS_RTOL,
          "logits of %d images on %s agree with the float32 CPU reference "
          "(relative error %.4f < %g)" % (n, mod._context[0], rel,
                                          REF_LOGITS_RTOL))
    top5 = np.argsort(-probs[:n], axis=1)[:, :5]
    check(all(want[i].argmax() in top5[i] for i in range(n)),
          "mod.predict ranks the reference's top class in its top five "
          "for all %d images" % n)


# (matmul precision, bound on max |kernel - twin|; values are O(1)).  At
# "highest" both sides are float32-exact; at the default — what serving
# runs — each side makes one bf16 MXU pass, so agreement is to ~1e-2.
KERNEL_PRECISIONS = (("highest", 1e-4), ("default", 5e-2))
PAGED_SHAPES = ((8, 1, 4, 64), (8, 32, 4, 64), (8, 1, 16, 128),
                (8, 32, 16, 128))           # (slots, C, heads, head_dim)
FC_SHAPE = (128, 2048, 2048)                # (M, K, N)


def lowers_to_mosaic(fn, *args):
    """Whether fn's program, as lowered for these args, calls Mosaic —
    the guard against a parity check passing on the dense twin."""
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def run_at(fn, args, precision):
    import jax
    with jax.default_matmul_precision(precision):
        return np.asarray(jax.jit(fn)(*args))


def paged_case(s, c, h, d, bt=16, blocks=64, max_blocks=10, seed=0):
    """A ragged paged-KV scenario at the engine's geometry: lengths that
    straddle block boundaries, physical blocks assigned out of order."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    lengths = rng.randint(c, max_blocks * bt + 1, size=s).astype(np.int32)
    pages = np.full((s, max_blocks), blocks, np.int32)      # sentinel
    order = rng.permutation(blocks)
    nxt = 0
    for i in range(s):
        for b in range(-(-int(lengths[i]) // bt)):
            pages[i, b] = order[nxt % blocks]
            nxt += 1
    k_pool = rng.randn(blocks + 1, bt, h, d).astype(np.float32)
    v_pool = rng.randn(blocks + 1, bt, h, d).astype(np.float32)
    q = rng.randn(s, c, h, d).astype(np.float32)
    q_pos = lengths[:, None] - c + np.arange(c, dtype=np.int32)[None, :]
    return tuple(jnp.asarray(a) for a in
                 (q, k_pool, v_pool, pages, lengths, q_pos))


def paged_parity(s, c, h, d):
    """paged_attention compiled (interpret=False) vs its dense twin ->
    (lowered to Mosaic?, [(precision, max |diff|, bound)])."""
    from mxnet_tpu.ops.pallas_kernels import (_paged_attention_dense,
                                              paged_attention)
    args = paged_case(s, c, h, d)

    def kernel(*a):
        return paged_attention(*a, causal=True)

    want = run_at(lambda *a: _paged_attention_dense(*a, causal=True), args,
                  "highest")
    return lowers_to_mosaic(kernel, *args), [
        (precision, float(np.abs(run_at(kernel, args, precision)
                                 - want).max()), tol)
        for precision, tol in KERNEL_PRECISIONS]


def fc_parity(out_scale):
    """fused_fc_epilogue (relu) compiled vs jnp -> as paged_parity; with
    ``out_scale`` the result is int8 and the difference is counted in
    quantization steps (values at a rounding boundary may move by one)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import fused_fc_epilogue
    m, k, n = FC_SHAPE
    rng = np.random.RandomState(1)
    args = (jnp.asarray(rng.randn(m, k).astype(np.float32)),
            jnp.asarray((rng.randn(n, k) / np.sqrt(k)).astype(np.float32)),
            jnp.asarray(rng.randn(n).astype(np.float32)))

    def kernel(x, w, b):
        return fused_fc_epilogue(x, w, b, "relu", out_scale=out_scale)

    want = run_at(lambda x, w, b: jnp.maximum(jnp.dot(x, w.T) + b, 0.0),
                  args, "highest")
    out_dtype = np.float32
    if out_scale is not None:
        want = np.clip(np.round(want / out_scale), -127, 127)
        out_dtype = np.int8
    errors = []
    for precision, tol in KERNEL_PRECISIONS:
        got = run_at(kernel, args, precision)
        if got.dtype != out_dtype:
            raise SmokeFailure("fused_fc_epilogue returned %s, not %s"
                               % (got.dtype, np.dtype(out_dtype)))
        errors.append((precision,
                       float(np.abs(got.astype(np.float32) - want).max()),
                       tol if out_scale is None else 1.5))
    return lowers_to_mosaic(kernel, *args), errors


def kernel_phase():
    """The two kernels that are on by default for TPU serving."""
    cases = [("paged_attention S=%d C=%d H=%d D=%d" % shape,
              lambda shape=shape: paged_parity(*shape))
             for shape in PAGED_SHAPES]
    cases += [("fused_fc_epilogue M=%d K=%d N=%d relu %s"
               % (FC_SHAPE + ("f32" if scale is None else "int8 out_scale",)),
               lambda scale=scale: fc_parity(scale))
              for scale in (None, 0.05)]
    for name, parity in cases:
        mosaic, errors = parity()
        check(mosaic, name + " lowers to a Mosaic kernel")
        for precision, err, tol in errors:
            check(math.isfinite(err) and err < tol,
                  "  ... matches its twin at %s precision (max |diff| "
                  "%.2e < %g)" % (precision, err, tol))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="devices to train over in this one process "
                         "(global batch 128 a chip); default 1")
    args = ap.parse_args(argv)
    os.environ.setdefault("MXNET_COMPUTE_DTYPE", "bfloat16")

    import jax
    from mxnet_tpu.compile_cache import place_jax_cache

    cache_dir = place_jax_cache()
    devs = jax.devices()
    dev = devs[0]
    print("platform=%s device_kind=%s device_count=%d"
          % (dev.platform, dev.device_kind, len(devs)), flush=True)
    if dev.platform != "tpu":
        sys.stderr.write(
            "chip_smoke: needs a TPU; JAX found platform %r (%s). Nothing "
            "was run.\n" % (dev.platform, devs))
        return 1
    if args.chips > len(devs):
        sys.stderr.write("chip_smoke: --chips %d but JAX found %d device(s)"
                         "\n" % (args.chips, len(devs)))
        return 1
    print("jax compile cache: %s" % cache_dir, flush=True)

    phase("train: ResNet-50 @224 b%d bf16, Module.fit, %d chip(s)"
          % (PER_CHIP_BATCH * args.chips, args.chips))
    mod, X, y, it, counter = train_phase(args.chips)
    phase("memory")
    memory_phase(list(mod._fused.mesh.devices.ravel()))
    phase("score / predict / float32 CPU reference")
    eval_phase(mod, X, y, it)
    phase("kernels: compiled by Mosaic vs jnp twins")
    kernel_phase()
    phase("done: %d compile requests in the train phase, %d compiled, %d "
          "from the persistent cache"
          % (counter.count, counter.compiled, counter.cache_hits))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
