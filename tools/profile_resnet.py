"""Per-HLO cost-analysis for the fused ResNet-50 train step.

Answers "where do the executed FLOPs go?" with XLA's own cost analysis of
the exact executable the bench times (bench.py drives the same
Module->fused path).  Usage:

    python tools/profile_resnet.py [--batch 256] [--time] [--hlo-top 25]

With --time, measures steady-state img/s exactly like bench.run().
Reference workload: example/image-classification/train_imagenet.py
(reference README numbers at example/image-classification/README.md).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def analytic_train_gflop_per_img():
    """ResNet-50 v1 @224 analytic cost, 2mnk convention (one multiply-add
    = 2 FLOP), the same convention as XLA cost analysis and the bench's
    bf16 peak probe.  Forward ~7.72 GFLOP/img; training = fwd + bwd-data
    + bwd-weight ~= 3x forward = 23.15 GFLOP/img.

    NB the literature's "4.1 GFLOPs" for ResNet-50 counts multiply-adds
    as ONE flop (GMACs); mixing that numerator with a 2mnk denominator
    understates MFU by 2x.
    """
    def conv(cin, cout, k, s, hw_in):
        hw_out = (hw_in + s - 1) // s if s > 1 else hw_in
        return 2 * cout * hw_out * hw_out * cin * k * k, hw_out

    total, hw = 0, 224
    f, hw = conv(3, 64, 7, 2, hw)
    total += f
    hw = 56  # 3x3/2 maxpool
    for blocks, cin, w, s in ((3, 64, 64, 1), (4, 256, 128, 2),
                              (6, 512, 256, 2), (3, 1024, 512, 2)):
        cout = w * 4
        for b in range(blocks):
            stride = s if b == 0 else 1
            c_in = cin if b == 0 else cout
            f1, hw1 = conv(c_in, w, 1, stride, hw)
            f2, hw2 = conv(w, w, 3, 1, hw1)
            f3, hw3 = conv(w, cout, 1, 1, hw2)
            total += f1 + f2 + f3
            if b == 0:
                fd, _ = conv(c_in, cout, 1, stride, hw)
                total += fd
            hw = hw3
    total += 2 * 2048 * 1000
    return 3 * total / 1e9


def build(batch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_resnet50

    net = get_resnet50(1000)
    rng = np.random.RandomState(0)
    X = rng.rand(batch, 3, 224, 224).astype(np.float32)
    y = rng.randint(0, 1000, batch).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(net, context=mx.tpu(0))
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier(factor_type="in", magnitude=2.34))
    mod.init_optimizer(optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    mod._fused_ensure_state()
    sh = mod._fused._batched()
    staged = mx.io.DataBatch(
        data=[mx.nd.NDArray(jax.device_put(jnp.asarray(X), sh))],
        label=[mx.nd.NDArray(jax.device_put(jnp.asarray(y), sh))])
    return mod, staged


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--time", action="store_true")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--hlo-top", type=int, default=25)
    args = ap.parse_args()
    os.environ.setdefault("MXNET_COMPUTE_DTYPE", "bfloat16")

    mod, staged = build(args.batch)
    f = mod._fused
    t0 = time.time()
    flops = f.aot_compile(mod._fused_state, f.make_batch(staged),
                          mod._fused_key)
    print("compile %.1fs; XLA executed GFLOP/img = %.2f (analytic %.2f)"
          % (time.time() - t0, flops / args.batch / 1e9,
             analytic_train_gflop_per_img()))

    compiled = f._step   # aot_compile installs the executable as the step
    if compiled is not None and args.hlo_top:
        # per-op flop breakdown via cost analysis of the optimized HLO
        try:
            import collections
            by_op = collections.Counter()
            by_dtype = collections.Counter()
            hlo = compiled.as_text()
            # count fusion/conv/dot lines and f32 pockets cheaply
            for ln in hlo.splitlines():
                ln = ln.strip()
                if " = " not in ln:
                    continue
                lhs, rhs = ln.split(" = ", 1)
                head = rhs.split("(", 1)[0].split()
                if not head:
                    continue
                opname = head[-1]
                if opname.startswith(("convolution", "dot", "fusion",
                                      "custom-call", "transpose", "copy",
                                      "reduce", "all-reduce")):
                    by_op[opname.split(".")[0]] += 1
                if lhs.split()[-1].startswith("f32") and \
                        ("convolution" in rhs or "dot" in rhs):
                    by_dtype["f32 conv/dot"] += 1
            print("optimized-HLO op counts:", dict(by_op.most_common(15)))
            print("f32 conv/dot instructions:", by_dtype["f32 conv/dot"])
        except Exception as e:
            print("hlo text analysis unavailable:", e)

    if args.time:
        import jax
        for _ in range(5):
            mod.forward(staged, is_train=True)
            mod.backward()
            mod.update()
        jax.block_until_ready(next(iter(mod._fused_state["params"].values())))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            mod.forward(staged, is_train=True)
            mod.backward()
            mod.update()
        jax.block_until_ready(next(iter(mod._fused_state["params"].values())))
        dt = time.perf_counter() - t0
        rate = args.batch * args.iters / dt
        print("batch=%d  %.1f img/s  (%.1f ms/step)"
              % (args.batch, rate, dt / args.iters * 1e3))


if __name__ == "__main__":
    main()
