"""Second north-star benchmark (BASELINE.json): PTB-style LSTM training
throughput, tokens/sec on one TPU chip — through the reference user API
(Module + fused train step, the same path example/rnn/lstm_bucketing.py
takes), batches pre-staged on device like bench.py.

Reference setup (example/rnn/lstm_bucketing.py): 2-layer LSTM, 200 hidden,
200 embed, seq_len 32, batch 32, vocab 10k, trained with truncated BPTT.
No published MXNet-CUDA tokens/sec exists in-repo (BASELINE.md has only
image models), so vs_baseline uses the derived TitanX estimate of the same
era: Inception-BN sustained ~128 img/s/GPU at ~4.4 GFLOP/img forward =
~1.7 TFLOP/s/GPU training; the PTB LSTM above costs ~21 MFLOP/token
(fwd+bwd), giving ~80k tokens/s/GPU as the comparable per-chip number.

Prints ONE JSON line like bench.py (incl. mfu/peak_tflops); run
`python bench.py` for the primary (ResNet-50) metric.
"""
import json
import os
import time

import numpy as np

BASELINE_TOKENS_S_PER_CHIP = 80000.0


def train_mflop_per_token(num_layer=2, hidden=200, embed=200, vocab=10000):
    """Analytic train cost per token: layer 0 sees an (E+H)-wide fused
    gate input, every later layer an (H+H)-wide one (its input is the
    previous layer's H-wide output); plus the H->vocab softmax
    projection.  2 FLOPs/MAC; backward ~2x forward."""
    fwd = (2 * 4 * hidden * (embed + hidden)
           + (num_layer - 1) * 2 * 4 * hidden * (2 * hidden)
           + 2 * hidden * vocab)
    return 3.0 * fwd / 1e6


TRAIN_MFLOP_PER_TOKEN = train_mflop_per_token()


def build_module(batch=32, seq_len=32, num_hidden=200, num_embed=200,
                 num_layer=2, vocab=10000, ctx=None):
    import os
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models.lstm import lstm_unroll, lstm_unroll_scan

    # MXNET_LSTM_SCAN=1 benches the fused lax.scan lowering (ops/rnn.py)
    # — same weights/gate layout/API as the unrolled form, ~3x faster
    # seq-len-independent compiles; the default stays on the
    # reference-style unrolled graph for bench continuity.
    builder = lstm_unroll_scan if os.environ.get("MXNET_LSTM_SCAN") == "1" \
        else lstm_unroll
    net = builder(num_layer, seq_len, vocab, num_hidden, num_embed,
                  vocab, dropout=0.0)
    rng = np.random.RandomState(0)
    init_states = {}
    for l in range(num_layer):
        init_states["l%d_init_c" % l] = (batch, num_hidden)
        init_states["l%d_init_h" % l] = (batch, num_hidden)
    data_names = ["data"] + sorted(init_states)
    data_shapes = [("data", (batch, seq_len))] + \
        [(k, init_states[k]) for k in sorted(init_states)]
    label_shapes = [("softmax_label", (batch, seq_len))]

    mod = mx.mod.Module(net, data_names=data_names,
                        label_names=["softmax_label"],
                        context=ctx if ctx is not None else mx.tpu(0))
    mod.bind(data_shapes, label_shapes)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    if mod._fused is None:
        raise RuntimeError("fused train step did not engage; this bench "
                           "measures the fused path only")
    mod._fused_ensure_state()
    sh = mod._fused._batched()

    def stage(a):
        return mx.nd.NDArray(jax.device_put(jnp.asarray(a), sh))
    data = [stage(rng.randint(0, vocab, (batch, seq_len)).astype(np.float32))]
    for k in sorted(init_states):
        data.append(stage(np.zeros(init_states[k], np.float32)))
    label = [stage(rng.randint(0, vocab, (batch, seq_len))
                   .astype(np.float32))]
    return mod, mx.io.DataBatch(data=data, label=label)


from bench import _sync  # noqa: E402  (same sync rule for both benches)


def run(batch=32, seq_len=32, num_hidden=200, num_embed=200,
        warmup=5, iters=50, windows=3):
    mod, staged = build_module(batch=batch, seq_len=seq_len,
                               num_hidden=num_hidden, num_embed=num_embed)
    for _ in range(warmup):
        mod.forward(staged, is_train=True)
        mod.backward()
        mod.update()
    _sync(mod)
    rates = []
    for _ in range(windows):   # median window
        t0 = time.perf_counter()
        for _ in range(iters):
            mod.forward(staged, is_train=True)
            mod.backward()
            mod.update()
        _sync(mod)
        rates.append(batch * seq_len * iters / (time.perf_counter() - t0))
    return sorted(rates)[len(rates) // 2]


def run_superstep_leg(batch=32, seq_len=32, num_hidden=200, num_embed=200,
                      k=8, warmup=2, iters=48, windows=3):
    """The dispatch-bound leg (BENCH_r05: LSTM-200h at 0.46 MFU while
    h1024 hits 0.95 — per-step dispatch + host sync, not compute, is the
    ceiling): K=1 sequential fused steps vs ONE lax.scan superstep
    program per K batches, same module, same pre-staged data.  Returns
    (tokens_per_sec_k1, tokens_per_sec_k8, host_overhead_s_per_step)."""
    import mxnet_tpu as mx
    from mxnet_tpu.feed import MegaBatch, stack_batch_arrays

    mod, staged = build_module(batch=batch, seq_len=seq_len,
                               num_hidden=num_hidden, num_embed=num_embed)

    def window_rates(step_fn, steps_per_iter, n_iters):
        rates = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(n_iters):
                step_fn()
            _sync(mod)
            rates.append(batch * seq_len * steps_per_iter * n_iters
                         / (time.perf_counter() - t0))
        return sorted(rates)[len(rates) // 2]

    def one_step():
        mod.forward(staged, is_train=True)
        mod.backward()
        mod.update()

    for _ in range(warmup):
        one_step()
    _sync(mod)
    r1 = window_rates(one_step, 1, iters)

    # megabatch pre-staged ONCE in the superstep input layout (K copies
    # of the same staged batch — identical FLOPs to the K=1 leg),
    # through the SAME staging primitive production uses
    sh = mod._fused.megabatched_sharding()

    def stack(arr):
        return mx.nd.NDArray(stack_batch_arrays([arr] * k, sh))
    mega = MegaBatch(data=[stack(a) for a in staged.data],
                     label=[stack(a) for a in staged.label], k=k)

    def one_superstep():
        if not mod.superstep_train(mega):
            raise RuntimeError("superstep refused to dispatch")
    one_superstep()   # compile
    _sync(mod)
    rk = window_rates(one_superstep, k, max(1, iters // k))

    # the host-side cost superstep amortizes away: per-step wall at K=1
    # minus per-step wall at K (same program body, K-fold fewer
    # dispatch+sync round trips)
    tokens = batch * seq_len
    overhead = max(0.0, tokens / r1 - tokens / rk)
    return r1, rk, overhead


def superstep_leg_json(k=8):
    """The superstep leg as bench-JSON keys (shared by this bench's main
    and bench.py so both entry points emit identical fields)."""
    r1, rk, overhead = run_superstep_leg(k=k)
    return {"lstm_superstep_k1_tokens_per_sec": round(r1, 1),
            "lstm_superstep_tokens_per_sec": round(rk, 1),
            "lstm_superstep_k": k,
            "lstm_step_host_overhead_s": round(overhead, 7)}


def main():
    os.environ.setdefault("MXNET_COMPUTE_DTYPE", "bfloat16")
    import jax
    from bench import probe_peak_tflops
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    # measured round-5 sweep (one process): b256 0.21 MFU -> b1024 0.28 ->
    # b2048 0.33 -> b4096 plateaus 0.34.  The plateau is the PTB shape's
    # ceiling: 76% of its FLOPs are the vocab projection with K=200 and
    # the gates have K=400 — both under-fill the 256-deep bf16 MXU tile,
    # so utilization saturates once M stops being the constraint.
    value = run(batch=2048)
    peak = probe_peak_tflops()
    dev = jax.devices()[0]
    out = {
        "metric": "ptb_lstm_train_tokens_per_chip",
        "value": round(value, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(value / BASELINE_TOKENS_S_PER_CHIP, 3),
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "path": "module_api_fused",
        "mfu": round(value * TRAIN_MFLOP_PER_TOKEN * 1e6 / (peak * 1e12), 4),
        "peak_tflops": round(peak, 1),
    }
    out.update(superstep_leg_json(k=8))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
