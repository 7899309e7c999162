"""``benchmark/kernel_rooflines.py`` and its four readers: the operations
and bytes against sums written out by hand at OLMoE-1B-7B's and
Kimi-Linear-48B-A3B's published sizes, and the share on reductions made
by hand.  A CPU run has no trace: there the readers return None, never a
number."""
import pytest

import cellbench_util as util  # noqa: F401
import kernel_rooflines as kr
import manifest

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = {"attn_roofline": ("Pallas kernels", "splash_mha"),
           "moe_gmm_roofline": ("routed experts", "ragged-dot"),
           "mla_attn_roofline": ("Pallas kernels", "splash_mha"),
           "kda_roofline": ("linear attention", "kda_chunk")}


@pytest.fixture(scope="module")
def olmoe():
    """The configuration and the traffic as the cell runs them."""
    cell = manifest.Manifest().cell("olmoe-1b-7b-train-4k")
    return cell.config, cell.traffic


def test_attention_at_olmoes_published_sizes(olmoe):
    """B 4, H 16, T 4096, Dh 128, one layer, bfloat16."""
    ops, nbytes = kr.causal_attention_work(*olmoe)
    forward = 2 * 4 * 16 * 4096 * 4096 * 128
    assert forward == 274_877_906_944
    assert ops == forward + 2.5 * forward == 962_072_674_304
    # q, k, v, o, dq, dk, dv, do: eight tensors of 4 x 4096 x 16 x 128 x 2 B
    assert nbytes == 8 * 67_108_864 == 536_870_912
    least, bound = kr.roofline_time((ops, nbytes), V5E)
    assert bound == "compute"
    assert least == pytest.approx(4.8836e-3, rel=1e-4)


def test_grouped_matmuls_at_olmoes_published_sizes(olmoe):
    """rows 4 x 4096 x 8 = 131 072, D 2048, W 1024, E 64, nine matmuls."""
    ops, nbytes = kr.grouped_matmul_work(*olmoe)
    one = 2 * 131_072 * 2048 * 1024
    assert one == 549_755_813_888
    assert ops == 9 * one == 4_947_802_324_992
    one_bytes = 2 * (131_072 * (2048 + 1024) + 64 * 2048 * 1024)
    assert one_bytes == 1_073_741_824
    assert nbytes == 9 * one_bytes == 9_663_676_416
    least, bound = kr.roofline_time((ops, nbytes), V5E)
    assert bound == "compute"
    assert least == pytest.approx(25.116e-3, rel=1e-4)
    assert nbytes / V5E["hbm_bytes_per_s"] == pytest.approx(11.80e-3,
                                                            rel=1e-3)


def test_the_work_follows_the_sizes_not_a_name(olmoe):
    config, traffic = olmoe
    deep = dict(config, num_hidden_layers=16, name="anything")
    for work in (kr.causal_attention_work, kr.grouped_matmul_work):
        a, b = work(config, traffic), work(deep, traffic)
        assert (b[0], b[1]) == (16 * a[0], 16 * a[1])
        wide = work(config, dict(traffic, batch_per_chip=8))
        assert wide[0] == 2 * a[0]
    f32 = kr.causal_attention_work(dict(config, compute_dtype="float32"),
                                   traffic)
    assert f32 == (962_072_674_304, 2 * 536_870_912)
    # grouped-query attention with a head size of its own (20 query and 4
    # key/value heads of 128 under a hidden size of 5120, one sequence of
    # 8192): the operations follow the query heads, k and v are smaller
    gqa = dict(config, num_attention_heads=20, num_key_value_heads=4,
               head_dim=128, hidden_size=5120, input={"seq_len": 8192})
    ops, nbytes = kr.causal_attention_work(gqa, dict(traffic,
                                                     batch_per_chip=1))
    assert ops == 3.5 * (2 * 20 * 8192 * 8192 * 128) == 1_202_590_842_880
    assert nbytes == 2 * 8192 * 128 * (4 * 20 + 4 * 4) == 201_326_592
    # an expert width of its own where the configuration has the key
    own = kr.grouped_matmul_work(dict(config, moe_intermediate_size=512),
                                 traffic)
    assert own[0] == 4_947_802_324_992 / 2
    # memory-bound where the rows are few: the stacked weight dominates
    few = kr.grouped_matmul_work(
        dict(config, num_experts_per_tok=1, input={"seq_len": 128}),
        dict(traffic, batch_per_chip=1))
    assert kr.roofline_time(few, V5E)[1] == "memory"


def _obs(olmoe, op_seconds, steps=20):
    config, traffic = olmoe
    return {"driver": "train_fit", "config": config, "traffic": traffic,
            "peaks": V5E, "trace": {"steps": steps, "op_seconds": op_seconds}}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_names_its_layer_and_its_kernels_prefix(name):
    reader = manifest.load_module("layer_metrics", name)
    assert (reader.LAYER, reader.PREFIX) == READERS[name]
    assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.DRIVERS) == \
        ("%", "higher", "device_trace", ("train_fit",))


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_finds_nothing_without_a_trace_or_its_kernel(name, olmoe):
    reader = manifest.load_module("layer_metrics", name)
    # an untraced run, a CPU run, a hand-built obs of another test
    assert reader.read({"trace": None}) is None
    assert reader.read({"steps_in_window": 5}) is None
    # a trace of a model without the kernel: every other cell
    assert reader.read(_obs(olmoe, {"fusion.70 fusion bf16[8]": 0.5})) is None
    assert reader.read(_obs(olmoe, {}, steps=0)) is None


def test_the_attention_reader_on_a_reduction_made_by_hand(olmoe):
    """PR 27's trace, 20 steps: forward 2.59 ms, fused backward 5.12."""
    ops = {"splash_mha_fwd_residuals custom-call bf16[4,16,4096,128]":
           20 * 2.59e-3,
           "splash_mha_dkv_no_residuals.1 custom-call bf16[4,4,16,4096,128]":
           20 * 5.12e-3,
           "ragged-dot-none.3 custom-call bf16[131072,2048]": 20 * 5.1e-3,
           "fusion.74 fusion bf16[16384,2048]": 20 * 5.9e-3}
    value, extra = manifest.load_module(
        "layer_metrics", "attn_roofline").read(_obs(olmoe, ops))
    assert extra["kernel_ms"] == pytest.approx(7.71)
    assert extra["roofline_ms"] == pytest.approx(4.8836, rel=1e-4)
    assert value == pytest.approx(100 * 4.8836 / 7.71, rel=1e-4)
    assert (extra["bound"], extra["steps"]) == ("compute", 20)


def test_the_grouped_matmul_reader_on_a_reduction_made_by_hand(olmoe):
    ops = {"ragged-dot-none.%d custom-call bf16[131072,2048]" % i:
           20 * 5.1e-3 for i in range(9)}
    ops["splash_mha_fwd_residuals custom-call bf16[4,16,4096,128]"] = 0.05
    value, extra = manifest.load_module(
        "layer_metrics", "moe_gmm_roofline").read(_obs(olmoe, ops))
    assert extra["kernel_ms"] == pytest.approx(45.9)
    assert value == pytest.approx(100 * 25.116 / 45.9, rel=1e-4)
    # a count that is too high reads over 100 and is not clipped: the
    # driver refuses it, which is how the fault is found
    ops = {"ragged-dot-none custom-call bf16[131072,2048]": 20 * 20e-3}
    value, _ = manifest.load_module(
        "layer_metrics", "moe_gmm_roofline").read(_obs(olmoe, ops))
    assert value == pytest.approx(125.58, rel=1e-4)


# -- the Kimi cell's two kernels ---------------------------------------------

@pytest.fixture(scope="module")
def kimi():
    cell = manifest.Manifest().cell("kimi-linear-48b-a3b-train-4k")
    return cell.config, cell.traffic


def test_latent_attention_at_kimis_published_sizes(kimi):
    """B 1, H 32, T 4096, q and k 128 + 64 = 192 wide, v 128, bfloat16;
    of the five layers built one (layer 4) mixes by latent attention."""
    ops, nbytes = kr.latent_attention_work(*kimi)
    pairs = 32 * 4096 * 4096            # 2 x the causal half, every head
    assert pairs == 536_870_912
    forward = pairs * (192 + 128)       # Q K^T at 192, P V at 128
    backward = pairs * (3 * 192 + 2 * 128)
    assert (forward, backward) == (171_798_691_840, 446_676_598_784)
    assert ops == forward + backward == 618_475_290_624
    # q, k, dq, dk of 4096 x 32 x 192 and v, o, dv, do of 4096 x 32 x 128,
    # 2 B an element; the 256 lanes the kernel pads q and k to count nowhere
    assert nbytes == 2 * 4096 * 32 * (4 * 192 + 4 * 128) == 335_544_320
    least, bound = kr.roofline_time((ops, nbytes), V5E)
    assert bound == "compute"
    assert least == pytest.approx(3.13947e-3, rel=1e-5)
    assert nbytes / V5E["hbm_bytes_per_s"] == pytest.approx(0.4097e-3,
                                                            rel=1e-3)


def test_the_kda_chunks_at_kimis_published_sizes(kimi):
    """B 1, H 32 heads of 128, T 4096 in chunks of 64, bfloat16; of the
    five layers built four (1, 2, 3, 5) are KDA."""
    ops, nbytes = kr.kda_chunk_work(*kimi)
    full = 2 * 64 * 128 * 128           # a chunk against a (128, 128) state
    half = 64 * 64 * 128                # the triangle of a (64, 64) product
    assert (full, half) == (2_097_152, 524_288)
    # scores of k and of q, read by k, system, read by q, intra-chunk
    # output, write
    forward = half + half + full + half + full + half + full
    assert forward == 3 * full + 4 * half == 8_388_608
    # the chunk again (two scores, the read, the system), then the
    # transposes: reads 2, write 2, state 2, intra-chunk 2 half, system
    # 2 half, scores 4 half
    backward = (2 * half + full + half) + 6 * full + 8 * half
    assert backward == 7 * full + 11 * half == 20_447_232
    chunk_heads = 32 * (4096 // 64)
    assert chunk_heads == 2048
    assert ops == 4 * chunk_heads * (forward + backward) == 236_223_201_280
    seq = 4096 * 32 * 128               # elements of q, k, v, o or the decay
    beta = 4 * 4096 * 32
    states = 4 * chunk_heads * 128 * 128
    assert (seq, beta, states) == (16_777_216, 524_288, 134_217_728)
    # forward: q, k, v, o at 2 B, the decay at 4 B, beta, the states out
    fwd = 4 * 2 * seq + 4 * seq + beta + states
    # backward: q, k, v, do at 2 B, the decay, beta, the states in; dq,
    # dk, dv at 2 B, the decay's and beta's gradients at 4 B out
    bwd = (4 * 2 * seq + 4 * seq + beta + states) \
        + (3 * 2 * seq + 4 * seq + beta)
    assert (fwd, bwd) == (336_068_608, 504_365_056)
    assert nbytes == 4 * (fwd + bwd) == 3_361_734_656
    least, bound = kr.roofline_time((ops, nbytes), V5E)
    assert bound == "memory"
    assert least == pytest.approx(4.10468e-3, rel=1e-5)
    assert ops / V5E["bf16_flops_per_s"] == pytest.approx(1.1991e-3,
                                                          rel=1e-4)


@pytest.mark.parametrize("heads, head_dim, seq_len, batch",
                         [(16, 128, 4096, 4), (20, 256, 8192, 1)])
def test_latent_attention_with_equal_heads_is_causal_attention(
        olmoe, heads, head_dim, seq_len, batch):
    """q, k and v of one size and as many key/value heads as query heads:
    the two functions count the same work."""
    config, traffic = olmoe
    traffic = dict(traffic, batch_per_chip=batch)
    config = dict(config, num_attention_heads=heads,
                  num_key_value_heads=heads, head_dim=head_dim,
                  hidden_size=heads * head_dim, input={"seq_len": seq_len},
                  qk_nope_head_dim=head_dim - 64, qk_rope_head_dim=64,
                  v_head_dim=head_dim)
    assert kr.latent_attention_work(config, traffic) == \
        kr.causal_attention_work(config, traffic)


@pytest.mark.parametrize("change, mla_layers, kda_layers", [
    ({}, 1, 4),
    ({"num_hidden_layers": 27}, 7, 20),         # as published
    ({"num_hidden_layers": 3}, 0, 3),
    ({"num_hidden_layers": 5, "num_nextn_predict_layers": 1}, 2, 4),
], ids=["as-run", "published-depth", "no-mla-built", "one-more-predicted"])
def test_the_layers_counted_are_the_mixers_built(kimi, change, mla_layers,
                                                 kda_layers):
    config, traffic = kimi
    one = dict(config, num_hidden_layers=4, name="anything",
               linear_attn_config=dict(config["linear_attn_config"],
                                       full_attn_layers=[4], kda_layers=[1]))
    changed = dict(config, **change)
    for work, layers in ((kr.latent_attention_work, mla_layers),
                         (kr.kda_chunk_work, kda_layers)):
        a, b = work(one, traffic), work(changed, traffic)
        assert b == (layers * a[0], layers * a[1])


def test_the_new_work_follows_the_sizes_not_a_name(kimi):
    config, traffic = kimi
    # a configuration without the list of mixers: every layer is latent
    # attention, and the one predicted token's module has one more
    plain = {k: v for k, v in config.items() if k != "linear_attn_config"}
    a = kr.latent_attention_work(config, traffic)
    b = kr.latent_attention_work(dict(plain, num_nextn_predict_layers=1,
                                      name="anything"), traffic)
    assert b == (6 * a[0], 6 * a[1])
    for work in (kr.latent_attention_work, kr.kda_chunk_work):
        a = work(config, traffic)
        wide = work(config, dict(traffic, batch_per_chip=2))
        assert wide == (2 * a[0], 2 * a[1])
    # float32 compute: every tensor of latent attention doubles; of the
    # KDA kernels' q, k, v, o and their gradients only (11 of them a
    # layer), the decay, beta and the states are float32 either way
    f32 = dict(config, compute_dtype="float32")
    assert kr.latent_attention_work(f32, traffic) == \
        (618_475_290_624, 2 * 335_544_320)
    assert kr.kda_chunk_work(f32, traffic) == \
        (236_223_201_280, 3_361_734_656 + 4 * 11 * 2 * 16_777_216)
    # a sequence of half a chunk is one chunk of 32 tokens a head
    short = kr.kda_chunk_work(dict(config, input={"seq_len": 32}), traffic)
    assert short[0] == 4 * 32 * (10 * 2 * 32 * 128 * 128
                                 + 15 * 32 * 32 * 128)


KIMI_TRACE = {
    # PERF.md section 5 (the builder's chip run, PR 33): 17 traced steps
    "mla_attn_roofline": (
        {"splash_mha_fwd_residuals custom-call bf16[1,32,4096,256]": 1.78e-3,
         "splash_mha_dkv_no_residuals.1 custom-call bf16[4,32,4096,256]":
         3.95e-3}, 5.73, 3.13947, "compute"),
    "kda_roofline": (
        dict([("kda_chunk_fwd.%d custom-call f32[1,32,64,128,128]" % i,
               2.48e-3) for i in range(4, 8)]
             + [("kda_chunk_bwd.%d custom-call f32[1,4096,4096]" % i,
                 4.48e-3) for i in range(4, 8)]), 27.84, 4.10468, "memory"),
}


@pytest.mark.parametrize("name", sorted(KIMI_TRACE))
def test_a_kimi_reader_on_a_reduction_made_by_hand(name, kimi):
    a_step, kernel_ms, roofline_ms, bound = KIMI_TRACE[name]
    ops = {op: 17 * s for op, s in a_step.items()}
    ops["fusion.370 fusion bf16[4096,2304]"] = 17 * 2.61e-3
    ops["ragged-dot-none.3 custom-call bf16[32768,2304]"] = 17 * 0.5e-3
    value, extra = manifest.load_module("layer_metrics", name).read(
        _obs(kimi, ops, steps=17))
    assert extra["kernel_ms"] == pytest.approx(kernel_ms)
    assert extra["roofline_ms"] == pytest.approx(roofline_ms, rel=1e-5)
    assert value == pytest.approx(100 * roofline_ms / kernel_ms, rel=1e-5)
    assert (extra["bound"], extra["steps"]) == (bound, 17)
    assert 0.0 < value < 100.0
