"""Per-layer metric ``mla_attn_roofline``: the attention kernel's share of
its roofline where the query and key heads are another size than the
value heads (latent attention): the least time the chip needs for a
step's causal attention at those sizes
(``kernel_rooflines.latent_attention_work``: the useful causal half at
``qk_nope_head_dim + qk_rope_head_dim`` and ``v_head_dim``, forward and
backward, no padded lane) over the device time of the operations whose
name begins ``splash_mha`` (the forward and the fused backward kernel of
JAX's splash attention, which ``causal_attention`` lowers to on a TPU).
Nothing where the trace holds no such operation."""
LAYER = "Pallas kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "splash_mha"


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(
        obs, PREFIX, kernel_rooflines.latent_attention_work)
