"""Per-layer metric ``kda_roofline``: the gated delta rule's two chunk
kernels' share of their roofline: the least time the chip needs for a
step's chunks of every KDA layer (``kernel_rooflines.kda_chunk_work``:
each product of the chunked rule once, forward and backward, and what the
kernels must read and write once, the float32 entry states among it) over
the device time of the operations whose name begins ``kda_chunk`` (the
Pallas kernels ``kda_chunk_fwd`` and ``kda_chunk_bwd`` that
``kimi_delta_attention`` lowers to on a TPU).  Nothing where the trace
holds no such operation."""
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "kda_chunk"


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(
        obs, PREFIX, kernel_rooflines.kda_chunk_work)
