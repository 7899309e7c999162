"""Per-layer metric ``gsc_roofline``: the double-gated short
convolution's backward kernel's share of its roofline: the least time
the chip needs for the backward pass of a step's ``y = C * conv(B * u)``
in every convolution layer built (``gated_conv_work``, below: what the
ALGORITHM must move once, and the taps' products) over the device time
of the operations whose name begins ``gated_conv_bwd`` (the Pallas
kernel ``CausalConv1D(gated=True)``'s backward pass lowers to on a TPU).
The forward pass is XLA's fusion of the plain form (PR 61 measured a
forward kernel no faster and deleted it) and has no name of its own to
read: ``scope_gsc_ms``'s ``gsc_conv`` holds both passes.  The work is
from the configuration alone, whatever implements the pass: a form that
cuts the thirds, pads a copy or writes the cotangent in parts moves more
than this counts, and the share says so.  Memory-bound by two orders:
the share is the kernel's bytes a second over the chip's.  Nothing where
the trace holds no such operation (the plain form; an older commit)."""
LAYER = "linear attention"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "gated_conv_bwd"


def conv_layers(config) -> int:
    """The layers BUILT (``built_layers`` of the published
    ``layer_types``) whose mixer is the convolution."""
    kinds = config["layer_types"]
    return sum(1 for layer in config["built_layers"]
               if kinds[layer] == "conv")


def gated_conv_work(config, traffic):
    """(operations, bytes) of the backward pass of a training step's
    gated convolutions, D = ``hidden_size`` channels and W =
    ``conv_L_cache`` taps, a token and layer.  Bytes in the compute
    dtype, each tensor once: ``[B | C | u]`` (3 D) and ``dy`` (D) read,
    the projection's cotangent (3 D) written: 7 D (the forward's 4 D are
    XLA's to move).  Operations: ``B * u`` and its W multiply-adds again
    (the pass keeps nothing but its input: 2 W + 1), the taps' transpose
    (2 W), the taps' own gradient (2 W) and five products of the gates'
    rule: 6 W + 6 a channel."""
    import kernel_rooflines
    b, t, _, item = kernel_rooflines._sizes(config, traffic)
    d, w = int(config["hidden_size"]), int(config["conv_L_cache"])
    tokens = b * t * conv_layers(config)
    return float(tokens * d * (6 * w + 6)), float(tokens * d * 7 * item)


def read(obs):
    import kernel_rooflines
    return kernel_rooflines.read_share(obs, PREFIX, gated_conv_work)
