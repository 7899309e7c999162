"""Per-layer metric ``mfu``: model FLOP/s utilization: the traced steps' sample
rate x the configuration's analytic FLOP per sample over chips x the
published bf16 peak.  Not a kernel's roofline share."""
LAYER = "ops"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    if not obs["traced_rate"]:
        return None
    return 100.0 * obs["traced_rate"] * obs["flops_per_sample"] \
        / (obs["chips"] * obs["peaks"]["bf16_flops_per_s"])
