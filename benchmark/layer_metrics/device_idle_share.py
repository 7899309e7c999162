"""Per-layer metric ``device_idle_share``: share of the traced window in which no
operation ran on the device (mean over the chips)."""
LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    tr = obs["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
