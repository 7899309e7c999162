"""Per-layer metric ``device_step_ms``: device busy time per step: the union of
the device's operation intervals over the traced steps."""
LAYER = "ops"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    tr = obs["trace"]
    if not tr or not tr["steps"]:
        return None
    return 1e3 * tr["busy_s"] / tr["steps"], {"steps": tr["steps"]}
