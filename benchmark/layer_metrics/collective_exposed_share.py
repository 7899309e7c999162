"""Per-layer metric ``collective_exposed_share``: share of the collectives' time
during which no other operation ran on that device; nothing on one
chip."""
LAYER = "mesh / collectives"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)


def read(obs):
    tr = obs["trace"]
    if not tr or not tr["collective_s"] or obs["chips"] < 2:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["collective_s"]
