"""Per-layer metric ``feed_reset_share``: share of the window spent inside the
iterator's reset() (an epoch boundary of the loader)."""
LAYER = "feed"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
DRIVERS = ("train_fit",)


def read(obs):
    if not obs["clean_s"]:
        return None
    return 100.0 * obs["reset_s"] / obs["clean_s"], {"resets": obs["resets"]}
