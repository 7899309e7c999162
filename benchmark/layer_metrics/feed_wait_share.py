"""Per-layer metric ``feed_wait_share``: share of the window the fit loop spends
inside the wrapper around the iterator's next() and reset()."""
LAYER = "feed"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
DRIVERS = ("train_fit",)


def read(obs):
    if not obs["clean_s"]:
        return None
    return 100.0 * obs["feed_s"] / obs["clean_s"]
