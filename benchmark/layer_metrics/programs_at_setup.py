"""Per-layer metric ``programs_at_setup``: compile requests before the window
opened, with how many of them the persistent cache did not answer."""
LAYER = "compile / cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    return float(obs["compile"]["at_setup"]), \
        {"compiled": obs["compile"]["compiled"]}
