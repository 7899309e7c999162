"""Per-layer metric ``compiles_in_window``: compile requests that reached JAX's
backend inside the window (``count_backend_compiles``); must read 0."""
LAYER = "compile / cache"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    return float(obs["compile"]["in_window"])
