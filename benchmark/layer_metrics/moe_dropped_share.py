"""Per-layer metric ``moe_dropped_share``: token-choices a capacity
dropped over all token-choices routed in the window, every routed block
together.  A drop-free configuration must read 0.  Nothing where the
program records no ``moe:load`` counter."""
LAYER = "routed experts"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    import moe_load
    rows = [r for block in moe_load.window_samples(obs).values()
            for r in block]
    total = sum(r["routed"] + r["dropped"] for r in rows)
    if not total:
        return None
    return 100.0 * sum(r["dropped"] for r in rows) / total, \
        {"samples": len(rows)}
