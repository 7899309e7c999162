"""Per-layer metric ``moe_load_max_over_mean``: the fullest expert's
token-choices over the mean expert's, per step: the median over the
window's steps, of the worst routed block.  1 is a perfectly balanced
router; the grouped matmuls' tiles and, across chips, the slowest
expert's rank pay for what is above it.  Nothing where the program
records no ``moe:load`` counter."""
LAYER = "routed experts"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    import moe_load
    import stats
    worst = None
    for block, rows in moe_load.window_samples(obs).items():
        ratios = [r["max"] / r["mean"] for r in rows if r.get("mean")]
        if not ratios:
            continue
        value = stats.median(ratios)
        if worst is None or value > worst[0]:
            worst = (value, {"samples": len(ratios), "block": block})
    return worst
