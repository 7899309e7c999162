"""Per-layer metric ``moe_held_rows_share``: of the token-choices routed
in a step, the share that fell on experts this rank holds: the median
over the window's steps, every routed block together.  8 of 256 experts
held under a balanced router read 3.1 %: how near the held experts' load
is to the deployment's, and whether the router collapses onto or away
from them.  Nothing where the program records no ``moe:load`` counter,
or one without ``held`` (a program that holds every expert)."""
LAYER = "routed experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    import moe_load
    import stats
    blocks = [rows for rows in moe_load.window_samples(obs).values()
              if rows and all("held" in r for r in rows)]
    steps = min((len(rows) for rows in blocks), default=0)
    if not steps:
        return None
    shares = []
    for at in range(-steps, 0):
        routed = sum(rows[at]["routed"] for rows in blocks)
        if routed:
            shares.append(100.0 * sum(rows[at]["held"] for rows in blocks)
                          / routed)
    if not shares:
        return None
    return stats.median(shares), {"samples": len(shares),
                                  "blocks": len(blocks)}
