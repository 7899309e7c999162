"""Per-layer metric ``moe_prefix_fit_share``: of the window's (step,
block) samples of ``moe:load`` that carry ``bound``, the static row bound
an expert-parallel rank's sorted layout is sized by, the share with
``held <= bound``: how often a routed block ran over its bound alone
and not once more over the rows behind it (the exact fallback).  100 %
under a router
that keeps the held experts' load under the bound's multiple of its
balanced share.  Nothing where the program records no ``moe:load``
counter or none with ``bound``: a commit before the bound, a program
that holds every expert."""
LAYER = "routed experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
DRIVERS = ("train_fit",)


def read(obs):
    import moe_load
    samples = [r for rows in moe_load.window_samples(obs).values()
               for r in rows if "bound" in r]
    if not samples:
        return None
    fits = sum(r["held"] <= r["bound"] for r in samples)
    return 100.0 * fits / len(samples), {
        "samples": len(samples), "fits": fits,
        "fullest_over_bound": max(r["held"] / r["bound"] for r in samples)}
