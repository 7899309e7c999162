"""Per-layer metric ``loop_other_ms_p50``: median, over the window's
steps, of ``fit:step`` less ``fit:feed_next``, ``fit:forward_backward``,
``fit:update`` and ``fit:update_metric``: the callbacks
(``fit:batch_end``) and the loop's own bookkeeping.  Nothing where the
program records no ``fit:step``."""
LAYER = "entry points"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import fit_spans
    return fit_spans.median_of(
        fit_spans.window_steps(obs), plus=(fit_spans.STEP,),
        minus=("fit:feed_next", "fit:forward_backward", "fit:update",
               "fit:update_metric"))
