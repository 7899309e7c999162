"""Per-layer metric ``fit_step_ms_p50``: median duration of the program's
``fit:step`` spans of the window (one iteration of ``fit``'s loop, from
before its pull to after its bookkeeping); the inside twin of
``step_ms_p50``.  Nothing where the program records no ``fit:step``.
``ring_events`` and ``ring_dropped`` say whether the span ring wrapped;
``by_bucket``, where batches have bucket keys, how many of the steps each
bucket drew and their median: the overall median follows the draw."""
LAYER = "entry points"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
DRIVERS = ("train_fit",)


def read(obs):
    import fit_spans
    import stats
    steps = fit_spans.window_steps(obs)
    got = fit_spans.median_of(steps, plus=(fit_spans.STEP,))
    if got is None:
        return None
    from mxnet_tpu import trace
    got[1].update(ring_events=trace.event_count(),
                  ring_dropped=trace.drop_count())
    by_bucket = {}
    for row in steps:
        if row["bucket_key"] is not None:
            by_bucket.setdefault(str(row["bucket_key"]), []).append(
                row[fit_spans.STEP])
    if by_bucket:
        got[1]["by_bucket"] = {k: {"steps": len(v), "p50_ms": stats.median(v)}
                               for k, v in sorted(by_bucket.items())}
    return got
