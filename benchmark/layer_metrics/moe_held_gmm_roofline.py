"""Per-layer metric ``moe_held_gmm_roofline``: the routed experts' grouped
products' share of their roofline over the rows this rank HOLDS: the least
time the chip needs for the products of every expert layer built over the
held rows of the traced steps (``held_gmm_work``, below) over the device
time of the operations whose name begins ``ragged-dot`` (XLA:TPU's kernel
for ``lax.ragged_dot`` and the repo's ``ragged-dot-gmm`` /
``ragged-dot-tgmm`` alike: the same work whatever implements it).  It
counts no row a tile pads to and no row a static bound sizes a pass by:
one expert-parallel rank's 8 of 128 experts see ~190 rows each in 256-row
tiles, and the share says what that costs (ROADMAP R16's yardstick, here
for one cell).  The held rows are the ``held`` of the program's
``moe:load`` samples (one a step and expert block) of the traced steps.
``obs`` does not say which steps those were, so they are found as the
driver finds them: the first step that ends behind the driver's own
``TRACE_START_SHARE`` of the window arms the profiler, the next opens the
trace, and the steps behind it are the traced ones (``traced_slice``); the
window's clock is read off the samples, so the slice can lie a step off.
The extras say what that would cost (``held_rows_a_step_off``,
``value_a_step_off``): in this cell a step's held rows swing with its
batch, a step off moves the 25 steps' mean by 6 % (my chip run, PR 71:
1362.6 / 1544.2 for 1448.9) and the reading by a quarter of a percent,
since the work is the weights' fetch and the rows are 4 % of its bytes
(``tests/benchmark/test_cell_nemotron.py`` shifts it).  Nothing where
the trace holds no such operation, the program records no ``moe:load``
sample with ``held``, or the configuration does not say its experts'
form (``moe_expert_matrices``)."""
LAYER = "routed experts"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PREFIX = "ragged-dot"
COUNTER = "moe:load"
# drivers/train_fit.py TraceControl: the step that arms the profiler and
# the one whose annotation opens the trace are not traced
UNTRACED_STEPS = 2


def expert_layers(config) -> int:
    """The layers BUILT (the first ``num_hidden_layers`` of
    ``layer_types``) that are routed expert layers."""
    built = config["layer_types"][:int(config["num_hidden_layers"])]
    return sum(1 for kind in built if kind == "moe")


def held_gmm_work(config, held_rows: float):
    """(operations, bytes) of a training step's grouped products over
    ``held_rows`` rows, all expert layers together.  An expert of
    ``moe_expert_matrices`` matrices ``D x W`` (2: plain, ``act(x W1)
    W2``; 3: gated) runs each forward, backward-data and backward-weight:
    ``3 x matrices`` products of ``2 rows D W``.  Bytes, a product: its
    rows in and out (``rows (D + W)`` whichever way it runs) and the held
    experts' stacked ``(held, D, W)`` weight, or its gradient, once, in
    the compute dtype."""
    import kernel_rooflines
    item = kernel_rooflines.ITEMSIZE[config["compute_dtype"]]
    d, w = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    held = int(config["n_routed_experts"])
    products = 3 * int(config["moe_expert_matrices"])
    ops = products * 2.0 * held_rows * d * w
    nbytes = products * item * (held_rows * (d + w)
                                + expert_layers(config) * held * d * w)
    return float(ops), float(nbytes)


def trace_start_share() -> float:
    """The share of the window that passes before the driver starts the
    profiler: the driver's own constant, read from its file."""
    import manifest
    return float(manifest.load_module("drivers", "train_fit")
                 .TRACE_START_SHARE)


def traced_slice(stamps, steps: int, window_s: float, shift: int = 0):
    """Which of a window's samples, ``stamps`` their times in microseconds,
    are the ``steps`` traced steps' -> a slice.  ``shift`` moves it by
    whole steps: the test's question of what a misplaced slice costs."""
    start = stamps[0] + 1e6 * trace_start_share() * window_s
    armed = next((i for i, ts in enumerate(stamps) if ts >= start),
                 len(stamps))
    first = min(max(armed + UNTRACED_STEPS + shift, 0),
                max(len(stamps) - steps, 0))
    return slice(first, first + steps)


def traced_held_rows(obs, shift: int = 0):
    """(held rows a traced step, every expert block together; samples a
    block) from the ring's ``moe:load`` samples, or None."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return None
    events = getattr(trace, "counter_events", None)
    n, steps = int(obs.get("steps_in_window") or 0), obs["trace"]["steps"]
    if events is None or not n:
        return None
    blocks = {}
    for e in sorted(events(names=(COUNTER,)), key=lambda e: e["ts"]):
        if "held" in (e.get("args") or {}):
            blocks.setdefault(e.get("id"), []).append(
                (e["ts"], float(e["args"]["held"])))
    total, used = 0.0, 0
    for rows in blocks.values():
        window = rows[-n:]
        traced = window[traced_slice([ts for ts, _ in window], steps,
                                     obs["window_s"], shift)]
        total += sum(held for _, held in traced) / len(traced)
        used = len(traced)
    return (total, used) if blocks else None


def read(obs):
    import kernel_rooflines
    import trace_reduce
    tr = obs.get("trace")
    config = obs["config"]
    if not tr or not tr["steps"] or "moe_expert_matrices" not in config:
        return None
    kernel_s = trace_reduce.seconds_of(tr["op_seconds"], PREFIX) / tr["steps"]
    held = traced_held_rows(obs) if kernel_s else None
    if not held:
        return None
    def share(rows):
        least_s, bound = kernel_rooflines.roofline_time(
            held_gmm_work(config, rows), obs["peaks"])
        return 100.0 * least_s / kernel_s, least_s, bound

    value, least_s, bound = share(held[0])
    # what the slice a step earlier and a step later would have read
    off = [traced_held_rows(obs, shift)[0] for shift in (-1, 1)]
    return value, {
        "kernel_ms": 1e3 * kernel_s, "roofline_ms": 1e3 * least_s,
        "bound": bound, "steps": tr["steps"], "held_rows_a_step": held[0],
        "samples_a_block": held[1], "held_rows_a_step_off": off,
        "value_a_step_off": [share(rows)[0] for rows in off]}
