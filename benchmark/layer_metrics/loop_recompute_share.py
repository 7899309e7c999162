"""Per-layer metric ``loop_recompute_share``: per cent of the first
device's busy time a traced step spends forming a loop node's passes
AGAIN in the backward pass: the operations of the step program whose
``op_name`` lies under ``RECOMPUTED`` (``rematted_computation``, the
segment JAX writes for what a ``jax.checkpoint`` forms again; the loop
node's ``recompute``, ``mxnet_tpu/ops/control_flow.py``).  The program
says which instruction has which ``op_name``
(``mxnet_tpu.trace.program_op_names``, read from the optimized HLO of the
executable that ran); the trace says how long each instruction ran.  It
is the price of keeping one pass's activations and not all passes': a
forward is a third of forward + backward, so about a quarter of the step
where the whole step is inside the loop.  A fusion has ONE ``op_name``: one
that spans a formed-again operation and a backward one counts under
either.  The ``while`` operations of a traced loop are events of their
own that span their bodies' operations; they carry no such segment and
are not counted, ``while_ms`` gives their time.  Nothing where the program
gives no such names (an older commit), the run has no trace, or no
operation lies under the segment (a step without a recomputed loop)."""
LAYER = "loop node"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
DRIVERS = ("train_fit",)
PROGRAM = "fused:step"
RECOMPUTED = "/rematted_computation/"


def program_op_names():
    """{instruction: op_name} of this process's step program, or None."""
    try:
        from mxnet_tpu import trace
    except ImportError:
        return None
    names_of = getattr(trace, "program_op_names", None)
    return names_of(PROGRAM) if names_of is not None else None


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    names = program_op_names()
    if names is None:
        return None
    busy_s = tr["per_device"][sorted(tr["per_device"])[0]]["busy_s"]
    again = loops = 0.0
    for key, seconds in tr["op_seconds"].items():
        instruction, _, rest = key.partition(" ")
        if RECOMPUTED in names.get(instruction, ""):
            again += seconds
        elif rest.split(" ", 1)[0] == "while":
            loops += seconds
    if not again or not busy_s:
        return None
    steps = tr["steps"]
    return 100.0 * again / busy_s, {
        "recomputed_ms": 1e3 * again / steps, "busy_ms": 1e3 * busy_s / steps,
        "while_ms": 1e3 * loops / steps, "steps": steps}
