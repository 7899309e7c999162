"""The seven readers of the set-up's span tree (``benchmark/setup_spans.py``
and ``layer_metrics/setup_*_s.py``): on spans put into the ring by hand,
and on the ring a real ``fit`` leaves behind on the CPU at tiny widths.
A CPU run checks the arithmetic; its times are never results.
"""
import threading
import time

import pytest

import cellbench_util as util
import manifest

import mxnet_tpu as mx

READERS = {"setup_check_module_s": "entry points",
           "setup_train_module_s": "entry points",
           "setup_bind_init_s": "entry points",
           "setup_warmup_s": "train step",
           "setup_compile_trace_s": "compile / cache",
           "setup_compile_lower_s": "compile / cache",
           "setup_compile_backend_s": "compile / cache"}
CHECK, TRAIN = 7, 3          # module numbers: no order between them
STEPS_IN_WINDOW = 4
OPENING = 26.0


@pytest.fixture(autouse=True)
def fresh_ring():
    mx.trace.reset()
    yield
    mx.trace.reset()


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _read(name, steps_in_window=STEPS_IN_WINDOW):
    got = _reader(name).read({"steps_in_window": steps_in_window})
    if got is None:
        return None
    return got if isinstance(got, tuple) else (got, {})


def _put(name, t0, dur, cat="train", **args):
    mx.trace.complete(name, t0, dur, cat=cat, **args)


def _put_compile(t0, fun, trace_s, lower_s, backend_s, **cache):
    """One program's three spans back to back from ``t0``."""
    _put("compile:trace", t0, trace_s, cat="compile", fun=fun)
    _put("compile:lower", t0 + trace_s, lower_s, cat="compile",
         fun="jit(%s)" % fun)
    _put("compile:backend", t0 + trace_s + lower_s, backend_s,
         cat="compile", fun="jit(%s)" % fun, **cache)


def _put_check_module():
    """The harness's reference check: bind, init, one eager step's
    programs; 10.0 .. 14.0, nothing of it inside any ``fit``."""
    _put("module:bind", 10.0, 0.5, module=CHECK, for_training=True)
    _put("module:init_params", 10.5, 1.0, module=CHECK)
    _put("module:init_optimizer", 11.5, 0.1, module=CHECK)
    # "inner" is traced inside "check_step"'s trace: counted once
    _put("compile:trace", 12.1, 0.2, cat="compile", fun="inner")
    _put_compile(12.0, "check_step", 0.4, 0.6, 0.25, cache="hit",
                 load_s=0.2)


def _put_training_module(with_call=True):
    """A bucketing module as the harness drives it: bind, init_params
    and prepare (around an inner bind) before ``fit``, then ``fit``'s
    own calls, two warm-up steps and the window's four."""
    _put("module:bind", 20.2, 0.6, module=TRAIN, for_training=True)
    _put("module:bind", 20.0, 1.0, module=TRAIN, for_training=True)
    _put("module:init_params", 21.0, 0.5, module=TRAIN)
    _put("module:bind", 21.6, 0.1, module=TRAIN, for_training=True)
    _put_compile(21.7, "bucket", 0.05, 0.05, 0.1, cache="miss")
    _put("module:prepare", 21.5, 0.4, module=TRAIN)
    # fit finds the module bound and initialised
    _put("module:bind", 22.0, 0.001, module=TRAIN, for_training=True)
    _put("module:init_params", 22.001, 0.001, module=TRAIN)
    _put("module:init_optimizer", 22.002, 0.098, module=TRAIN)
    _put_compile(22.2, "step", 0.5, 1.0, 1.2, cache="miss")
    _put("fit:step", 22.1, 3.0, count=1)
    _put("fit:step", 25.2, 0.1, count=1)
    # asked for before the opening, ended after it: left out, and not a
    # span that started in the window either
    _put("compile:backend", 25.9, 0.5, cat="compile", fun="jit(late)",
         cache="miss")
    for i in range(STEPS_IN_WINDOW):
        _put("fit:step", OPENING + i, 0.9, count=1)
    if with_call:
        _put("fit:call", 22.0, 9.0, module=TRAIN)


def _put_other_thread():
    """A worker that compiled during the training module's set-up
    (``parallel_warm``), and steps of another thread's ``fit``."""
    def work():
        _put_compile(23.0, "warm", 0.1, 0.2, 0.3, cache="hit", load_s=0.25)
        _put("fit:step", 24.0, 0.5, count=1)
        _put("fit:call", 23.5, 50.0, module=99)
    t = threading.Thread(target=work)
    t.start()
    t.join()


def _put_everything():
    _put_check_module()
    _put_training_module()
    _put_other_thread()


EXPECTED = {
    # first span of any module 10.0, of the training module 20.0
    "setup_check_module_s": (10.0, {}),
    "setup_train_module_s": (OPENING - 20.0, {
        "fit_call_to_window_s": OPENING - 22.0,
        "other_s": 6.0 - 2.0 - 3.1, "ring_dropped": 0}),
    # the outermost of each nest: 1.0 + 0.5 + 0.4 before fit, then
    # fit's own 0.001 + 0.001 + 0.098
    "setup_bind_init_s": (2.0, {
        "bind_s": 1.001, "init_params_s": 0.501, "init_optimizer_s": 0.098,
        "prepare_s": 0.4}),
    "setup_warmup_s": (3.1, {"steps": 2, "first_step_s": 3.0}),
    "setup_compile_trace_s": (0.4 + 0.05 + 0.5 + 0.1, {
        "before_training_module_s": 0.4, "in_training_module_s": 0.65,
        "top": [["step", 0.5], ["check_step", 0.4], ["warm", 0.1],
                ["bucket", 0.05]],
        "in_window": 0}),
    "setup_compile_lower_s": (0.6 + 0.05 + 1.0 + 0.2, {
        "before_training_module_s": 0.6, "in_training_module_s": 1.25,
        "top": [["jit(step)", 1.0], ["jit(check_step)", 0.6],
                ["jit(warm)", 0.2], ["jit(bucket)", 0.05]],
        "in_window": 0}),
    "setup_compile_backend_s": (0.25 + 0.1 + 1.2 + 0.3, {
        "before_training_module_s": 0.25, "in_training_module_s": 1.6,
        "top": [["jit(step)", 1.2], ["jit(warm)", 0.3],
                ["jit(check_step)", 0.25], ["jit(bucket)", 0.1]],
        "in_window": 0, "requests": 4, "cache_hits": 2,
        "load_s": 0.45, "miss_s": 1.3}),
}


def _approx(x):
    if isinstance(x, list):
        return [_approx(v) for v in x]
    return pytest.approx(x, abs=1e-6) if isinstance(x, float) else x


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_built_ring(name):
    _put_everything()
    value, extra = _read(name)
    want, want_extra = EXPECTED[name]
    assert value == pytest.approx(want, abs=1e-6)
    assert set(extra) == set(want_extra)
    for key, v in want_extra.items():
        assert extra[key] == _approx(v), key


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_without_a_fit_call(name):
    """An older program's ring: steps and, at most, spans this reader
    does not know the module of."""
    assert _read(name) is None
    _put_check_module()
    _put_training_module(with_call=False)
    _put_other_thread()            # another thread's fit:call is not ours
    assert _read(name) is None
    _put("fit:call", 22.0, 9.0, module=TRAIN)
    assert _read(name) is not None
    assert _read(name, steps_in_window=0) is None


def test_the_training_module_is_told_by_its_number_not_by_order():
    """A third module that binds while the training module sets up (an
    evaluation module, say) moves neither side of the cut; with no
    earlier module the check reads 0."""
    _put_training_module()
    assert _read("setup_check_module_s")[0] == 0.0
    _put("module:bind", 21.2, 0.2, module=TRAIN + 1, for_training=False)
    assert _read("setup_check_module_s")[0] == 0.0
    assert _read("setup_bind_init_s")[0] == pytest.approx(2.0, abs=1e-6)
    assert _read("setup_train_module_s")[0] == pytest.approx(6.0, abs=1e-6)
    # an earlier fit of ANOTHER module on this thread is part of what came
    # before: the last fit:call names the training module
    _put("module:bind", 5.0, 0.2, module=1, for_training=True)
    _put("fit:step", 5.3, 0.1, count=1)
    _put("fit:call", 5.0, 1.0, module=1)
    assert _read("setup_check_module_s")[0] == pytest.approx(15.0, abs=1e-6)
    assert _read("setup_warmup_s")[1]["steps"] == 2


def test_a_compile_span_in_the_window_is_counted_as_such():
    _put_everything()
    _put_compile(OPENING + 1.5, "retrace", 0.01, 0.01, 0.01, cache="miss")
    # after fit returned (22.0 + 9.0): the request for the step's table
    # of device scopes traces the step once more, and is no one's fault
    _put("compile:trace", 31.5, 0.001, cat="compile", fun="step")
    for name in ("setup_compile_trace_s", "setup_compile_lower_s",
                 "setup_compile_backend_s"):
        value, extra = _read(name)
        assert extra["in_window"] == 1
        assert value == pytest.approx(EXPECTED[name][0], abs=1e-6)


def test_compile_readers_find_nothing_where_no_listener_ran():
    """``MXNET_TRACE=0`` at import and tracing switched on later: module
    spans and no ``compile:*``."""
    _put("module:bind", 20.0, 1.0, module=TRAIN, for_training=True)
    _put("fit:step", 22.1, 3.0, count=1)
    _put("fit:step", OPENING, 0.9, count=1)
    _put("fit:call", 20.0, 9.0, module=TRAIN)
    assert _read("setup_train_module_s", 1)[0] == pytest.approx(6.0)
    for name in ("setup_compile_trace_s", "setup_compile_lower_s",
                 "setup_compile_backend_s"):
        assert _read(name, 1) is None


def test_the_seven_entries_agree_with_their_readers():
    """Appended to ``per_layer``, each equal to its reader, each moving
    ``setup_s`` in every one-chip cell.  The four-chip cell is on no
    list: ``test_cellbench_driver.py`` wants every entry listed for it
    to read from a hand-built ``obs``, and a ring reader finds nothing
    there (the ten ``fit:step`` entries leave it out for the same
    reason); the readers read that cell's ring all the same."""
    doc = manifest.Manifest().doc
    one_chip = [w["name"] for w in doc["workloads"] if w["chips"] == 1]
    mine = [m for m in doc["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    for m in mine:
        reader = _reader(m["name"])
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "program_span", "layer": READERS[m["name"]],
                     "moves": "setup_s", "workloads": one_chip}
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.DRIVERS) == ("s", "lower", "program_span",
                                    READERS[m["name"]], ("train_fit",))
    for w in doc["workloads"]:
        cell = manifest.Manifest().cell(w["name"])
        listed = set(READERS) & {m["name"] for m in cell.per_layer}
        assert listed == (set(READERS) if w["chips"] == 1 else set())


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return util.tiny_copy(tmp_path_factory.mktemp("cellbench_setup"))


@pytest.mark.parametrize("cell_name", ["tiny-dev", "tiny-lstm"])
def test_readers_on_the_ring_a_real_fit_leaves(copy, cell_name):
    """Module.fit and (bind, init_params, prepare, then)
    BucketingModule.fit through the driver, behind its reference check:
    seven numbers that add up inside the harness's own ``setup_s``, and
    two sources for one count of compile requests."""
    import run as bench_run
    import setup_spans
    cell = manifest.Manifest(copy).cell(cell_name)
    driver = manifest.load_module("drivers", cell.driver, cell.bench_dir)
    t_process = time.perf_counter()
    result = driver.run(cell, [mx.cpu(0)], 3, 1.0, False, t_process,
                        {"bf16_flops_per_s": 1e12}, lambda line: None)
    obs, elapsed = result["_obs"], result["_e2e"]["setup_s"]
    got = bench_run.layer_metrics(cell, obs)
    assert not set(READERS) & set(bench_run.absent_metrics(cell, got))
    value = {name: got[name]["value"] for name in READERS}
    assert all(got[name]["unit"] == "s" for name in READERS)
    assert all(v >= 0.0 for v in value.values()), value
    # the reference check's module came first and is not the one fit ran
    assert value["setup_check_module_s"] > 0.0
    assert value["setup_check_module_s"] + value["setup_train_module_s"] \
        <= elapsed
    train = got["setup_train_module_s"]
    assert value["setup_bind_init_s"] + value["setup_warmup_s"] \
        <= value["setup_train_module_s"]
    assert train["other_s"] >= 0.0 and train["ring_dropped"] == 0
    assert 0.0 < train["fit_call_to_window_s"] <= train["value"]
    bind_init = got["setup_bind_init_s"]
    assert bind_init["bind_s"] > 0 and bind_init["init_params_s"] > 0 \
        and bind_init["init_optimizer_s"] > 0
    # only the bucketing module is prepared
    assert (bind_init["prepare_s"] > 0) == (cell_name == "tiny-lstm")
    warm = got["setup_warmup_s"]
    assert warm["steps"] >= cell.traffic["warmup_steps"]
    assert 0.0 < warm["first_step_s"] <= warm["value"]
    backend = got["setup_compile_backend_s"]
    assert backend["requests"] == got["programs_at_setup"]["value"]
    assert backend["requests"] - backend["cache_hits"] \
        == got["programs_at_setup"]["compiled"]
    assert backend["load_s"] <= backend["value"]
    for name in ("setup_compile_trace_s", "setup_compile_lower_s",
                 "setup_compile_backend_s"):
        m = got[name]
        assert m["in_window"] == 0 and got["compiles_in_window"][
            "value"] == 0
        assert m["value"] > 0
        assert m["before_training_module_s"] + m["in_training_module_s"] \
            == pytest.approx(m["value"])
        assert 1 <= len(m["top"]) <= setup_spans.TOP_FUNS
        assert m["value"] <= elapsed
