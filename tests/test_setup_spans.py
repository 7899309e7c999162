"""The span tree of the set-up (tier-1, CPU).

``BaseModule.fit`` records one ``fit:call`` a call; ``Module`` and
``BucketingModule`` record ``module:bind``, ``module:init_params``,
``module:init_optimizer`` and ``module:prepare`` around those methods
whoever calls them, each with the module's number (``module``: unique in
the process, a bucket's inner module carrying its owner's); and one
listener on ``jax.monitoring``, registered when ``mxnet_tpu`` is imported
unless ``MXNET_TRACE=0``, records ``compile:trace``, ``compile:lower``
and ``compile:backend`` for every program JAX compiles, in the ring of
the thread that compiled.  The readers are ``benchmark/setup_spans.py``'s
(tests/benchmark/test_layer_setup_spans.py).
"""
import os
import subprocess
import sys
import threading

import pytest

import mxnet_tpu as mx
from mxnet_tpu import trace
from mxnet_tpu.compile_cache import count_backend_compiles, jaxcache
from mxnet_tpu.io import DataIter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))
from jax_cache import jax_cache_dir  # noqa: E402,F401

from test_fit_spans import (IN_DIM, _BucketIter, _bucket_sym, _data_iter,
                            _end, _mlp)

MODULE_SPANS = ["module:bind", "module:init_params", "module:init_optimizer",
                "module:prepare"]
COMPILE_SPANS = ["compile:trace", "compile:lower", "compile:backend"]


@pytest.fixture(autouse=True)
def fresh_trace():
    trace.reset()
    yield
    trace.reset()


def _inside(child, parent, slack=0.01):
    return child["ts"] >= parent["ts"] - slack \
        and _end(child) <= _end(parent) + slack


def _spans(names):
    return sorted(trace.span_events(names=names), key=lambda e: e["ts"])


# -- fit:call and module:* ---------------------------------------------------

def test_fit_leaves_one_call_and_its_modules_setup_under_one_number():
    mod = mx.mod.Module(_mlp(), context=[mx.current_context()])
    mod.fit(_data_iter(), num_epoch=2,
            optimizer_params=(("learning_rate", 0.5),))
    mod.prepare()
    (call,) = _spans(["fit:call"])
    assert call["cat"] == "train"
    assert call["args"] == {"module": mod._trace_module}
    setup = _spans(MODULE_SPANS)
    assert [e["name"] for e in setup] == MODULE_SPANS
    bind, init, opt, prepare = setup
    assert bind["args"] == {"module": mod._trace_module,
                            "for_training": True}
    for e in (init, opt, prepare):
        assert e["args"] == {"module": mod._trace_module}
        assert e["cat"] == "train"
    # fit's own three lie inside the call, in order, before any step;
    # the user's prepare after it
    for a, b in zip((bind, init, opt), (init, opt, prepare)):
        assert _inside(a, call) and _end(a) <= b["ts"] + 0.01
    steps = _spans(["fit:step"])
    assert len(steps) == 8 and all(_inside(s, call) for s in steps)
    assert _end(opt) <= steps[0]["ts"] + 0.01
    assert all(_inside(e, call) for e in _spans(["fit:epoch"]))
    assert prepare["ts"] >= _end(call) - 0.01


def test_a_second_module_gets_another_number_and_keeps_it():
    it = _data_iter()
    first = mx.mod.Module(_mlp(), context=[mx.current_context()])
    second = mx.mod.Module(_mlp(), context=[mx.current_context()])
    assert isinstance(first._trace_module, int)
    assert first._trace_module != second._trace_module
    second.bind(it.provide_data, it.provide_label, for_training=False)
    first.bind(it.provide_data, it.provide_label)
    first.init_params()
    second.init_params()
    first.bind(it.provide_data, it.provide_label)      # bound: ignored
    got = [(e["name"], e["args"]["module"]) for e in _spans(MODULE_SPANS)]
    assert got == [("module:bind", second._trace_module),
                   ("module:bind", first._trace_module),
                   ("module:init_params", first._trace_module),
                   ("module:init_params", second._trace_module),
                   ("module:bind", first._trace_module)]
    assert [e["args"]["for_training"] for e in _spans(["module:bind"])] \
        == [False, True, True]


def test_a_call_that_raises_is_recorded_all_the_same():
    mod = mx.mod.Module(_mlp(), context=[mx.current_context()])
    with pytest.raises(AssertionError):
        mod.init_params()                              # not bound
    (e,) = _spans(["module:init_params"])
    assert e["args"] == {"module": mod._trace_module}

    class Broken(DataIter):
        provide_data = [("data", (16, IN_DIM))]
        provide_label = [("softmax_label", (16,))]
        batch_size = 16

        def next(self):
            raise RuntimeError("no data")

    with pytest.raises(RuntimeError, match="no data"):
        mod.fit(Broken(), num_epoch=1)
    (call,) = _spans(["fit:call"])
    assert call["args"] == {"module": mod._trace_module}


def test_a_bucketing_modules_inner_binds_carry_the_owners_number():
    keys = [4, 6, 4, 8]
    mod = mx.mod.BucketingModule(_bucket_sym, default_bucket_key=8,
                                 context=mx.current_context())
    it = _BucketIter(keys)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.prepare({6: ([("data", (8, 6))], [("softmax_label", (8,))])})
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    setup = _spans(MODULE_SPANS)
    assert {e["args"]["module"] for e in setup} == {mod._trace_module}
    for inner in mod._buckets.values():
        assert inner._trace_module == mod._trace_module
    binds = [e for e in setup if e["name"] == "module:bind"]
    # the owner's bind around the default bucket's; bucket 6's inside
    # prepare; fit's (ignored: bound); bucket 4's inside its first step
    assert len(binds) == 5
    assert _inside(binds[1], binds[0])
    (prepare,) = [e for e in setup if e["name"] == "module:prepare"]
    assert _inside(binds[2], prepare)
    (call,) = _spans(["fit:call"])
    assert _inside(binds[3], call) and _inside(binds[4], call)
    assert _inside(binds[4], _spans(["fit:step"])[0])
    assert prepare["ts"] < call["ts"]
    # init_params: the owner's around the inner module's, then fit's
    inits = [e for e in setup if e["name"] == "module:init_params"]
    assert _inside(inits[1], inits[0])


def test_nothing_is_recorded_while_tracing_is_off():
    trace.set_enabled(False)
    mod = mx.mod.Module(_mlp(), context=[mx.current_context()])
    mod.fit(_data_iter(), num_epoch=1)
    trace.set_enabled(True)
    assert trace.event_count() == 0


# -- compile:* ---------------------------------------------------------------

def _fresh_jit(name, body=None):
    """A new jitted function called ``name``; its default body is ``lax``
    primitives alone (a ``jnp`` function is itself jitted, and traced
    inside the trace of whoever calls it)."""
    import jax
    from jax import lax

    def fn(x):
        return lax.add(lax.mul(x, x), x) if body is None else body(x)
    fn.__name__ = name
    return jax.jit(fn)


def test_a_fresh_jit_leaves_three_spans_with_its_name_and_a_second_call_none():
    import jax.numpy as jnp
    x = jnp.ones((5, 3))
    y = (x + 1.0).block_until_ready()
    fn = _fresh_jit("setup_spans_probe")
    trace.reset()
    with count_backend_compiles() as counter:
        fn(x)
        got = trace.span_events(cat="compile")
        assert sorted(e["name"] for e in got) == sorted(COMPILE_SPANS)
        by_name = {e["name"]: e for e in got}
        assert by_name["compile:trace"]["args"] == \
            {"fun": "setup_spans_probe"}
        assert by_name["compile:lower"]["args"] == \
            {"fun": "jit(setup_spans_probe)"}
        backend = by_name["compile:backend"]["args"]
        assert backend["fun"] == "jit(setup_spans_probe)"
        assert backend["cache"] in ("hit", "miss")
        assert ("load_s" in backend) == (backend["cache"] == "hit")
        # in the order JAX went through them, on this thread, ending by now
        order = [by_name[n] for n in COMPILE_SPANS]
        for a, b in zip(order, order[1:]):
            assert _end(a) <= b["ts"] + 1e3       # time.time() durations
        assert all(e["tid"] == threading.get_ident() and e["dur"] > 0
                   for e in got)
        fn(x)
        fn(y)
        assert len(trace.span_events(cat="compile")) == 3
        # the counter beside the listener reads what it always did
        assert (counter.count, counter.cache_hits) == \
            (1, int(backend["cache"] == "hit"))


def test_a_function_traced_inside_anothers_trace_lies_inside_its_span():
    import jax.numpy as jnp
    x = jnp.ones((5, 3))
    x.block_until_ready()
    trace.reset()
    _fresh_jit("setup_spans_outer", lambda x: jnp.tanh(x * 3.0).sum())(x)
    traces = _spans(["compile:trace"])
    assert traces[0]["args"] == {"fun": "setup_spans_outer"}
    assert len(traces) > 1
    # the outer's start is made from its duration on another clock
    assert all(_inside(e, traces[0], slack=1e3) for e in traces[1:])
    assert len(_spans(["compile:lower"])) == 1
    assert len(_spans(["compile:backend"])) == 1


def test_the_persistent_caches_answer_reads_as_a_hit_with_its_load_time(
        jax_cache_dir):
    """The same program from a new function object: JAX traces and
    lowers again, and its backend stage is the cache's read.  The
    counter beside the listener counts what it did without it."""
    import jax.numpy as jnp
    x = jnp.ones((7, 3)).block_until_ready()
    trace.reset()
    with count_backend_compiles() as counter:
        _fresh_jit("setup_spans_twice")(x)
        assert (counter.count, counter.cache_hits) == (1, 0)
        _fresh_jit("setup_spans_twice")(x)
        assert (counter.count, counter.cache_hits) == (2, 1)
        assert counter.compiled == 1
    first, second = _spans(["compile:backend"])
    assert first["args"] == {"fun": "jit(setup_spans_twice)",
                             "cache": "miss"}
    assert second["args"]["cache"] == "hit"
    assert 0.0 < second["args"]["load_s"] <= second["dur"] / 1e6 + 1e-3
    assert len(_spans(["compile:trace"])) == 2
    assert len(_spans(["compile:lower"])) == 2
    # a hit's verdict does not leak into the next program's span
    _fresh_jit("setup_spans_thrice")(x)
    assert _spans(["compile:backend"])[-1]["args"]["cache"] == "miss"


def test_a_workers_compile_lands_in_the_workers_ring():
    import jax.numpy as jnp
    x = jnp.ones((9, 3))
    x.block_until_ready()
    trace.reset()
    seen = []

    def work():
        seen.append(threading.get_ident())
        _fresh_jit("setup_spans_worker")(x)

    t = threading.Thread(target=work)
    t.start()
    t.join()
    got = trace.span_events(cat="compile")
    assert sorted(e["name"] for e in got) == sorted(COMPILE_SPANS)
    assert {e["tid"] for e in got} == set(seen)
    assert seen[0] != threading.get_ident()


def test_the_listener_is_registered_once_and_off_is_a_no_op():
    import jax.numpy as jnp
    from jax._src import monitoring
    assert jaxcache._recording_spans           # import mxnet_tpu did
    jaxcache.record_compile_spans()
    assert monitoring.get_event_duration_listeners().count(
        jaxcache._spans_on_duration) == 1
    assert monitoring.get_event_listeners().count(
        jaxcache._spans_on_event) == 1
    trace.set_enabled(False)
    _fresh_jit("setup_spans_off")(jnp.ones((11, 3)))
    trace.set_enabled(True)
    assert not trace.span_events(cat="compile")


def test_no_listener_is_registered_under_mxnet_trace_0():
    code = (
        "import mxnet_tpu as mx, jax, jax.numpy as jnp\n"
        "from jax._src import monitoring\n"
        "from mxnet_tpu.compile_cache import jaxcache\n"
        "want = %r == '0'\n"
        "assert jaxcache._recording_spans != want\n"
        "mine = [f for f in monitoring.get_event_duration_listeners()\n"
        "        + monitoring.get_event_listeners()\n"
        "        if getattr(f, '__module__', '').startswith('mxnet_tpu')]\n"
        "assert len(mine) == (0 if want else 2), mine\n"
        "mx.trace.set_enabled(True)\n"
        "jax.jit(lambda x: x + 1)(jnp.ones(3))\n"
        "n = len(mx.trace.span_events(cat='compile'))\n"
        "assert (n == 0) == want, n\n")
    for value in ("0", "1"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TRACE=value)
        r = subprocess.run([sys.executable, "-c", code % value], env=env,
                           capture_output=True, text=True, timeout=300,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode == 0, (value, r.stderr[-2000:])
