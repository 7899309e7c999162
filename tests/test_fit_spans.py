"""The span tree of one training step (tier-1, CPU).

``BaseModule.fit`` records one ``fit:step`` per iteration of its loop
with, inside it and not overlapping, ``fit:feed_next``,
``fit:forward_backward``, ``fit:update``, ``fit:update_metric`` and
``fit:batch_end`` (the last two of the step before where the loop is
pipelined: tests/test_fit_pipeline.py); the fused path nests ``fused:dispatch`` in
``fit:update``, the classic path ``executor:forward``/``backward`` in
``fit:forward_backward`` and ``optimizer:update_params`` in
``fit:update``.  Every ``mx.trace.span`` is also a
``jax.profiler.TraceAnnotation`` of the same name while tracing is
enabled, and the fused step no longer blocks on the device to time it.
"""
import glob
import inspect
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import trace
from mxnet_tpu.io import DataBatch, DataIter

IN_DIM = 6
CHILDREN = ["fit:feed_next", "fit:forward_backward", "fit:update",
            "fit:update_metric", "fit:batch_end"]


@pytest.fixture(autouse=True)
def fresh_trace():
    trace.reset()
    yield
    trace.reset()


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(mx.sym.FullyConnected(data, num_hidden=8,
                                                name="fc1"),
                          act_type="relu")
    return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(h, num_hidden=3,
                                                      name="fc2"),
                                name="softmax")


def _data_iter(n=64, batch=16):
    rng = np.random.RandomState(0)
    X = rng.randn(n, IN_DIM).astype(np.float32)
    y = rng.randint(0, 3, n).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch)


def _fit_module(it=None, context=None, **fit_kw):
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=context or [mx.current_context()])
    mod.fit(it or _data_iter(), num_epoch=1,
            optimizer_params=(("learning_rate", 0.5),), **fit_kw)
    return mod


class _BucketIter(DataIter):
    """Eight-row batches whose width is their bucket key."""

    def __init__(self, keys, batch=8):
        super().__init__()
        self.keys, self.batch_size, self.i = list(keys), batch, 0
        self.default_bucket_key = max(keys)
        self.provide_data = [("data", (batch, self.default_bucket_key))]
        self.provide_label = [("softmax_label", (batch,))]

    def reset(self):
        self.i = 0

    def next(self):
        if self.i == len(self.keys):
            raise StopIteration
        key = self.keys[self.i]
        self.i += 1
        rng = np.random.RandomState(self.i)
        X = rng.randn(self.batch_size, key).astype(np.float32)
        y = (X.sum(axis=1) > 0).astype(np.float32)
        return DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                         bucket_key=key, pad=0,
                         provide_data=[("data", (self.batch_size, key))],
                         provide_label=[("softmax_label",
                                         (self.batch_size,))])


def _bucket_sym(seq_len):
    data = mx.sym.Variable("data")
    # parameters do not depend on the bucket's width
    feat = mx.sym.sum_axis(data, axis=1, keepdims=True)
    net = mx.sym.FullyConnected(feat, num_hidden=2, name="out")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _end(e):
    return e["ts"] + e["dur"]


def _inside(child, parent, slack=0.01):
    return child["ts"] >= parent["ts"] and _end(child) <= _end(parent) + slack


def _tree(names=None):
    """-> [(step event, its children in time order)] of this thread."""
    evs = sorted(trace.span_events(cat="train"), key=lambda e: e["ts"])
    steps = [e for e in evs if e["name"] == "fit:step"]
    return [(s, [e for e in evs if e is not s and _inside(e, s)
                 and (names is None or e["name"] in names)])
            for s in steps]


def _assert_children_in_order(step, kids):
    assert [k["name"] for k in kids] == CHILDREN
    for a, b in zip(kids, kids[1:]):
        assert _end(a) <= b["ts"] + 0.01, (a["name"], b["name"])
    assert sum(k["dur"] for k in kids) <= step["dur"] + 0.01


def _serial(param):
    """A callback that forces the serial loop."""


_serial.inspects_outputs = True
LOOPS = pytest.mark.parametrize("pipelined", [True, False],
                                ids=["pipelined", "serial"])


def _host_share(step):
    """The ``fit:update_metric`` and ``fit:batch_end`` of global step
    ``step``, wherever they lie."""
    return [e for e in sorted(trace.span_events(names=CHILDREN[3:]),
                              key=lambda e: e["ts"])
            if e["args"]["for_step"] == step]


@pytest.fixture
def loop(pipelined, monkeypatch):
    """The loop an accelerator gets (the CPU backend's arrays are the
    host's own memory, and the loop stays serial for them), or the
    serial one the way a user forces it."""
    from mxnet_tpu.module import module
    monkeypatch.setattr(module, "_lives_on_host", lambda array: False)
    return None if pipelined else _serial


@LOOPS
def test_module_fit_records_one_step_per_batch_with_its_children(pipelined,
                                                                 loop):
    _fit_module(batch_end_callback=loop)
    tree = _tree(CHILDREN)
    assert len(tree) == 4
    for i, (step, kids) in enumerate(tree):
        assert step["args"] == {"step": i, "epoch": 0, "nbatch": i,
                                "count": 1}
        if pipelined and i == 0:
            # the epoch's first step has no step before it to finish
            assert [k["name"] for k in kids] == CHILDREN[:3]
            continue
        _assert_children_in_order(step, kids)
        # the metric and the callbacks are those of the step before
        # where the loop is pipelined, this step's own where it is not
        assert [k["args"] for k in kids[3:]] == \
            [{"for_step": i - int(pipelined), "lag": int(pipelined)}] * 2
        assert kids[3:] == _host_share(i - int(pipelined))
    # the epoch's drain: the last step's share, after the last fit:step
    last, (metric, batch_end) = tree[-1][0], _host_share(3)
    assert [metric["name"], batch_end["name"]] == CHILDREN[3:]
    assert metric["args"] == batch_end["args"] == {"for_step": 3, "lag": 0}
    assert _end(metric) <= batch_end["ts"] + 0.01
    assert (metric["ts"] >= _end(last)) == pipelined


def test_fused_dispatch_lies_inside_fit_update():
    _fit_module()
    updates = trace.span_events(names=["fit:update"])
    dispatches = trace.span_events(names=["fused:dispatch"])
    assert len(updates) == len(dispatches) == 4
    for u, d in zip(updates, dispatches):
        assert _inside(d, u)
    # the fused path runs no classic executor step
    assert not trace.span_events(names=["executor:backward",
                                        "optimizer:update_params"])


@LOOPS
def test_epoch_ending_pull_records_no_step(pipelined, loop):
    _fit_module(batch_end_callback=loop)
    pulls = trace.span_events(names=["fit:feed_next"])
    assert len(pulls) == 5
    assert [(p.get("args") or {}).get("end") for p in pulls] == \
        [None] * 4 + [True]
    steps = trace.span_events(names=["fit:step"])
    assert len(steps) == 4
    assert not any(_inside(pulls[-1], s) for s in steps)
    # pipelined, the epoch's drain follows the pull that ended the epoch
    # and precedes the epoch's own span's end
    (drain,) = [e for e in trace.span_events(names=["fit:update_metric"])
                if e["args"]["for_step"] == 3]
    assert (drain["ts"] >= _end(pulls[-1])) == pipelined
    (epoch,) = trace.span_events(names=["fit:epoch"])
    assert _end(drain) <= _end(epoch) + 0.01


def test_bucketing_fit_records_the_classic_step():
    keys = [4, 6, 4, 6, 6]
    mod = mx.mod.BucketingModule(_bucket_sym, default_bucket_key=6,
                                 context=mx.current_context())
    mod.fit(_BucketIter(keys), num_epoch=1,
            optimizer_params={"learning_rate": 0.1})
    tree = _tree()
    assert [s["args"]["bucket_key"] for s, _ in tree] == keys
    n_params = len(mod._curr_module._exec_group.param_arrays)
    for step, kids in tree:
        assert step["args"]["count"] == 1
        _assert_children_in_order(
            step, [k for k in kids if k["name"] in CHILDREN])
        by_name = {k["name"]: k for k in kids}
        fb, upd = by_name["fit:forward_backward"], by_name["fit:update"]
        assert _inside(by_name["executor:forward"], fb)
        assert _inside(by_name["executor:backward"], fb)
        assert _end(by_name["executor:forward"]) <= \
            by_name["executor:backward"]["ts"] + 0.01
        assert _inside(by_name["optimizer:update_params"], upd)
        assert by_name["optimizer:update_params"]["args"] == \
            {"arrays": n_params}
        assert "fused:dispatch" not in by_name


def test_superstep_fit_wraps_each_group_in_one_step():
    _fit_module(it=_data_iter(n=128), superstep=4)
    tree = _tree()
    assert len(tree) == 2
    for g, (step, kids) in enumerate(tree):
        assert step["args"] == {"step": 4 * g, "epoch": 0, "nbatch": 4 * g,
                                "count": 4}
        names = [k["name"] for k in kids]
        assert names[:4] == ["fit:feed_next"] * 4
        for want in ("superstep:h2d_stage", "superstep:dispatch",
                     "superstep:metric_drain", "fit:batch_end"):
            assert names.count(want) == 1, (want, names)
        assert names[-1] == "fit:batch_end"
        assert "fit:update" not in names


def test_superstep_tail_trains_per_batch_inside_its_step():
    # 6 batches at K=4: one full group, then a tail of two
    _fit_module(it=_data_iter(n=96), superstep=4)
    (_, full), (tail, kids) = _tree()
    assert tail["args"]["count"] == 2 and tail["args"]["step"] == 4
    names = [k["name"] for k in kids if k["name"] in CHILDREN]
    # two pulls, the pull that ends the epoch, then two per-batch bodies
    assert names == ["fit:feed_next"] * 3 + CHILDREN[1:] * 2
    assert "superstep:dispatch" in [k["name"] for k in full]


class _FakeAnnotation:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture
def fake_annotation(monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(trace, "_annotation", None)
    _FakeAnnotation.log = []
    return _FakeAnnotation.log


def test_span_opens_an_annotation_of_its_name(fake_annotation):
    with trace.span("outer", cat="t", k=1):
        with mx.profiler.scope("inner"):
            pass

    @trace.span("decorated")
    def f():
        return 3

    assert f() == 3
    assert fake_annotation == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
        ("exit", "outer"), ("enter", "decorated"), ("exit", "decorated")]
    assert [e["name"] for e in trace.span_events()] == \
        ["inner", "outer", "decorated"]


def test_disabled_span_opens_no_annotation(fake_annotation):
    trace.set_enabled(False)
    with trace.span("quiet"):
        pass
    trace.span("quiet-too")(lambda: None)()
    assert fake_annotation == []
    assert trace._annotation is None          # not even looked up
    trace.set_enabled(True)
    assert not trace.span_events()


def test_cancelled_span_records_nothing_but_closes_its_annotation(
        fake_annotation):
    with trace.span("dropped") as sp:
        sp.cancel()
    assert fake_annotation == [("enter", "dropped"), ("exit", "dropped")]
    assert not trace.span_events()


def test_importing_trace_does_not_import_the_profiler():
    code = ("import sys, mxnet_tpu.trace as t\n"
            "assert 'jax.profiler' not in sys.modules, 'at import'\n"
            "assert t._annotation is None\n"
            "with t.span('x'): pass\n"
            "assert t._annotation is not None\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # mxnet_tpu/__init__ imports jax (and so jax.profiler) for its own
    # needs: load the trace package alone, as a child of a bare stub
    stub = ("import sys, types, os\n"
            "pkg = types.ModuleType('mxnet_tpu')\n"
            "pkg.__path__ = [os.path.join(%r, 'mxnet_tpu')]\n"
            "sys.modules['mxnet_tpu'] = pkg\n" % os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
    r = subprocess.run([sys.executable, "-c", stub + code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_mesh_dispatch_never_blocks_on_the_device(monkeypatch):
    import jax
    from mxnet_tpu.module.fused import FusedTrainStep
    blocked = []
    real = jax.block_until_ready

    def spy(x):
        f = sys._getframe(1)
        while f is not None:
            if f.f_code.co_name == "_dispatch":
                blocked.append(f.f_code.co_filename)
            f = f.f_back
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", spy)
    # 20 steps: past the 16 the removed sampling branch counted to
    mod = _fit_module(it=_data_iter(n=320), context=mx.cpu(0),
                      mesh=[("dp", 8)])
    stats = mod._fused.multichip_stats
    assert stats is not None and stats.steps == 20
    assert blocked == []
    assert "block_until_ready" not in inspect.getsource(
        FusedTrainStep._dispatch)
    report = stats.report()
    # no K=1 step is timed by blocking it; the keys stay for the reports
    assert report["sampled_steps"] == 0 and report["sampled_device_s"] == 0
    assert report["dispatch_s_per_step"] > 0 and report["first_step_s"] > 0
    for gone in ("should_sample", "add_wait", "sample_every"):
        assert not hasattr(stats, gone)
    names = [e["name"] for e in trace.span_events(cat="train")]
    assert names.count("fused:first_step(compile)") == 1
    assert names.count("fused:dispatch") == 19
    assert "fused:device_wait(sampled)" not in names


def test_fit_steps_reach_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData
    mod = _fit_module()                      # compiled, bound
    it = _data_iter(n=48)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mod.fit(it, num_epoch=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("fit:step") >= 3
    for child in CHILDREN + ["fused:dispatch"]:
        assert names.count(child) >= 3, child


def test_idle_gaps_tool_labels_a_gap_by_the_span_that_covers_it():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import idle_gaps
    ms = 1_000_000
    # three 50 ms steps on the device with gaps of 3, 6 and (inside the
    # second step) 1 ms
    device = [("fusion.1", 0, 50 * ms), ("fusion.1", 53 * ms, 20 * ms),
              ("fusion.2", 74 * ms, 29 * ms), ("fusion.1", 109 * ms, 50 * ms)]
    spans = [("fit:step", 0, 160 * ms),
             ("fit:update_metric", 1 * ms, 49 * ms),
             ("fit:batch_end", 50 * ms, 1 * ms),
             ("fit:update", 51 * ms, 2 * ms + ms // 2),
             ("fused:dispatch", 51 * ms + ms // 2, 2 * ms),
             ("fit:forward_backward", 73 * ms, 1 * ms),
             ("executor:backward", 73 * ms, 1 * ms),
             ("fit:batch_end", 103 * ms, 5 * ms),
             ("fit:update", 108 * ms, 1 * ms)]
    rows = idle_gaps.longest_gaps(device, spans, top=10)
    assert rows == [
        (6 * ms, 103 * ms, "fit:batch_end", "fit-loop-other",
         {"fit:batch_end": 5 / 6, "fit:update": 1 / 6}),
        (3 * ms, 50 * ms, "fit:update", "fused:dispatch",
         {"fit:update": 2 / 3, "fit:batch_end": 1 / 3}),
        (1 * ms, 73 * ms, "fit:forward_backward", "executor:backward",
         {"fit:forward_backward": 1.0})]
    assert idle_gaps.longest_gaps(device, spans, top=1) == rows[:1]
    # fit:step covers every gap and is never the label
    assert idle_gaps.longest_gaps(device, spans[:1]) == [
        (g, at, "fit-loop-other", "fit-loop-other", {})
        for g, at, _, _, _ in rows]
    assert idle_gaps.longest_gaps([], spans) == []
