"""``Module.fit``'s K=1 loop, software-pipelined by one step (tier-1, CPU).

Where a step's outputs are device arrays still in flight (the
one-process fused step), ``fit`` enqueues step N+1 before it runs step
N's metric and ``batch_end_callback``s, on N's outputs and labels, held
meanwhile.  Same programs, same arithmetic, same metric totals, same
callback order and arguments as the serial loop; a callback that
declares ``inspects_outputs = True`` forces the serial loop, which is
what every case here compares the pipelined one with.  Points where the
module must hold exactly N steps (a checkpoint, a preemption, the
epoch's end, an exception) drain first.
"""
import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ck
from mxnet_tpu import trace
from mxnet_tpu.io import DataBatch, DataIter
from mxnet_tpu.module.base_module import _hold_labels
from test_fit_spans import IN_DIM, _BucketIter, _bucket_sym, _mlp

CLASSES, BATCH, BATCHES = 3, 8, 5


@pytest.fixture(autouse=True)
def fresh_trace():
    trace.reset()
    yield
    trace.reset()


@pytest.fixture(autouse=True)
def as_on_an_accelerator(monkeypatch):
    """The CPU backend's arrays are the host's own memory and the loop
    stays serial for them: these tests run the loop an accelerator
    gets, on the arrays this host has."""
    from mxnet_tpu.module import module
    monkeypatch.setattr(module, "_lives_on_host", lambda array: False)


def _arrays(n=BATCH * BATCHES):
    rng = np.random.RandomState(0)
    return (rng.randn(n, IN_DIM).astype(np.float32),
            rng.randint(0, CLASSES, n).astype(np.float32))


class _Recording(DataIter):
    """Wraps an iterator and keeps every batch it handed out."""

    def __init__(self, inner):
        super().__init__()
        self.inner, self.batches = inner, []
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label
        self.batch_size = inner.batch_size

    def reset(self):
        self.inner.reset()

    def next(self):
        self.batches.append(self.inner.next())
        return self.batches[-1]


class _RefillInPlace(DataIter):
    """One data and one label buffer for the whole run, rewritten by
    every ``next()`` as the DataIter contract allows."""

    def __init__(self):
        super().__init__()
        self.X, self.y = _arrays()
        self.i, self.batch_size = 0, BATCH
        self.provide_data = [("data", (BATCH, IN_DIM))]
        self.provide_label = [("softmax_label", (BATCH,))]
        self.data = mx.nd.zeros((BATCH, IN_DIM))
        self.label = mx.nd.zeros((BATCH,))
        self.batch = DataBatch(data=[self.data], label=[self.label], pad=0)

    def reset(self):
        self.i = 0

    def next(self):
        if self.i == BATCHES:
            raise StopIteration
        rows = slice(self.i * BATCH, (self.i + 1) * BATCH)
        self.i += 1
        self.data[:] = self.X[rows]
        self.label[:] = self.y[rows]
        return self.batch


def _neg_log_lik(label, pred):
    picked = pred[np.arange(len(label)), label.astype(np.int64)]
    return float(-np.log(picked + 1e-8).sum()), len(label)


METRICS = {
    "acc": lambda: "acc",
    "ce": lambda: "ce",
    "custom": lambda: mx.metric.CustomMetric(_neg_log_lik),
    "composite": lambda: mx.metric.CompositeEvalMetric(
        [mx.metric.create("acc"), mx.metric.create("ce"),
         mx.metric.CustomMetric(_neg_log_lik)]),
}


def _fit(it=None, metric="acc", serial=False, callback=None, num_epoch=2,
         module=None, **fit_kw):
    """-> (module, [(epoch, nbatch, metric values, data_batch)] of the
    callbacks).  ``serial`` forces the loop serial the way a user does."""
    seen = []

    def on_batch_end(param):
        seen.append((param.epoch, param.nbatch,
                     tuple(v for _, v in param.eval_metric.get_name_value()),
                     param.locals["data_batch"]))
        if callback is not None:
            callback(param)

    on_batch_end.inspects_outputs = serial
    mx.random.seed(7)
    mod = module or mx.mod.Module(_mlp(), context=mx.cpu(0))
    mod.fit(it or mx.io.NDArrayIter(*_arrays(), batch_size=BATCH),
            eval_metric=METRICS[metric](), num_epoch=num_epoch,
            batch_end_callback=on_batch_end,
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            **fit_kw)
    return mod, seen


def _params(mod):
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def _assert_same_params(a, b):
    assert a.keys() == b.keys()
    for k in a:
        assert np.array_equal(a[k], b[k]), k


def _spans(name):
    return sorted(trace.span_events(names=[name]), key=lambda e: e["ts"])


def _lags():
    return [e["args"]["lag"] for e in _spans("fit:update_metric")]


def _deferred(tmp_path):
    """The ``fit:deferred`` counter samples, one an epoch."""
    with open(trace.dump_trace(str(tmp_path / "t.json"))) as f:
        events = json.load(f)["traceEvents"]
    return [e["args"] for e in events
            if e["name"] == "fit:deferred" and e["ph"] == "C"]


# -- the pipelined loop against the serial one --------------------------------

@pytest.mark.parametrize("metric", sorted(METRICS))
def test_pipelined_fit_equals_the_serial_one(metric):
    its = [_Recording(mx.io.NDArrayIter(*_arrays(), batch_size=BATCH))
           for _ in range(2)]
    piped, seen_p = _fit(its[0], metric)
    assert set(_lags()) == {0, 1}
    trace.reset()
    serial, seen_s = _fit(its[1], metric, serial=True)
    assert set(_lags()) == {0}
    _assert_same_params(_params(piped), _params(serial))
    assert [s[:3] for s in seen_p] == [s[:3] for s in seen_s]
    assert [s[:2] for s in seen_p] == \
        [(e, n) for e in range(2) for n in range(BATCHES)]
    for it, seen in zip(its, (seen_p, seen_s)):
        assert len(it.batches) == len(seen) == 2 * BATCHES
        for batch, (_, _, _, got) in zip(it.batches, seen):
            assert got is batch


@pytest.mark.parametrize("metric", ["acc", "ce"])
def test_a_label_buffer_refilled_in_place_does_not_shift_a_deferred_step(
        metric):
    _, seen_p = _fit(_RefillInPlace(), metric, num_epoch=1)
    assert _lags() == [1] * (BATCHES - 1) + [0]
    _, seen_s = _fit(_RefillInPlace(), metric, num_epoch=1, serial=True)
    _, want = _fit(mx.io.NDArrayIter(*_arrays(), batch_size=BATCH), metric,
                   num_epoch=1, serial=True)
    assert [s[:3] for s in seen_p] == [s[:3] for s in seen_s] == \
        [s[:3] for s in want]


def test_held_labels_are_not_read_back_from_the_device():
    dev = mx.nd.array(np.arange(6.0))
    view = dev[2:4]
    host = np.arange(3.0)
    held = _hold_labels([dev, view, host, None])
    assert held[0]._get() is dev._get()          # the same device array
    assert held[3] is None
    dev[:] = 9.0
    host[:] = 9.0
    assert held[0].asnumpy().tolist() == list(np.arange(6.0))
    assert held[1].asnumpy().tolist() == [2.0, 3.0]
    assert held[2].tolist() == [0.0, 1.0, 2.0]
    assert _hold_labels(None) == []


def test_next_step_is_enqueued_before_the_metric_of_the_step_before(
        tmp_path):
    epochs = 3
    _fit(num_epoch=epochs)
    updates, metrics, ends = (_spans(n) for n in (
        "fit:update", "fit:update_metric", "fit:batch_end"))
    steps = epochs * BATCHES
    assert len(updates) == len(metrics) == len(ends) == steps
    assert [m["args"]["for_step"] for m in metrics] == list(range(steps))
    assert [m["args"] for m in metrics] == [e["args"] for e in ends]
    for n, m in enumerate(metrics):
        last_of_epoch = n % BATCHES == BATCHES - 1
        assert m["args"]["lag"] == (0 if last_of_epoch else 1)
        # this step's program was enqueued before its own metric, ...
        assert updates[n]["ts"] + updates[n]["dur"] <= m["ts"] + 0.01
        if not last_of_epoch:
            # ... and so was the next step's
            assert updates[n + 1]["ts"] + updates[n + 1]["dur"] \
                <= m["ts"] + 0.01
    # steps whose metric ran a step late: all but each epoch's last
    assert _deferred(tmp_path) == \
        [{"steps": BATCHES - 1, "drained_early": 1}] * epochs
    assert sum(d["steps"] for d in _deferred(tmp_path)) == steps - epochs


# -- where the loop stays serial ------------------------------------------------

def _fit_classic(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_TRAIN", "0")
    mod, _ = _fit(num_epoch=1)
    assert mod._fused is None


def _fit_bucketing(monkeypatch):
    mod = mx.mod.BucketingModule(_bucket_sym, default_bucket_key=6,
                                 context=mx.cpu(0))
    mod.fit(_BucketIter([4, 6, 4, 6, 6]), num_epoch=1,
            optimizer_params={"learning_rate": 0.1})


def _fit_monitor(monkeypatch):
    _fit(num_epoch=1, monitor=mx.Monitor(1, pattern="nothing"))


def _fit_on_the_host_platform(monkeypatch):
    monkeypatch.undo()                  # the CPU backend as it is
    mod, _ = _fit(num_epoch=1)
    assert mod._fused is not None and mod._outputs_in_flight() is None


def _fit_multi_worker(monkeypatch):
    from mxnet_tpu.module.fused import FusedTrainStep
    # a worker of several: the step's outputs come back as this worker's
    # rows, read synchronously (here the one worker's rows are all rows)
    monkeypatch.setattr(FusedTrainStep, "host_outputs",
                        lambda self, outs, batch:
                        [mx.nd.array(np.asarray(o)) for o in outs])
    mod, _ = _fit(num_epoch=1)
    monkeypatch.setattr(mod._fused, "_mesh_procs", 2)
    assert mod._outputs_in_flight() is None
    trace.reset()
    _fit(num_epoch=1, module=mod)


@pytest.mark.parametrize("run", [_fit_classic, _fit_bucketing, _fit_monitor,
                                 _fit_multi_worker,
                                 _fit_on_the_host_platform],
                         ids=["classic", "bucketing", "monitor",
                              "multi-worker", "host-platform"])
def test_no_step_is_deferred_without_outputs_in_flight(run, monkeypatch,
                                                       tmp_path):
    run(monkeypatch)
    lags = _lags()
    assert len(lags) == BATCHES and set(lags) == {0}
    assert _deferred(tmp_path) == [{"steps": 0, "drained_early": 0}]


def test_score_and_fit_share_the_hook_and_the_label_holder():
    from mxnet_tpu.module import base_module
    from mxnet_tpu.module.module import Module
    assert "_outputs_in_flight" in Module.__dict__
    for gone in ("_eval_outputs_async", "snap_labels"):
        assert not hasattr(Module, gone)
        assert gone not in open(base_module.__file__).read()
    mod, _ = _fit(num_epoch=1)
    outs = mod._outputs_in_flight()
    assert [o.shape for o in outs] == [(BATCH, CLASSES)]
    # the same totals from score() deferred and from score() serial
    it = mx.io.NDArrayIter(*_arrays(), batch_size=BATCH)
    deferred = mod.score(it, "ce")

    def serial(param):
        pass
    serial.inspects_outputs = True
    assert mod.score(it, "ce", batch_end_callback=serial) == deferred


# -- points where the module must hold exactly N steps ------------------------

class _SaveLog(ck.CheckpointManager):
    """Notes, at every save, the step, how many samples the metric had
    seen, and the last callback that had fired."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.metric, self.callbacks, self.log = None, [], []

    def save(self, step, tree, meta=None, blocking=None):
        self.log.append((step, self.metric.num_inst,
                         self.callbacks[-1] if self.callbacks else None))
        super().save(step, tree, meta, blocking=blocking)


def _ckpt_fit(store, serial=False, resume=False, callback=None, num_epoch=2,
              preempt_at=None):
    seen = []
    with _SaveLog(str(store), save_every_steps=3, keep_last_n=None) as mgr:
        def on_batch_end(param):
            mgr.metric = param.eval_metric
            mgr.callbacks.append((param.epoch, param.nbatch))
            seen.append((param.epoch, param.nbatch,
                         param.eval_metric.get()[1]))
            if (param.epoch, param.nbatch) == preempt_at:
                mgr.preempted = True
            if callback is not None:
                callback(param)
        on_batch_end.inspects_outputs = serial
        mx.random.seed(7)
        mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
        mod.fit(mx.io.NDArrayIter(*_arrays(), batch_size=BATCH),
                eval_metric="ce", num_epoch=num_epoch, checkpoint=mgr,
                resume=resume, batch_end_callback=on_batch_end,
                optimizer_params={"learning_rate": 0.5, "momentum": 0.9})
        mgr.wait()
        return mod, seen, mgr.log


def _restored(store, step):
    mx.random.seed(1)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    mod.bind([("data", (BATCH, IN_DIM))], [("softmax_label", (BATCH,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.5,
                                         "momentum": 0.9})
    with ck.CheckpointManager(str(store), keep_last_n=None) as mgr:
        meta = ck.restore_module(mgr, mod, step=step)
    return _params(mod), meta


def test_a_checkpoint_holds_exactly_its_steps_and_resume_ends_the_same(
        tmp_path):
    import shutil
    piped, seen_p, log_p = _ckpt_fit(tmp_path / "p")
    lags = _lags()
    trace.reset()
    serial, seen_s, log_s = _ckpt_fit(tmp_path / "s", serial=True)
    # step 3 = (0, 2), 5 = the epoch's end, 6 = (1, 0), 9 = (1, 3), 10
    assert log_p == log_s
    assert [(s, cb) for s, _, cb in log_p] == [
        (3, (0, 2)), (5, (0, 4)), (6, (1, 0)), (9, (1, 3)), (10, (1, 4))]
    assert [n for _, n, _ in log_p] == [3 * BATCH, 5 * BATCH, BATCH,
                                        4 * BATCH, 5 * BATCH]
    assert seen_p == seen_s
    _assert_same_params(_params(piped), _params(serial))
    # the steps a save follows were not deferred, the others were
    assert lags == [1, 1, 0, 1, 0, 0, 1, 1, 0, 0]
    for step in (3, 6, 9):
        got, meta = _restored(tmp_path / "p", step)
        want, meta_s = _restored(tmp_path / "s", step)
        _assert_same_params(got, want)
        assert meta["global_step"] == meta_s["global_step"] == step
        assert (meta["epoch"], meta["nbatch"]) == \
            (meta_s["epoch"], meta_s["nbatch"])
    # resume from step 3 alone, pipelined: the same end state
    for s in ck.all_steps(str(tmp_path / "p")):
        if s != 3:
            shutil.rmtree(tmp_path / "p" / ck.step_dir_name(s))
    resumed, seen_r, _ = _ckpt_fit(tmp_path / "p", resume=True)
    assert [s[:2] for s in seen_r] == [s[:2] for s in seen_s[3:]]
    assert seen_r[2:] == seen_s[5:]           # whole epochs: same metric
    _assert_same_params(_params(resumed), _params(serial))


def test_a_preemption_mid_epoch_fires_the_outstanding_callback_and_saves(
        tmp_path):
    # the flag is set inside step (0, 1)'s callback, which runs after
    # step (0, 2) was enqueued: (0, 2) trains, is finished and saved
    # (it is also step 3 of the cadence), and fit leaves
    piped, seen, log = _ckpt_fit(tmp_path / "p", preempt_at=(0, 1))
    assert [s[:2] for s in seen] == [(0, 0), (0, 1), (0, 2)]
    assert log == [(3, 3 * BATCH, (0, 2))]
    # off the cadence: set in (1, 1)'s callback, answered after (1, 2)
    trace.reset()
    piped, seen, log = _ckpt_fit(tmp_path / "q", preempt_at=(1, 1))
    assert [s[:2] for s in seen][-3:] == [(1, 0), (1, 1), (1, 2)]
    assert log[-1] == (8, 3 * BATCH, (1, 2))
    # (1, 0) is step 6 of the cadence and (1, 2) answers the flag: serial
    assert _lags()[-3:] == [0, 1, 0]
    # a serial run told to stop after the same step holds the same state
    serial, seen_s, log_s = _ckpt_fit(tmp_path / "s", serial=True,
                                      preempt_at=(1, 2))
    assert seen == seen_s and log_s[-1] == log[-1]
    _assert_same_params(_params(piped), _params(serial))
    _assert_same_params(_restored(tmp_path / "q", 8)[0],
                        _restored(tmp_path / "s", 8)[0])


class _FailingIter(_Recording):
    def next(self):
        if len(self.batches) == 3:
            raise RuntimeError("the feed broke")
        return super().next()


def test_a_failing_pull_still_fires_the_outstanding_callback(tmp_path):
    it = _FailingIter(mx.io.NDArrayIter(*_arrays(), batch_size=BATCH))
    seen = []

    def on_batch_end(param):
        seen.append((param.nbatch, param.eval_metric.num_inst,
                     param.locals["data_batch"]))

    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(0))
    with pytest.raises(RuntimeError, match="the feed broke"):
        mod.fit(it, num_epoch=1, batch_end_callback=on_batch_end,
                optimizer_params={"learning_rate": 0.5})
    assert [(n, inst) for n, inst, _ in seen] == \
        [(0, BATCH), (1, 2 * BATCH), (2, 3 * BATCH)]
    assert [b for _, _, b in seen] == it.batches
    assert _lags() == [1, 1, 0]
    assert _deferred(tmp_path) == [{"steps": 2, "drained_early": 1}]


@pytest.mark.parametrize("mutate", ["neutral", "weight-decay"])
def test_leaving_the_fused_step_mid_epoch_drains_and_carries_on_serially(
        mutate, tmp_path):
    """A callback that changes an optimizer hyperparameter makes the next
    dispatch leave the fused step.  Pipelined, step N's callback runs
    after step N+1 was enqueued, so the change reaches step N+2: the
    serial run that matches makes it in step N+1's callback."""
    def mutation(at):
        def callback(param):
            if (param.epoch, param.nbatch) == (0, at):
                opt = param.locals["self"]._optimizer
                if mutate == "neutral":
                    # in the compiled step's signature, in no arithmetic
                    opt.lr_mult["no_such_weight"] = 1.0
                else:
                    opt.wd = 0.01
        return callback

    piped, seen_p = _fit(callback=mutation(1))
    assert piped._fused is None
    lags = _lags()
    # (0, 3) is the first dispatch after the change: it drains (0, 2)
    # and every step from there on is its own
    assert lags == [1, 1, 1] + [0] * (2 * BATCHES - 3)
    assert _deferred(tmp_path) == [{"steps": 3, "drained_early": 0},
                                   {"steps": 0, "drained_early": 0}]
    serial, seen_s = _fit(callback=mutation(2), serial=True)
    assert serial._fused is None
    assert [s[:2] for s in seen_p] == [s[:2] for s in seen_s]
    for (_, _, got, _), (_, _, want, _) in zip(seen_p, seen_s):
        np.testing.assert_allclose(got, want, rtol=1e-6)
    got, want = _params(piped), _params(serial)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-7)
    if mutate == "neutral":
        # and the change's timing then moves no number at all
        other, seen_o = _fit(callback=mutation(1), serial=True)
        for (_, _, got, _), (_, _, want, _) in zip(seen_p, seen_o):
            np.testing.assert_allclose(got, want, rtol=1e-5)
