"""Driver kind ``train_fit``: one call of ``Module.fit`` (or
``BucketingModule.fit``) on a wrapper iterator, measured from outside.

The program gets the generated batches and nothing else: no performance
option is passed to ``fit`` and no ``MXNET_*`` variable is set but the
configuration's compute dtype, so a later PR that makes the default path
faster shows and a switch does not.

What is measured, and where (all clocks are the harness's own):

* the window opens at the end of the ``batch_end_callback`` of the last
  warm-up step (after ``warmup_steps`` steps and, with buckets, once
  every bucket has been visited ``bucket_visits`` times) and closes
  when ``fit`` has returned and every live device array is ready; the
  wrapper ends the epoch at the first ``next()`` past the deadline;
* rate = samples of the steps that completed in the window over the
  window's length;
* ``setup_s`` = process start to the window's opening;
* with ``--trace 1`` a profiler trace covers at most TRACE_MAX_STEPS
  steps or TRACE_MAX_SECONDS inside the window, and at least
  TRACE_MIN_STEPS whole steps however long they take (or as many as
  the window still holds).  Host-clock layer metrics then come from
  the window's steps outside the traced part; device metrics come from
  the trace.
"""
from __future__ import annotations

import importlib
import logging
import math
import os
import shutil
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import manifest as _manifest  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

TRACE_MAX_STEPS = 50
TRACE_MAX_SECONDS = 3.0
TRACE_MIN_STEPS = 3               # steps of a second or more: still steps
TRACE_START_SHARE = 0.25          # of the window, before the trace starts
FEED_ANNOTATION = "bench:feed_next"
RESET_ANNOTATION = "bench:feed_reset"
DISPATCH_SPAN = "fused:dispatch"


def _resolve(dotted: str):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


class TraceControl:
    """Starts and stops the profiler from inside the fit loop's
    callbacks.  The traced window runs from the end of the first
    ``bench:batch_end`` annotation the trace holds to the end of the
    last: whole steps only."""

    def __init__(self, out_dir, start_at):
        self.out_dir, self.start_at = out_dir, start_at
        self.state = "idle"
        self.opened_at = None
        self.first_step = self.last_step = None   # callback indices

    def on_step(self, index: int, now: float) -> bool:
        """Called at the end of callback ``index``; True where this
        callback's step was disturbed by starting or stopping."""
        import jax
        if self.state == "idle" and now >= self.start_at:
            if os.path.isdir(self.out_dir):
                shutil.rmtree(self.out_dir)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans come from
            opts.host_tracer_level = 2        # TraceAnnotations alone
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.state = "armed"
            return True
        if self.state == "armed":
            # this callback's annotation is the first the trace holds
            self.state, self.opened_at, self.first_step = "open", now, index
            return True
        if self.state == "open":
            held = index - self.first_step      # annotations, this one too
            if (held >= TRACE_MAX_STEPS
                    or (held > TRACE_MIN_STEPS
                        and now - self.opened_at >= TRACE_MAX_SECONDS)):
                # this callback's annotation is still open and is lost
                self.last_step = index - 1
                jax.profiler.stop_trace()
                self.state = "done"
            return True
        return False

    def finish(self, last_index: int):
        """``fit`` has returned.  A trace the window closed on keeps the
        whole steps it holds (every annotation has ended by now)."""
        import jax
        if self.state == "open" and last_index > self.first_step:
            self.last_step = last_index
            jax.profiler.stop_trace()
            self.state = "done"
        elif self.state != "done":
            self.abort()

    def abort(self):
        import jax
        if self.state in ("armed", "open"):
            jax.profiler.stop_trace()
            self.state = "aborted"


class Window:
    """The wrapper iterator ``fit`` trains on, and its
    ``batch_end_callback``: cycles the traffic's iterator, times
    ``next()`` and ``reset()``, opens the window after the warm-up and
    ends the epoch at the deadline."""

    def __init__(self, traffic, seconds, warmup_steps, bucket_visits,
                 counter, tracer_for):
        self.traffic, self.seconds = traffic, float(seconds)
        self.warmup_steps = int(warmup_steps)
        self.bucket_visits = int(bucket_visits)
        self.counter, self.tracer_for = counter, tracer_for
        self.tracer = None
        self.provide_data = traffic.provide_data
        self.provide_label = traffic.provide_label
        self.batch_size = traffic.batch
        if getattr(traffic, "default_bucket_key", None) is not None:
            self.default_bucket_key = traffic.default_bucket_key
        self.t_open = self.deadline = self.open_ns = None
        self.requests_at_open = None
        self.attempted = 0
        self.steps = []               # one dict per completed step
        self._pending = None          # (samples, feed_s, reset_s, bucket)
        self._visits = {k: 0 for k in traffic.bucket_keys}
        self._prev = (0.0, 0)
        # a traffic with buckets hands over its warm-up itself (every
        # bucket ``bucket_visits`` times), or a rare bucket would keep
        # the window shut for many steps
        warm = getattr(traffic, "warmup_batches", None)
        self._warmup = list(warm(self.bucket_visits)) if warm else []

    # -- iterator protocol ---------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def reset(self):
        """``fit`` resets its iterator after the epoch: nothing to do."""

    def next(self):
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        if self.deadline is not None and t0 >= self.deadline:
            raise StopIteration
        reset_s = 0.0
        with TraceAnnotation(FEED_ANNOTATION):
            try:
                batch = self._warmup.pop(0) if self._warmup \
                    else self.traffic.next()
            except StopIteration:
                t1 = time.perf_counter()
                with TraceAnnotation(RESET_ANNOTATION):
                    self.traffic.reset()
                reset_s = time.perf_counter() - t1
                batch = self.traffic.next()
        self.attempted += 1
        self._pending = (self.traffic.samples(batch),
                         time.perf_counter() - t0, reset_s,
                         getattr(batch, "bucket_key", None))
        return batch

    # -- batch_end_callback --------------------------------------------------
    def on_batch_end(self, param):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(trace_reduce.STEP_ANNOTATION):
            now = time.perf_counter()
            m = param.eval_metric
            total, count = float(m.sum_metric), int(m.num_inst)
            loss = (total - self._prev[0]) / max(1, count - self._prev[1])
            self._prev = (total, count)
            samples, feed_s, reset_s, bucket = self._pending
            step = {"t": now, "samples": samples, "feed_s": feed_s,
                    "reset_s": reset_s, "loss": loss, "bucket": bucket,
                    "in_window": self.t_open is not None,
                    "disturbed": False}
            self.steps.append(step)
            if bucket in self._visits:
                self._visits[bucket] += 1
            if self.t_open is None:
                if (len(self.steps) >= self.warmup_steps
                        and all(n >= self.bucket_visits
                                for n in self._visits.values())):
                    import jax
                    # from here on JAX names what it compiles (nothing,
                    # if the warm-up did its work)
                    jax.config.update("jax_log_compiles", True)
                    self.requests_at_open = self.counter.count
                    self.open_ns = time.perf_counter_ns()
                    self.t_open = time.perf_counter()
                    self.deadline = self.t_open + self.seconds
                    self.tracer = self.tracer_for(self.t_open)
            elif self.tracer is not None:
                step["disturbed"] = self.tracer.on_step(
                    len(self.steps) - 1, time.perf_counter())


class CompileNames(logging.Handler):
    """What JAX says it compiles while the window is open
    (``jax_log_compiles``), so that a compile inside the window can be
    named, not only counted."""

    def __init__(self, window):
        super().__init__(logging.WARNING)
        self.window, self.names = window, []

    def emit(self, record):
        msg = record.getMessage()
        if self.window.t_open is not None and msg.startswith("Compiling"):
            self.names.append(msg[:160])


def _make_module(cell, traffic, contexts):
    """(module, whether it is a BucketingModule)."""
    import mxnet_tpu as mx
    cfg = cell.config
    builder = _resolve(cfg["model"]["builder"])
    kwargs = dict(cfg["model"]["kwargs"])
    kind = cfg["module"]["class"]
    if kind == "Module":
        return mx.mod.Module(builder(**kwargs), context=contexts), False
    if kind != "BucketingModule":
        raise _manifest.ManifestError("configuration %r: module class %r"
                                      % (cell.config_name, kind))
    bucket_arg = cfg["model"]["bucket_arg"]

    def sym_gen(key):
        return (builder(**dict(kwargs, **{bucket_arg: key})),
                tuple(traffic.data_names), tuple(traffic.label_names))

    return mx.mod.BucketingModule(
        sym_gen, default_bucket_key=traffic.default_bucket_key,
        context=contexts), True


def _initializer(cfg):
    import mxnet_tpu as mx
    return getattr(mx.init, cfg["initializer"]["name"])(
        **cfg["initializer"].get("kwargs", {}))


def reference_check(cell, ref, traffic, context, seed, log):
    """One SGD step of a second, small module through the public API
    against the configuration's plain reference: the loss, and the
    change of the named weights.  ``ref`` is the configuration's
    reference module.  -> (ok, details)."""
    import mxnet_tpu as mx
    cfg = cell.config
    ref_cfg = cfg["reference"]
    n = int(ref_cfg["samples"])
    data, labels, bucket_key = traffic.reference_batch(n)
    builder = _resolve(cfg["model"]["builder"])
    kwargs = dict(cfg["model"]["kwargs"])
    if bucket_key is not None:
        kwargs[cfg["model"]["bucket_arg"]] = bucket_key
    data_names = list(data)
    mod = mx.mod.Module(builder(**kwargs), data_names=data_names,
                        label_names=list(labels), context=context)
    mod.bind(data_shapes=[(k, v.shape) for k, v in data.items()],
             label_shapes=[(k, v.shape) for k, v in labels.items()])
    mx.random.seed(int(seed))
    mod.init_params(_initializer(cfg))
    mod.init_optimizer(optimizer=cfg["optimizer"]["name"],
                       optimizer_params=dict(cfg["optimizer"]["params"]))
    before = {k: v.asnumpy().astype(np.float32)
              for k, v in mod.get_params()[0].items()}
    metric = traffic.eval_metric(cfg)
    if isinstance(metric, str):
        metric = mx.metric.create(metric)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(data[k]) for k in data_names],
        label=[mx.nd.array(v) for v in labels.values()], pad=0)
    mod.forward_backward(batch)
    mod.update()
    mod.update_metric(metric, batch.label)
    loss = float(metric.get()[1])
    after = {k: v.asnumpy().astype(np.float32)
             for k, v in mod.get_params()[0].items()}
    names = list(ref_cfg["weights"])
    want = ref.reference_step(cfg, before, data, labels,
                              cfg["optimizer"]["params"], names)
    details = {"loss": loss, "reference_loss": want["loss"], "updates": {}}
    ok = math.isfinite(loss) and \
        abs(loss - want["loss"]) <= ref_cfg["loss_rtol"] * abs(want["loss"])
    for name in names:
        got = after[name] - before[name]
        ref_upd = np.asarray(want["updates"][name], np.float32)
        err = float(np.linalg.norm(got - ref_upd)
                    / max(np.linalg.norm(ref_upd), 1e-30))
        details["updates"][name] = err
        ok = ok and math.isfinite(err) and err <= ref_cfg["update_rtol"][name]
    log("reference check: loss %.5f vs %.5f (rtol %g); update errors %s "
        "(bounds %s) -> %s" % (loss, want["loss"], ref_cfg["loss_rtol"],
                               details["updates"], ref_cfg["update_rtol"],
                               "ok" if ok else "FAILED"))
    return ok, details


def _steps_per_second(inside, window):
    """Steps completed in each whole second of the window, as a string:
    shows whether a slow run was slow throughout or stalled."""
    counts = [0] * int(window.seconds)
    for step in inside:
        i = int(step["t"] - window.t_open)
        if i < len(counts):
            counts[i] += 1
    return " ".join(str(c) for c in counts)


def _memory(devices):
    """Peak bytes on the fullest device.  ``peak_bytes_in_use`` counts
    live arrays; XLA's temp space for loaded programs is counted under
    ``peak_bytes_reserved`` (PERF.md, findings of PR 22): the peak is
    their sum."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append({"in_use": int(s.get("peak_bytes_in_use", 0)),
                      "reserved": int(s.get("peak_bytes_reserved", 0))})
    fullest = max(peaks, key=lambda p: p["in_use"] + p["reserved"])
    return {"peak_bytes": fullest["in_use"] + fullest["reserved"],
            "peak_in_use_bytes": fullest["in_use"],
            "peak_reserved_bytes": fullest["reserved"]}


def run(cell, contexts, seed, seconds, trace, t_process, peaks, log):
    """Run the cell once.  -> the result line's dict, plus ``obs`` (what
    the per-layer readers read) under the key ``_obs``."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.compile_cache import count_backend_compiles

    cfg, traffic_cfg = cell.config, cell.traffic
    devices = [c.jax_device() for c in contexts]
    ref = _manifest.load_module("reference", cell.config_name,
                                cell.bench_dir)
    flops_per_sample = float(ref.train_flops_per_sample(cfg))
    generator = _manifest.load_module("generators", traffic_cfg["generator"],
                                      cell.bench_dir)
    cache_dir = os.path.join(cell.bench_dir, ".cache")
    trace_dir = os.path.join(cache_dir, "trace", cell.name)
    if trace:
        mx.trace.set_enabled(True)

    with count_backend_compiles() as counter:
        mx.random.seed(int(seed))
        traffic = generator.build(traffic_cfg, cfg, int(seed), contexts,
                                  cache_dir)
        log("traffic built (%s)" % traffic_cfg["generator"])
        ref_ok, ref_details = reference_check(cell, ref, traffic,
                                              contexts[0], seed, log)
        mx.random.seed(int(seed))
        mod, bucketing = _make_module(cell, traffic, contexts)
        metric = traffic.eval_metric(cfg)
        initializer = _initializer(cfg)

        def tracer_for(t_open):
            if not trace:
                return None
            return TraceControl(trace_dir,
                                t_open + TRACE_START_SHARE * float(seconds))

        window = Window(traffic, seconds, traffic_cfg["warmup_steps"],
                        traffic_cfg.get("bucket_visits", 1), counter,
                        tracer_for)
        if bucketing:
            # the example's order: bind, init_params, prepare, fit
            mod.bind(data_shapes=traffic.provide_data,
                     label_shapes=traffic.provide_label)
            mod.init_params(initializer)
            mod.prepare(traffic.bucket_shapes)
        raised = None
        names = CompileNames(window)
        logging.getLogger("jax").addHandler(names)
        was_logging = jax.config.jax_log_compiles
        try:
            mod.fit(window, eval_metric=metric, num_epoch=1,
                    batch_end_callback=window.on_batch_end,
                    initializer=initializer,
                    optimizer=cfg["optimizer"]["name"],
                    optimizer_params=dict(cfg["optimizer"]["params"]))
        except Exception:                 # the boundary: report, not hide
            raised = traceback.format_exc()
            sys.stderr.write(raised)
            if window.tracer is not None:
                window.tracer.abort()
        finally:
            jax.config.update("jax_log_compiles", was_logging)
            logging.getLogger("jax").removeHandler(names)
        jax.block_until_ready(jax.live_arrays())
        t_close = time.perf_counter()
        if window.tracer is not None:
            window.tracer.finish(len(window.steps) - 1)
        requests_total = counter.count
        compiled_total = counter.compiled

    steps = window.steps
    if window.t_open is None:
        raise RuntimeError("the window never opened: %d steps ran, warm-up "
                           "needs %d and buckets %s"
                           % (len(steps), window.warmup_steps,
                              list(traffic.bucket_keys)))
    inside = [s for s in steps if s["in_window"]]
    if not inside:
        raise RuntimeError("no step completed inside the window")
    window_s = t_close - window.t_open
    samples = sum(s["samples"] for s in inside)
    rate = stats.rate(samples, window.t_open, t_close)
    setup_s = window.t_open - t_process

    # step gaps: callback to callback, both ends inside the window and
    # neither step disturbed by the profiler starting or stopping
    first_in = steps.index(inside[0])
    gaps = []
    for a, b in zip(steps[first_in - 1:], steps[first_in:]):
        if not b["disturbed"]:
            gaps.append((b["t"] - a["t"]) * 1e3)
    clean = [s for s in inside if not s["disturbed"]]
    clean_s = sum(gaps) / 1e3
    feed_s = sum(s["feed_s"] for s in clean)
    reset_s = sum(s["reset_s"] for s in clean)

    losses = [s["loss"] for s in steps]
    finite = all(math.isfinite(v) for v in losses)
    failed = sum(1 for v in losses if not math.isfinite(v)) \
        + (1 if raised else 0)
    # the window's last tenth, and never fewer than three steps: one
    # step's loss swings with its bucket
    tenth = min(len(inside), max(3, len(inside) // 10))
    last_tenth = float(np.mean([s["loss"] for s in inside[-tenth:]]))
    chance = math.log(cfg["chance_loss_classes"])
    bar = min(losses[0], chance) - float(traffic_cfg["learn_margin"])
    learned = last_tenth < bar
    compiles_in_window = requests_total - window.requests_at_open
    correct = bool(ref_ok and finite and learned and not raised
                   and compiles_in_window == 0)
    log("steps %d (window %d, %.3f s); loss first %.4f, last tenth %.4f "
        "(bar %.4f = min(first, ln %d) - %g) -> %s; compile requests: %d "
        "before the window (%d compiled), %d inside"
        % (len(steps), len(inside), window_s, losses[0], last_tenth, bar,
           cfg["chance_loss_classes"], traffic_cfg["learn_margin"],
           "learned" if learned else "NOT learned", window.requests_at_open,
           compiled_total, compiles_in_window))
    for line in names.names:
        log("compiled inside the window: " + line)
    log("step gaps in the window: p50 %.2f ms, p90 %.2f, max %.2f; inside "
        "next()/reset() %.2f %% of it; steps in each second: %s" % (
            stats.median(gaps), stats.percentile(gaps, 90.0), max(gaps),
            100.0 * feed_s / clean_s, _steps_per_second(inside, window)))
    every = max(1, len(losses) // 12)
    log("loss every %d steps: %s" % (every, " ".join(
        "%.3f" % v for v in losses[::every])))

    memory = _memory(devices)
    obs = {
        "driver": "train_fit",
        "gaps_ms": gaps, "clean_s": clean_s, "feed_s": feed_s,
        "reset_s": reset_s, "resets": sum(1 for s in clean if s["reset_s"]),
        "window_s": window_s, "steps_in_window": len(inside),
        "dispatch_ms": [], "trace": None, "traced_rate": None,
        "compile": {"in_window": compiles_in_window,
                    "at_setup": window.requests_at_open,
                    "compiled": compiled_total},
        "flops_per_sample": flops_per_sample, "chips": len(contexts),
        "peaks": peaks, "memory": memory, "rate": rate,
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory["peak_bytes"]}
    result = {"correct": correct, "attempted": window.attempted,
              "failed": failed, "metrics": {}, "device": device}

    tr = window.tracer
    if trace:
        obs["dispatch_ms"] = [
            e["dur"] / 1e3 for e in mx.trace.span_events(
                names=[DISPATCH_SPAN], since_ns=window.open_ns)]
        if tr is None or tr.state != "done":
            raise RuntimeError("the trace did not complete (state %s)"
                               % (tr.state if tr else "no tracer"))
        devs, annotations = trace_reduce.load_xplane(
            trace_reduce.find_xplane(trace_dir))
        reduced = trace_reduce.reduce_trace(devs, annotations)
        traced = steps[tr.first_step + 1:tr.last_step + 1]
        if reduced["steps"] != len(traced):
            raise RuntimeError(
                "the trace holds %d whole steps, the harness counted %d"
                % (reduced["steps"], len(traced)))
        obs["trace"] = reduced
        obs["traced_rate"] = sum(s["samples"] for s in traced) \
            / reduced["window_s"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        log("traced run: %.4f %s/s over the whole window (tracing and its "
            "start and stop included), %.4f over the traced steps"
            % (rate, cfg["sample_unit"], obs["traced_rate"]))
        shutil.rmtree(trace_dir, ignore_errors=True)

    result["_obs"] = obs
    result["_e2e"] = {traffic_cfg["rate_metric"]: rate, "setup_s": setup_s}
    result["_reference"] = ref_details
    traffic.close()
    return result
