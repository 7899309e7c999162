"""mxnet_tpu.compile_cache: the one jit wrapper, its AOT warm-up, and
JAX's persistent cache behind it.

* a second instance of anything (a function, a training module, a
  bucketing module's grid, a serve engine) in a process whose persistent
  cache holds its programs compiles nothing on the backend
* what ``warm()`` compiled is what the next call dispatches to, over one
  device or a mesh; a wrapped executable that refuses its first call is
  replaced by a fresh compile
* parallel AOT warmup: ServeEngine grid, BucketingModule.precompile,
  Module.prepare, Executor.precompile
* steady-state recompile guard on fit (K=1 fused and superstep K>1),
  score(), and warmed bucket/serve loops
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "common"))

import mxnet_tpu as mx                                    # noqa: E402
from mxnet_tpu import compile_cache as cc                 # noqa: E402
from mxnet_tpu.compile_cache.stats import _reset_stats    # noqa: E402
from compile_guard import assert_no_compiles, count_backend_compiles  # noqa: E402
from jax_cache import jax_cache_dir                       # noqa: E402,F401

import jax                                                # noqa: E402
import jax.numpy as jnp                                   # noqa: E402


@pytest.fixture(autouse=True)
def fresh_stats():
    """The process-global compile counters start and end empty."""
    _reset_stats()
    yield
    _reset_stats()


def _totals():
    return mx.profiler.compile_report()["totals"]


# ---------------------------------------------------------------------------
# the one cache: a fresh wrapper models a process restart (jit's own
# cache cannot help, only JAX's persistent cache can)


def test_same_program_second_instance_compiles_nothing(jax_cache_dir):
    def make():
        return cc.cached_jit(lambda x, y: jnp.tanh(x) @ y + 1.0,
                             name="t:mm")
    x = jnp.ones((16, 16))
    with count_backend_compiles() as c:
        r1 = make()(x, x)
        assert (c.count, c.compiled) == (1, 1)
        r2 = make()(x, x)
        assert (c.count, c.compiled) == (2, 1)
        # the AOT handle reads the same cache
        assert make().warm(x, x) == "compiled"
        assert (c.count, c.compiled) == (3, 1)
    assert np.allclose(np.asarray(r1), np.asarray(r2))
    assert os.listdir(jax_cache_dir)


def test_aval_changes_compile_their_own_program(jax_cache_dir):
    def make():
        # a new function object each time: jit's own cache goes by it
        return cc.cached_jit(lambda x: x * 2 + 1, name="t:a")

    variants = [jnp.ones((4, 4), jnp.float32), jnp.ones((8, 4), jnp.float32),
                jnp.ones((4, 4), jnp.bfloat16)]
    with count_backend_compiles() as c:
        for v in variants:
            make()(v)
        assert (c.count, c.compiled) == (3, 3)
        # and each variant is now served from the cache
        for v in variants:
            make()(v)
        assert (c.count, c.compiled) == (6, 3)
    # one wrapper, three warmed signatures: three entries, each call
    # dispatched to its own
    f = make()
    for v in variants:
        f.warm(v)
    assert len(f._entries) == 3
    for v in variants:
        out = f(v)
        assert out.shape == v.shape and out.dtype == v.dtype


# ---------------------------------------------------------------------------
# the dispatch of a warmed program


def test_wrapped_executable_refusing_first_call_falls_back(caplog):
    """A wrapped executable that cannot serve its first call (here: a
    pruning record naming an argument that does not exist) is dropped,
    the program compiles fresh, and the call still succeeds."""
    f = cc.cached_jit(lambda x: x * 5.0, name="t:stale")
    x = jnp.ones((4,))
    f.warm(x)
    (sig, entry), = f._entries.items()
    assert type(entry).__name__ == "_CachedExecutable"
    entry._kept = (7,)      # nonsense pruning record
    with caplog.at_level("WARNING"):
        got = np.asarray(f(x))
    assert np.allclose(got, 5.0)
    assert any("failed on first use" in r.message for r in caplog.records)
    assert f._entries[sig] is not entry
    assert np.allclose(np.asarray(f(x)), 5.0)


def test_warm_is_compile_only_and_reports_what_it_did():
    calls = []

    def fn(x):
        calls.append(1)             # runs at trace time only
        return x + 1

    f = cc.cached_jit(fn, name="t:warm")
    assert not f.has_compiled
    x = jnp.ones((3,))
    assert f.warm(x) == "compiled" and f.has_compiled
    assert f.warm(x) == "present"
    assert f.compile_for(x) is f._entries[next(iter(f._entries))]
    assert len(calls) == 1
    with assert_no_compiles("the call after warm()"):
        assert np.allclose(np.asarray(f(x)), 2.0)
    assert len(calls) == 1
    t = _totals()
    assert (t["programs"], t["compiles"]) == (1, 1)


def test_unwarmed_function_is_plain_jit():
    """Nothing warmed: the call goes to ``jax.jit`` and the wrapper
    holds no entry; ``optimized_hlo`` still finds the program."""
    f = cc.cached_jit(lambda x: jnp.tanh(x) * 2, name="t:plain")
    assert f.optimized_hlo() is None
    x = jnp.ones((5,))
    f(x)
    f(x)
    assert f.has_compiled and not f._entries
    assert _totals()["programs"] == 0
    assert "tanh" in f.optimized_hlo()


def test_cached_jit_takes_no_static_argnums():
    with pytest.raises(ValueError, match="dynamic args only"):
        cc.cached_jit(lambda x, n: x * n, name="t:static",
                      static_argnums=(1,))


def test_donated_argument_is_consumed_by_a_warmed_program():
    f = cc.cached_jit(lambda s, x: s + x, name="t:donate",
                      donate_argnums=(0,))
    s, x = jnp.ones((256,)), jnp.ones((256,))
    f.warm(s, x)
    out = f(s, x)
    assert np.allclose(np.asarray(out), 2.0)
    assert s.is_deleted() and not x.is_deleted()
    # the specs kept for ``optimized_hlo`` hold no buffer
    assert not any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(f._specs))


def test_multi_device_program_roundtrips():
    """An 8-device NamedSharding program (the fused mesh shape) warms
    and replays: the wrapped executable accepts sharded inputs and
    produces jit's values."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    x = jax.device_put(jnp.arange(32.0).reshape(8, 4), sh)

    def make():
        return cc.cached_jit(lambda a: (a * 2).sum(0), name="t:mesh")
    want = np.asarray(make()(x))          # plain jit
    warmed = make()
    assert warmed.warm(x) == "compiled"
    entry, = warmed._entries.values()
    assert type(entry).__name__ == "_CachedExecutable" and entry._multi
    got = np.asarray(warmed(x))
    assert np.allclose(got, want)
    assert np.allclose(np.asarray(warmed(x)), want)


def test_multi_device_sharded_outputs_and_uncommitted_args():
    """The two multi-device traps: (a) a PARTITIONED output must come
    back whole, not as shard 0 (replay reassembles from
    execute_sharded); (b) an uncommitted argument (the unpinned RNG key
    pattern) must land in the EXECUTABLE's sharding, which jit chose at
    compile time, not wherever the caller left it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    shd = NamedSharding(mesh, P("dp"))

    def fn(a, key):
        noise = jax.random.uniform(key, a.shape)
        y = a * 2 + noise * 0          # dp-sharded output
        return {"rows": y, "total": y.sum()}

    a = jax.device_put(jnp.arange(32.0).reshape(8, 4), shd)
    key = jax.random.PRNGKey(3)        # uncommitted, single-device

    def make():
        return cc.cached_jit(fn, name="t:meshout")
    w = make()(a, key)                    # plain jit
    warmed = make()
    warmed.warm(a, key)
    entry, = warmed._entries.values()
    assert type(entry).__name__ == "_CachedExecutable" and entry._multi
    g = warmed(a, key)
    assert np.asarray(g["rows"]).shape == (8, 4), \
        "partitioned output came back as a single shard"
    assert np.allclose(np.asarray(g["rows"]), np.asarray(w["rows"]))
    assert np.allclose(float(g["total"]), float(w["total"]))
    # steady-state calls keep working (per-call placement of the
    # uncommitted key)
    assert np.allclose(np.asarray(warmed(a, key)["rows"]),
                       np.asarray(w["rows"]))



# ---------------------------------------------------------------------------
# executor / module / fused integration


def _blobs(n=64, dim=8, classes=2, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, dim).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    return X, y


def _mlp(dim=8, classes=2):
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_executor_precompile_is_compile_only():
    """precompile builds the program without executing: outputs stay
    unset, and the later forward() finds the program already built
    (zero backend compiles) even with NO disk cache — warm() primes the
    wrapper's AOT dispatch."""
    x = mx.sym.Variable("x")
    y = mx.sym.FullyConnected(x, num_hidden=4, name="fc")
    ex = y.simple_bind(mx.cpu(), grad_req="null", x=(2, 3))
    assert not ex.has_compiled()
    assert ex.precompile() == ("fwd_eval",)
    assert ex.has_compiled()
    with pytest.raises(mx.base.MXNetError):
        ex.outputs            # nothing executed
    # prime the tiny eager key-derivation ops forward() runs per call
    # (precompile deliberately uses a dummy key and must not advance the
    # global RNG chain); the guard below is about GRAPH programs
    ex._next_rng()
    with assert_no_compiles("forward after precompile"):
        ex.forward(is_train=False)
    assert ex.outputs[0].shape == (2, 4)


def test_executor_fwdbwd_precompile_covers_train_loop():
    X, y = _blobs()
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    # classic path (no optimizer yet): the bound executors' train
    # program is fwdbwd_ones; precompile it, then forward+backward must
    # not compile
    for ex in mod._exec_group.execs:
        assert ex.precompile() == ("fwdbwd_ones",)
    batch = next(iter(it))
    with assert_no_compiles("forward/backward after precompile"):
        mod.forward(batch, is_train=True)
        mod.backward()


def test_module_prepare_then_fit_no_compiles():
    """Module.prepare AOT-compiles the fused step; the fit loop then
    runs with zero XLA compiles from the very first batch (modulo the
    tiny eager host ops, which are primed by one throwaway batch)."""
    X, y = _blobs(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None
    mod.prepare()
    with count_backend_compiles() as c:
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.update()
    # the one donated step program was prepared; nothing big compiled.
    # (host_outputs / metric plumbing may trace trivial eager ops once)
    assert c.count <= 2, "fused step recompiled after prepare()"


def test_fit_steady_state_no_compiles():
    """K=1 fused fit: after the first epoch built its programs, later
    epochs compile NOTHING (generalized from test_serve's
    no-compiles-in-loop into the shared compile_guard helper)."""
    X, y = _blobs(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, eval_metric="acc",
            optimizer_params={"learning_rate": 0.1})
    with assert_no_compiles("fit epoch 2 (fused K=1)"):
        mod.fit(it, num_epoch=2, begin_epoch=1, eval_metric="acc",
                optimizer_params={"learning_rate": 0.1})


def test_superstep_steady_state_no_compiles():
    """K>1 superstep fit: the scan-of-K program compiles once; later
    epochs (same K, same metric reducer) compile nothing."""
    X, y = _blobs(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, eval_metric="acc", superstep=2,
            optimizer_params={"learning_rate": 0.1})
    with assert_no_compiles("fit epoch 2 (superstep K=2)"):
        mod.fit(it, num_epoch=2, begin_epoch=1, eval_metric="acc",
                superstep=2, optimizer_params={"learning_rate": 0.1})


def test_score_steady_state_no_compiles():
    X, y = _blobs(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, eval_metric="acc",
            optimizer_params={"learning_rate": 0.1})
    mod.score(it, "acc")        # builds the eval program
    with assert_no_compiles("second score()"):
        mod.score(it, "acc")


# ---------------------------------------------------------------------------
# bucketing


def _bucket_batch(key, bs=8):
    from mxnet_tpu.io import DataBatch
    rng = np.random.RandomState(key)
    X = rng.randn(bs, key).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    return DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                     bucket_key=key, pad=0,
                     provide_data=[("data", (bs, key))],
                     provide_label=[("softmax_label", (bs,))])


def _bucketing_module():
    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=8, name="fc_shared")
        net = mx.sym.FullyConnected(net, num_hidden=2, name="out")
        return mx.sym.SoftmaxOutput(net, name="softmax")
    return mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                  context=mx.cpu())


def test_bucketing_precompile_then_loop_no_compiles():
    """precompile binds + compiles the whole bucket grid (through the
    warmup pool); a training sweep over every bucket then triggers no
    XLA compiles — the generalized no-compiles-in-loop guard applied to
    bucketed training."""
    mod = _bucketing_module()
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    buckets = {k: ([("data", (8, k))], [("softmax_label", (8,))])
               for k in (4, 6, 8)}
    mod.precompile(buckets, threads=2)
    # the per-bucket graph programs were all precompiled: the FIRST
    # forward+backward of every bucket runs without touching XLA
    with assert_no_compiles("first fwd/bwd sweep after precompile"):
        for key in (4, 6, 8):
            b = _bucket_batch(key)
            mod.forward(b, is_train=True)
            mod.backward()
    # one update per bucket primes the classic updater's per-shape eager
    # host ops (tiny, shape-keyed — outside precompile's contract)...
    for key in (4, 6, 8):
        b = _bucket_batch(key)
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()
    # ...after which the steady full train sweep is compile-free
    with assert_no_compiles("steady bucketed train sweep"):
        for key in (4, 6, 8, 4, 6, 8):
            b = _bucket_batch(key)
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()
    assert set(mod._buckets.keys()) == {4, 6, 8}



# ---------------------------------------------------------------------------
# serve engine warmup


def _save_pair(tmp_path, name="m"):
    X, y = _blobs(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=8)
    net = _mlp()
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    prefix = str(tmp_path / name)
    mx.model.save_checkpoint(prefix, 0, net, arg, aux)
    return prefix, X


def _engine(prefix, **kw):
    kw.setdefault("batch_buckets", (1, 2, 4))
    kw.setdefault("input_shapes", {"data": (1, 8), "softmax_label": (1,)})
    return mx.serve.ServeEngine.from_checkpoint(prefix, 0, **kw)



# ---------------------------------------------------------------------------
# a second instance / a restart compiles nothing on the backend


def _train_once():
    X, y = _blobs(n=64)
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False)
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    for batch in it:
        mod.forward(batch, is_train=True)
        mod.update()
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def _precompile_grid():
    mod = _bucketing_module()
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    mod.precompile({k: ([("data", (8, k))], [("softmax_label", (8,))])
                    for k in (4, 8)})
    return sorted(mod._buckets)


def _serve_once(prefix, X):
    eng = _engine(prefix)
    try:
        return np.asarray(eng.predict(X[0], timeout=30))
    finally:
        eng.close()


@pytest.mark.parametrize("what", ["fused_step", "bucketing_precompile",
                                  "serve_engine"])
def test_second_instance_compiles_nothing(what, jax_cache_dir, tmp_path):
    """The restart story: a second same-shaped training module's donated
    fused step, a rebuilt bucketing module's grid, a second serve
    engine's whole bucket grid are read from JAX's persistent cache
    (compile requests, none of them compiled) and give the first one's
    answers bit for bit."""
    if what == "serve_engine":
        prefix, X = _save_pair(tmp_path)
        build = lambda: _serve_once(prefix, X)            # noqa: E731
    else:
        build = {"fused_step": _train_once,
                 "bucketing_precompile": _precompile_grid}[what]
    first = build()
    with count_backend_compiles() as c:
        second = build()
    assert c.count > 0, "the second instance asked for no program"
    assert c.compiled == 0, \
        "%d of the second instance's %d programs were compiled" \
        % (c.compiled, c.count)
    if isinstance(first, dict):
        for k in first:
            assert np.array_equal(first[k], second[k]), k
    else:
        assert np.array_equal(first, second)


def test_serve_warmup_failure_names_bucket(tmp_path, monkeypatch):
    """A mid-grid warmup failure surfaces the offending bucket and its
    shapes, not a bare jax traceback."""
    prefix, _X = _save_pair(tmp_path)
    from mxnet_tpu.executor import Executor
    real = Executor.precompile

    def boom(self, kinds=None):
        if self.arg_dict["data"].shape[0] == 2:
            raise RuntimeError("XLA exploded mid-grid")
        return real(self, kinds)

    monkeypatch.setattr(Executor, "precompile", boom)
    with pytest.raises(mx.serve.ServeError) as ei:
        _engine(prefix)
    msg = str(ei.value)
    assert "bucket 2" in msg and "data" in msg and "compile" in msg
    assert "XLA exploded" in msg


def test_serve_warmup_thread_env(tmp_path, monkeypatch):
    prefix, X = _save_pair(tmp_path)
    monkeypatch.setenv("MXNET_SERVE_WARMUP_THREADS", "2")
    eng = _engine(prefix)
    try:
        assert eng._warmup_threads == 2
        assert np.asarray(eng.predict(X[0], timeout=30)).shape == (2,)
    finally:
        eng.close()


def test_predictor_precompile():
    X, _y = _blobs()
    net = _mlp()
    it = mx.io.NDArrayIter(X, np.zeros(len(X), np.float32), batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    params = {k: v for k, v in arg.items()}
    params.update(aux)
    from mxnet_tpu.predictor import Predictor
    p = Predictor(net.tojson(), params,
                  {"data": (8, 8), "softmax_label": (8,)})
    shapes = [{"data": (b, 8), "softmax_label": (b,)} for b in (1, 2, 8)]
    p.precompile(shapes, threads=2)
    with assert_no_compiles("predictor bucket cycling after precompile"):
        for s in shapes:
            p.reshape(s)
            p.set_input("data", np.zeros(s["data"], np.float32))
            p.forward()
            p.get_output(0)



# ---------------------------------------------------------------------------
# observability


def test_compile_report_surfaces_programs():
    f = cc.cached_jit(lambda x: x * 2, name="t:report")
    f.warm(jnp.ones((4,)))
    rep = mx.profiler.compile_report()
    assert set(rep) == {"totals", "per_program"}
    assert rep["totals"]["compiles"] >= 1
    assert "t:report" in rep["per_program"]
    per = rep["per_program"]["t:report"]
    assert set(per) == {"trace_lower_s", "compile_s", "compiles",
                        "steady_retraces"}
    assert per["compile_s"] > 0 and per["trace_lower_s"] > 0
    s = mx.profiler.compile_report_str()
    assert "t:report" in s and "steady retraces" in s


def test_steady_retrace_counter():
    """A program object compiling a SECOND signature is a retrace — the
    regression the counter exists to expose."""
    f = cc.cached_jit(lambda x: x + 1, name="t:retrace")
    f.warm(jnp.ones((2,)))
    assert _totals()["steady_retraces"] == 0
    f.warm(jnp.ones((3,)))      # new avals on a compiled program
    assert _totals()["steady_retraces"] == 1


def test_the_package_exports_what_the_entry_points_import():
    """``benchmark/run.py``, ``chip_smoke.py`` and the bench mains import
    these by name."""
    for name in ("cached_jit", "CachedFunction", "place_jax_cache",
                 "jax_cache_dir", "count_backend_compiles",
                 "record_compile_spans", "parallel_warm", "WarmupError",
                 "get_stats"):
        assert name in cc.__all__ and hasattr(cc, name), name
