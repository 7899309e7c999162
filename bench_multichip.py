"""Multichip scaling benchmark leg: Module.fit(mesh=...) + tp-sharded serve.

Measures what ISSUE 7 shipped — the first-class mesh path — as scaling
efficiency against the 1-device fused step, plus the tp-sharded
ServeEngine's closed-loop throughput:

  multichip_scaling_eff_dp8      img/s(dp=8) / (8 x img/s(1 dev)),
                                 weak scaling: per-device batch fixed
  multichip_scaling_eff_dp4tp2   same for the dp=4 x tp=2 mesh with the
                                 conv head tensor-parallel over tp
  multichip_serve_tp_qps         closed-loop QPS of a tp=2-sharded
                                 ServeEngine (8 client threads)
  multichip_backend              always 'cpu': every datapoint is a
                                 child on 8 forced host devices (the
                                 tier-1 topology).  A chip belongs to
                                 one process, so a child can never have
                                 it; efficiencies on a shared-core host
                                 measure the GSPMD path's overhead, not
                                 chip scaling (that is one process over
                                 a host's chips — chip_smoke.py --chips)

ISSUE 18 (mxnet_tpu.dist) adds the multi-PROCESS legs (the gloo
process-boundary overhead is what they measure):

  dist_scaling_eff_2proc         img/s(2 processes x 1 dev, dp=2 mesh
                                 across the process boundary) / img/s
                                 (1 process x 2 forced host devices,
                                 same dp=2 mesh) — the cost of crossing
                                 from XLA-internal collectives to gloo
  dist_host_recovery_s           FleetSupervisor under the dist.host
                                 chaos spec: SIGKILL'd rank ->
                                 checkpoint-commit recovery seconds
  shardsearch_vs_hand_frac       worst-case (CNN, LSTM) ratio of the
                                 sharding="auto" winner's steady step
                                 time over the hand-written PR 7 specs
                                 on a dp=4 x tp=2 mesh; <= 1.05 is the
                                 acceptance bar ("within 5% of hand")

Each datapoint runs in a FRESH subprocess (same pattern as
bench_compile.py) whose env pins JAX_PLATFORMS=cpu: the mesh is a
process-level property of the backend, and the parent may hold the chip.
The 2-process leg goes through ``tools/launch.py --launcher local`` —
the exact rendezvous a real fleet uses.
"""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

PER_DEVICE_BATCH = 16
IMG_SHAPE = (3, 16, 16)
CLASSES = 10
FILTERS = 32
TRAIN_ITERS = 16
TRAIN_WINDOWS = 3
SERVE_THREADS = 8
SERVE_SECONDS = 4.0
SERVE_HIDDEN = 64
DIST_PORT = 9343
FLEET_CHAOS = "points=dist.host@rank1,kinds=crash,after=5,max=1,attempts=0"
SHARD_WINDOWS = 5
SHARD_ITERS = 8


def _cnn():
    import mxnet_tpu as mx
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1),
                             num_filter=FILTERS, name="conv0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=SERVE_HIDDEN, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _train_child(mesh_spec):
    """One steady-state throughput measurement; prints a json line."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from jax.sharding import PartitionSpec as P

    mesh = None
    sharding = None
    dp = 1
    if mesh_spec:
        from mxnet_tpu.parallel import make_mesh, parse_mesh_spec
        axes = parse_mesh_spec(mesh_spec)
        mesh = make_mesh(axes)
        dp = int(dict(axes)["dp"])
        if "tp" in dict(mesh.shape):
            # tensor-parallel head: fc1 column-parallel over tp
            sharding = {"fc1_weight": P("tp", None), "fc1_bias": P("tp")}
    batch = PER_DEVICE_BATCH * dp

    rng = np.random.RandomState(0)
    X = rng.rand(batch, *IMG_SHAPE).astype(np.float32)
    y = rng.randint(0, CLASSES, batch).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(_cnn(), context=mx.cpu(0))
    mod.bind(it.provide_data, it.provide_label, mesh=mesh,
             sharding=sharding)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    # pre-stage the batch in the step's input layout (device throughput,
    # not input-pipeline throughput — same convention as bench.py)
    if mod._fused is not None:
        mod._fused_ensure_state()
        sh = mod._fused.batched_sharding()
        staged = mx.io.DataBatch(
            data=[mx.nd.NDArray(jax.device_put(jnp.asarray(X), sh))],
            label=[mx.nd.NDArray(jax.device_put(jnp.asarray(y), sh))])
    else:
        staged = next(iter(it))
    for _ in range(4):
        mod.forward(staged, is_train=True)
        mod.backward()
        mod.update()
    jax.block_until_ready(next(iter(mod._fused_state["params"].values()))
                          if mod._fused_state is not None else 0)
    rates = []
    for _ in range(TRAIN_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(TRAIN_ITERS):
            mod.forward(staged, is_train=True)
            mod.backward()
            mod.update()
        if mod._fused_state is not None:
            jax.block_until_ready(
                next(iter(mod._fused_state["params"].values())))
        rates.append(batch * TRAIN_ITERS / (time.perf_counter() - t0))
    img_s = sorted(rates)[len(rates) // 2]
    print("BENCH_MULTICHIP_CHILD " + json.dumps(
        {"img_s": img_s, "devices": jax.device_count(), "batch": batch}),
        flush=True)


def _serve_child():
    """tp=2-sharded ServeEngine closed-loop QPS; prints a json line."""
    import tempfile
    import threading
    import jax
    import mxnet_tpu as mx
    from jax.sharding import PartitionSpec as P

    net = _cnn()
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(np.zeros((8,) + IMG_SHAPE, np.float32),
                           np.zeros(8, np.float32), batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    arg, aux = mod.get_params()
    tmp = tempfile.mkdtemp(prefix="bench_mc_")
    prefix = os.path.join(tmp, "model")
    mx.model.save_checkpoint(prefix, 0, net, arg, aux)

    eng = mx.serve.ServeEngine.from_checkpoint(
        prefix, 0,
        input_shapes={"data": (1,) + IMG_SHAPE, "softmax_label": (1,)},
        batch_buckets=(1, 2, 4, 8), mesh="tp=2",
        param_specs={"fc1_weight": P("tp", None), "fc1_bias": P("tp")},
        name="bench_serve_tp")
    xs = rng.rand(64, *IMG_SHAPE).astype(np.float32)
    done = [0]
    stop = threading.Event()
    lock = threading.Lock()

    def client(i):
        j = i
        while not stop.is_set():
            eng.predict(xs[j % len(xs)], timeout=30)
            j += SERVE_THREADS
            with lock:
                done[0] += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(SERVE_THREADS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(SERVE_SECONDS)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    dt = time.perf_counter() - t0
    eng.close()
    print("BENCH_MULTICHIP_CHILD " + json.dumps(
        {"qps": done[0] / dt, "requests": done[0],
         "devices": jax.device_count()}), flush=True)


def _dist_train_child(ref):
    """One side of the 2-process scaling leg: the SAME dp=2 CNN step,
    either across two launch.py workers (1 host device each, dist_sync
    rendezvous — the gloo path) or in one process over 2 forced host
    devices (the XLA-internal-collectives baseline).  Prints a json
    line with the GLOBAL img/s."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    global_bs = PER_DEVICE_BATCH * 2
    if ref:
        assert jax.device_count() == 2, \
            "--ref needs XLA_FLAGS=--xla_force_host_platform_device_count=2"
        kv, rank, bs = None, 0, global_bs
    else:
        kv = mx.kv.create("dist_sync")
        rank, bs = kv.rank, PER_DEVICE_BATCH

    rng = np.random.RandomState(0)
    X = rng.rand(bs, *IMG_SHAPE).astype(np.float32)
    y = rng.randint(0, CLASSES, bs).astype(np.float32)
    mod = mx.mod.Module(_cnn(), context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (bs,) + IMG_SHAPE)],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params(mx.init.Xavier())
    mod.set_mesh(make_mesh([("dp", 2)]))
    mod.init_optimizer(kvstore=kv, optimizer_params={
        "learning_rate": 0.05, "momentum": 0.9})
    assert mod._fused is not None, "fused mesh path did not engage"
    # both sides feed host arrays through the normal DataBatch path:
    # the ratio must charge the input transfer to BOTH legs equally
    batch = mx.io.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    for _ in range(4):
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    jax.block_until_ready(next(iter(mod._fused_state["params"].values())))
    if not ref:
        from jax.experimental import multihost_utils as mhu
        mhu.sync_global_devices("bench_dist_warm")
    rates = []
    for _ in range(TRAIN_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(TRAIN_ITERS):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        jax.block_until_ready(
            next(iter(mod._fused_state["params"].values())))
        rates.append(global_bs * TRAIN_ITERS / (time.perf_counter() - t0))
    img_s = sorted(rates)[len(rates) // 2]
    print("BENCH_MULTICHIP_CHILD " + json.dumps(
        {"img_s": img_s, "rank": rank, "nproc": 1 if ref else 2}),
        flush=True)
    if not ref:
        from jax.experimental import multihost_utils as mhu
        mhu.sync_global_devices("bench_dist_done")


def _fleet_child():
    """FleetSupervisor recovery leg: 2 fleet workers, the dist.host
    chaos spec SIGKILLs rank1 mid-run, and the supervisor's
    commit-watch clocks death-to-recommit seconds."""
    import tempfile
    from mxnet_tpu.dist import FleetSupervisor

    root = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(root, "tests", "nightly", "dist_fleet_worker.py")
    ckpt = tempfile.mkdtemp(prefix="bench_fleet_")
    sup = FleetSupervisor(
        [sys.executable, worker, "--ckpt", ckpt],
        nworkers=2, on_loss="rejoin", checkpoint_dir=ckpt,
        timeout_s=240, env={"MXNET_FAULTS": FLEET_CHAOS})
    rc = sup.run()
    doc = sup.stats.report()
    doc["rc"] = rc
    print("BENCH_MULTICHIP_CHILD " + json.dumps(doc), flush=True)


def _shard_cnn(mesh, sharding):
    import mxnet_tpu as mx
    bs = PER_DEVICE_BATCH * 4
    mod = mx.mod.Module(_cnn(), context=mx.cpu(0))
    mod.bind(data_shapes=[("data", (bs,) + IMG_SHAPE)],
             label_shapes=[("softmax_label", (bs,))])
    mod.init_params(mx.init.Xavier())
    mod.set_mesh(mesh, sharding=sharding)
    mod.init_optimizer(optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(bs, *IMG_SHAPE).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, CLASSES, bs)
                           .astype(np.float32))])
    return mod, batch


def _shard_lstm(mesh, sharding):
    import mxnet_tpu as mx
    from mxnet_tpu.models.lstm import lstm_unroll
    bs, seq, vocab, hidden = 32, 8, 256, 64
    net = lstm_unroll(1, seq, vocab, hidden, hidden, vocab, dropout=0.0)
    data_names = ["data", "l0_init_c", "l0_init_h"]
    data_shapes = [("data", (bs, seq)), ("l0_init_c", (bs, hidden)),
                   ("l0_init_h", (bs, hidden))]
    mod = mx.mod.Module(net, data_names=data_names,
                        label_names=["softmax_label"], context=mx.cpu(0))
    mod.bind(data_shapes, [("softmax_label", (bs, seq))])
    mod.init_params(mx.init.Xavier())
    mod.set_mesh(mesh, sharding=sharding)
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.randint(0, vocab, (bs, seq))
                          .astype(np.float32)),
              mx.nd.array(np.zeros((bs, hidden), np.float32)),
              mx.nd.array(np.zeros((bs, hidden), np.float32))],
        label=[mx.nd.array(rng.randint(0, vocab, (bs, seq))
                           .astype(np.float32))])
    return mod, batch


def _shard_child(model, mode):
    """Steady step time of MODEL on a dp=4 x tp=2 mesh under either the
    hand-written PR 7 specs or the persisted sharding="auto" winner
    (the search runs before timing starts; only the chosen program is
    measured)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh

    mesh = make_mesh([("dp", 4), ("tp", 2)])
    if mode == "auto":
        sharding = "auto"
    elif model == "cnn":
        sharding = {"fc1_weight": P("tp", None), "fc1_bias": P("tp")}
    else:
        # Megatron-style vocab parallelism: the embedding table and the
        # classifier head split their vocab rows over tp
        sharding = {"embed_weight": P("tp", None),
                    "cls_weight": P("tp", None)}
    mod, batch = (_shard_cnn if model == "cnn" else _shard_lstm)(
        mesh, sharding)
    for _ in range(4):
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
    jax.block_until_ready(next(iter(mod._fused_state["params"].values())))
    times = []
    for _ in range(SHARD_WINDOWS):
        t0 = time.perf_counter()
        for _ in range(SHARD_ITERS):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        jax.block_until_ready(
            next(iter(mod._fused_state["params"].values())))
        times.append((time.perf_counter() - t0) / SHARD_ITERS)
    step_ms = sorted(times)[len(times) // 2] * 1e3
    print("BENCH_MULTICHIP_CHILD " + json.dumps(
        {"step_ms": step_ms, "model": model, "mode": mode}), flush=True)


def _cpu_env(ndev=None):
    """Env for every child: pinned to the host CPU (the parent may hold
    the chip, and a chip belongs to one process), with an EXACT forced
    device count when asked — the parent's own XLA_FLAGS never leak into
    a worker that must see 1."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    if ndev:
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % ndev)
    return env


def _run_child(args, env, timeout_s=600):
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"] + args,
        env=env, capture_output=True, text=True, timeout=timeout_s)
    if res.returncode != 0:
        raise RuntimeError("bench_multichip child %s failed: %s"
                           % (args, res.stderr[-1200:]))
    for ln in res.stdout.splitlines():
        if ln.startswith("BENCH_MULTICHIP_CHILD "):
            return json.loads(ln.split(" ", 1)[1])
    raise RuntimeError("bench_multichip child %s printed no result: %s"
                       % (args, res.stdout[-800:]))


def _dist_leg():
    """2-process vs 1-process dp=2: the gloo process-boundary tax."""
    root = os.path.dirname(os.path.abspath(__file__))
    args = [sys.executable, os.path.join(root, "tools", "launch.py"),
            "-n", "2", "--launcher", "local", "--port", str(DIST_PORT),
            "%s %s --child dist_train"
            % (sys.executable, os.path.abspath(__file__))]
    res = subprocess.run(args, capture_output=True, text=True,
                         timeout=600, env=_cpu_env(), cwd=root)
    if res.returncode != 0:
        raise RuntimeError("dist_train workers failed: %s"
                           % (res.stderr[-1200:] or res.stdout[-1200:]))
    # two ranks share one pipe — match by pattern, not by line
    docs = [json.loads(m) for m in
            re.findall(r"BENCH_MULTICHIP_CHILD (\{[^{}\n]*\})",
                       res.stdout)]
    two = next(d for d in docs if d.get("rank") == 0)
    ref = _run_child(["dist_ref"], _cpu_env(2))
    eff = two["img_s"] / ref["img_s"] if ref["img_s"] else None
    return {"dist_img_s_2proc": round(two["img_s"], 1),
            "dist_img_s_1proc_2dev": round(ref["img_s"], 1),
            "dist_scaling_eff_2proc": round(eff, 4) if eff else None}


def _fleet_leg():
    doc = _run_child(["fleet"], _cpu_env())
    if doc.get("rc") not in (0, None) or not doc.get("restarts"):
        raise RuntimeError("fleet leg did not recover: %r" % doc)
    return {"dist_host_recovery_s": round(float(doc["last_recovery_s"]),
                                          2)}


def _shard_leg(feed):
    """auto-vs-hand specs on the dp=4 x tp=2 mesh; the published frac
    is the WORST model's ratio (<= 1.05 = within 5% of hand)."""
    import tempfile
    store = tempfile.mkdtemp(prefix="bench_shard_store_")
    env8 = _cpu_env(8)
    out = {}
    fracs = []
    for model in ("cnn", "lstm"):
        feed("shardsearch-" + model)
        hand = _run_child(["shard", model, "hand"], dict(env8))
        auto = _run_child(["shard", model, "auto"],
                          dict(env8, MXNET_AUTOTUNE_DIR=store))
        out["shardsearch_%s_hand_step_ms" % model] = \
            round(hand["step_ms"], 2)
        out["shardsearch_%s_auto_step_ms" % model] = \
            round(auto["step_ms"], 2)
        fracs.append(auto["step_ms"] / hand["step_ms"])
    out["shardsearch_vs_hand_frac"] = round(max(fracs), 4)
    return out


def run(feed=lambda *_: None):
    """Returns the multichip_* metrics dict.  ``feed`` is the watchdog
    heartbeat.  Touches no JAX in this process; a child that fails
    raises."""
    env8 = _cpu_env(8)
    feed("multichip-1dev")
    one = _run_child(["train", ""], env8)
    feed("multichip-dp8")
    dp8 = _run_child(["train", "dp=8"], env8)
    feed("multichip-dp4tp2")
    dp4tp2 = _run_child(["train", "dp=4,tp=2"], env8)
    feed("multichip-serve-tp")
    serve = _run_child(["serve"], env8)

    base = one["img_s"]
    out = {
        "multichip_backend": "cpu",
        "multichip_img_s_1dev": round(base, 1),
        "multichip_img_s_dp8": round(dp8["img_s"], 1),
        "multichip_img_s_dp4tp2": round(dp4tp2["img_s"], 1),
        "multichip_scaling_eff_dp8": round(dp8["img_s"] / (8 * base), 4)
        if base else None,
        "multichip_scaling_eff_dp4tp2": round(
            dp4tp2["img_s"] / (8 * base), 4) if base else None,
        "multichip_serve_tp_qps": round(serve["qps"], 1),
        # the acceptance key names it serve_tp_qps; publish both
        "serve_tp_qps": round(serve["qps"], 1),
    }
    for name, leg in (("dist-2proc", _dist_leg),
                      ("dist-fleet", _fleet_leg),
                      ("shardsearch", lambda: _shard_leg(feed))):
        feed(name)
        out.update(leg())
    return out


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        if sys.argv[2] == "train":
            _train_child(sys.argv[3] if len(sys.argv) > 3 else "")
        elif sys.argv[2] == "dist_train":
            _dist_train_child(ref=False)
        elif sys.argv[2] == "dist_ref":
            _dist_train_child(ref=True)
        elif sys.argv[2] == "fleet":
            _fleet_child()
        elif sys.argv[2] == "shard":
            _shard_child(sys.argv[3], sys.argv[4])
        else:
            _serve_child()
        return
    from mxnet_tpu.compile_cache import place_jax_cache
    place_jax_cache()          # children inherit the exported choice
    print(json.dumps(run()), flush=True)


if __name__ == "__main__":
    main()
