"""Module + training convergence tests. Modeled on reference
tests/python/train/test_mlp.py and module unit usage."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def make_blobs(n=400, dim=10, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, dim) * 3
    X = []
    y = []
    for i in range(n):
        c = rng.randint(classes)
        X.append(centers[c] + rng.randn(dim) * 0.5)
        y.append(c)
    return np.asarray(X, dtype=np.float32), np.asarray(y, dtype=np.float32)


def mlp_sym(classes=4):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_module_fit_convergence():
    np.random.seed(0)
    mx.random.seed(0)
    X, y = make_blobs()
    it = mx.io.NDArrayIter(X, y, batch_size=40, shuffle=True)
    mod = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod.fit(it, num_epoch=5, optimizer_params={"learning_rate": 0.5})
    acc = mod.score(it, "acc")
    assert acc[0][1] > 0.95, acc


def test_module_multi_device_data_parallel():
    """Fake multi-device data parallelism over cpu(0..3)."""
    np.random.seed(0)
    mx.random.seed(0)
    X, y = make_blobs()
    it = mx.io.NDArrayIter(X, y, batch_size=40, shuffle=True)
    mod = mx.mod.Module(mlp_sym(), context=[mx.cpu(i) for i in range(4)])
    mod.fit(it, num_epoch=5, optimizer_params={"learning_rate": 0.5})
    acc = mod.score(it, "acc")
    assert acc[0][1] > 0.95, acc


def test_module_predict_and_params():
    np.random.seed(0)
    X, y = make_blobs(n=100)
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    mod = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    out = mod.predict(it)
    assert out.shape == (100, 4)
    arg, aux = mod.get_params()
    assert "fc1_weight" in arg
    # set_params round trip
    mod2 = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod2.init_params(arg_params=arg, aux_params=aux)
    out2 = mod2.predict(it)
    assert np.allclose(out.asnumpy(), out2.asnumpy(), atol=1e-5)


def test_module_save_load_params(tmp_path):
    X, y = make_blobs(n=100)
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    mod = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    fname = str(tmp_path / "params")
    mod.save_params(fname)
    arg1, _ = mod.get_params()
    mod.load_params(fname)
    arg2, _ = mod.get_params()
    for k in arg1:
        assert np.allclose(arg1[k].asnumpy(), arg2[k].asnumpy())


def test_feedforward_fit_and_checkpoint(tmp_path):
    np.random.seed(0)
    mx.random.seed(0)
    X, y = make_blobs()
    it = mx.io.NDArrayIter(X, y, batch_size=40, shuffle=True)
    model = mx.model.FeedForward(mlp_sym(), ctx=mx.current_context(), num_epoch=4,
                                 learning_rate=0.5)
    model.fit(it)
    acc = model.score(it)
    assert acc > 0.9, acc
    prefix = str(tmp_path / "ffn")
    model.save(prefix)
    model2 = mx.model.FeedForward.load(prefix, 4, ctx=mx.current_context())
    acc2 = model2.score(it)
    assert abs(acc - acc2) < 1e-6
    pred = model2.predict(it)
    assert pred.shape == (400, 4)


@pytest.mark.parametrize("keys", [(8, 4, 8, 4, 6), (8, 8, 8, 4, 6, 8),
                                  (4, 8, 6, 8, 8)],
                         ids=lambda keys: "-".join(map(str, keys)))
def test_bucketing_module(keys):
    """Buckets of different sequence lengths share parameters
    (reference bucketing flow): ONE dict of them, whichever bucket's
    module trains.  The default bucket's steps before the first switch
    run on the fused state, which then holds the weights alone; the
    switch reads them back, and every later read sees every bucket's
    steps, as on the classic path throughout."""
    def sym_gen(seq_len):
        # params are seq-len independent (real bucketing's property)
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=10, output_dim=8, name="emb")
        net = mx.sym.FullyConnected(mx.sym.sum_axis(emb, axis=1),
                                    num_hidden=8, name="fc_shared")
        net = mx.sym.FullyConnected(net, num_hidden=2, name="out")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    from mxnet_tpu.io import DataBatch

    def batch(key, bs=8):
        X = np.random.randint(0, 10, (bs, key)).astype(np.float32)
        y = (X.sum(axis=1) > 4.5 * key).astype(np.float32)
        return DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                         bucket_key=key, pad=0,
                         provide_data=[("data", (bs, key))],
                         provide_label=[("softmax_label", (bs,))])

    def run(fused):
        os.environ["MXNET_FUSED_TRAIN"] = "1" if fused else "0"
        try:
            np.random.seed(0)
            mx.random.seed(0)
            mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                         context=mx.current_context())
            mod.bind(data_shapes=[("data", (8, 8))],
                     label_shapes=[("softmax_label", (8,))])
            mod.init_params()
            mod.init_optimizer(optimizer_params={"learning_rate": 0.1,
                                                 "momentum": 0.9})
            default = mod._buckets[8]
            reads = []
            for key in keys:
                b = batch(key)
                mod.forward(b, is_train=True)
                mod.backward()
                mod.update()
                # the state lives as long as the default bucket is alone
                assert (default._fused_state is not None) == \
                    (fused and set(mod._buckets) == {8})
                for m in mod._buckets.values():
                    assert m._arg_params is default._arg_params
                arg, _ = mod.get_params()
                assert all(v.context == mx.cpu(0) for v in arg.values())
                reads.append({k: v.asnumpy() for k, v in arg.items()})
            assert set(mod._buckets.keys()) == set(keys) | {8}
            return reads
        finally:
            os.environ.pop("MXNET_FUSED_TRAIN", None)

    fused, classic = run(True), run(False)
    for got, want in zip(fused, classic):
        for name in want:
            assert np.abs(got[name] - want[name]).max() < 1e-5, name
    # every step moved the shared weights
    for a, b in zip(fused, fused[1:]):
        assert np.abs(a["fc_shared_bias"] - b["fc_shared_bias"]).max() > 0


def test_monitor_in_module():
    X, y = make_blobs(n=80)
    it = mx.io.NDArrayIter(X, y, batch_size=20)
    seen = []
    mon = mx.Monitor(1, stat_func=lambda x: x, pattern=".*output")
    mon.stat_helper_orig = mon.stat_helper
    mod = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.install_monitor(mon)
    mon.tic()
    mod.forward(next(iter(it)), is_train=True)
    # backward with a monitor installed must not leak tracers into the
    # callback (regression: vjp re-trace fired monitor on traced arrays)
    mod.backward()
    res = mon.toc()
    assert len(res) > 0


def test_checkpoint_resume_training(tmp_path):
    """Crash-recovery story (SURVEY §5.3): train, checkpoint every epoch,
    reload with --load-epoch semantics, resume to completion."""
    import os
    rng = np.random.RandomState(0)
    centers = np.random.RandomState(42).randn(3, 6) * 3
    y = rng.randint(3, size=240)
    X = (centers[y] + rng.randn(240, 6) * 0.4).astype(np.float32)
    it = mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=24,
                           shuffle=True)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    prefix = str(tmp_path / "resume")

    ff = mx.model.FeedForward(net, ctx=mx.current_context(), num_epoch=2,
                              learning_rate=0.3)
    ff.fit(it, epoch_end_callback=mx.callback.do_checkpoint(prefix))
    assert os.path.exists(prefix + "-0002.params")

    # resume from epoch 2, run to epoch 4 (reference --load-epoch path)
    ff2 = mx.model.FeedForward.load(prefix, 2, ctx=mx.current_context(), num_epoch=4,
                                    learning_rate=0.3)
    it.reset()
    ff2.fit(it, epoch_end_callback=mx.callback.do_checkpoint(prefix))
    assert os.path.exists(prefix + "-0004.params")

    eval_it = mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=24)
    preds = ff2.predict(eval_it)
    acc = (preds.argmax(axis=1) == y[:preds.shape[0]]).mean()
    assert acc > 0.9, acc


def test_sequential_module():
    """SequentialModule chains sub-modules; labels feed only the tagged
    one (reference sequential_module.py take_labels/auto_wiring)."""
    rng = np.random.RandomState(0)
    X = rng.randn(64, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)

    d1 = mx.sym.Variable("data")
    feat = mx.sym.Activation(mx.sym.FullyConnected(d1, num_hidden=12,
                                                   name="fc1"),
                             act_type="relu")
    m1 = mx.mod.Module(feat, label_names=[], context=mx.current_context())
    d2 = mx.sym.Variable("data")
    head = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(d2, num_hidden=2,
                                                      name="fc2"),
                                name="softmax")
    m2 = mx.mod.Module(head, context=mx.current_context())

    seq = mx.mod.SequentialModule()
    seq.add(m1).add(m2, take_labels=True, auto_wiring=True)
    seq.fit(it, num_epoch=12, optimizer_params={"learning_rate": 0.5})
    it.reset()
    acc = seq.score(it, "acc")[0][1]
    assert acc >= 0.9, acc
    # gradient flowed through the chain into the first module
    w1 = m1.get_params()[0]["fc1_weight"].asnumpy()
    assert w1.std() > 0.05, w1.std()


def test_python_loss_module():
    """PythonLossModule computes gradients in python against the chained
    symbolic module (reference python_module.py usage pattern)."""
    from mxnet_tpu.module.python_module import PythonLossModule
    m = PythonLossModule(grad_func=lambda scores, labels:
                         scores.asnumpy() - labels.asnumpy())
    m.bind(data_shapes=[("data", (4, 3))])
    x = mx.nd.array(np.random.RandomState(0).rand(4, 3).astype(np.float32))
    from mxnet_tpu.io import DataBatch
    b = DataBatch(data=[x], label=[x], pad=0)
    m.forward(b, is_train=True)
    out = m.get_outputs()[0]
    assert out.shape == (4, 3)
    m.backward()
    grads = m.get_input_grads()
    assert grads[0].shape == (4, 3)


def test_module_reshape():
    """Module.reshape changes batch size keeping trained params
    (reference module.py reshape)."""
    rng = np.random.RandomState(0)
    X = rng.randn(64, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=2,
                                                     name="fc"),
                               name="softmax")
    mod = mx.mod.Module(net, context=mx.current_context())
    mod.fit(it, num_epoch=6, optimizer_params={"learning_rate": 0.5})
    w_before = mod.get_params()[0]["fc_weight"].asnumpy()

    mod.reshape(data_shapes=[("data", (4, 6))],
                label_shapes=[("softmax_label", (4,))])
    assert mod.data_shapes[0][1] == (4, 6)
    w_after = mod.get_params()[0]["fc_weight"].asnumpy()
    assert np.allclose(w_before, w_after)
    it4 = mx.io.NDArrayIter(X, y, batch_size=4)
    acc = mod.score(it4, "acc")[0][1]
    assert acc >= 0.9, acc


def test_module_reshape_syncs_dirty_params():
    """reshape() right after fit() must carry the trained device params
    into the new exec group — without an intervening get_params() call
    (which sync'd as a side effect and masked the bug)."""
    rng = np.random.RandomState(1)
    X = rng.randn(64, 6).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=2,
                                                     name="fc"),
                               name="softmax")
    mod = mx.mod.Module(net, context=mx.current_context())
    mod.fit(it, num_epoch=6, optimizer_params={"learning_rate": 0.5})
    # deliberately no get_params() here
    mod.reshape(data_shapes=[("data", (4, 6))],
                label_shapes=[("softmax_label", (4,))])
    it4 = mx.io.NDArrayIter(X, y, batch_size=4)
    acc = mod.score(it4, "acc")[0][1]
    assert acc >= 0.9, acc


def test_module_reshape_keeps_grad_req():
    """grad_req='add' must survive a reshape (accumulation semantics)."""
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(data, num_hidden=2,
                                                     name="fc"),
                               name="softmax")
    mod = mx.mod.Module(net, context=mx.current_context())
    mod.bind(data_shapes=[("data", (8, 6))],
             label_shapes=[("softmax_label", (8,))], grad_req="add")
    mod.init_params()
    mod.reshape(data_shapes=[("data", (4, 6))],
                label_shapes=[("softmax_label", (4,))])
    rng = np.random.RandomState(2)
    batch = mx.io.DataBatch(data=[mx.nd.array(rng.randn(4, 6))],
                            label=[mx.nd.array(np.zeros(4))])
    mod.forward(batch, is_train=True)
    mod.backward()
    g1 = [g[0].asnumpy().copy() for g in mod._exec_group.grad_arrays]
    mod.forward(batch, is_train=True)
    mod.backward()
    g2 = [g[0].asnumpy() for g in mod._exec_group.grad_arrays]
    for a, b in zip(g1, g2):
        assert np.allclose(2 * a, b, atol=1e-5), "grad_req='add' lost"


def test_bucketing_prepare_precompiles():
    """prepare() binds and warms every bucket before the training loop
    (the shared-pool switching-cost answer: docs/bucketing.md)."""
    np.random.seed(0)
    mx.random.seed(0)

    def sym_gen(seq_len):
        # params are seq-len independent (real bucketing's property)
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=10, output_dim=8, name="emb")
        feat = mx.sym.sum_axis(emb, axis=1)
        net = mx.sym.FullyConnected(feat, num_hidden=2, name="out")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.current_context())
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.prepare({k: ([("data", (8, k))], [("softmax_label", (8,))])
                 for k in (4, 6)})
    # every bucket bound, each executor's train program already compiled
    assert set(mod._buckets.keys()) == {8, 4, 6}
    for key in (4, 6):
        for ex in mod._buckets[key]._exec_group.execs:
            assert ex._jit_cache, key
    cache_snapshot = {key: [set(ex._jit_cache) for ex in
                            mod._buckets[key]._exec_group.execs]
                      for key in mod._buckets}
    # prepare must not disturb the current module or training
    assert mod._curr_module is mod._buckets[8]
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    from mxnet_tpu.io import DataBatch
    params_before = {k: v.asnumpy().copy()
                     for k, v in mod.get_params()[0].items()}
    for key in (4, 8, 6):
        X = np.random.randint(0, 10, (8, key)).astype(np.float32)
        y = (X.sum(axis=1) > key * 4.5).astype(np.float32)
        b = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                      bucket_key=key, pad=0,
                      provide_data=[("data", (8, key))],
                      provide_label=[("softmax_label", (8,))])
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()
    params_after = mod.get_params()[0]
    assert any(np.abs(params_after[k].asnumpy() - params_before[k]).max() > 0
               for k in params_before)
    # the docs/bucketing.md guarantee: a prepared run triggers no new
    # program compilation inside the training loop
    for key, snaps in cache_snapshot.items():
        now = [set(ex._jit_cache) for ex in
               mod._buckets[key]._exec_group.execs]
        assert now == snaps, (key, snaps, now)


def test_bucketing_prepare_keeps_shared_params_consistent():
    """prepare() before init_optimizer must not let the lent-out default
    bucket re-engage the private fused path: a prepared run and a
    lazy-bind run of the same batches train identical parameters."""
    def run(prepared):
        np.random.seed(3)
        mx.random.seed(3)

        def sym_gen(seq_len):
            data = mx.sym.Variable("data")
            emb = mx.sym.Embedding(data, input_dim=10, output_dim=8,
                                   name="emb")
            feat = mx.sym.sum_axis(emb, axis=1)
            net = mx.sym.FullyConnected(feat, num_hidden=2, name="out")
            return mx.sym.SoftmaxOutput(net, name="softmax")

        mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                     context=mx.current_context())
        mod.bind(data_shapes=[("data", (8, 8))],
                 label_shapes=[("softmax_label", (8,))])
        mod.init_params()
        if prepared:
            mod.prepare({k: ([("data", (8, k))], [("softmax_label", (8,))])
                         for k in (4, 6)})
        mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
        if prepared:
            # exec group already lent to the prepared buckets: fusion must
            # not re-engage (the lazy path tears it down at first switch)
            assert mod._buckets[8]._fused is None
        from mxnet_tpu.io import DataBatch
        for key in (8, 8, 4, 8, 6):
            X = np.random.randint(0, 10, (8, key)).astype(np.float32)
            y = (X.sum(axis=1) > key * 4.5).astype(np.float32)
            b = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                          bucket_key=key, pad=0,
                          provide_data=[("data", (8, key))],
                          provide_label=[("softmax_label", (8,))])
            mod.forward(b, is_train=True)
            mod.backward()
            mod.update()
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    pa = run(prepared=True)
    pb = run(prepared=False)
    for k in pb:
        assert np.abs(pa[k] - pb[k]).max() < 1e-6, k


def test_bucketing_prepare_preserves_live_state():
    """prepare() must not clobber outputs/gradients of buckets that have
    already run; only cold buckets get the zero-batch warmup."""
    np.random.seed(1)
    mx.random.seed(1)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=10, output_dim=8, name="emb")
        feat = mx.sym.sum_axis(emb, axis=1)
        net = mx.sym.FullyConnected(feat, num_hidden=2, name="out")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.current_context())
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    from mxnet_tpu.io import DataBatch
    X = np.random.randint(0, 10, (8, 8)).astype(np.float32)
    y = (X.sum(axis=1) > 36).astype(np.float32)
    b = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                  bucket_key=8, pad=0,
                  provide_data=[("data", (8, 8))],
                  provide_label=[("softmax_label", (8,))])
    mod.forward(b, is_train=True)
    live_out = mod.get_outputs()[0].asnumpy().copy()

    mod.prepare({4: ([("data", (8, 4))], [("softmax_label", (8,))])})
    # the default bucket already ran: its outputs survive prepare
    assert np.allclose(mod.get_outputs()[0].asnumpy(), live_out)
    assert 4 in mod._buckets


def test_module_non_batch_major_inputs():
    """Inputs whose leading dim is not the batch size (Fast R-CNN rois:
    R rois over B images) must not be sliced to the batch dim by the
    executor group (regression: rois (R,5) was silently rebound to (B,5)
    and outputs collapsed)."""
    rng = np.random.RandomState(0)
    B, R = 2, 12
    data = mx.sym.Variable("data")            # (B, 4)
    rois = mx.sym.Variable("rois")            # (R, 2) [batch_idx, feat]
    # roi-level feature: gather image feature rows by roi batch index
    # via Embedding over the batch index is overkill — use a simple
    # concat-able formulation: scores over rois from their own features
    net = mx.sym.FullyConnected(rois, num_hidden=3, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, data_names=("rois",),
                        label_names=("softmax_label",),
                        context=mx.current_context())
    # rois batch-major dim (R) deliberately != any data batch; label has
    # R rows too
    mod.bind(data_shapes=[("rois", (R, 2))],
             label_shapes=[("softmax_label", (R,))])
    mod.init_params()
    from mxnet_tpu.io import DataBatch
    X = rng.rand(R, 2).astype(np.float32)
    y = rng.randint(0, 3, R).astype(np.float32)
    b = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    mod.forward(b, is_train=False)
    out = mod.get_outputs()[0]
    assert out.shape == (R, 3), out.shape

    # the mixed case: batch-major data (B) + non-batch-major rois (R)
    net2 = mx.sym.Group([
        mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("rois"), num_hidden=3,
                                  name="fc2"), name="sm"),
        mx.sym.BlockGrad(mx.sym.Variable("data"))])
    mod2 = mx.mod.Module(net2, data_names=("data", "rois"),
                         label_names=("sm_label",),
                         context=mx.current_context())
    mod2.bind(data_shapes=[("data", (B, 4)), ("rois", (R, 2))],
              label_shapes=[("sm_label", (R,))], for_training=False)
    mod2.init_params()
    b2 = DataBatch(data=[mx.nd.array(rng.rand(B, 4).astype(np.float32)),
                         mx.nd.array(X)],
                   label=[mx.nd.array(y)])
    mod2.forward(b2, is_train=False)
    outs = mod2.get_outputs()
    assert outs[0].shape == (R, 3)
    assert outs[1].shape == (B, 4)


def test_bucketing_prepare_rejects_pending_grads():
    """prepare() between backward() and update() would clobber the live
    bucket's pending gradients through the shared exec arrays — it must
    refuse instead of corrupting the step."""
    np.random.seed(2)
    mx.random.seed(2)

    def sym_gen(seq_len):
        data = mx.sym.Variable("data")
        emb = mx.sym.Embedding(data, input_dim=10, output_dim=8, name="emb")
        feat = mx.sym.sum_axis(emb, axis=1)
        net = mx.sym.FullyConnected(feat, num_hidden=2, name="out")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8,
                                 context=mx.current_context())
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    from mxnet_tpu.io import DataBatch
    X = np.random.randint(0, 10, (8, 8)).astype(np.float32)
    y = (X.sum(axis=1) > 36).astype(np.float32)
    b = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                  bucket_key=8, pad=0,
                  provide_data=[("data", (8, 8))],
                  provide_label=[("softmax_label", (8,))])
    mod.forward(b, is_train=True)
    mod.backward()
    if mod._curr_module._grads_pending:   # classic path: grads are live
        with pytest.raises(AssertionError, match="between backward"):
            mod.prepare({4: ([("data", (8, 4))], [("softmax_label", (8,))])})
    mod.update()
    # after the step commits, warming is safe again
    mod.prepare({4: ([("data", (8, 4))], [("softmax_label", (8,))])})
    assert 4 in mod._buckets
    # the warmup's own throwaway backward must not trip the guard on a
    # second prepare()
    mod.prepare({6: ([("data", (8, 6))], [("softmax_label", (8,))])})
    assert 6 in mod._buckets


def test_no_slice_names_mark_coincident_batch_dim():
    """An input whose leading dim coincidentally equals the batch size
    (rcnn rois with num_rois == batch_size) can be marked no-slice at
    bind time: multi-device binds then refuse to split it instead of
    silently slicing, and single-device metric updates leave it whole."""
    B = 4
    rois = mx.sym.Variable("rois")            # (B, 3) but NOT batch-major
    net = mx.sym.FullyConnected(rois, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    # multi-device: marked input cannot be split -> explicit error, not a
    # silent per-device slice
    mod = mx.mod.Module(net, data_names=("rois",),
                        label_names=("softmax_label",),
                        context=[mx.cpu(0), mx.cpu(1)])
    with pytest.raises(mx.base.MXNetError, match="no-slice"):
        mod.bind(data_shapes=[("rois", (B, 3))],
                 label_shapes=[("softmax_label", (B,))],
                 no_slice_names=("rois",))

    # single device: binds fine and the exec group replicates it whole
    mod = mx.mod.Module(net, data_names=("rois",),
                        label_names=("softmax_label",),
                        context=mx.cpu(0))
    # a typo in the marker list fails eagerly instead of silently
    # re-enabling the slicing it was meant to prevent
    with pytest.raises(mx.base.MXNetError, match="match no bound"):
        mod.bind(data_shapes=[("rois", (B, 3))],
                 label_shapes=[("softmax_label", (B,))],
                 no_slice_names=("roi",))
    mod.bind(data_shapes=[("rois", (B, 3))],
             label_shapes=[("softmax_label", (B,))],
             no_slice_names=("rois",))
    (slc, _), = mod._exec_group.data_arrays[0]
    assert (slc.start, slc.stop) == (0, B)


def test_input_grads_do_not_release_pending_param_grads():
    """GAN-style flow: read input grads, THEN update().  The input-grad
    read must not release the backward-to-update guard while an optimizer
    still owns the pending param gradients (a bucketing prepare() in that
    window could clobber them)."""
    np.random.seed(3)
    mx.random.seed(3)
    X, y = make_blobs(n=40)
    it = mx.io.NDArrayIter(X, y, batch_size=40)
    mod = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod.bind(it.provide_data, it.provide_label, inputs_need_grad=True)
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    b = next(iter(it))
    mod.forward(b, is_train=True)
    mod.backward()
    assert mod._grads_pending
    g = mod.get_input_grads()
    assert g[0].shape == X.shape
    assert mod._grads_pending, \
        "input-grad read released the guard with an optimizer live"
    mod.update()
    assert not mod._grads_pending

    # grad-only flow (no optimizer): the read IS the consumer and must
    # release the guard, as before
    mod2 = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod2.bind(it.provide_data, it.provide_label, inputs_need_grad=True)
    mod2.init_params()
    mod2.forward(b, is_train=True)
    mod2.backward()
    mod2.get_input_grads()
    assert not mod2._grads_pending


def test_discarded_speculation_restores_num_update():
    """forward(); get_outputs(); forward() — the early-committed step of
    the first batch is discarded, so the optimizer's step count must roll
    back or an lr scheduler keyed on num_update fires one step early."""
    np.random.seed(4)
    mx.random.seed(4)
    X, y = make_blobs(n=80)
    it = mx.io.NDArrayIter(X, y, batch_size=40)
    mod = mx.mod.Module(mlp_sym(), context=mx.current_context())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    if mod._fused is None:
        pytest.skip("fused train path not engaged")
    batches = list(it)
    mod.forward(batches[0], is_train=True)
    before = mod._optimizer.num_update
    mod.get_outputs()          # speculative early commit bumps the count
    assert mod._fused_next is not None
    assert mod._optimizer.num_update == before + 1
    mod.forward(batches[1], is_train=True)   # discards the speculation
    assert mod._fused_next is None
    assert mod._optimizer.num_update == before, \
        "discarded speculation left num_update one ahead"
    mod.update()               # commits batch 1 as the real step 1
    assert mod._optimizer.num_update == before + 1
