"""One home for the weights on the fused training path (module/module.py,
module/fused.py): while a fused state exists it is that state and nothing
else on any device, and ``get_params`` hands out ``cpu``-context arrays.

The module trains on a forced host device of its own, which is also the
default context, the way ``tpu(0)`` is in a TPU process: what lies on
that device is "on the chip", what lies on ``cpu(0)`` is on the host.
"""
import gc
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx

CHIP = mx.cpu(5)
BATCH, WIDTH, HIDDEN = 8, 64, 256


def _mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=HIDDEN, name="fc1")
    h = mx.sym.BatchNorm(h, name="bn")
    h = mx.sym.Activation(h, act_type="relu")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=2, name="fc2"), name="softmax")


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(BATCH, WIDTH).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    return mx.io.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)],
                           pad=0)


def _module(optimizer="adam", ctx=CHIP):
    mx.random.seed(3)
    mod = mx.mod.Module(_mlp(), context=ctx)
    mod.bind(data_shapes=[("data", (BATCH, WIDTH))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params={"learning_rate": 0.05})
    assert mod._fused is not None
    return mod


def _steps(mod, n, seed=0):
    for i in range(n):
        mod.forward_backward(_batch(seed + i))
        mod.update()


def _bytes_on(ctx):
    """Bytes of the live buffers on ``ctx``'s device, each counted once
    (jax hands out a sharded array's shards as arrays over the same
    buffers)."""
    gc.collect()
    dev, seen = ctx.jax_device(), {}
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            if shard.device == dev:
                seen[shard.data.unsafe_buffer_pointer()] = shard.data.nbytes
    return sum(seen.values())


def _state_bytes(mod):
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(mod._fused_state))


def _param_bytes(mod):
    return sum(x.nbytes for x in
               jax.tree_util.tree_leaves(mod._fused_state["params"]))


def _state_values(mod):
    st = mod._fused_state
    return {n: np.asarray(v) for g in ("params", "fixed", "aux")
            for n, v in st[g].items()}


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_no_weight_on_the_device_outside_the_fused_state(optimizer):
    with CHIP:
        base = _bytes_on(CHIP)
        mod = _module(optimizer)
        mod._fused_ensure_state()
        weights = _param_bytes(mod)
        # data, label, outputs, the key: far less than the weights
        slack = weights // 4
        extra = _bytes_on(CHIP) - base - _state_bytes(mod)
        assert 0 <= extra < slack, (extra, weights)
        # the shapes stay readable without the buffers (dist/shardsearch.py)
        assert mod._arg_params["fc1_weight"].shape == (HIDDEN, WIDTH)
        assert mod._aux_params["bn_moving_mean"].shape == (HIDDEN,)
        _steps(mod, 2)
        extra = _bytes_on(CHIP) - base - _state_bytes(mod)
        assert 0 <= extra < slack, (extra, weights)


def _on_the_host_and_current(mod, arg, aux):
    want = _state_values(mod)
    for name, arr in list(arg.items()) + list(aux.items()):
        assert arr.context == mx.cpu(0), (name, arr.context)
        assert np.array_equal(arr.asnumpy(), want[name]), name


def test_get_params_hands_out_host_arrays():
    with CHIP:
        mod = _module()
        _steps(mod, 2)
        chip, host = _bytes_on(CHIP), _bytes_on(mx.cpu(0))
        weights = _param_bytes(mod)
        arg, aux = mod.get_params()
        _on_the_host_and_current(mod, arg, aux)
        assert _bytes_on(CHIP) == chip
        once = _bytes_on(mx.cpu(0))
        assert once - host >= weights
        del arg, aux
        # a second read is the same dicts; one after a step takes the
        # first one's place
        mod.get_params()
        assert (_bytes_on(CHIP), _bytes_on(mx.cpu(0))) == (chip, once)
        mod.forward_backward(_batch(9))
        mod.update()
        fed = _bytes_on(CHIP)
        arg, aux = mod.get_params()
        assert (_bytes_on(CHIP), _bytes_on(mx.cpu(0))) == (fed, once)
        _on_the_host_and_current(mod, arg, aux)


@pytest.mark.parametrize("ctx", [mx.cpu(0), CHIP], ids=["host", "chip"])
def test_array_taken_before_the_first_step_survives(ctx):
    """On the host's own device nothing travels between the dicts and the
    state, so an alias would be deleted by the first donated step."""
    with ctx:
        mod = _module(ctx=ctx)
        arg, aux = mod.get_params()
        held = dict(arg, **aux)
        before = {n: v.asnumpy() for n, v in held.items()}
        _steps(mod, 2)
        for n, v in held.items():
            assert v.context == ctx
            assert np.array_equal(v.asnumpy(), before[n]), n
        moved = mod.get_params()[0]["fc1_weight"].asnumpy()
        assert np.abs(moved - before["fc1_weight"]).max() > 0


@pytest.mark.parametrize("partial", [False, True], ids=["all", "partial"])
def test_set_params_after_a_step(partial):
    with CHIP:
        mod = _module()
        _steps(mod, 2)
        trained = _state_values(mod)
        new = {"fc2_weight": mx.nd.ones((2, HIDDEN)) * 0.25}
        if partial:
            mod.set_params(new, {}, allow_missing=True)
        else:
            arg, aux = mod.get_params()
            mod.set_params(dict(arg, **new), aux)
        arg, aux = mod.get_params()
        assert np.all(arg["fc2_weight"].asnumpy() == 0.25)
        # a name the call left out keeps what training made of it
        assert np.array_equal(arg["fc1_weight"].asnumpy(),
                              trained["fc1_weight"])
        assert np.array_equal(aux["bn_moving_var"].asnumpy(),
                              trained["bn_moving_var"])
        _steps(mod, 1)
        assert mod._fused_state is not None
        assert np.abs(_state_values(mod)["fc2_weight"] - 0.25).max() < 0.2


def test_checkpoint_written_in_mid_training_holds_the_state(tmp_path):
    with CHIP:
        mod = _module()
        _steps(mod, 2)
        prefix = str(tmp_path / "home")
        mod.save_checkpoint(prefix, 2)
        want = _state_values(mod)
        _, arg, aux = mx.model.load_checkpoint(prefix, 2)
        for name, arr in list(arg.items()) + list(aux.items()):
            assert np.array_equal(arr.asnumpy(), want[name]), name
        # and a module that loads it scores as the trained one does
        other = mx.mod.Module(_mlp(), context=CHIP)
        other.bind(data_shapes=[("data", (BATCH, WIDTH))],
                   label_shapes=[("softmax_label", (BATCH,))],
                   for_training=False)
        other.set_params(arg, aux)
        batch = _batch(5)
        mod.forward(batch, is_train=False)
        other.forward(batch, is_train=False)
        assert np.allclose(mod.get_outputs()[0].asnumpy(),
                           other.get_outputs()[0].asnumpy(), atol=1e-6)


def test_score_between_epochs_reads_the_state():
    rng = np.random.RandomState(1)
    X = rng.randn(4 * BATCH, WIDTH).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    with CHIP:
        mx.random.seed(3)
        it = mx.io.NDArrayIter(X, y, batch_size=BATCH)
        mod = mx.mod.Module(_mlp(), context=CHIP)
        seen = []
        mod.fit(it, eval_data=it, num_epoch=3, optimizer="adam",
                initializer=mx.init.Xavier(),
                optimizer_params={"learning_rate": 0.05},
                eval_batch_end_callback=lambda p: seen.append(
                    p.eval_metric.get()[1]))
        assert mod._fused_state is not None
        chip = _bytes_on(CHIP)
        live = mod.score(it, "acc")[0][1]
        assert _bytes_on(CHIP) <= chip + _param_bytes(mod) // 4
        # what a module holding get_params()' arrays scores
        arg, aux = mod.get_params()
        other = mx.mod.Module(_mlp(), context=CHIP)
        other.bind(data_shapes=it.provide_data,
                   label_shapes=it.provide_label, for_training=False)
        other.set_params(arg, aux)
        assert other.score(it, "acc")[0][1] == live
        assert live > 0.8 and seen[-1] == live


@pytest.mark.parametrize("how", ["hyperparameters", "teardown", "rebind"])
def test_leaving_the_fused_state_sees_current_weights(how):
    """The fall-back to the classic path with a batch pending (its
    replay runs on the executor group, bound again from host arrays),
    and a second bind in mid-training."""
    def run(fused):
        os.environ["MXNET_FUSED_TRAIN"] = "1" if fused else "0"
        try:
            with CHIP:
                mx.random.seed(3)
                mod = mx.mod.Module(_mlp(), context=CHIP)
                mod.bind(data_shapes=[("data", (BATCH, WIDTH))],
                         label_shapes=[("softmax_label", (BATCH,))])
                mod.init_params(mx.init.Xavier())
                mod.init_optimizer(optimizer="sgd", optimizer_params={
                    "learning_rate": 0.05, "momentum": 0.9})
                assert (mod._fused is not None) == fused
                _steps(mod, 2)
                mod.forward_backward(_batch(2))
                if how == "hyperparameters":
                    mod._optimizer.set_lr_mult({"fc1_weight": 0.5})
                elif how == "teardown" and fused:
                    mod._disable_fused("a monitor, say")
                elif how == "rebind":
                    mod.update()
                    mod.bind(data_shapes=[("data", (BATCH, WIDTH))],
                             label_shapes=[("softmax_label", (BATCH,))],
                             force_rebind=True)
                    assert (mod._fused_state is not None) == fused
                    mod.forward_backward(_batch(3))
                mod.update()
                assert (mod._fused is None) == (how != "rebind" or not fused)
                _steps(mod, 1, seed=4)
                arg, aux = mod.get_params()
                return {n: v.asnumpy() for n, v in dict(arg, **aux).items()}
        finally:
            os.environ.pop("MXNET_FUSED_TRAIN", None)

    left, classic = run(True), run(False)
    for name in classic:
        assert np.abs(left[name] - classic[name]).max() < 1e-5, name
