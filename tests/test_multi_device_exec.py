"""Multi-device / model-parallel executor tests.

Mirrors reference tests/python/unittest/test_multi_device_exec.py:35 and
test_model_parallel.py:12-54 — distinct cpu dev_ids act as fake devices;
ctx_group attrs place ops, the executor inserts transfers.
"""
import numpy as np

import mxnet_tpu as mx


def test_ctx_group():
    with mx.AttrScope(ctx_group="stage1"):
        data = mx.sym.Variable("data")
        fc1 = mx.sym.FullyConnected(data=data, name="fc1", num_hidden=16)
        act1 = mx.sym.Activation(data=fc1, name="relu1", act_type="relu")

    set_stage1 = set(act1.list_arguments())
    with mx.AttrScope(ctx_group="stage2"):
        fc2 = mx.sym.FullyConnected(data=act1, name="fc2", num_hidden=8)
        act2 = mx.sym.Activation(data=fc2, name="relu2", act_type="relu")
        fc3 = mx.sym.FullyConnected(data=act2, name="fc3", num_hidden=4)
        mlp = mx.sym.SoftmaxOutput(data=fc3, name="softmax")

    set_stage2 = set(mlp.list_arguments()) - set_stage1 - {"softmax_label"}

    group2ctx = {"stage1": mx.cpu(1), "stage2": mx.cpu(2)}
    texec = mlp.simple_bind(mx.cpu(0), group2ctx=group2ctx,
                            data=(8, 10), softmax_label=(8,))
    for name, arr in texec.arg_dict.items():
        if name in set_stage1:
            assert arr.context == group2ctx["stage1"], name
        elif name in set_stage2:
            assert arr.context == group2ctx["stage2"], name
    # executes correctly across devices
    texec.arg_dict["data"][:] = np.random.randn(8, 10).astype(np.float32)
    for n in ["fc1_weight", "fc2_weight", "fc3_weight"]:
        texec.arg_dict[n][:] = np.random.randn(
            *texec.arg_dict[n].shape).astype(np.float32) * 0.1
    texec.forward(is_train=True)
    out = texec.outputs[0].asnumpy()
    assert out.shape == (8, 4)
    assert np.allclose(out.sum(axis=1), 1, atol=1e-5)


def test_model_parallel_matches_single_device():
    """Model-parallel forward/backward equals single-context execution
    (reference test_model_parallel.py)."""
    np.random.seed(0)
    shape = (4, 5)
    data1 = mx.sym.Variable("data1")
    data2 = mx.sym.Variable("data2")
    data3 = mx.sym.Variable("data3")
    with mx.AttrScope(ctx_group="dev1"):
        net = data1 + data2
        net = net * 3.0
    with mx.AttrScope(ctx_group="dev2"):
        net = net + data3

    arr = [mx.nd.array(np.random.rand(*shape)) for _ in range(3)]
    arr_grad = [mx.nd.empty(shape) for _ in range(3)]

    # single device
    exec1 = net.bind(mx.cpu(),
                     args={"data1": arr[0], "data2": arr[1], "data3": arr[2]},
                     args_grad={"data1": arr_grad[0], "data2": arr_grad[1],
                                "data3": arr_grad[2]})
    exec1.forward(is_train=True)
    out1 = exec1.outputs[0].asnumpy()
    exec1.backward()
    g1 = [g.asnumpy() for g in arr_grad]

    # model parallel over two fake devices
    arr_grad2 = [mx.nd.empty(shape) for _ in range(3)]
    exec2 = net.bind(mx.cpu(),
                     args={"data1": arr[0], "data2": arr[1], "data3": arr[2]},
                     args_grad={"data1": arr_grad2[0], "data2": arr_grad2[1],
                                "data3": arr_grad2[2]},
                     group2ctx={"dev1": mx.cpu(3), "dev2": mx.cpu(4)})
    exec2.forward(is_train=True)
    out2 = exec2.outputs[0].asnumpy()
    exec2.backward()
    g2 = [g.asnumpy() for g in arr_grad2]

    assert np.allclose(out1, out2, atol=1e-6)
    for a, b in zip(g1, g2):
        assert np.allclose(a, b, atol=1e-6)


def test_mesh_dp_train_step_bf16(monkeypatch):
    """What resnet50-dp4-b512 runs, at toy size: Module.fit over a dp=4
    mesh with bf16 compute learns, and the master weights stay f32."""
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    mx.random.seed(0)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    centers = rng.randn(4, 10) * 3
    ys = rng.randint(4, size=64 * 25)
    X = (centers[ys] + rng.randn(len(ys), 10) * 0.5).astype(np.float32)
    it = mx.io.NDArrayIter(X, ys.astype(np.float32), batch_size=64)
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.fit(it, num_epoch=1, initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9},
            mesh="dp=4")
    fused = mod._fused
    assert fused is not None and fused.named_mesh
    assert dict(fused.mesh.shape) == {"dp": 4}
    assert str(fused.compute_dtype) == "bfloat16"
    arg_params, _ = mod.get_params()
    for name, arr in arg_params.items():
        assert arr.dtype == np.float32, name     # master stays f32
    assert dict(mod.score(it, "acc"))["accuracy"] > 0.9
