"""NDArray tests. Modeled on reference tests/python/unittest/test_ndarray.py."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx


def same(a, b):
    return np.sum(a != b) == 0


def reldiff(a, b):
    diff = np.sum(np.abs(a - b))
    norm = np.sum(np.abs(a)) + 1e-12
    return diff / norm


def random_ndarray(dim):
    shape = tuple(np.random.randint(1, 8, size=dim))
    return mx.nd.array(np.random.uniform(-10, 10, shape))


def test_ndarray_setitem():
    shape = (3, 4, 2)
    x = mx.nd.zeros(shape)
    x[:] = 1
    x_np = np.ones(shape, dtype=x.dtype)
    assert same(x.asnumpy(), x_np)

    x = mx.nd.zeros(shape)
    x[1] = 1
    x_np = np.zeros(shape, dtype=x.dtype)
    x_np[1] = 1
    assert same(x.asnumpy(), x_np)

    x = mx.nd.zeros(shape)
    x[1:3] = 1
    x_np = np.zeros(shape, dtype=x.dtype)
    x_np[1:3] = 1
    assert same(x.asnumpy(), x_np)


def test_ndarray_elementwise():
    np.random.seed(0)
    for scale in [1, 10]:
        for dim in [1, 2, 3, 4]:
            shape = tuple(np.random.randint(1, 6, size=dim))
            a_np = np.random.uniform(1, 10, shape).astype(np.float32)
            b_np = np.random.uniform(1, 10, shape).astype(np.float32)
            a = mx.nd.array(a_np)
            b = mx.nd.array(b_np)
            assert reldiff((a + b).asnumpy(), a_np + b_np) < 1e-6
            assert reldiff((a - b).asnumpy(), a_np - b_np) < 1e-6
            assert reldiff((a * b).asnumpy(), a_np * b_np) < 1e-6
            assert reldiff((a / b).asnumpy(), a_np / b_np) < 1e-5
            assert reldiff((a + 2).asnumpy(), a_np + 2) < 1e-6
            assert reldiff((2 - a).asnumpy(), 2 - a_np) < 1e-5
            assert reldiff((a ** 2).asnumpy(), a_np ** 2) < 1e-5


def test_ndarray_inplace():
    a = mx.nd.ones((2, 3))
    b = a
    a += 2
    assert same(a.asnumpy(), np.ones((2, 3)) * 3)
    assert same(b.asnumpy(), np.ones((2, 3)) * 3)  # same handle sees mutation
    a *= 2
    assert same(a.asnumpy(), np.ones((2, 3)) * 6)
    a -= 1
    a /= 5
    assert same(a.asnumpy(), np.ones((2, 3)))


def test_ndarray_negate():
    npy = np.random.uniform(-10, 10, (2, 3, 4)).astype(np.float32)
    arr = mx.nd.array(npy)
    assert reldiff(npy, arr.asnumpy()) < 1e-6
    assert reldiff(-npy, (-arr).asnumpy()) < 1e-6
    # negation doesn't mutate the source
    assert reldiff(npy, arr.asnumpy()) < 1e-6


def test_ndarray_slice():
    shape = (10,)
    A = mx.nd.array(np.random.uniform(-10, 10, shape))
    A2 = A.asnumpy()
    assert same(A[3:8].asnumpy(), A2[3:8])
    A2[3:8] *= 10
    A[3:8] = A2[3:8]
    assert same(A[3:8].asnumpy(), A2[3:8])
    assert same(A.asnumpy(), A2)


def test_ndarray_slice_writethrough():
    a = mx.nd.zeros((4, 3))
    s = a[1:3]
    s[:] = 5
    out = a.asnumpy()
    assert same(out[1:3], np.ones((2, 3)) * 5)
    assert same(out[0], np.zeros(3))


def test_ndarray_at_reshape_views():
    a = mx.nd.array(np.arange(12).reshape(3, 4))
    r = a.reshape((4, 3))
    assert same(r.asnumpy(), np.arange(12).reshape(4, 3))
    r[:] = 0
    assert same(a.asnumpy(), np.zeros((3, 4)))
    row = a[2]
    row[:] = 7
    assert same(a.asnumpy()[2], np.ones(4) * 7)


def test_ndarray_scalar():
    c = mx.nd.empty((10, 10))
    d = mx.nd.empty((10, 10))
    c[:] = 0.5
    d[:] = 1.0
    d -= c * 2 / 3 * 6.0
    c += 0.5
    assert np.sum(c.asnumpy()) - 100 < 1e-5
    assert np.sum(d.asnumpy()) + 100 < 1e-5
    c[:] = 2
    assert np.sum(c.asnumpy()) == 200
    d = -c + 2
    assert np.sum(d.asnumpy()) == 0


def test_ndarray_copy():
    c = mx.nd.array(np.random.uniform(-10, 10, (10, 10)))
    d = c.copyto(mx.cpu(0))
    assert np.sum(np.abs(c.asnumpy() != d.asnumpy())) == 0.0
    d2 = mx.nd.zeros((10, 10))
    c.copyto(d2)
    assert same(c.asnumpy(), d2.asnumpy())


def test_ndarray_saveload():
    np.random.seed(0)
    nrepeat = 2
    with tempfile.TemporaryDirectory() as tmpdir:
        fname = os.path.join(tmpdir, "tmp_list.bin")
        for _ in range(nrepeat):
            data = []
            for _ in range(5):
                data.append(random_ndarray(np.random.randint(1, 5)))
            mx.nd.save(fname, data)
            data2 = mx.nd.load(fname)
            assert len(data) == len(data2)
            for x, y in zip(data, data2):
                assert same(x.asnumpy(), y.asnumpy())
            dmap = {"ndarray xx %s" % i: x for i, x in enumerate(data)}
            mx.nd.save(fname, dmap)
            dmap2 = mx.nd.load(fname)
            assert len(dmap2) == len(dmap)
            for k, x in dmap.items():
                y = dmap2[k]
                assert same(x.asnumpy(), y.asnumpy())


def test_ndarray_pickle():
    import pickle
    np.random.seed(0)
    for _ in range(5):
        dim = np.random.randint(1, 5)
        a = random_ndarray(dim)
        a[:] = 0.5 * a + 1
        data = pickle.dumps(a)
        a2 = pickle.loads(data)
        assert same(a.asnumpy(), a2.asnumpy())


def test_clip():
    shape = (10,)
    A = mx.nd.array(np.random.uniform(-10, 10, shape))
    B = mx.nd.clip(A, -2, 2)
    B1 = B.asnumpy()
    for i in range(shape[0]):
        assert -2 <= B1[i] <= 2


def test_dot():
    a = np.random.uniform(-3, 3, (3, 4)).astype(np.float32)
    b = np.random.uniform(-3, 3, (4, 5)).astype(np.float32)
    c = np.dot(a, b)
    A = mx.nd.array(a)
    B = mx.nd.array(b)
    C = mx.nd.dot(A, B)
    assert reldiff(c, C.asnumpy()) < 1e-5


def test_ndarray_onehot():
    shape = (4, 5)
    out = mx.nd.zeros(shape)
    idx = mx.nd.array([1, 0, 2, 4])
    mx.nd.onehot_encode(idx, out)
    exp = np.zeros(shape, dtype=np.float32)
    exp[np.arange(4), [1, 0, 2, 4]] = 1
    assert same(out.asnumpy(), exp)


def test_ndarray_choose():
    a = np.random.uniform(-10, 10, (5, 4)).astype(np.float32)
    idx = np.array([0, 1, 2, 3, 0], dtype=np.float32)
    out = mx.nd.choose_element_0index(mx.nd.array(a), mx.nd.array(idx))
    assert same(out.asnumpy(), a[np.arange(5), idx.astype(int)])


def test_ndarray_broadcast_to():
    a = mx.nd.array(np.arange(3).reshape(1, 3))
    b = a.broadcast_to((4, 3))
    assert same(b.asnumpy(), np.broadcast_to(np.arange(3).reshape(1, 3), (4, 3)))


def test_ndarray_concatenate():
    arrs = [mx.nd.array(np.random.rand(3, 4)) for _ in range(3)]
    out = mx.nd.concatenate(arrs, axis=0)
    exp = np.concatenate([a.asnumpy() for a in arrs], axis=0)
    assert same(out.asnumpy(), exp)


def test_ndarray_dtype():
    a = mx.nd.zeros((3, 3), dtype=np.int32)
    assert a.dtype == np.int32
    b = a.astype(np.float32)
    assert b.dtype == np.float32


def test_waitall():
    a = mx.nd.ones((10, 10))
    b = a * 2
    mx.nd.waitall()
    assert same(b.asnumpy(), np.ones((10, 10)) * 2)


def test_multi_cpu_devices():
    """Fake-device trick: distinct cpu dev ids are independent devices."""
    import jax
    assert len(jax.devices("cpu")) >= 8
    a = mx.nd.ones((4,), ctx=mx.cpu(2))
    assert a.context == mx.Context("cpu", 2)
    b = a.as_in_context(mx.cpu(5))
    assert b.context == mx.Context("cpu", 5)
    assert same(b.asnumpy(), np.ones(4))


def test_dtype_matrix():
    """fp16/bf16/int32/uint8 dtype support (reference v0.7 NEWS: 'support
    fp16, fp64, int32, uint8 dtypes').  float64 is a documented TPU-native
    divergence: it truncates to float32 unless JAX_ENABLE_X64 is set (the
    MXU has no f64)."""
    for dt, tol in [(np.float16, 1e-2), ("bfloat16", 1e-1),
                    (np.int32, 0), (np.uint8, 0)]:
        a = mx.nd.ones((3, 4), dtype=dt)
        b = a + a
        out = b.asnumpy()
        assert np.allclose(out.astype(np.float64), 2.0, atol=tol), dt
        if dt != "bfloat16":
            assert str(mx.nd.zeros((2,), dtype=dt).dtype) == np.dtype(dt).name
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        f64 = mx.nd.ones((2, 2), dtype=np.float64)
    assert str(f64.dtype) in ("float32", "float64")


def test_cast_between_dtypes():
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    for target in ("float16", "int32", "uint8"):
        data = mx.sym.Variable("data")
        c = mx.sym.Cast(data, dtype=target)
        ex = c.simple_bind(mx.current_context(), grad_req="null", data=(2, 3))
        ex.arg_dict["data"][:] = x
        ex.forward(is_train=False)
        got = ex.outputs[0].asnumpy()
        assert got.dtype == np.dtype(target), (target, got.dtype)
        assert np.allclose(got.astype(np.float64),
                           np.arange(6).reshape(2, 3)), target


def test_mixed_precision_save_load(tmp_path):
    path = str(tmp_path / "mixed.nd")
    arrs = {"f16": mx.nd.ones((2, 2), dtype=np.float16),
            "bf16": mx.nd.ones((2, 2), dtype="bfloat16") * 3,
            "i32": mx.nd.ones((2, 2), dtype=np.int32) * 7}
    mx.nd.save(path, arrs)
    loaded = mx.nd.load(path)
    for k, v in arrs.items():
        assert str(loaded[k].dtype) == str(v.dtype), k
        assert np.array_equal(loaded[k].asnumpy(), v.asnumpy()), k


def test_save_load_uri_schemes(tmp_path):
    """file:// URIs work; exotic schemes raise a clear error instead of
    writing a bogus local file (reference dmlc::Stream transparency)."""
    path = str(tmp_path / "u.nd")
    mx.nd.save("file://" + path, {"a": mx.nd.ones((2, 2))})
    back = mx.nd.load("file://" + path)
    assert (back["a"].asnumpy() == 1).all()
    with pytest.raises(Exception) as e:
        mx.nd.save("bogus-scheme://bucket/x.nd", {"a": mx.nd.ones((2,))})
    assert "bogus-scheme" in str(e.value) or "protocol" in str(e.value)


# -- asnumpy: one transfer into memory the caller owns ----------------------

def _on_host(nd):
    from mxnet_tpu.ndarray import _lives_on_host
    return _lives_on_host(nd._get())


def _asnumpy_samples():
    """(bytes, route) of every ``ndarray:asnumpy`` sample in the ring."""
    return [(e["args"]["bytes"],
             [r for r in ("direct", "copied", "cached") if e["args"][r]])
            for e in mx.trace.counter_events(names=["ndarray:asnumpy"])]


def _make_view(kind, dtype):
    """(NDArray, its value) for one way an NDArray can hold a value."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    value = (np.arange(16 * 6) % 251).reshape(16, 6).astype(dtype)
    base = mx.nd.array(value, dtype=dtype)
    if kind == "base":
        return base, value
    if kind == "slice":
        return base[3:11], value[3:11]
    if kind == "at":
        return base[5], value[5]
    if kind == "reshape":
        return base.reshape((6, 16)), value.reshape(6, 16)
    if kind == "zero_d":
        return mx.nd.array(value[2, 3], dtype=dtype), value[2, 3]
    assert kind == "sharded"
    devices = jax.devices()
    mesh = Mesh(np.array(devices if 16 % len(devices) == 0 else devices[:1]),
                ("dp",))
    base._place(NamedSharding(mesh, P("dp")))
    assert len(base._get().sharding.device_set) == len(mesh.devices)
    return base, value


@pytest.mark.parametrize("kind", ["base", "slice", "at", "reshape",
                                  "zero_d", "sharded"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32", "uint8"])
def test_asnumpy_result_is_the_callers(dtype, kind):
    """Writable, its own memory, C-contiguous, equal to the value; a
    write to it reaches neither the NDArray, nor another asnumpy()
    result, nor a later device computation on the same array."""
    import ml_dtypes
    dtype = np.dtype(getattr(ml_dtypes, dtype, dtype))
    nd, value = _make_view(kind, dtype)
    first = nd.asnumpy()
    assert isinstance(first, np.ndarray)
    assert first.dtype == dtype and first.shape == np.shape(value)
    assert first.flags.writeable and first.flags.owndata
    # the bytes come in the device's dimension order: row-major on the
    # CPU backend, the compiler's choice on an accelerator
    assert first.flags.c_contiguous or not _on_host(nd)
    assert same(first, value)
    second = nd.asnumpy()
    assert second is not first and not np.shares_memory(first, second)
    first[...] = 7
    assert same(second, value)
    assert same(nd.asnumpy(), value)
    one = np.asarray(1, dtype)
    assert same((nd + 1).asnumpy(), np.asarray(value + one, dtype))
    second[...] = 9
    assert same(nd.copy().asnumpy(), value)


def test_asnumpy_started_copy_is_served_without_a_fetch(monkeypatch):
    """_start_host_copy, then asnumpy: the value comes from the copy
    already made (route ``cached``), nothing is fetched again, and the
    result is still writable and nobody else's."""
    from mxnet_tpu import ndarray as nd_mod

    def no_fetch(*a, **k):
        raise AssertionError("a second device->host fetch was started")

    value = np.random.uniform(-1, 1, (512, 1024)).astype(np.float32)   # 2 MiB
    nd = mx.nd.array(value)
    nd._start_host_copy()
    # whatever the platform: a started copy must not reach the fetch
    with monkeypatch.context() as m:
        m.setattr(nd_mod.jax, "make_array_from_single_device_arrays",
                  no_fetch)
        mx.trace.reset()
        first = nd.asnumpy()
        second = nd.asnumpy()
        assert _asnumpy_samples() == [(value.nbytes, ["cached"])] * 2
        assert first.flags.writeable and first.flags.owndata
        assert not np.shares_memory(first, second)
        first[...] = 0
        assert same(second, value) and same(nd.asnumpy(), value)
    # a write replaces the device value: the started copy is stale
    nd[:] = 1
    assert nd._host_copy is None
    assert same(nd.asnumpy(), np.ones_like(value))


def test_asnumpy_counter_route_and_bytes():
    """One sample a read of 1 MiB or more, with its bytes and the one
    route taken; smaller reads are silent."""
    big = mx.nd.zeros((1 << 18,))            # 1 MiB of float32
    small = mx.nd.zeros(((1 << 18) - 1,))
    expect = "copied" if _on_host(big) else "direct"
    mx.trace.reset()
    small.asnumpy()
    mx.nd.zeros((3,)).asnumpy()
    assert _asnumpy_samples() == []
    big.asnumpy()
    big[0:1 << 17].asnumpy()                 # a view of 512 KiB: silent
    big.reshape((512, 512)).asnumpy()
    assert _asnumpy_samples() == [(1 << 20, [expect])] * 2
    ev = mx.trace.counter_events(names=["ndarray:asnumpy"])[0]
    assert ev["cat"] == "ndarray"


def test_asnumpy_fetch_path_takes_ownership_or_copies(monkeypatch):
    """The path an accelerator's arrays take, driven here by hiding the
    platform: an array in shards is assembled into memory of the
    result's own (``direct``); a single CPU buffer comes back as a view
    of the device's memory, which is not ours to hand out (``copied``).
    Either way the device array keeps no host twin."""
    import jax
    from mxnet_tpu import ndarray as nd_mod
    monkeypatch.setattr(nd_mod, "_lives_on_host", lambda array: False)
    if len(jax.devices()) == 1:
        pytest.skip("needs the CPU mesh: one device has no shards")
    sharded, value = _make_view("sharded", np.dtype("float32"))
    host, route = nd_mod._read_to_host(sharded._get(), False)
    assert route == "direct" and host.flags.writeable and host.flags.owndata
    assert same(host, value)
    assert sharded._get()._npy_value is None
    host[...] = 0
    assert same(sharded.asnumpy(), value)
    single, value = _make_view("base", np.dtype("float32"))
    host, route = nd_mod._read_to_host(single._get(), False)
    assert route == "copied" and host.flags.writeable and host.flags.owndata
    host[...] = 0
    assert same(single.asnumpy(), value)
    assert single._get()._npy_value is None


def test_custom_metric_feval_may_write_into_pred():
    """A numpy feval that works in place on what it is handed (the
    reference's metrics do) sees its own array: the outputs are intact
    and a second update reads the same values."""
    def feval(label, pred):
        pred -= pred.max(axis=1, keepdims=True)    # in place
        np.exp(pred, out=pred)
        pred /= pred.sum(axis=1, keepdims=True)
        label[...] = 0                             # and the label too
        return float(pred[:, 0].sum()), pred.shape[0]

    logits = np.random.uniform(-1, 1, (8, 5)).astype(np.float32)
    pred = mx.nd.array(logits)
    label = mx.nd.array(np.arange(8) % 5)
    metric = mx.metric.CustomMetric(feval)
    metric.update([label], [pred])
    once = metric.get()[1]
    metric.update([label], [pred])
    assert abs(metric.get()[1] - once) < 1e-6
    assert same(pred.asnumpy(), logits)
    assert same(label.asnumpy(), np.arange(8) % 5)
