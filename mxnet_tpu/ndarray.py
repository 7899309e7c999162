"""NDArray: imperative, asynchronously-dispatched tensor.

Reference: include/mxnet/ndarray.h (670 LoC), src/ndarray/ (1434 LoC),
python/mxnet/ndarray.py (1229 LoC).

TPU-native design, not a port.  The reference NDArray is a ref-counted
Chunk{Storage::Handle, Engine::Var}; every mutating op is pushed to the
dependency engine and the python thread never blocks (SURVEY §3.6).  JAX
already *is* that model: dispatch is async, results are futures, and
``asnumpy()``/``wait_to_read()`` are the sync points.  What JAX does not have
is mutability and views — so:

* a "chunk" here is the ``_data`` jax.Array of an **owner** NDArray; mutation
  swaps the buffer (functional update under the hood, ordering guaranteed by
  data dependence — the Var semantics collapse into SSA);
* ``Slice/At/Reshape`` views (zero-copy in the reference, ndarray.h:228-262)
  are write-through views: they record (base, spec), read lazily, and write
  back into the base chunk with ``.at[...].set`` — aliasing semantics
  preserved, XLA fuses the scatter.
"""
from __future__ import annotations

import io as _io
import pickle
import struct
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError, numeric_types
from .context import Context, cpu, current_context
from . import engine as _engine
from . import trace as _trace

__all__ = [
    "NDArray", "array", "empty", "zeros", "ones", "full", "arange",
    "load", "save", "concatenate", "concat", "onehot_encode", "clip", "dot",
    "batch_dot", "sum", "max", "min", "norm", "argmax_channel",
    "choose_element_0index", "waitall", "imdecode", "transpose",
]

# ---------------------------------------------------------------------------
# registry of ndarray functions (reference NDArrayFunctionReg, ndarray.h:483)
# populated here and extended by ops/ (SimpleOp dual registration).
_NDARRAY_FUNCS: Dict[str, Any] = {}


def register_ndarray_fn(name, fn):
    """MXNET_REGISTER_NDARRAY_FUN analogue; also exposes fn on this module."""
    _NDARRAY_FUNCS[name] = fn
    import sys
    mod = sys.modules[__name__]
    public = name.lstrip("_")
    if not hasattr(mod, public):
        setattr(mod, public, fn)
    setattr(mod, name, fn)
    return fn


def list_functions():
    """MXListFunctions analogue."""
    return sorted(_NDARRAY_FUNCS)


def _dev_put(arr, ctx: Optional[Context]):
    if ctx is None:
        return arr
    return jax.device_put(arr, ctx.jax_device())


def _ctx_of(jarr) -> Context:
    try:
        dev = list(jarr.devices())[0]
    except Exception:
        return current_context()
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    return Context("tpu", dev.id)


def _lives_on_host(array) -> bool:
    """Whether a jax array's buffers are the host's own memory (the CPU
    backend): nothing then travels to the host, what jax hands out is a
    view of the live buffer, and a host read is one copy of it."""
    return all(d.platform == "cpu" for d in array.devices())


# host reads of this many bytes or more leave an ``ndarray:asnumpy``
# sample; under it, scalars and metric heads would fill the trace ring
_COUNTED_READ_BYTES = 1 << 20


def _read_to_host(x, started: bool):
    """``(array, route)``: the value of ``x`` as a numpy array that
    only the caller holds, and how it got there.

    ``direct``: one device->host transfer, and the array it filled is
    the result.  jax keeps whatever it fetches as a read-only twin of
    the device array, on the ``jax.Array`` wrapper and in the transfer
    state below it, and ``np.array`` of that is a second pass over the
    same bytes on the host (a page fault a page: four fifths of a
    1.6 GB read on a v5e host).  Both caches belong to the wrapper, not to the device
    buffers, so the fetch goes through a wrapper of its own over the
    same buffers; with that dropped the fetched array has no other
    holder and is made writable in place.  An array in shards is
    assembled by jax into one ``np.empty`` the same way.

    ``cached``: the host value is already there (somebody read ``x``
    before) or on its way (``started``: ``NDArray._start_host_copy``),
    so it is copied and nothing travels twice.  ``copied``: the CPU
    backend, where the "fetch" is a view of the device's own memory and
    the copy is the only copy; and whatever jax cannot hand over whole
    (a host array, an array with shards in other processes)."""
    if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer) \
            or not x.is_fully_addressable:
        return np.array(x), "copied"
    if started or getattr(x, "_npy_value", None) is not None:
        return np.array(x), "cached"
    if _lives_on_host(x):
        return np.array(x), "copied"
    own = jax.make_array_from_single_device_arrays(
        x.shape, x.sharding, [s.data for s in x.addressable_shards])
    host = np.asarray(own)
    del own
    if not host.flags.owndata:
        return np.array(host), "copied"
    host.flags.writeable = True
    return host, "direct"


def _as_jax(value, dtype=None):
    if isinstance(value, NDArray):
        return value._get()
    if isinstance(value, (np.ndarray, jnp.ndarray, jax.Array)):
        return jnp.asarray(value, dtype=dtype)
    return jnp.asarray(value, dtype=dtype)


class NDArray:
    """Multi-dimensional array with async dispatch and mutable semantics."""

    __slots__ = ("_data", "_base", "_spec", "writable", "_host_copy")

    def __init__(self, data=None, base: "NDArray" = None, spec=None, writable=True):
        self._data = data          # jax.Array when owner, None when view
        self._base = base          # owner NDArray when this is a view
        self._spec = spec          # ("slice", start, stop) | ("at", i) | ("reshape", shape)
        self.writable = writable
        self._host_copy = None     # the jax.Array whose host copy _start_host_copy began

    # -- chunk access -------------------------------------------------------
    def _root(self) -> "NDArray":
        n = self
        while n._base is not None:
            n = n._base
        return n

    def _get(self):
        """Current jax.Array value (views computed from base)."""
        if self._base is None:
            return self._data
        parent = self._base._get()
        kind = self._spec[0]
        if kind == "slice":
            return parent[self._spec[1]:self._spec[2]]
        if kind == "at":
            return parent[self._spec[1]]
        if kind == "reshape":
            return parent.reshape(self._spec[1])
        raise MXNetError("unknown view spec %r" % (self._spec,))

    def _set(self, new):
        """Write a full new value into this array (write-through for views)."""
        if not self.writable:
            raise MXNetError("trying to write to a read-only NDArray")
        if self._base is None:
            if self._data is not None and tuple(new.shape) != tuple(self._data.shape):
                raise MXNetError(
                    "shape mismatch: cannot assign %s to NDArray of shape %s"
                    % (tuple(new.shape), tuple(self._data.shape)))
            if self._data is not None and new.dtype != self._data.dtype:
                new = new.astype(self._data.dtype)
            if self._data is not None:
                # a write mutates the chunk in place in the reference —
                # keep the buffer at its original PLACEMENT: the single
                # device it lived on, or (mesh-placed arrays, see
                # Executor.set_mesh) its multi-device sharding — a write
                # must not silently collapse a tp-sharded weight onto
                # one chip
                try:
                    old_devs = self._data.devices()
                    if len(old_devs) > 1:
                        old_sh = self._data.sharding
                        if getattr(new, "sharding", None) != old_sh:
                            new = jax.device_put(new, old_sh)
                    else:
                        old_dev = next(iter(old_devs))
                        if hasattr(new, "devices") and \
                                new.devices() != {old_dev}:
                            new = jax.device_put(new, old_dev)
                except Exception:
                    pass
            self._data = _engine.track(new)
            self._host_copy = None
            return
        parent = self._base._get()
        kind = self._spec[0]
        if kind == "slice":
            upd = parent.at[self._spec[1]:self._spec[2]].set(
                jnp.asarray(new, dtype=parent.dtype))
        elif kind == "at":
            upd = parent.at[self._spec[1]].set(jnp.asarray(new, dtype=parent.dtype))
        elif kind == "reshape":
            upd = jnp.asarray(new, dtype=parent.dtype).reshape(parent.shape)
        else:
            raise MXNetError("unknown view spec %r" % (self._spec,))
        self._base._set(upd)

    def _place(self, sharding):
        """Move the owning chunk to an explicit jax sharding (or device),
        keeping its value.  Later ``_set`` writes preserve the placement
        (see the multi-device branch there) — this is how
        ``Executor.set_mesh`` pins bound arrays to a mesh once and every
        subsequent ``set_input``/``set_params`` write stays sharded."""
        root = self._root()
        root._data = _engine.track(jax.device_put(root._get(), sharding))
        root._host_copy = None
        return self

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._get().shape)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return np.dtype(self._get().dtype)

    @property
    def context(self) -> Context:
        return _ctx_of(self._root()._data)

    ctx = context

    @property
    def T(self) -> "NDArray":
        return NDArray(jnp.transpose(self._get()))

    @property
    def handle(self):
        """Compat: the reference exposed a ctypes handle; here the jax.Array."""
        return self._get()

    # -- sync points --------------------------------------------------------
    def wait_to_read(self):
        """Block until all pending writes to this array complete
        (reference Engine::WaitForVar, ndarray.h WaitToRead)."""
        jax.block_until_ready(self._get())

    wait_to_write = wait_to_read

    def asnumpy(self) -> np.ndarray:
        """The value as a host numpy array — THE sync point (SURVEY §3.6).

        Blocks until the value is ready.  The result is writable and
        the caller's own: independent of this array, of its views and
        of every other ``asnumpy()`` result.  It costs one device->host
        transfer and no further pass over the bytes on the host
        (``_read_to_host``); on the CPU backend, where the device's
        memory is the host's, it is one copy of that memory.  The bytes
        arrive in the device's dimension order and the strides say
        which: C-contiguous from the CPU backend and wherever the
        device holds the array row-major; XLA:TPU holds some shapes
        otherwise (a float32 ``(N, 10000)`` is column-major on a v5e)
        and those come back Fortran-ordered, since the first chip run
        (``np.ascontiguousarray`` where the order matters: it is a
        transposing pass over the whole array).  Reads of 1 MiB or more
        leave a sample of the ``ndarray:asnumpy`` trace counter:
        ``bytes`` and the route, ``direct`` / ``copied`` / ``cached``."""
        x = self._get()
        host, route = _read_to_host(x, x is self._host_copy)
        if host.nbytes >= _COUNTED_READ_BYTES:
            _trace.counter("ndarray:asnumpy", cat="ndarray",
                           bytes=host.nbytes, direct=int(route == "direct"),
                           copied=int(route == "copied"),
                           cached=int(route == "cached"))
        return host

    def _start_host_copy(self):
        """Start this array's device->host copy and return at once; the
        ``asnumpy()`` that follows waits for it and copies what landed
        (route ``cached``) where it would have fetched.  For a caller
        that dispatches more work before it reads: ``fit`` and ``score``
        on the fused step (``Module._outputs_in_flight``)."""
        x = self._get()
        if isinstance(x, jax.Array):
            x.copy_to_host_async()
            self._host_copy = x

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(-1)[0]

    def astype(self, dtype) -> "NDArray":
        return NDArray(self._get().astype(np.dtype(dtype)))

    # -- copies / context moves --------------------------------------------
    def copy(self) -> "NDArray":
        return NDArray(jnp.array(self._get()))

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        """CopyFromTo (reference src/ndarray/ndarray.cc:226-286)."""
        if isinstance(other, NDArray):
            if other is self or (other._root() is self._root() and other._spec == self._spec):
                return other
            val = jnp.asarray(self._get(), dtype=other.dtype)
            if other.context != self.context:
                val = jax.device_put(val, other.context.jax_device())
            other._set(val)
            return other
        if isinstance(other, Context):
            return NDArray(_dev_put(self._get(), other))
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context: Context) -> "NDArray":
        if self.context == context:
            return self
        return self.copyto(context)

    # -- views (zero-copy in reference; write-through here) -----------------
    def reshape(self, new_shape) -> "NDArray":
        new_shape = tuple(int(x) for x in new_shape)
        if int(np.prod(new_shape)) != self.size:
            raise MXNetError("reshape size mismatch %s -> %s" % (self.shape, new_shape))
        return NDArray(None, base=self, spec=("reshape", new_shape), writable=self.writable)

    def _slice(self, start: int, stop: int) -> "NDArray":
        start, stop = int(start), int(stop)
        if not (0 <= start <= stop <= self.shape[0]):
            raise MXNetError("invalid slice [%d,%d) for shape %s" % (start, stop, self.shape))
        return NDArray(None, base=self, spec=("slice", start, stop), writable=self.writable)

    def _at(self, idx: int) -> "NDArray":
        idx = int(idx)
        if not 0 <= idx < self.shape[0]:
            raise MXNetError("index %d out of range" % idx)
        return NDArray(None, base=self, spec=("at", idx), writable=self.writable)

    def __getitem__(self, key):
        if isinstance(key, int):
            return self._at(key)
        if isinstance(key, slice):
            if key.step is not None and key.step != 1:
                raise MXNetError("slice step not supported")
            start = 0 if key.start is None else key.start
            stop = self.shape[0] if key.stop is None else key.stop
            return self._slice(start, stop)
        raise MXNetError("NDArray only supports int/contiguous-slice indexing; got %r" % (key,))

    def __setitem__(self, key, value):
        if isinstance(key, slice) and key.start is None and key.stop is None and key.step is None:
            target = self
        elif isinstance(key, (int, slice)):
            target = self[key]
        else:
            raise MXNetError("unsupported key %r" % (key,))
        if isinstance(value, NDArray):
            target._set(jnp.asarray(value._get(), dtype=target.dtype).reshape(target.shape)
                        if value.shape != target.shape and value.size == target.size
                        else jnp.asarray(value._get(), dtype=target.dtype))
        elif isinstance(value, numeric_types):
            target._set(jnp.full(target.shape, value, dtype=target.dtype))
        elif isinstance(value, (np.ndarray, np.generic, list, tuple)):
            target._set(jnp.asarray(value, dtype=target.dtype))
        else:
            raise TypeError("type %s not supported" % str(type(value)))

    def _sync_copyfrom(self, source_array):
        source_array = np.asarray(source_array, dtype=self.dtype)
        if source_array.shape != self.shape:
            raise MXNetError("array shape do not match %s vs %s"
                             % (source_array.shape, self.shape))
        self._set(jnp.asarray(source_array))

    # -- arithmetic ---------------------------------------------------------
    def _binary(self, other, fn, reverse=False):
        a = self._get()
        if isinstance(other, NDArray):
            b = other._get()
        elif isinstance(other, numeric_types):
            b = other
        else:
            raise TypeError("type %s not supported" % str(type(other)))
        out = fn(b, a) if reverse else fn(a, b)
        return NDArray(_engine.track(out))

    def __add__(self, other): return self._binary(other, jnp.add)
    def __radd__(self, other): return self._binary(other, jnp.add)
    def __sub__(self, other): return self._binary(other, jnp.subtract)
    def __rsub__(self, other): return self._binary(other, jnp.subtract, reverse=True)
    def __mul__(self, other): return self._binary(other, jnp.multiply)
    def __rmul__(self, other): return self._binary(other, jnp.multiply)
    def __div__(self, other): return self._binary(other, jnp.divide)
    def __rdiv__(self, other): return self._binary(other, jnp.divide, reverse=True)
    def __truediv__(self, other): return self._binary(other, jnp.divide)
    def __rtruediv__(self, other): return self._binary(other, jnp.divide, reverse=True)
    def __pow__(self, other): return self._binary(other, jnp.power)
    def __rpow__(self, other): return self._binary(other, jnp.power, reverse=True)
    def __mod__(self, other): return self._binary(other, jnp.mod)
    def __neg__(self): return NDArray(-self._get())

    def __iadd__(self, other):
        self._set(self._binary(other, jnp.add)._get())
        return self

    def __isub__(self, other):
        self._set(self._binary(other, jnp.subtract)._get())
        return self

    def __imul__(self, other):
        self._set(self._binary(other, jnp.multiply)._get())
        return self

    def __itruediv__(self, other):
        self._set(self._binary(other, jnp.divide)._get())
        return self

    __idiv__ = __itruediv__

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(map(str, self.shape)), self.context)

    def __getstate__(self):
        return {"data": self.asnumpy()}

    def __setstate__(self, state):
        self._base = None
        self._spec = None
        self.writable = True
        self._host_copy = None
        self._data = jnp.asarray(state["data"])

    def broadcast_to(self, shape) -> "NDArray":
        shape = tuple(int(x) for x in shape)
        cur = self.shape
        # reference broadcasting rule: same ndim, dims equal or 1
        if len(cur) != len(shape):
            raise MXNetError("Broadcasting needs same ndim: %s vs %s" % (cur, shape))
        for c, s in zip(cur, shape):
            if c != s and c != 1:
                raise MXNetError("cannot broadcast %s to %s" % (cur, shape))
        return NDArray(jnp.broadcast_to(self._get(), shape))


# ---------------------------------------------------------------------------
# creation functions (reference python/mxnet/ndarray.py zeros/ones/array/...)

def _resolve_ctx(ctx: Optional[Context]) -> Context:
    return ctx if ctx is not None else current_context()


def empty(shape, ctx: Optional[Context] = None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx: Optional[Context] = None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    ctx = _resolve_ctx(ctx)
    return NDArray(_engine.track(_dev_put(jnp.zeros(shape, dtype=np.dtype(dtype)), ctx)))


def ones(shape, ctx: Optional[Context] = None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    ctx = _resolve_ctx(ctx)
    return NDArray(_engine.track(_dev_put(jnp.ones(shape, dtype=np.dtype(dtype)), ctx)))


def full(shape, val, ctx: Optional[Context] = None, dtype=np.float32) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    ctx = _resolve_ctx(ctx)
    return NDArray(_engine.track(_dev_put(jnp.full(shape, val, dtype=np.dtype(dtype)), ctx)))


def array(source_array, ctx: Optional[Context] = None, dtype=np.float32) -> NDArray:
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    arr = np.asarray(source_array, dtype=np.dtype(dtype))
    ctx = _resolve_ctx(ctx)
    return NDArray(_engine.track(_dev_put(jnp.asarray(arr), ctx)))


def arange(start, stop=None, step=1.0, ctx=None, dtype=np.float32) -> NDArray:
    ctx = _resolve_ctx(ctx)
    return NDArray(_dev_put(jnp.arange(start, stop, step, dtype=np.dtype(dtype)), ctx))


# ---------------------------------------------------------------------------
# save / load (reference NDArray::Save/Load dmlc::Stream format, ndarray.h:276)
# TPU build: self-describing binary container; same capability (named or listed
# arrays, one file), different byte format.

_SAVE_MAGIC = b"MXTPU001"


def save(fname: str, data) -> None:
    """Save list or dict of NDArray (reference python/mxnet/ndarray.py save).

    Local paths publish atomically (temp file + fsync + ``os.replace``,
    base.atomic_local_write): a crash mid-save can never leave a
    truncated file at the published name — the torn-``.params`` failure
    mode that used to break ``load_checkpoint``.  URI targets stream
    through their protocol driver unchanged."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    elif isinstance(data, (list, tuple)):
        names = None
        arrays = list(data)
    else:
        raise TypeError("save only accepts dict or list of NDArray")
    for a in arrays:
        if not isinstance(a, NDArray):
            raise TypeError("save only accepts dict or list of NDArray")
    raw = [a.asnumpy() for a in arrays]
    # npz has no bfloat16: store as uint16 bits + a dtype tag per array
    dtypes = [str(a.dtype) for a in raw]
    raw = [a.view(np.uint16) if d == "bfloat16" else a
           for a, d in zip(raw, dtypes)]

    def _write(f):
        f.write(_SAVE_MAGIC)
        np_bytes = _io.BytesIO()
        np.savez(np_bytes, *raw)
        meta = pickle.dumps({"names": names, "dtypes": dtypes})
        f.write(struct.pack("<Q", len(meta)))
        f.write(meta)
        f.write(np_bytes.getvalue())

    from .base import atomic_local_write, is_local_path, open_stream
    if is_local_path(fname):
        with atomic_local_write(fname, "wb") as f:
            _write(f)
    else:
        with open_stream(fname, "wb") as f:
            _write(f)


def load(fname: str):
    """Load NDArrays saved by :func:`save` (local paths or URIs — the
    reference's dmlc::Stream S3/HDFS transparency, via fsspec here)."""
    from .base import open_stream
    with open_stream(fname, "rb") as f:
        return loads(f.read(), name=fname)


def loads(buf: bytes, name: str = "<bytes>"):
    """Load NDArrays from an in-memory save() blob (the form the C predict
    ABI receives param blobs in, c_predict_api.h MXPredCreate)."""
    stream = _io.BytesIO(buf)
    magic = stream.read(len(_SAVE_MAGIC))
    if magic != _SAVE_MAGIC:
        raise MXNetError("invalid NDArray file %s" % name)
    (meta_len,) = struct.unpack("<Q", stream.read(8))
    meta = pickle.loads(stream.read(meta_len))
    if isinstance(meta, dict):
        names, dtypes = meta["names"], meta.get("dtypes")
    else:                      # blobs from older saves: names only
        names, dtypes = meta, None
    npz = np.load(_io.BytesIO(stream.read()))
    arrays = []
    for i in range(len(npz.files)):
        a = npz["arr_%d" % i]
        dt = dtypes[i] if dtypes else str(a.dtype)
        if dt == "bfloat16":
            import ml_dtypes
            a = a.view(ml_dtypes.bfloat16)
        arrays.append(array(a, dtype=dt))
    if names is None:
        return arrays
    return dict(zip(names, arrays))


# ---------------------------------------------------------------------------
# registered functions (reference src/ndarray/ndarray.cc registrations)

def concatenate(arrays: Sequence[NDArray], axis: int = 0, always_copy: bool = True) -> NDArray:
    if not arrays:
        raise MXNetError("need at least one array")
    if len(arrays) == 1 and not always_copy:
        return arrays[0]
    return NDArray(jnp.concatenate([a._get() for a in arrays], axis=axis))


def concat(*arrays, **kwargs):
    dim = kwargs.get("dim", 1)
    return concatenate(list(arrays), axis=dim)


def onehot_encode(indices: NDArray, out: NDArray) -> NDArray:
    """reference ndarray.cc onehot_encode: out[i, indices[i]] = 1."""
    n, k = out.shape
    idx = indices._get().astype(jnp.int32)
    out._set(jax.nn.one_hot(idx, k, dtype=out.dtype))
    return out


def clip(arr: NDArray, a_min, a_max) -> NDArray:
    return NDArray(jnp.clip(arr._get(), a_min, a_max))


def dot(lhs: NDArray, rhs: NDArray) -> NDArray:
    return NDArray(_engine.track(jnp.dot(lhs._get(), rhs._get())))


def batch_dot(lhs: NDArray, rhs: NDArray) -> NDArray:
    return NDArray(_engine.track(jnp.matmul(lhs._get(), rhs._get())))


def transpose(arr: NDArray, axes=None) -> NDArray:
    return NDArray(jnp.transpose(arr._get(), axes))


def sum(arr: NDArray, axis=None, keepdims=False) -> NDArray:
    return NDArray(jnp.sum(arr._get(), axis=axis, keepdims=keepdims).reshape(-1)
                   if axis is None and not keepdims
                   else jnp.sum(arr._get(), axis=axis, keepdims=keepdims))


def max(arr: NDArray, axis=None, keepdims=False) -> NDArray:  # noqa: A001
    return NDArray(jnp.max(arr._get(), axis=axis, keepdims=keepdims).reshape(-1)
                   if axis is None and not keepdims
                   else jnp.max(arr._get(), axis=axis, keepdims=keepdims))


def min(arr: NDArray, axis=None, keepdims=False) -> NDArray:  # noqa: A001
    return NDArray(jnp.min(arr._get(), axis=axis, keepdims=keepdims).reshape(-1)
                   if axis is None and not keepdims
                   else jnp.min(arr._get(), axis=axis, keepdims=keepdims))


def norm(arr: NDArray) -> NDArray:
    return NDArray(jnp.sqrt(jnp.sum(jnp.square(arr._get()))).reshape(1))


def argmax_channel(arr: NDArray) -> NDArray:
    return NDArray(jnp.argmax(arr._get(), axis=1).astype(arr._get().dtype))


def choose_element_0index(lhs: NDArray, rhs: NDArray) -> NDArray:
    """out[i] = lhs[i, rhs[i]] (reference ndarray choose_element_0index)."""
    a = lhs._get()
    idx = rhs._get().astype(jnp.int32)
    return NDArray(a[jnp.arange(a.shape[0]), idx])


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0, channels=3, mean=None):
    """Decode an image (reference plugin/opencv). Uses PIL if available."""
    raise MXNetError("imdecode requires the opencv plugin; not available in this build")


def waitall():
    """Block until all pending async work completes (MXNDArrayWaitAll)."""
    _engine.wait_for_all()


for _name, _fn in [("_plus", lambda a, b: a + b), ("_minus", lambda a, b: a - b),
                   ("_mul", lambda a, b: a * b), ("_div", lambda a, b: a / b),
                   ("clip", clip), ("dot", dot), ("batch_dot", batch_dot),
                   ("onehot_encode", onehot_encode), ("sum", sum), ("max", max),
                   ("min", min), ("norm", norm), ("argmax_channel", argmax_channel),
                   ("choose_element_0index", choose_element_0index),
                   ("transpose", transpose)]:
    register_ndarray_fn(_name, _fn)
