"""mxnet_tpu: a TPU-native deep learning framework with the capabilities of
MXNet v0.7 (reference: kaiyuzhao/mxnet), re-designed for JAX/XLA/Pallas.

Usage mirrors the reference python package:

    import mxnet_tpu as mx
    data = mx.sym.Variable('data')
    fc = mx.sym.FullyConnected(data, num_hidden=10)
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(fc), context=mx.tpu())
"""
from . import _distributed_boot  # must precede any jax backend init
from . import base
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context
from . import engine
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import random
from . import random as rnd
from . import symbol
from . import symbol as sym
from .ops import nd_bridge as _nd_bridge
_nd_bridge.register_all()  # SimpleOp dual registration: ops -> mx.nd.*
from .symbol import Symbol
from . import executor
from .executor import Executor
from . import io
from . import initializer
from . import initializer as init
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import kvstore as kv
from . import kvstore
from .kvstore import create as create_kvstore
from . import callback
from . import monitor
from .monitor import Monitor
from . import model
from .model import FeedForward
from . import module
from . import module as mod
from . import visualization
from . import visualization as viz
from . import operator
from .operator import CustomOp, CustomOpProp, NumpyOp, NDArrayOp
from . import recordio
from . import rtc
from .attribute import AttrScope
from .name import NameManager, Prefix
from . import parallel
from . import plugins
from .plugins import torch_bridge as th
from . import native_io
from . import feed
from . import checkpoint
from . import compile_cache
from . import passes
from . import autotune
from . import embed
from . import moe
from . import predictor
from . import serve
from . import trace
if trace.enabled():
    # every program JAX compiles from here on, the traffic's and a
    # checking module's too, leaves compile:trace / lower / backend on
    # the ring; jax.monitoring alone is touched, no backend starts
    compile_cache.record_compile_spans()
from . import profiler
from . import faults
from . import online
from . import libinfo
from . import misc
from . import symbol_doc
# must be last: on DMLC_ROLE=server/scheduler this runs the parameter-server
# loop and exits (reference python/mxnet/__init__.py imports kvstore_server
# so that `import mxnet` on a server role never returns to user code)
from . import kvstore_server

__version__ = "0.7.0-tpu.1"
