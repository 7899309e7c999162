"""Operator library: registry + full op inventory (SURVEY §2.2).

Importing this package registers every op.  The symbol and ndarray layers
generate their user-facing constructors from this registry, mirroring the
reference's dual SimpleOp registration (include/mxnet/operator_util.h:92-486).
"""
from .registry import (OpDef, OpContext, Param, register_op,
                       register_simple_op, get_op, list_ops)
from . import tensor  # noqa: F401  (registers elementwise/broadcast/reduce/matrix)
from . import nn      # noqa: F401  (registers NN layers)
from . import special  # noqa: F401 (registers ROIPooling/SpatialTransformer/Correlation)
from . import rnn     # noqa: F401  (registers the fused scan-based RNN)
from . import quantized  # noqa: F401 (registers q/dq + int8 matmul/conv)
from . import fused   # noqa: F401  (registers the epilogue-fused op family)
from . import moe     # noqa: F401  (registers the routed-MoE dispatch family)
from . import transformer  # noqa: F401 (registers RMSNorm/RoPE/attention/loss head)
from . import linear_attention  # noqa: F401 (registers the delta-rule ops and CausalConv1D)
from . import ssd  # noqa: F401 (registers SSDScan, the state-space scan)
from . import gated_norm  # noqa: F401 (registers GatedRMSNorm, the mixers' output stage)
from . import head_rotary  # noqa: F401 (registers HeadNormRotary, q's and k's norm and rotation)
from . import control_flow  # noqa: F401 (registers Repeat, the loop node)
from . import sparse_attention  # noqa: F401 (registers IndexedSelfAttention)

__all__ = ["OpDef", "OpContext", "Param", "register_op", "register_simple_op",
           "get_op", "list_ops"]
