"""Model zoo: the reference's example/ network definitions, rebuilt on the
mxnet_tpu symbol API (reference example/image-classification/symbol_*.py,
example/rnn/lstm.py — capability parity, fresh implementations)."""
from .mlp import get_mlp
from .lenet import get_lenet
from .alexnet import get_alexnet
from .googlenet import get_googlenet
from .inception_v3 import get_inception_v3
from .resnet import get_resnet, get_resnet50, get_resnet_cifar
from .inception_bn import get_inception_bn, get_inception_bn_28small
from .vgg import get_vgg
from .lstm import (lstm_unroll, lstm_unroll_scan, lstm_cell,
                   LSTMState, LSTMParam)
from .dcgan import make_generator, make_discriminator
from .fcn import get_fcn32s, get_fcn16s, get_fcn8s
from .rcnn import get_fast_rcnn, get_rpn
from .olmoe import olmoe_lm
from .kimi_linear import kimi_linear_lm
from .glm_moe_lite import glm_moe_lite_lm
from .sdar_moe import sdar_moe_lm
from .afmoe import afmoe_lm
from .smallthinker import smallthinker_lm
from .qwen3_next import qwen3_next_lm
from .ouro import ouro_lm
from .keye_vl import keye_lm
from .lfm2_moe import lfm2_moe_lm
from .granite_hybrid import granite_hybrid_lm
from .nemotron_h import nemotron_h_lm
from .gru import gru_unroll, gru_cell, rnn_unroll, rnn_cell, GRUState, \
    GRUParam, RNNState, RNNParam

__all__ = ["get_mlp", "get_lenet", "get_resnet", "get_resnet50",
           "get_resnet_cifar",
           "get_inception_bn", "get_inception_bn_28small", "get_vgg",
           "lstm_unroll", "lstm_unroll_scan", "lstm_cell", "LSTMState",
           "LSTMParam", "make_generator", "make_discriminator",
           "get_fcn32s", "get_fcn16s", "get_fcn8s", "get_fast_rcnn",
           "get_rpn", "olmoe_lm", "kimi_linear_lm", "glm_moe_lite_lm",
           "sdar_moe_lm", "afmoe_lm", "smallthinker_lm", "qwen3_next_lm",
           "ouro_lm", "keye_lm", "lfm2_moe_lm", "granite_hybrid_lm",
           "nemotron_h_lm",
           "gru_unroll", "gru_cell", "rnn_unroll",
           "rnn_cell", "GRUState", "GRUParam", "RNNState", "RNNParam"]
