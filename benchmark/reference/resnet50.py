"""Plain reference of the ``resnet50`` configuration: bottleneck ResNet
(He et al. 2015, arXiv:1512.03385, Table 1) forward, loss and gradients
in float32 ``jax.numpy``, with no program code.

It follows the network as ``mxnet_tpu.models.resnet.get_resnet`` builds
it, parameter names included, so that the program's own weights can be
handed over.  Departures from the paper, both the program's: the stride
of a down-sampling unit sits on the 3x3 convolution, not the first 1x1;
the projection shortcut of the first stage has stride 1.  BatchNorm runs
in training mode (batch statistics, eps 2e-5), as a training step does.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import flops  # noqa: E402  (the benchmark's own arithmetic)

BN_EPS = 2e-5


def train_flops_per_sample(config) -> float:
    m, i = config["model"]["kwargs"], config["input"]
    units = m.get("units", [3, 4, 6, 3])
    filters = m.get("filter_list", [64, 256, 512, 1024, 2048])
    return flops.resnet_bottleneck_train_flops(
        units, filters, i["num_classes"], i["image_shape"][1],
        stride_on="3x3")


def _conv(x, w, stride, pad):
    from jax import lax
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)


def _bn(x, gamma, beta):
    import jax.numpy as jnp
    mean = x.mean(axis=(0, 2, 3), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(0, 2, 3), keepdims=True)
    xn = (x - mean) / jnp.sqrt(var + BN_EPS)
    return xn * gamma[None, :, None, None] + beta[None, :, None, None]


def _conv_bn(p, x, name, stride, pad, act=True):
    import jax.numpy as jnp
    y = _bn(_conv(x, p[name + "_conv_weight"], stride, pad),
            p[name + "_bn_gamma"], p[name + "_bn_beta"])
    return jnp.maximum(y, 0.0) if act else y


def _bottleneck(p, x, name, stride, dim_match):
    import jax.numpy as jnp
    c1 = _conv_bn(p, x, name + "_b1", 1, 0)
    c2 = _conv_bn(p, c1, name + "_b2", stride, 1)
    c3 = _conv_bn(p, c2, name + "_b3", 1, 0, act=False)
    sc = x if dim_match else _conv_bn(p, x, name + "_sc", stride, 0,
                                      act=False)
    return jnp.maximum(c3 + sc, 0.0)


def mean_loss(p, x, y, units):
    """Mean softmax cross-entropy of the batch."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    h = _conv_bn(p, x, "stem", 2, 3)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for stage, n in enumerate(units):
        h = _bottleneck(p, h, "stage%d_unit0" % (stage + 1),
                        1 if stage == 0 else 2, False)
        for i in range(1, n):
            h = _bottleneck(p, h, "stage%d_unit%d" % (stage + 1, i), 1, True)
    h = h.mean(axis=(2, 3))
    logits = jnp.dot(h, p["fc1_weight"].T,
                     precision=lax.Precision.HIGHEST) + p["fc1_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


def reference_step(config, params, data, labels, optimizer, names):
    """Loss of the batch and the first SGD step's change of ``names``.

    The program's SoftmaxOutput hands back ``p - onehot`` summed over
    the batch and the module rescales by 1/batch, which is the gradient
    of the mean loss; momentum starts at zero, so the first step is
    ``-lr * (grad + wd * w)``."""
    import jax
    import jax.numpy as jnp
    units = config["model"]["kwargs"].get("units", [3, 4, 6, 3])
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = jnp.asarray(data["data"], jnp.float32)
    y = jnp.asarray(labels["softmax_label"]).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        # data and weights are arguments, not constants of the program:
        # the compiled reference is then the same for every seed and is
        # found in the compilation cache by every run after the first
        loss, grads = jax.jit(jax.value_and_grad(
            lambda q, xs, ys: mean_loss(q, xs, ys, units)))(p, x, y)
    lr, wd = optimizer["learning_rate"], optimizer.get("wd", 0.0)
    return {"loss": float(loss),
            "updates": {n: -lr * (jax.device_get(grads[n])
                                  + wd * jax.device_get(p[n]))
                        for n in names}}
