"""Operations a configuration's training step requires per sample.

Analytic, from the configuration's sizes alone, in the 2mnk convention
(one multiply-add = 2 FLOP) that the published peaks use.  Training =
forward + backward-data + backward-weight = 3x forward; recomputation
does not count.  Elementwise work (BatchNorm, ReLU, gates, softmax) is
left out, as in the program's own functions these were copied from
(``tools/profile_resnet.analytic_train_gflop_per_img``,
``bench_lstm.train_mflop_per_token``).
"""
from __future__ import annotations

from typing import Sequence


def resnet_bottleneck_train_flops(units: Sequence[int],
                                  filter_list: Sequence[int],
                                  num_classes: int, image_size: int,
                                  stride_on: str = "3x3") -> float:
    """FLOP per trained image of a bottleneck ResNet (He et al. 2015).

    ``stride_on`` says which convolution of a down-sampling unit carries
    the stride: ``"3x3"`` is what ``mxnet_tpu.models.resnet._bottleneck``
    builds (the 1x1 before it still runs at the larger resolution);
    ``"1x1"`` is the paper's original placement, which the program's
    ``analytic_train_gflop_per_img`` assumes (23.15 GFLOP at 50 layers).
    """
    if stride_on not in ("3x3", "1x1"):
        raise ValueError("stride_on must be '3x3' or '1x1'")

    def conv(cin, cout, k, hw_out):
        return 2 * cout * hw_out * hw_out * cin * k * k

    def down(hw, s):
        return (hw + s - 1) // s

    hw = down(image_size, 2)                    # 7x7/2 stem
    total = conv(3, filter_list[0], 7, hw)
    hw = down(hw, 2)                            # 3x3/2 max pool
    cin = filter_list[0]
    for stage, (n, cout) in enumerate(zip(units, filter_list[1:])):
        mid = cout // 4
        for unit in range(n):
            s = 2 if (unit == 0 and stage > 0) else 1
            hw_out = down(hw, s)
            hw_1x1 = hw if stride_on == "3x3" else hw_out
            total += conv(cin, mid, 1, hw_1x1)
            total += conv(mid, mid, 3, hw_out)
            total += conv(mid, cout, 1, hw_out)
            if unit == 0:                       # projection shortcut
                total += conv(cin, cout, 1, hw_out)
            cin, hw = cout, hw_out
    total += 2 * cin * num_classes
    return 3.0 * total


def lstm_lm_train_flops(num_lstm_layer: int, num_hidden: int,
                        num_embed: int, vocab_size: int) -> float:
    """FLOP per trained token of the unrolled LSTM language model: the
    first layer's gates see an (E+H)-wide input, each later layer's an
    (H+H)-wide one, then the H -> vocabulary projection."""
    fwd = (2 * 4 * num_hidden * (num_embed + num_hidden)
           + (num_lstm_layer - 1) * 2 * 4 * num_hidden * (2 * num_hidden)
           + 2 * num_hidden * vocab_size)
    return 3.0 * fwd

