"""Operations of LeNet-5 training, counted from its layer shapes.

The model of ``bench/reference/lenet_fl.py`` and ``repro.models.lenet``:
two 5x5 valid convolutions (3 -> 6 and 6 -> 16 maps), each followed by
2x2 max pooling, then 400 -> 120 -> 84 -> 10 dense layers. Only the
multiply-adds of the convolutions and dense layers are counted; biases,
ReLU, pooling and the loss are not. A trained sample costs, by the usual
convention, three forward passes of FLOPs: the forward, and the gradients
with respect to the inputs and to the weights of every layer (the first
layer's input gradient, which nothing needs, included).
"""
from __future__ import annotations


def forward_macs() -> int:
    """Multiply-adds of one sample's forward pass on a 32x32x3 image."""
    macs, size, cin = 0, 32, 3
    for cout in (6, 16):
        size -= 4                          # 5x5 valid convolution
        macs += size * size * cout * 5 * 5 * cin
        size //= 2                         # 2x2 max pooling
        cin = cout
    dims = (size * size * cin, 120, 84, 10)
    macs += sum(a * b for a, b in zip(dims, dims[1:]))
    return macs


def train_flops_per_sample() -> float:
    """FLOPs of one trained sample: 3 x 2 x the forward multiply-adds."""
    return 3.0 * 2.0 * forward_macs()
