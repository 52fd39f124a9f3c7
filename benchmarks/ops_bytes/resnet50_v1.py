"""Operations a ResNet-50 v1 training step needs, from the layer shapes.

2 operations per multiply-add, over every convolution and the classifier,
x 3 for forward + backward (the backward pass computes one product for the
input gradient and one for the weight gradient per forward product; the
first convolution's input gradient is not needed, which this over-counts by
0.1 GFLOP in 23).  Element-wise work (BatchNorm, ReLU, the optimizer) is
not counted: the step is compute-bound on the MXU and the share says so."""
from __future__ import annotations


def forward_macs_per_image(sizes):
    image, classes = int(sizes["image"]), int(sizes["classes"])
    layers, channels = sizes["layers"], sizes["channels"]
    hw = (image + 2 * 3 - 7) // 2 + 1
    macs = 3 * channels[0] * 49 * hw * hw
    hw = (hw + 2 - 3) // 2 + 1
    cin = channels[0]
    for s, blocks in enumerate(layers):
        cout = channels[s + 1]
        mid = cout // 4
        for i in range(blocks):
            stride = 2 if (i == 0 and s > 0) else 1
            out = (hw - 1) // stride + 1
            macs += cin * mid * out * out          # 1x1, strided
            macs += mid * mid * 9 * out * out      # 3x3
            macs += mid * cout * out * out         # 1x1
            if i == 0:
                macs += cin * cout * out * out     # projection shortcut
            cin, hw = cout, out
    return macs + cin * classes


def step_flops(sizes, batch):
    """Floating-point operations of one training step at ``batch``."""
    return 2 * 3 * forward_macs_per_image(sizes) * batch

