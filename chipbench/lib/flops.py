"""Operations the algorithms need, counted from shapes (never from the
compiled program: XLA's cost analysis counts recomputation and prices a
Mosaic call at nothing)."""
from __future__ import annotations


def _conv_out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def resnet_v1_layers(layers, channels, classes, hw, bottleneck=True,
                     stride_in_3x3=False):
    """(name, macs per image) for every conv and the dense layer of a
    ResNet v1 (He et al. 2015, Table 1: 3.8e9 multiply-adds at 50 layers).
    A stage's stride sits in its first 1x1 conv, where the paper and MXNet's
    v1 place it; ``stride_in_3x3`` gives the "v1.5" variant other zoos ship,
    whose first 1x1 runs at the input's size (the often quoted 4.1e9)."""
    rows = []
    s = _conv_out(hw, 7, 2, 3)
    rows.append(("stem_conv7x7", s * s * 7 * 7 * 3 * channels[0]))
    s = _conv_out(s, 3, 2, 1)                         # max pool
    cin = channels[0]
    for i, n in enumerate(layers):
        cout = channels[i + 1]
        for b in range(n):
            stride = 2 if (b == 0 and i > 0) else 1
            so = _conv_out(s, 1, stride, 0)
            tag = "stage%d_block%d" % (i + 1, b)
            if bottleneck:
                mid = cout // 4
                sa = s if stride_in_3x3 else so
                rows.append((tag + "_conv1x1a", sa * sa * cin * mid))
                rows.append((tag + "_conv3x3", so * so * 9 * mid * mid))
                rows.append((tag + "_conv1x1b", so * so * mid * cout))
            else:
                rows.append((tag + "_conv3x3a", so * so * 9 * cin * cout))
                rows.append((tag + "_conv3x3b", so * so * 9 * cout * cout))
            if b == 0 and cin != cout:
                rows.append((tag + "_downsample", so * so * cin * cout))
            cin, s = cout, so
    rows.append(("dense", cin * classes))
    return rows


def resnet_v1_train_flops_per_image(layers, channels, classes, hw,
                                    bottleneck=True):
    """Forward + backward of one image: 3 x forward, 2 FLOPs per MAC.
    Batch norm, ReLU, pooling and the update are not counted (they are
    bandwidth, not matrix work)."""
    macs = sum(m for _, m in resnet_v1_layers(layers, channels, classes, hw,
                                              bottleneck))
    return 3 * 2 * macs


def transformer_lm_flops_per_token(units, hidden_size, num_layers,
                                   vocab_size, context):
    """Forward of one generated token at a context of ``context`` cached
    positions: the dense projections, the tied head and the attention
    dots, 2 FLOPs per MAC."""
    per_layer = 4 * units * units + 2 * units * hidden_size \
        + 2 * context * units
    return 2 * (num_layers * per_layer + units * vocab_size)
