"""ResNet v1/v2 (derived from the reference implementation
python/mxnet/gluon/model_zoo/vision/resnet.py — resnet18-152,
BasicBlock/Bottleneck, v1 and pre-activation v2; class structure and
parameter naming kept for checkpoint compatibility).

TPU notes: NCHW at the API (reference layout); build under
`gluon.nn.layout_scope()` for the MXU-preferred channels-last layout.
Two zoo-level performance rewrites ride behind flags (both default to the
reference graph; both are checkpoint-compatible — see each flag):

- ``fuse_epilogue``: every BN→ReLU(→+residual) epilogue collapses into
  the fused BatchNormRelu / BatchNormAddRelu ops (one op in the graph;
  the same lowering as the separate ops, see ops/nn.py ``_bn_act``).
  Parameter names are unchanged — the fused layers are the same
  ``nn.BatchNorm`` class, the paramless ``nn.Activation`` blocks simply
  disappear.
- ``stem_s2d`` (env ``MXTPU_S2D_STEM``): the MXU-hostile 7×7/s2 3-channel
  stem becomes space-to-depth(2) + a 4×4/s1 conv over 12 channels —
  numerically equivalent under the weight-space transform
  ``stem_weight_to_s2d`` (zero-pad the 7×7 kernel to 8×8, regroup into
  2×2 parities); ``convert_stem_params`` converts existing checkpoints.
"""
from __future__ import annotations


from .... import env as _env
from ....base import MXNetError
from ....ops.nn import _channels_last
from ...block import HybridBlock
from ... import nn

__all__ = ["ResNetV1", "ResNetV2", "BasicBlockV1", "BasicBlockV2",
           "BottleneckV1", "BottleneckV2", "resnet18_v1", "resnet34_v1",
           "resnet50_v1", "resnet101_v1", "resnet152_v1", "resnet18_v2",
           "resnet34_v2", "resnet50_v2", "resnet101_v2", "resnet152_v2",
           "get_resnet", "stem_weight_to_s2d", "convert_stem_params"]


def _stem_s2d_default(flag):
    if flag is not None:
        return bool(flag)
    return _env.get("MXTPU_S2D_STEM")


def _conv3x3(channels, stride, in_channels):
    return nn.Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                     use_bias=False, in_channels=in_channels)


def _bn(fused, act=None):
    """BatchNorm, optionally carrying the fused epilogue activation. The
    fused variant is the SAME class (same auto-name counter, same params) —
    only the trailing paramless Activation block is dropped by callers."""
    return nn.BatchNorm(act_type=act if fused else None)


def _fused_body_forward(body, x, residual):
    """Run a fused block body whose TAIL is the BatchNormAddRelu layer:
    every child except the last consumes one input; the last gets the
    residual as its fused addend. Shared by BasicBlockV1/BottleneckV1 so
    the tail-position assumption lives in exactly one place."""
    children = list(body._children.values())
    out = x
    for blk in children[:-1]:
        out = blk(out)
    return children[-1](out, residual)


class _SpaceToDepthStem(HybridBlock):
    """Paramless stem transform: space-to-depth(2) + the asymmetric (2, 1)
    spatial zero-pad that makes a following 4×4/s1 VALID conv reproduce the
    reference 7×7/s2/pad-3 stem exactly (see stem_weight_to_s2d for the
    matching weight-space transform). Requires even spatial dims."""

    def __init__(self, layout="NCHW", **kwargs):
        super().__init__(**kwargs)
        self._layout = layout
        self._ch_last = _channels_last(layout)

    def hybrid_forward(self, F, x):
        shape = getattr(x, "shape", None)
        if shape:  # eager/jit trace: shapes known; Symbol tracing has none
            sp = shape[1:3] if self._ch_last else shape[2:4]
            if any(isinstance(d, int) and d % 2 for d in sp):
                raise MXNetError(
                    "space-to-depth stem requires even spatial dims, got "
                    "%s — the reference 7x7/s2 stem handles odd sizes; "
                    "build with stem_s2d=False for this input" % (sp,))
        z = F.space_to_depth(x, block_size=2, layout=self._layout)
        if self._ch_last:
            pw = (0, 0, 2, 1, 2, 1, 0, 0)
        else:
            pw = (0, 0, 0, 0, 2, 1, 2, 1)
        return F.pad(z, mode="constant", pad_width=pw)


def stem_weight_to_s2d(w, layout="NCHW"):
    """Weight-space transform for the space-to-depth stem: a 7×7 stem conv
    weight (O, C, 7, 7) (NCHW; (O, 7, 7, C) for NHWC) becomes the 4×4
    weight over C·4 space-to-depth channels that computes the IDENTICAL
    convolution (y[p] = Σ w7[i]·x[2p+i-3] = Σ w8[2di+a]·z_a[p+di-2] after
    zero-padding the kernel to 8×8 at the top/left and regrouping by 2×2
    spatial parity). Depth order matches ops space_to_depth:
    channel = a·2C + b·C + c. Accepts numpy or jax arrays."""
    import numpy as np

    w = np.asarray(w)
    if _channels_last(layout):
        o, kh, kw, c = w.shape
        if (kh, kw) != (7, 7):
            raise MXNetError("stem_weight_to_s2d expects a 7x7 kernel, "
                             "got %s" % ((kh, kw),))
        w8 = np.pad(w, ((0, 0), (1, 0), (1, 0), (0, 0)))
        w8 = w8.reshape(o, 4, 2, 4, 2, c)           # (O, di, a, dj, b, C)
        return np.ascontiguousarray(
            w8.transpose(0, 1, 3, 2, 4, 5).reshape(o, 4, 4, 4 * c))
    o, c, kh, kw = w.shape
    if (kh, kw) != (7, 7):
        raise MXNetError("stem_weight_to_s2d expects a 7x7 kernel, got %s"
                         % ((kh, kw),))
    w8 = np.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))
    w8 = w8.reshape(o, c, 4, 2, 4, 2)               # (O, C, di, a, dj, b)
    return np.ascontiguousarray(
        w8.transpose(0, 3, 5, 1, 2, 4).reshape(o, 4 * c, 4, 4))


def convert_stem_params(params, layout="NCHW"):
    """Convert a checkpoint dict from the 7×7 stem to the space-to-depth
    stem: every value with a 7×7 stem-conv weight shape is transformed via
    stem_weight_to_s2d, everything else passes through. Works on the dicts
    net.save_parameters/load_parameters exchange. Only the STEM conv is
    converted — matched by its auto-name (first conv: `conv0_weight` /
    `conv2d0_weight`) AND a 7x7 kernel — so other 7x7 convs a custom model
    might contain pass through untouched."""
    ch_last = _channels_last(layout)
    out = {}
    for k, v in params.items():
        shp = tuple(getattr(v, "shape", ()))
        is_stem = (len(shp) == 4
                   and (k.endswith("conv0_weight")
                        or k.endswith("conv2d0_weight"))
                   and (shp[1:3] == (7, 7) if ch_last
                        else shp[2:] == (7, 7)))
        out[k] = stem_weight_to_s2d(v, layout) if is_stem else v
    return out


class BasicBlockV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 fuse_epilogue=False, **kwargs):
        super().__init__(**kwargs)
        self._fused = fuse_epilogue
        self.body = nn.HybridSequential(prefix="")
        self.body.add(_conv3x3(channels, stride, in_channels))
        self.body.add(_bn(fuse_epilogue, "relu"))
        if not fuse_epilogue:
            self.body.add(nn.Activation("relu"))
        self.body.add(_conv3x3(channels, 1, channels))
        self.body.add(_bn(fuse_epilogue, "relu"))  # fused tail: bn+add+relu
        if downsample:
            self.downsample = nn.HybridSequential(prefix="")
            self.downsample.add(nn.Conv2D(channels, kernel_size=1, strides=stride,
                                          use_bias=False, in_channels=in_channels))
            self.downsample.add(nn.BatchNorm())
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        if self.downsample is not None:
            residual = self.downsample(x)
        if self._fused:
            return _fused_body_forward(self.body, x, residual)
        out = self.body(x)
        return F.Activation(out + residual, act_type="relu")


class BottleneckV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 fuse_epilogue=False, **kwargs):
        super().__init__(**kwargs)
        self._fused = fuse_epilogue
        self.body = nn.HybridSequential(prefix="")
        self.body.add(nn.Conv2D(channels // 4, kernel_size=1, strides=stride,
                                use_bias=False))
        self.body.add(_bn(fuse_epilogue, "relu"))
        if not fuse_epilogue:
            self.body.add(nn.Activation("relu"))
        self.body.add(_conv3x3(channels // 4, 1, channels // 4))
        self.body.add(_bn(fuse_epilogue, "relu"))
        if not fuse_epilogue:
            self.body.add(nn.Activation("relu"))
        self.body.add(nn.Conv2D(channels, kernel_size=1, strides=1, use_bias=False))
        self.body.add(_bn(fuse_epilogue, "relu"))  # fused tail: bn+add+relu
        if downsample:
            self.downsample = nn.HybridSequential(prefix="")
            self.downsample.add(nn.Conv2D(channels, kernel_size=1, strides=stride,
                                          use_bias=False, in_channels=in_channels))
            self.downsample.add(nn.BatchNorm())
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        if self.downsample is not None:
            residual = self.downsample(x)
        if self._fused:
            return _fused_body_forward(self.body, x, residual)
        out = self.body(x)
        return F.Activation(out + residual, act_type="relu")


class BasicBlockV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 fuse_epilogue=False, **kwargs):
        super().__init__(**kwargs)
        self._fused = fuse_epilogue
        self.bn1 = _bn(fuse_epilogue, "relu")
        self.conv1 = _conv3x3(channels, stride, in_channels)
        self.bn2 = _bn(fuse_epilogue, "relu")
        self.conv2 = _conv3x3(channels, 1, channels)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels)
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        out = self.bn1(x)
        if not self._fused:
            out = F.Activation(out, act_type="relu")
        if self.downsample is not None:
            residual = self.downsample(out)
        out = self.conv1(out)
        out = self.bn2(out)
        if not self._fused:
            out = F.Activation(out, act_type="relu")
        out = self.conv2(out)
        return out + residual


class BottleneckV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 fuse_epilogue=False, **kwargs):
        super().__init__(**kwargs)
        self._fused = fuse_epilogue
        self.bn1 = _bn(fuse_epilogue, "relu")
        self.conv1 = nn.Conv2D(channels // 4, kernel_size=1, strides=1, use_bias=False)
        self.bn2 = _bn(fuse_epilogue, "relu")
        self.conv2 = _conv3x3(channels // 4, stride, channels // 4)
        self.bn3 = _bn(fuse_epilogue, "relu")
        self.conv3 = nn.Conv2D(channels, kernel_size=1, strides=1, use_bias=False)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels)
        else:
            self.downsample = None

    def hybrid_forward(self, F, x):
        residual = x
        out = self.bn1(x)
        if not self._fused:
            out = F.Activation(out, act_type="relu")
        if self.downsample is not None:
            residual = self.downsample(out)
        out = self.conv1(out)
        out = self.bn2(out)
        if not self._fused:
            out = F.Activation(out, act_type="relu")
        out = self.conv2(out)
        out = self.bn3(out)
        if not self._fused:
            out = F.Activation(out, act_type="relu")
        out = self.conv3(out)
        return out + residual


def _add_stem(features, channels0, stem_s2d, fuse_epilogue):
    """The non-thumbnail stem: reference 7×7/s2/pad-3 conv, or the
    space-to-depth rewrite (stem_s2d). The conv keeps auto-name conv0_
    in both variants (the s2d transform block is paramless), so the only
    checkpoint delta is the stem weight's shape — convert_stem_params
    maps one onto the other."""
    from ...nn.conv_layers import in_channels_last_scope

    if stem_s2d:
        layout = "NHWC" if in_channels_last_scope() else "NCHW"
        features.add(_SpaceToDepthStem(layout=layout))
        # in_channels deferred: space_to_depth(2) yields 4*C_in channels
        # (12 for RGB), resolved at first forward like the 7x7 stem
        features.add(nn.Conv2D(channels0, kernel_size=4, strides=1,
                               padding=0, use_bias=False))
    else:
        features.add(nn.Conv2D(channels0, 7, 2, 3, use_bias=False))
    features.add(_bn(fuse_epilogue, "relu"))
    if not fuse_epilogue:
        features.add(nn.Activation("relu"))
    features.add(nn.MaxPool2D(3, 2, 1))


class ResNetV1(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 fuse_epilogue=False, stem_s2d=None, **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        stem_s2d = _stem_s2d_default(stem_s2d)
        self._fuse_epilogue = fuse_epilogue
        with self.name_scope():
            self.features = nn.HybridSequential(prefix="")
            if thumbnail:
                self.features.add(_conv3x3(channels[0], 1, 0))
            else:
                _add_stem(self.features, channels[0], stem_s2d, fuse_epilogue)
            for i, num_layer in enumerate(layers):
                stride = 1 if i == 0 else 2
                self.features.add(self._make_layer(block, num_layer, channels[i + 1],
                                                   stride, i + 1,
                                                   in_channels=channels[i],
                                                   fuse_epilogue=fuse_epilogue))
            self.features.add(nn.GlobalAvgPool2D())
            self.output = nn.Dense(classes, in_units=channels[-1])

    def _make_layer(self, block, layers, channels, stride, stage_index,
                    in_channels=0, fuse_epilogue=False):
        layer = nn.HybridSequential(prefix="stage%d_" % stage_index)
        with layer.name_scope():
            layer.add(block(channels, stride, channels != in_channels,
                            in_channels=in_channels,
                            fuse_epilogue=fuse_epilogue, prefix=""))
            for _ in range(layers - 1):
                layer.add(block(channels, 1, False, in_channels=channels,
                                fuse_epilogue=fuse_epilogue, prefix=""))
        return layer

    def hybrid_forward(self, F, x):
        x = self.features(x)
        x = self.output(x)
        return x


class ResNetV2(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 fuse_epilogue=False, stem_s2d=None, **kwargs):
        super().__init__(**kwargs)
        assert len(layers) == len(channels) - 1
        stem_s2d = _stem_s2d_default(stem_s2d)
        self._fuse_epilogue = fuse_epilogue
        with self.name_scope():
            self.features = nn.HybridSequential(prefix="")
            self.features.add(nn.BatchNorm(scale=False, center=False))
            if thumbnail:
                self.features.add(_conv3x3(channels[0], 1, 0))
            else:
                _add_stem(self.features, channels[0], stem_s2d, fuse_epilogue)
            in_channels = channels[0]
            for i, num_layer in enumerate(layers):
                stride = 1 if i == 0 else 2
                self.features.add(self._make_layer(block, num_layer, channels[i + 1],
                                                   stride, i + 1,
                                                   in_channels=in_channels,
                                                   fuse_epilogue=fuse_epilogue))
                in_channels = channels[i + 1]
            self.features.add(_bn(fuse_epilogue, "relu"))
            if not fuse_epilogue:
                self.features.add(nn.Activation("relu"))
            self.features.add(nn.GlobalAvgPool2D())
            self.features.add(nn.Flatten())
            self.output = nn.Dense(classes, in_units=in_channels)

    _make_layer = ResNetV1._make_layer

    def hybrid_forward(self, F, x):
        x = self.features(x)
        x = self.output(x)
        return x


resnet_spec = {18: ("basic_block", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
               34: ("basic_block", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
               50: ("bottle_neck", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
               101: ("bottle_neck", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
               152: ("bottle_neck", [3, 8, 36, 3], [64, 256, 512, 1024, 2048])}

resnet_net_versions = [ResNetV1, ResNetV2]
resnet_block_versions = [{"basic_block": BasicBlockV1, "bottle_neck": BottleneckV1},
                         {"basic_block": BasicBlockV2, "bottle_neck": BottleneckV2}]


def get_resnet(version, num_layers, pretrained=False, ctx=None, root=None, **kwargs):
    """reference: resnet.py get_resnet. TPU extensions: fuse_epilogue=
    (default off) and stem_s2d= (defaults to MXTPU_S2D_STEM); see module
    docstring."""
    assert num_layers in resnet_spec, \
        "Invalid resnet depth %d; options: %s" % (num_layers, sorted(resnet_spec))
    block_type, layers, channels = resnet_spec[num_layers]
    assert version in (1, 2)
    resnet_class = resnet_net_versions[version - 1]
    block_class = resnet_block_versions[version - 1][block_type]
    net = resnet_class(block_class, layers, channels, **kwargs)
    if pretrained:
        raise MXNetError("pretrained weights unavailable (zero-egress build); "
                         "load with net.load_parameters(path) instead")
    return net


def resnet18_v1(**kwargs):
    return get_resnet(1, 18, **kwargs)


def resnet34_v1(**kwargs):
    return get_resnet(1, 34, **kwargs)


def resnet50_v1(**kwargs):
    return get_resnet(1, 50, **kwargs)


def resnet101_v1(**kwargs):
    return get_resnet(1, 101, **kwargs)


def resnet152_v1(**kwargs):
    return get_resnet(1, 152, **kwargs)


def resnet18_v2(**kwargs):
    return get_resnet(2, 18, **kwargs)


def resnet34_v2(**kwargs):
    return get_resnet(2, 34, **kwargs)


def resnet50_v2(**kwargs):
    return get_resnet(2, 50, **kwargs)


def resnet101_v2(**kwargs):
    return get_resnet(2, 101, **kwargs)


def resnet152_v2(**kwargs):
    return get_resnet(2, 152, **kwargs)
