"""ResNet v1/v2 (reference ``python/mxnet/gluon/model_zoo/vision/resnet.py``).

He et al. "Deep Residual Learning" (v1) and "Identity Mappings" (v2),
18/34/50/101/152 layers.  The reference is NCHW-only; here every network
additionally takes ``layout`` ("NCHW" default / "NHWC") because on TPU the
channel-minor layout keeps convolutions and batch-norm reductions on XLA's
preferred tiling, and ``stem_s2d`` which re-expresses the 7x7/stride-2 stem
convolution as a mathematically IDENTICAL 4x4/stride-1 convolution over a
2x2 space-to-depth input (the MLPerf ResNet trick: conv0 at C=3 badly
underfills the 128x128 MXU; at C=12 the contraction is 4x wider).  Both
options preserve the reference model function exactly (tests
``tests/test_resnet_layout.py`` assert equivalence numerically).
"""
from __future__ import annotations

from ... import nn
from ...block import HybridBlock
from ...parameter import Parameter
from ....ndarray.ndarray import invoke

__all__ = [
    "ResNetV1", "ResNetV2",
    "BasicBlockV1", "BasicBlockV2", "BottleneckV1", "BottleneckV2",
    "resnet18_v1", "resnet34_v1", "resnet50_v1", "resnet101_v1",
    "resnet152_v1",
    "resnet18_v2", "resnet34_v2", "resnet50_v2", "resnet101_v2",
    "resnet152_v2",
    "get_resnet",
]


def _conv3x3(channels, stride, in_channels, layout="NCHW"):
    return nn.Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                     use_bias=False, in_channels=in_channels, layout=layout)


def _bn(layout="NCHW", **kwargs):
    return nn.BatchNorm(axis=layout.index("C"), **kwargs)


class _StemConvS2D(HybridBlock):
    """The stem 7x7/stride-2/pad-3 conv, re-expressed via space-to-depth.

    Holds the SAME weight shape as the plain ``Conv2D(channels, 7, 2, 3)``
    stem (so checkpoints interoperate and param counts match) and computes
    the same function: with input space-to-depth'd 2x2, output pixel i reads
    input rows m = 2i + p - 3 (p in 0..6); substituting m = 2I + d gives
    I - i in {-2..1} — i.e. an exact 4x4/stride-1 conv with asymmetric
    (2, 1) padding whose kernel is the 7x7 kernel zero-padded to 8x8 (one
    leading zero) and regrouped.  The weight regroup runs in-graph each
    step (64*C*64 elements — noise) so gradients flow to the canonical
    7x7 weight.
    """

    def __init__(self, channels, layout="NCHW", in_channels=0):
        super().__init__()
        self._channels = channels
        self._layout = layout
        self._in_channels = in_channels
        if layout.index("C") == 1:
            wshape = (channels, in_channels, 7, 7)
        else:
            wshape = (channels, 7, 7, in_channels)
        self.weight = Parameter("weight", shape=wshape,
                                allow_deferred_init=True)

    def infer_shape(self, x):
        c = int(x.shape[self._layout.index("C")])
        if self._layout.index("C") == 1:
            self.weight.shape = (self._channels, c, 7, 7)
        else:
            self.weight.shape = (self._channels, 7, 7, c)
        self._in_channels = c

    def forward(self, x):
        w = self.weight.data(x.ctx)
        o = self._channels
        sp = [x.shape[i] for i, a in enumerate(self._layout) if a in "HW"]
        if sp[0] % 2 or sp[1] % 2:
            # odd H/W cannot space-to-depth 2x2; run the canonical conv
            # directly (same weight, same function) instead of crashing
            return invoke("Convolution", [x, w],
                          {"kernel": (7, 7), "stride": (2, 2),
                           "pad": (3, 3), "num_filter": o, "no_bias": True,
                           "layout": self._layout})
        if self._layout.index("C") == 1:
            _n, c, h, wd = x.shape
            # batch dim stays -1: a traced graph (int8 export, hybridize)
            # must not bake the tracing batch size into the reshape
            xs = x.reshape(-1, c, h // 2, 2, wd // 2, 2)
            xs = xs.transpose(0, 3, 5, 1, 2, 4)       # N,di,dj,C,H2,W2
            xs = xs.reshape(-1, 4 * c, h // 2, wd // 2)
            xp = invoke("pad", [xs], {"mode": "constant",
                                      "pad_width": (0, 0, 0, 0, 2, 1, 2, 1)})
            wp = invoke("pad", [w], {"mode": "constant",
                                     "pad_width": (0, 0, 0, 0, 1, 0, 1, 0)})
            wp = wp.reshape(o, c, 4, 2, 4, 2)         # O,C,Ai,di,Aj,dj
            wt = wp.transpose(0, 3, 5, 1, 2, 4)       # O,di,dj,C,Ai,Aj
            wt = wt.reshape(o, 4 * c, 4, 4)
        else:
            _n, h, wd, c = x.shape
            xs = x.reshape(-1, h // 2, 2, wd // 2, 2, c)
            xs = xs.transpose(0, 1, 3, 2, 4, 5)       # N,H2,W2,di,dj,C
            xs = xs.reshape(-1, h // 2, wd // 2, 4 * c)
            xp = invoke("pad", [xs], {"mode": "constant",
                                      "pad_width": (0, 0, 2, 1, 2, 1, 0, 0)})
            wp = invoke("pad", [w], {"mode": "constant",
                                     "pad_width": (0, 0, 1, 0, 1, 0, 0, 0)})
            wp = wp.reshape(o, 4, 2, 4, 2, c)         # O,Ai,di,Aj,dj,C
            wt = wp.transpose(0, 1, 3, 2, 4, 5)       # O,Ai,Aj,di,dj,C
            wt = wt.reshape(o, 4, 4, 4 * c)
        return invoke("Convolution", [xp, wt],
                      {"kernel": (4, 4), "stride": (1, 1), "pad": (0, 0),
                       "num_filter": o, "no_bias": True,
                       "layout": self._layout})


class BasicBlockV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        self.body = nn.HybridSequential()
        self.body.add(_conv3x3(channels, stride, in_channels, layout))
        self.body.add(_bn(layout))
        self.body.add(nn.Activation("relu"))
        self.body.add(_conv3x3(channels, 1, channels, layout))
        self.body.add(_bn(layout))
        if downsample:
            self.downsample = nn.HybridSequential()
            self.downsample.add(nn.Conv2D(channels, kernel_size=1,
                                          strides=stride, use_bias=False,
                                          in_channels=in_channels,
                                          layout=layout))
            self.downsample.add(_bn(layout))
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return (x + residual).relu()


class BottleneckV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        self.body = nn.HybridSequential()
        self.body.add(nn.Conv2D(channels // 4, kernel_size=1, strides=stride,
                                layout=layout))
        self.body.add(_bn(layout))
        self.body.add(nn.Activation("relu"))
        self.body.add(_conv3x3(channels // 4, 1, channels // 4, layout))
        self.body.add(_bn(layout))
        self.body.add(nn.Activation("relu"))
        self.body.add(nn.Conv2D(channels, kernel_size=1, strides=1,
                                layout=layout))
        self.body.add(_bn(layout))
        if downsample:
            self.downsample = nn.HybridSequential()
            self.downsample.add(nn.Conv2D(channels, kernel_size=1,
                                          strides=stride, use_bias=False,
                                          in_channels=in_channels,
                                          layout=layout))
            self.downsample.add(_bn(layout))
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return (x + residual).relu()


class BasicBlockV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        self.bn1 = _bn(layout)
        self.conv1 = _conv3x3(channels, stride, in_channels, layout)
        self.bn2 = _bn(layout)
        self.conv2 = _conv3x3(channels, 1, channels, layout)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.bn1(x).relu()
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x).relu()
        x = self.conv2(x)
        return x + residual


class BottleneckV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        self.bn1 = _bn(layout)
        self.conv1 = nn.Conv2D(channels // 4, 1, 1, use_bias=False,
                               layout=layout)
        self.bn2 = _bn(layout)
        self.conv2 = _conv3x3(channels // 4, stride, channels // 4, layout)
        self.bn3 = _bn(layout)
        self.conv3 = nn.Conv2D(channels, 1, 1, use_bias=False, layout=layout)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride, use_bias=False,
                                        in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.bn1(x).relu()
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x).relu()
        x = self.conv2(x)
        x = self.bn3(x).relu()
        x = self.conv3(x)
        return x + residual


class _ResNetBase(HybridBlock):
    """Shared layout plumbing: models accept input in ``input_layout``
    (default NCHW, the MXNet convention) and compute in ``layout``; when
    they differ ONE transpose runs at graph entry (on the small input
    image, before the channel count grows)."""

    def __init__(self, layout="NCHW", input_layout=None):
        super().__init__()
        if layout not in ("NCHW", "NHWC"):
            raise ValueError(f"resnet layout must be NCHW or NHWC: {layout}")
        self._layout = layout
        self._input_layout = input_layout or "NCHW"

    def _add_stem(self, channels, stem_s2d):
        """The 7x7 convolution, batch norm, ReLU and max-pool, as ONE run a
        staged program recomputes in its backward pass from the input batch
        (``HybridSequential.recompute``).  These are the net's largest
        activations.  Under AMP they are bf16, and XLA keeps a cheap bf16
        tensor for the whole step where it recomputes the float32 one on its
        own: left alone, ResNet-50's step at batch 256 held 0.59 GiB more
        than with float32 activations.  Recomputing the three blocks behind
        the convolution still held 0.20 GiB more; with the convolution
        inside, 0.25 GiB LESS, for 1% of the step (PERF.md, PR 29)."""
        first = len(self.features)
        if stem_s2d:
            self.features.add(_StemConvS2D(channels, self._layout))
        else:
            self.features.add(nn.Conv2D(channels, 7, 2, 3, use_bias=False,
                                        layout=self._layout))
        self.features.add(_bn(self._layout))
        self.features.add(nn.Activation("relu"))
        self.features.add(nn.MaxPool2D(3, 2, 1, layout=self._layout))
        self.features.recompute(first, first + 4)

    def _to_compute_layout(self, x):
        if self._input_layout == self._layout:
            return x
        if self._layout == "NHWC":
            return x.transpose(0, 2, 3, 1)
        return x.transpose(0, 3, 1, 2)


class ResNetV1(_ResNetBase):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW", input_layout=None, stem_s2d=False):
        super().__init__(layout, input_layout)
        assert len(layers) == len(channels) - 1
        self.features = nn.HybridSequential()
        if thumbnail:
            self.features.add(_conv3x3(channels[0], 1, 0, layout))
        else:
            self._add_stem(channels[0], stem_s2d)
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            self.features.add(self._make_layer(
                block, num_layer, channels[i + 1], stride,
                in_channels=channels[i]))
        self.features.add(nn.GlobalAvgPool2D(layout=layout))
        self.output = nn.Dense(classes, in_units=channels[-1])

    def _make_layer(self, block, layers, channels, stride, in_channels=0):
        layer = nn.HybridSequential()
        layer.add(block(channels, stride, channels != in_channels,
                        in_channels=in_channels, layout=self._layout))
        for _ in range(layers - 1):
            layer.add(block(channels, 1, False, in_channels=channels,
                            layout=self._layout))
        return layer

    def forward(self, x):
        x = self.features(self._to_compute_layout(x))
        return self.output(x.flatten())


class ResNetV2(_ResNetBase):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW", input_layout=None, stem_s2d=False):
        super().__init__(layout, input_layout)
        assert len(layers) == len(channels) - 1
        self.features = nn.HybridSequential()
        self.features.add(_bn(layout, scale=False, center=False))
        if thumbnail:
            self.features.add(_conv3x3(channels[0], 1, 0, layout))
        else:
            self._add_stem(channels[0], stem_s2d)
        in_channels = channels[0]
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            self.features.add(self._make_layer(
                block, num_layer, channels[i + 1], stride,
                in_channels=in_channels))
            in_channels = channels[i + 1]
        self.features.add(_bn(layout))
        self.features.add(nn.Activation("relu"))
        self.features.add(nn.GlobalAvgPool2D(layout=layout))
        self.features.add(nn.Flatten())
        self.output = nn.Dense(classes, in_units=in_channels)

    def _make_layer(self, block, layers, channels, stride, in_channels=0):
        layer = nn.HybridSequential()
        layer.add(block(channels, stride, channels != in_channels,
                        in_channels=in_channels, layout=self._layout))
        for _ in range(layers - 1):
            layer.add(block(channels, 1, False, in_channels=channels,
                            layout=self._layout))
        return layer

    def forward(self, x):
        x = self.features(self._to_compute_layout(x))
        return self.output(x)


resnet_spec = {
    18: ("basic_block", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
    34: ("basic_block", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
    50: ("bottle_neck", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
    101: ("bottle_neck", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
    152: ("bottle_neck", [3, 8, 36, 3], [64, 256, 512, 1024, 2048]),
}
resnet_net_versions = [ResNetV1, ResNetV2]
resnet_block_versions = [
    {"basic_block": BasicBlockV1, "bottle_neck": BottleneckV1},
    {"basic_block": BasicBlockV2, "bottle_neck": BottleneckV2},
]


def get_resnet(version, num_layers, pretrained=False, ctx=None, root=None,
               **kwargs):
    assert num_layers in resnet_spec, (
        f"Invalid number of layers: {num_layers}. "
        f"Options are {str(resnet_spec.keys())}"
    )
    block_type, layers, channels = resnet_spec[num_layers]
    assert 1 <= version <= 2, f"Invalid resnet version: {version}."
    resnet_class = resnet_net_versions[version - 1]
    block_class = resnet_block_versions[version - 1][block_type]
    net = resnet_class(block_class, layers, channels, **kwargs)
    if pretrained:
        from ..model_store import load_pretrained

        load_pretrained(net, f"resnet{num_layers}_v{version}", ctx=ctx,
                        root=root)
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
