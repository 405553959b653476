"""Convolution and pooling layers (reference
``python/mxnet/gluon/nn/conv_layers.py``)."""
from __future__ import annotations

from typing import Optional

from ...ndarray.ndarray import invoke
from ..block import HybridBlock
from ..parameter import Parameter
from .activations import Activation

__all__ = [
    "Conv1D", "Conv2D", "Conv3D",
    "Conv1DTranspose", "Conv2DTranspose", "Conv3DTranspose",
    "MaxPool1D", "MaxPool2D", "MaxPool3D",
    "AvgPool1D", "AvgPool2D", "AvgPool3D",
    "GlobalMaxPool1D", "GlobalMaxPool2D", "GlobalMaxPool3D",
    "GlobalAvgPool1D", "GlobalAvgPool2D", "GlobalAvgPool3D",
    "ReflectionPad2D",
    "PixelShuffle1D", "PixelShuffle2D", "PixelShuffle3D",
    "DeformableConvolution", "ModulatedDeformableConvolution",
]


def _tuple(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


class _Conv(HybridBlock):
    """Shared conv implementation (reference conv_layers.py:42 _Conv)."""

    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels=0, activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 op_name="Convolution", adj=None, dtype="float32"):
        super().__init__()
        from ... import initializer as init

        self._channels = channels
        self._in_channels = in_channels
        nsp = len(kernel_size)
        self._kwargs = {
            "kernel": kernel_size,
            "stride": strides,
            "dilate": dilation,
            "pad": padding,
            "num_filter": channels,
            "num_group": groups,
            "no_bias": not use_bias,
            "layout": layout,
        }
        if adj is not None:
            self._kwargs["adj"] = adj
        self._op_name = op_name
        self._layout = layout
        self._nsp = nsp
        self._groups = groups
        self._use_bias = use_bias

        wshape = self._weight_shape(in_channels)
        self.weight = Parameter("weight", shape=wshape, dtype=dtype,
                                init=weight_initializer,
                                allow_deferred_init=True)
        if use_bias:
            self.bias = Parameter("bias", shape=(channels,), dtype=dtype,
                                  init=init.create(bias_initializer),
                                  allow_deferred_init=True)
        else:
            self.bias = None
        self.act = Activation(activation) if activation else None
        if self.act is not None:
            self.register_child(self.act, "act")

    def _weight_shape(self, in_channels):
        kernel = self._kwargs["kernel"]
        if self._op_name == "Convolution":
            if self._layout.index("C") == 1:
                return (self._channels, in_channels // self._groups) + tuple(kernel)
            return (self._channels,) + tuple(kernel) + (in_channels // self._groups,)
        # Deconvolution: weight is (in_channels, channels//groups, *kernel)
        if self._layout.index("C") == 1:
            return (in_channels, self._channels // self._groups) + tuple(kernel)
        return (in_channels,) + tuple(kernel) + (self._channels // self._groups,)

    def infer_shape(self, x):
        c_axis = self._layout.index("C")
        in_c = int(x.shape[c_axis])
        self.weight.shape = self._weight_shape(in_c)
        self._in_channels = in_c

    def forward(self, x):
        args = [x, self.weight.data(x.ctx)]
        if self._use_bias:
            args.append(self.bias.data(x.ctx))
        out = invoke(self._op_name, args, dict(self._kwargs))
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        return (f"{type(self).__name__}({self._in_channels} -> "
                f"{self._channels}, kernel_size={self._kwargs['kernel']}, "
                f"stride={self._kwargs['stride']})")


class Conv1D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0, dilation=1,
                 groups=1, layout="NCW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, _tuple(kernel_size, 1), _tuple(strides, 1),
                         _tuple(padding, 1), _tuple(dilation, 1), groups,
                         layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv2D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 dilation=(1, 1), groups=1, layout="NCHW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, _tuple(kernel_size, 2), _tuple(strides, 2),
                         _tuple(padding, 2), _tuple(dilation, 2), groups,
                         layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv3D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), dilation=(1, 1, 1), groups=1,
                 layout="NCDHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, _tuple(kernel_size, 3), _tuple(strides, 3),
                         _tuple(padding, 3), _tuple(dilation, 3), groups,
                         layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer, **kwargs)


class Conv1DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCW",
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, _tuple(kernel_size, 1), _tuple(strides, 1),
                         _tuple(padding, 1), _tuple(dilation, 1), groups,
                         layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution",
                         adj=_tuple(output_padding, 1), **kwargs)


class Conv2DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 output_padding=(0, 0), dilation=(1, 1), groups=1,
                 layout="NCHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        super().__init__(channels, _tuple(kernel_size, 2), _tuple(strides, 2),
                         _tuple(padding, 2), _tuple(dilation, 2), groups,
                         layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution",
                         adj=_tuple(output_padding, 2), **kwargs)


class Conv3DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), output_padding=(0, 0, 0),
                 dilation=(1, 1, 1), groups=1, layout="NCDHW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        super().__init__(channels, _tuple(kernel_size, 3), _tuple(strides, 3),
                         _tuple(padding, 3), _tuple(dilation, 3), groups,
                         layout, in_channels, activation, use_bias,
                         weight_initializer, bias_initializer,
                         op_name="Deconvolution",
                         adj=_tuple(output_padding, 3), **kwargs)


class _Pooling(HybridBlock):
    def __init__(self, pool_size, strides, padding, ceil_mode, global_pool,
                 pool_type, layout, count_include_pad=None):
        super().__init__()
        if strides is None:
            strides = pool_size
        self._kwargs = {
            "kernel": pool_size,
            "stride": strides,
            "pad": padding,
            "global_pool": global_pool,
            "pool_type": pool_type,
            "pooling_convention": "full" if ceil_mode else "valid",
            "layout": layout,
        }
        if count_include_pad is not None:
            self._kwargs["count_include_pad"] = count_include_pad

    def forward(self, x):
        return invoke("Pooling", [x], dict(self._kwargs))

    def __repr__(self):
        return (f"{type(self).__name__}(size={self._kwargs['kernel']}, "
                f"stride={self._kwargs['stride']}, "
                f"padding={self._kwargs['pad']})")


class MaxPool1D(_Pooling):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False):
        super().__init__(_tuple(pool_size, 1),
                         _tuple(strides, 1) if strides is not None else None,
                         _tuple(padding, 1), ceil_mode, False, "max", layout)


class MaxPool2D(_Pooling):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False):
        super().__init__(_tuple(pool_size, 2),
                         _tuple(strides, 2) if strides is not None else None,
                         _tuple(padding, 2), ceil_mode, False, "max", layout)


class MaxPool3D(_Pooling):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout="NCDHW", ceil_mode=False):
        super().__init__(_tuple(pool_size, 3),
                         _tuple(strides, 3) if strides is not None else None,
                         _tuple(padding, 3), ceil_mode, False, "max", layout)


class AvgPool1D(_Pooling):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False, count_include_pad=True):
        super().__init__(_tuple(pool_size, 1),
                         _tuple(strides, 1) if strides is not None else None,
                         _tuple(padding, 1), ceil_mode, False, "avg", layout,
                         count_include_pad)


class AvgPool2D(_Pooling):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False, count_include_pad=True):
        super().__init__(_tuple(pool_size, 2),
                         _tuple(strides, 2) if strides is not None else None,
                         _tuple(padding, 2), ceil_mode, False, "avg", layout,
                         count_include_pad)


class AvgPool3D(_Pooling):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout="NCDHW", ceil_mode=False, count_include_pad=True):
        super().__init__(_tuple(pool_size, 3),
                         _tuple(strides, 3) if strides is not None else None,
                         _tuple(padding, 3), ceil_mode, False, "avg", layout,
                         count_include_pad)


class GlobalMaxPool1D(_Pooling):
    def __init__(self, layout="NCW"):
        super().__init__((1,), None, (0,), False, True, "max", layout)


class GlobalMaxPool2D(_Pooling):
    def __init__(self, layout="NCHW"):
        super().__init__((1, 1), None, (0, 0), False, True, "max", layout)


class GlobalMaxPool3D(_Pooling):
    def __init__(self, layout="NCDHW"):
        super().__init__((1, 1, 1), None, (0, 0, 0), False, True, "max", layout)


class GlobalAvgPool1D(_Pooling):
    def __init__(self, layout="NCW"):
        super().__init__((1,), None, (0,), False, True, "avg", layout)


class GlobalAvgPool2D(_Pooling):
    def __init__(self, layout="NCHW"):
        super().__init__((1, 1), None, (0, 0), False, True, "avg", layout)


class GlobalAvgPool3D(_Pooling):
    def __init__(self, layout="NCDHW"):
        super().__init__((1, 1, 1), None, (0, 0, 0), False, True, "avg", layout)


class ReflectionPad2D(HybridBlock):
    """Reference conv_layers.py ReflectionPad2D → pad op mode='reflect'."""

    def __init__(self, padding=0):
        super().__init__()
        if isinstance(padding, int):
            padding = (0, 0, 0, 0, padding, padding, padding, padding)
        self._padding = tuple(padding)

    def forward(self, x):
        return invoke("pad", [x],
                      {"mode": "reflect", "pad_width": self._padding})


class _PixelShuffle(HybridBlock):
    """Sub-pixel upsampling (reference conv_layers.py PixelShuffle1-3D):
    regroup channel blocks into spatial blocks — pure reshape/transpose,
    which XLA folds into neighboring ops for free."""

    def __init__(self, factor, ndim):
        super().__init__()
        if isinstance(factor, int):
            self._factors = (factor,) * ndim
        else:
            self._factors = tuple(int(f) for f in factor)
            if len(self._factors) != ndim:
                raise ValueError(
                    f"factor must be an int or length-{ndim} tuple")

    def forward(self, x):
        fs = self._factors
        n = len(fs)
        shape = x.shape               # (N, C*prod(f), *spatial)
        fprod = 1
        for f in fs:
            fprod *= f
        C = shape[1] // fprod
        spatial = shape[2:]
        # reference channel grouping: C outermost, then f1..fn
        # (N, C, f1..fn, s1..sn) -> interleave (si, fi) pairs
        x = x.reshape((shape[0], C) + fs + spatial)
        perm = [0, 1]
        for i in range(n):
            perm += [2 + n + i, 2 + i]
        x = x.transpose(perm)
        out_spatial = tuple(s * f for s, f in zip(spatial, fs))
        return x.reshape((shape[0], C) + out_spatial)

    def __repr__(self):
        return f"{type(self).__name__}({self._factors})"


class PixelShuffle1D(_PixelShuffle):
    """(N, f*C, W) -> (N, C, W*f)."""

    def __init__(self, factor):
        super().__init__(factor, 1)


class PixelShuffle2D(_PixelShuffle):
    """(N, f1*f2*C, H, W) -> (N, C, H*f1, W*f2)."""

    def __init__(self, factor):
        super().__init__(factor, 2)


class PixelShuffle3D(_PixelShuffle):
    """(N, f1*f2*f3*C, D, H, W) -> (N, C, D*f1, H*f2, W*f3)."""

    def __init__(self, factor):
        super().__init__(factor, 3)


class DeformableConvolution(HybridBlock):
    """Deformable convolution v1 layer (reference conv_layers.py
    DeformableConvolution): an internal regular conv predicts per-position
    sampling offsets, the main kernel samples there.  Offset conv weights
    initialize to zero so training starts as a plain convolution."""

    _op_name = "DeformableConvolution"
    _mask_factor = 0          # v2 adds kh*kw*ndg mask channels

    def __init__(self, channels, kernel_size=(1, 1), strides=(1, 1),
                 padding=(0, 0), dilation=(1, 1), groups=1,
                 num_deformable_group=1, layout="NCHW", use_bias=True,
                 in_channels=0, activation=None, weight_initializer=None,
                 bias_initializer="zeros",
                 offset_weight_initializer="zeros",
                 offset_bias_initializer="zeros", offset_use_bias=True,
                 **kwargs):
        super().__init__()
        from ... import initializer as init

        if layout != "NCHW":
            raise ValueError("deformable convolution supports NCHW layout")
        kernel_size = _tuple(kernel_size, 2)
        self._channels = channels
        self._in_channels = in_channels
        self._ndg = num_deformable_group
        kh, kw = kernel_size
        self._kwargs = {
            "kernel": kernel_size, "stride": _tuple(strides, 2),
            "dilate": _tuple(dilation, 2), "pad": _tuple(padding, 2),
            "num_filter": channels, "num_group": groups,
            "num_deformable_group": num_deformable_group,
            "no_bias": not use_bias, "layout": layout,
        }
        off_channels = (2 + (1 if self._mask_factor else 0)) * \
            kh * kw * num_deformable_group

        def _init(v):
            return init.create(v) if isinstance(v, str) else v

        self._offset = Conv2D(off_channels, kernel_size,
                              strides=_tuple(strides, 2),
                              padding=_tuple(padding, 2),
                              dilation=_tuple(dilation, 2),
                              use_bias=offset_use_bias,
                              in_channels=in_channels,
                              weight_initializer=_init(
                                  offset_weight_initializer),
                              bias_initializer=offset_bias_initializer)
        self.register_child(self._offset, "offset_conv")
        self._groups = groups
        self.weight = Parameter(
            "weight",
            shape=(channels, in_channels // groups if in_channels else 0)
            + kernel_size,
            init=_init(weight_initializer), allow_deferred_init=True)
        self.bias = Parameter("bias", shape=(channels,),
                              init=init.create(bias_initializer),
                              allow_deferred_init=True) if use_bias else None
        self.act = Activation(activation) if activation else None
        if self.act is not None:
            self.register_child(self.act, "act")

    def infer_shape(self, x):
        in_c = int(x.shape[1])
        self.weight.shape = (self._channels, in_c // self._groups) + \
            tuple(self._kwargs["kernel"])
        self._in_channels = in_c

    def _split_offset(self, raw):
        return raw, None

    def forward(self, x):
        raw = self._offset(x)
        offset, mask = self._split_offset(raw)
        args = [x, offset]
        if mask is not None:
            args.append(mask)
        args.append(self.weight.data(x.ctx))
        if self.bias is not None:
            args.append(self.bias.data(x.ctx))
        out = invoke(self._op_name, args, dict(self._kwargs))
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        return (f"{type(self).__name__}({self._in_channels} -> "
                f"{self._channels}, kernel_size={self._kwargs['kernel']})")


class ModulatedDeformableConvolution(DeformableConvolution):
    """Deformable convolution v2 (reference conv_layers.py
    ModulatedDeformableConvolution): the offset conv additionally predicts
    a sigmoid modulation mask per sampling point."""

    _op_name = "ModulatedDeformableConvolution"
    _mask_factor = 1

    def _split_offset(self, raw):
        kh, kw = self._kwargs["kernel"]
        n_off = 2 * kh * kw * self._ndg
        offset = raw[:, :n_off]
        mask = invoke("sigmoid", [raw[:, n_off:]], {})
        return offset, mask
