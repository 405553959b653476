"""INT8 quantization flow tests (VERDICT round-1 item 10).

Reference analog: tests/python/quantization/test_quantization.py —
quantize/dequantize/requantize op semantics, calibration, and the end-to-
end quantize_model accuracy check (quantized net within 1% of fp32 on a
synthetic classification check).
"""
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config
from mxnet_tpu.contrib import quantization as q
from mxnet_tpu.gluon import nn


def test_quantize_dequantize_roundtrip():
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(64).astype(onp.float32))
    qd, lo, hi = q.quantize(x, min_range=-3.0, max_range=3.0)
    assert qd.dtype == jnp.int8
    back = q.dequantize(qd, lo, hi)
    # max error is half a quantization step
    step = 3.0 / 127.0
    assert float(jnp.max(jnp.abs(back - jnp.clip(x, -3, 3)))) <= step


def test_requantize_s32_to_s8():
    acc = jnp.asarray([1000, -500, 20000], jnp.int32)
    qd, lo, hi = q.requantize(acc, jnp.float32(-2.0), jnp.float32(2.0),
                              min_calib_range=-3.0, max_calib_range=3.0)
    assert qd.dtype == jnp.int8
    in_scale = 2.0 / (127.0 * 127.0)
    expect = onp.clip(onp.round(onp.asarray(acc) * in_scale * 127.0 / 3.0),
                      -127, 127)
    assert onp.allclose(onp.asarray(qd), expect)


def test_quantized_fc_matches_fp32():
    rng = onp.random.RandomState(1)
    x = rng.randn(4, 16).astype(onp.float32)
    w = (rng.randn(8, 16) * 0.2).astype(onp.float32)
    b = rng.randn(8).astype(onp.float32)
    ref = x @ w.T + b
    lo, hi = float(x.min()), float(x.max())
    d_scale = max(abs(lo), abs(hi)) / 127.0
    w_scale = abs(w).max() / 127.0
    qx = onp.clip(onp.round(x / d_scale), -127, 127).astype(onp.int8)
    qw = onp.clip(onp.round(w / w_scale), -127, 127).astype(onp.int8)
    out = q.quantized_fully_connected(
        [jnp.asarray(qx), jnp.asarray(qw), jnp.asarray(b)],
        num_hidden=8, data_scale=d_scale, w_scale=w_scale)
    rel = onp.abs(onp.asarray(out) - ref).max() / (abs(ref).max() + 1e-9)
    assert rel < 0.03, rel


def test_quantized_conv_matches_fp32():
    rng = onp.random.RandomState(2)
    x = rng.randn(2, 3, 8, 8).astype(onp.float32)
    w = (rng.randn(4, 3, 3, 3) * 0.2).astype(onp.float32)
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    d_scale = abs(x).max() / 127.0
    w_scale = abs(w).max() / 127.0
    qx = onp.clip(onp.round(x / d_scale), -127, 127).astype(onp.int8)
    qw = onp.clip(onp.round(w / w_scale), -127, 127).astype(onp.int8)
    out = q.quantized_conv([jnp.asarray(qx), jnp.asarray(qw)],
                           kernel=(3, 3), pad=(1, 1), num_filter=4,
                           no_bias=True, data_scale=d_scale,
                           w_scale=w_scale)
    rel = onp.abs(onp.asarray(out) - onp.asarray(ref)).max() / (
        float(jnp.abs(ref).max()) + 1e-9)
    assert rel < 0.03, rel


def test_collect_calib_ranges_modes():
    from mxnet_tpu import symbol as S

    x = S.var("data")
    y = S.relu(x)
    rng = onp.random.RandomState(3)
    feeds = [{"data": rng.randn(100).astype(onp.float32)} for _ in range(3)]
    naive = q.collect_calib_ranges(y, feeds, mode="naive")
    pct = q.collect_calib_ranges(y, feeds, mode="percentile",
                                 percentile=90.0)
    (k,) = [k for k in naive if "relu" in k]
    assert naive[k][0] == 0.0                 # relu output min
    assert pct[k][1] <= naive[k][1]           # clipped high tail


def test_quantize_net_accuracy_within_1pct():
    """End-to-end: conv net classifier, int8 predictions track fp32 —
    top-1 agreement >= 99% on a synthetic check (the reference
    quantize_model acceptance bar)."""
    rng = onp.random.RandomState(4)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, in_channels=3, activation="relu"),
            nn.Conv2D(16, 3, padding=1, in_channels=8, activation="relu"),
            nn.GlobalAvgPool2D(),
            nn.Dense(10, in_units=16))
    net.initialize(mx.init.Xavier())

    calib = [mx.nd.array(rng.rand(8, 3, 16, 16).astype(onp.float32))
             for _ in range(4)]
    qnet = q.quantize_net(net, calib)

    agree = total = 0
    max_rel = 0.0
    for _ in range(4):
        x = mx.nd.array(rng.rand(32, 3, 16, 16).astype(onp.float32))
        ref = net(x).asnumpy()
        got = onp.asarray(qnet(x))
        agree += (ref.argmax(1) == got.argmax(1)).sum()
        total += ref.shape[0]
        max_rel = max(max_rel,
                      float(onp.abs(got - ref).max() / (abs(ref).max()
                                                        + 1e-9)))
    assert agree / total >= 0.99, (agree, total, max_rel)

    # the quantized graph really runs int8 kernels
    qops = {n.op for n in qnet.sym._topo() if n.op}
    assert "quantized_conv" in qops and "quantized_fully_connected" in qops
    assert any(v.dtype == jnp.int8 for v in qnet.params.values())


def test_conv_bn_relu_folds_and_requantize_fuses():
    """The int8 graph pass collapses conv+BN+relu into ONE quantized
    kernel with folded weights and a relu epilogue, and adjacent quantized
    kernels exchange int8 directly (requantize fused into the producer's
    epilogue — reference quantize_graph_pass.cc).  Accuracy stays within
    int8 tolerance of fp32."""
    rng = onp.random.RandomState(7)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, in_channels=3, use_bias=False),
            nn.BatchNorm(in_channels=8),
            nn.Activation("relu"),
            nn.Conv2D(16, 3, padding=1, in_channels=8, use_bias=False),
            nn.BatchNorm(in_channels=16),
            nn.Activation("relu"),
            nn.GlobalAvgPool2D(),
            nn.Dense(10, in_units=16))
    net.initialize(mx.init.Xavier())
    # settle BN moving stats with a few forward passes in autograd-less
    # training=False mode the fold expects
    calib = [mx.nd.array(rng.rand(8, 3, 12, 12).astype(onp.float32) * 2)
             for _ in range(4)]
    qnet = q.quantize_net(net, calib)

    ops = [n.op for n in qnet.sym._topo() if n.op]
    # BatchNorm and standalone Activation are GONE: folded into the convs
    assert "BatchNorm" not in ops, ops
    assert "Activation" not in ops and "relu" not in ops, ops
    assert ops.count("quantized_conv") == 2
    convs = [n for n in qnet.sym._topo() if n.op == "quantized_conv"]
    assert all(n.attrs.get("fused_relu") for n in convs)
    # first conv emits int8 directly for the second (requantize fused):
    # the only quantize nodes left are the graph input and the one after
    # the fp32 pooling, NOT one per quantized kernel
    assert ops.count("quantize") == 2, ops
    first = [n for n in convs if any(
        c is n for c2 in convs for (c, _i) in c2.inputs)]
    assert first and first[0].attrs.get("out_min") is not None

    x = mx.nd.array(rng.rand(16, 3, 12, 12).astype(onp.float32) * 2)
    ref = net(x).asnumpy()
    got = onp.asarray(qnet(x))
    rel = float(onp.abs(got - ref).max() / (abs(ref).max() + 1e-9))
    assert rel < 0.06, rel
    assert (ref.argmax(1) == got.argmax(1)).mean() >= 0.9


def test_quantize_net_nhwc_s2d_fast_path():
    """The channel-minor fast path quantizes natively: NHWC convs
    (incl. the space-to-depth stem) become quantized_conv with layout NHWC
    and the axis=3 BatchNorms still fold (reference quantized_conv.cc is
    NCHW-only; this build is layout-general so no relayout is needed)."""
    from mxnet_tpu.gluon.model_zoo import vision

    rng = onp.random.RandomState(11)
    net = vision.get_model("resnet18_v1", classes=10, layout="NHWC",
                           input_layout="NHWC", stem_s2d=True)
    net.initialize(mx.init.Xavier())
    calib = [mx.nd.array(rng.rand(4, 32, 32, 3).astype(onp.float32))
             for _ in range(2)]
    qnet = q.quantize_net(net, calib)
    convs = [n for n in qnet.sym._topo() if n.op == "quantized_conv"]
    assert convs
    assert all(n.attrs.get("layout") == "NHWC" for n in convs), \
        sorted({n.attrs.get("layout") for n in convs})
    ops = [n.op for n in qnet.sym._topo() if n.op]
    assert "BatchNorm" not in ops, ops       # axis=3 folds too
    x = mx.nd.array(rng.rand(8, 32, 32, 3).astype(onp.float32))
    ref = net(x).asnumpy()
    got = onp.asarray(qnet(x))
    rel = float(onp.abs(got - ref).max() / (abs(ref).max() + 1e-9))
    assert rel < 0.1, rel


def test_quantize_symbol_excluded_layers_stay_fp32():
    """Symbol-level API (the reference quantize_model workflow): users
    pick excluded node names off the traced symbol they pass in."""
    rng = onp.random.RandomState(5)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"),
            nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(rng.rand(4, 4).astype(onp.float32))
    net(x)
    sym = net._trace_symbol()
    params = {k: v.data() for k, v in net.collect_params().items()}
    fc_names = [n.name for n in sym._topo() if n.op == "FullyConnected"]
    assert len(fc_names) == 2
    feeds = [{"data": x._data,
              **{k: v._data for k, v in params.items()}}]
    ranges = q.collect_calib_ranges(sym, feeds)
    ranges["data"] = (0.0, 1.0)
    qsym, qparams = q.quantize_symbol(sym, params, ranges,
                                      excluded_names=(fc_names[0],))
    ops = [n.op for n in qsym._topo() if n.op]
    assert ops.count("quantized_fully_connected") == 1
    assert ops.count("FullyConnected") == 1
    # and it still evaluates close to fp32
    ref = net(x).asnumpy()
    got = onp.asarray(q.QuantizedNet(qsym, qparams)(x))
    assert onp.abs(got - ref).max() / (abs(ref).max() + 1e-9) < 0.05


def test_quantized_conv_strided_shape():
    rng = onp.random.RandomState(3)
    qd = onp.asarray(rng.randint(-10, 10, (1, 4, 4, 8)), onp.int8)
    qw3 = onp.asarray(rng.randint(-10, 10, (8, 3, 3, 8)), onp.int8)
    out = q.quantized_conv([jnp.asarray(qd), jnp.asarray(qw3)],
                           kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                           num_filter=8, layout="NHWC", no_bias=True,
                           data_scale=0.1, w_scale=0.1)
    assert onp.asarray(out).shape == (1, 2, 2, 8)


def test_quantized_conv_pad_channels_bit_exact(monkeypatch):
    """The MXU alignment pass on the s8 path (quantum 32): a traced
    misaligned-channel quantized conv pads with zero taps and slices
    back — integer math, so EXACT — and the eager call never pads."""
    from mxnet_tpu.ops import nn as ops_nn

    rng = onp.random.RandomState(5)
    qd = jnp.asarray(rng.randint(-127, 128, (2, 6, 6, 24)), jnp.int8)
    qw = jnp.asarray(rng.randint(-127, 128, (48, 1, 1, 24)), jnp.int8)

    def make_run():
        # fresh function object per mode: jax's trace cache keys on the
        # function identity, and the knob must really retrace
        def run(qd, qw):
            return q.quantized_conv([qd, qw], kernel=(1, 1),
                                    num_filter=48, layout="NHWC",
                                    no_bias=True, data_scale=0.02,
                                    w_scale=0.01)
        return run

    monkeypatch.setenv("MXNET_PAD_CHANNELS", "0")
    config.refresh("MXNET_PAD_CHANNELS")
    ref = onp.asarray(jax.jit(make_run())(qd, qw))
    monkeypatch.setenv("MXNET_PAD_CHANNELS", "2")
    config.refresh("MXNET_PAD_CHANNELS")
    c0 = ops_nn.pad_channels_count()
    padded = onp.asarray(jax.jit(make_run())(qd, qw))
    assert ops_nn.pad_channels_count() - c0 == 1
    onp.testing.assert_array_equal(ref, padded)
    c1 = ops_nn.pad_channels_count()
    make_run()(qd, qw)                            # eager: tracer gate
    assert ops_nn.pad_channels_count() == c1
    os.environ.pop("MXNET_PAD_CHANNELS", None)
    config.refresh("MXNET_PAD_CHANNELS")


def test_quantize_net_end_to_end_lax():
    """Whole quantize->convert->run flow on the (only) lax route:
    int8 predictions track the fp32 reference."""
    rng = onp.random.RandomState(4)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(32, 1, use_bias=False, in_channels=16, layout="NHWC",
                      activation="relu"),
            nn.Conv2D(64, 1, use_bias=False, in_channels=32, layout="NHWC"),
            nn.GlobalAvgPool2D(layout="NHWC"),
            nn.Dense(10, in_units=64))
    net.initialize(mx.init.Xavier())
    calib = [mx.nd.array(rng.rand(4, 8, 8, 16).astype(onp.float32))
             for _ in range(3)]
    x = mx.nd.array(rng.rand(8, 8, 8, 16).astype(onp.float32))
    qnet = q.quantize_net(net, calib)
    out = onp.asarray(qnet(x))
    ref = net(x).asnumpy()
    assert (ref.argmax(1) == out.argmax(1)).mean() >= 0.99
