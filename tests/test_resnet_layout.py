"""ResNet TPU-layout rewrites are EXACT model-function rewrites.

The bench path runs ResNet channel-minor (NHWC) with the space-to-depth
stem (MLPerf trick; see model_zoo/vision/resnet.py _StemConvS2D docstring
for the index algebra).  These tests pin the claim that both options
compute the reference NCHW model bit-for-bit-up-to-float-noise, so the
benchmark numbers are comparable with the reference's
(benchmark_score.py methodology, reference perf.md).
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon.model_zoo import vision


def _transplant(src_net, dst_net, x, transpose_convs):
    """Copy src params into dst, moving conv weights OIHW->OHWI if asked."""
    dst_net.initialize(mx.init.Xavier())
    dst_net(x)  # materialize deferred shapes
    dst = dst_net.collect_params()
    for n, p in src_net.collect_params().items():
        a = onp.asarray(p._data[0]._data)
        if transpose_convs and a.ndim == 4:
            a = a.transpose(0, 2, 3, 1)
        dst[n]._data[0]._set_data(mx.nd.array(a)._data)


def _build_ref(version, num_layers, x):
    net = vision.get_resnet(version, num_layers)
    net.initialize(mx.init.Xavier())
    return net, net(x).asnumpy()


@pytest.mark.parametrize("version", [1, 2])
def test_nhwc_matches_nchw(version):
    x = mx.nd.array(onp.random.RandomState(0)
                    .randn(2, 3, 64, 64).astype(onp.float32))
    ref_net, ref_out = _build_ref(version, 18, x)
    net = vision.get_resnet(version, 18, layout="NHWC")
    _transplant(ref_net, net, x, transpose_convs=True)
    out = net(x).asnumpy()
    onp.testing.assert_allclose(out, ref_out, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_s2d_stem_matches_plain_stem(layout):
    x = mx.nd.array(onp.random.RandomState(1)
                    .randn(2, 3, 64, 64).astype(onp.float32))
    ref_net, ref_out = _build_ref(1, 18, x)
    net = vision.get_resnet(1, 18, layout=layout, stem_s2d=True)
    _transplant(ref_net, net, x, transpose_convs=(layout == "NHWC"))
    out = net(x).asnumpy()
    onp.testing.assert_allclose(out, ref_out, rtol=2e-5, atol=2e-5)
    # same parameter inventory: the s2d stem holds the canonical 7x7 weight
    ref_shapes = {n: p.shape for n, p in ref_net.collect_params().items()}
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert set(shapes) == set(ref_shapes)
    if layout == "NCHW":
        assert shapes == ref_shapes


def test_s2d_stem_gradients_match():
    """Gradients w.r.t. the canonical 7x7 stem weight flow through the
    in-graph regroup and equal the plain stem's.

    Compared on the ISOLATED stem block: through a deep BN net the two
    (mathematically identical) forms diverge chaotically in fp32 — BN's
    rsqrt amplifies summation-order noise layer over layer — so a
    whole-net fp32 grad comparison is not a meaningful oracle (verified:
    the same comparison in float64 agrees to 1e-11).
    """
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.vision.resnet import _StemConvS2D

    x = mx.nd.array(onp.random.RandomState(2)
                    .randn(2, 3, 32, 32).astype(onp.float32))
    plain = nn.Conv2D(16, 7, 2, 3, use_bias=False)
    plain.initialize(mx.init.Xavier())
    plain(x)
    s2d = _StemConvS2D(16)
    s2d.initialize(mx.init.Xavier())
    s2d(x)
    w = onp.asarray(plain.weight._data[0]._data)
    s2d.weight._data[0]._set_data(mx.nd.array(w)._data)

    grads, outs = [], []
    for block in (plain, s2d):
        block.weight.zero_grad()
        with autograd.record():
            out = block(x)
            loss = (out * out).mean()
        loss.backward()
        outs.append(out.asnumpy())
        grads.append(onp.asarray(block.weight.grad()._data))
    onp.testing.assert_allclose(outs[1], outs[0], rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(grads[1], grads[0], rtol=1e-4, atol=1e-5)


def test_s2d_stem_odd_size_falls_back():
    """Odd H/W can't space-to-depth 2x2; the stem runs the canonical conv
    instead (the plain stem accepts odd sizes, so must this one)."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.vision.resnet import _StemConvS2D

    x = mx.nd.array(onp.random.RandomState(5)
                    .randn(1, 3, 33, 33).astype(onp.float32))
    plain = nn.Conv2D(8, 7, 2, 3, use_bias=False)
    plain.initialize(mx.init.Xavier())
    plain(x)
    s2d = _StemConvS2D(8)
    s2d.initialize(mx.init.Xavier())
    s2d(x)
    w = onp.asarray(plain.weight._data[0]._data)
    s2d.weight._data[0]._set_data(mx.nd.array(w)._data)
    onp.testing.assert_allclose(s2d(x).asnumpy(), plain(x).asnumpy(),
                                rtol=1e-5, atol=1e-5)


def test_nhwc_input_layout_transpose():
    """input_layout='NHWC' feeds channel-last batches with no entry
    transpose; result equals the NCHW-fed model."""
    rs = onp.random.RandomState(3)
    x_nchw = rs.randn(2, 3, 64, 64).astype(onp.float32)
    ref_net, ref_out = _build_ref(1, 18, mx.nd.array(x_nchw))
    net = vision.get_resnet(1, 18, layout="NHWC", input_layout="NHWC")
    x_nhwc = mx.nd.array(x_nchw.transpose(0, 2, 3, 1))
    _transplant(ref_net, net, x_nhwc, transpose_convs=True)
    out = net(x_nhwc).asnumpy()
    onp.testing.assert_allclose(out, ref_out, rtol=2e-5, atol=2e-5)


def test_batchnorm_single_pass_stats_numerics():
    """The fused E[x]/E[x^2] batch stats equal two-pass mean/var, fp32
    accumulation, for bf16 activations too."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.nn import batch_norm

    rs = onp.random.RandomState(4)
    x = (rs.randn(8, 5, 6, 3) * 3 + 1.5).astype(onp.float32)
    gamma = rs.rand(3).astype(onp.float32) + 0.5
    beta = rs.randn(3).astype(onp.float32)
    rm = onp.zeros(3, onp.float32)
    rv = onp.ones(3, onp.float32)
    out, mean, var = batch_norm(
        [jnp.asarray(x), jnp.asarray(gamma), jnp.asarray(beta),
         jnp.asarray(rm), jnp.asarray(rv)],
        eps=1e-5, fix_gamma=False, axis=3, training=True)
    exp_mean = x.reshape(-1, 3).mean(0)
    exp_var = x.reshape(-1, 3).var(0)
    onp.testing.assert_allclose(onp.asarray(mean), exp_mean, rtol=1e-5)
    onp.testing.assert_allclose(onp.asarray(var), exp_var, rtol=1e-4,
                                atol=1e-5)
    exp_out = (x - exp_mean) / onp.sqrt(exp_var + 1e-5) * gamma + beta
    onp.testing.assert_allclose(onp.asarray(out), exp_out, rtol=1e-4,
                                atol=1e-4)
    # bf16 activations: stats still accumulate fp32
    xb = jnp.asarray(x, jnp.bfloat16)
    outb, meanb, varb = batch_norm(
        [xb, jnp.asarray(gamma), jnp.asarray(beta), jnp.asarray(rm),
         jnp.asarray(rv)],
        eps=1e-5, fix_gamma=False, axis=3, training=True)
    assert outb.dtype == jnp.bfloat16
    onp.testing.assert_allclose(onp.asarray(meanb, dtype=onp.float32),
                                exp_mean, rtol=2e-2, atol=2e-2)
    onp.testing.assert_allclose(onp.asarray(varb, dtype=onp.float32),
                                exp_var, rtol=5e-2, atol=5e-2)
