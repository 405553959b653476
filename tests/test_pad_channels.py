"""The MXU channel-alignment padding pass (ops/nn.py
maybe_pad_conv_channels, MXNET_PAD_CHANNELS): bit-exact, trace-only,
retrace-free, and composing with AMP and the SPMD mesh.
MXNET_PAD_CHANNELS=2 forces it on the CPU backend.
"""
import os

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, config
from mxnet_tpu.gluon import nn


@pytest.fixture
def force_pad(monkeypatch):
    monkeypatch.setenv("MXNET_PAD_CHANNELS", "2")
    config.refresh("MXNET_PAD_CHANNELS")
    yield
    os.environ.pop("MXNET_PAD_CHANNELS", None)
    config.refresh("MXNET_PAD_CHANNELS")


def _rand(*shape):
    return onp.random.RandomState(hash(shape) % 2**31).randn(*shape) \
        .astype(onp.float32)


def _misaligned_net():
    net = nn.HybridSequential()
    # cin=3 and cout=10 both miss the 8-lane quantum
    net.add(nn.Conv2D(10, kernel_size=3, padding=1, use_bias=True,
                      layout="NHWC", in_channels=3))
    net.add(nn.BatchNorm(axis=3))
    net.add(nn.Activation("relu"))
    return net


def test_pad_channels_bit_exact_hybridized(force_pad):
    from mxnet_tpu.ops import nn as ops_nn

    x = mx.nd.array(_rand(2, 8, 8, 3))
    outs = {}
    for env in ("0", "2"):
        os.environ["MXNET_PAD_CHANNELS"] = env
        config.refresh("MXNET_PAD_CHANNELS")
        net = _misaligned_net()
        net.initialize(mx.init.Xavier())
        net(x)
        if env == "0":
            saved = {n: p._data[0]._data
                     for n, p in net.collect_params().items()}
        else:
            for n, p in net.collect_params().items():
                p._data[0]._set_data(saved[n])
        net.hybridize()
        c0 = ops_nn.pad_channels_count()
        with autograd.record():
            out = net(x)
            (out * out).sum().backward()
        outs[env] = (out.asnumpy(),
                     net[0].weight._data[0].grad.asnumpy(),
                     ops_nn.pad_channels_count() - c0)
    assert outs["0"][2] == 0 and outs["2"][2] >= 1
    # the slice is provably exact: forward AND weight grad bit-equal
    onp.testing.assert_array_equal(outs["0"][0], outs["2"][0])
    onp.testing.assert_array_equal(outs["0"][1], outs["2"][1])


def test_pad_channels_train_step_parity_and_zero_retraces(force_pad):
    from mxnet_tpu import cached_step, gluon
    from mxnet_tpu.ops import nn as ops_nn

    rng = onp.random.RandomState(11)
    data = mx.nd.array(rng.randn(4, 8, 8, 3).astype(onp.float32))
    label = mx.nd.array(rng.randn(4, 10).astype(onp.float32))
    losses = {}
    for env in ("0", "2"):
        os.environ["MXNET_PAD_CHANNELS"] = env
        config.refresh("MXNET_PAD_CHANNELS")
        net = nn.HybridSequential()
        net.add(nn.Conv2D(10, kernel_size=3, padding=1, use_bias=True,
                          layout="NHWC", in_channels=3))
        net.add(nn.GlobalAvgPool2D(layout="NHWC"))
        net.add(nn.Flatten())
        net.initialize(mx.init.Xavier())
        net(data)
        if env == "0":
            saved = {n: p._data[0]._data
                     for n, p in net.collect_params().items()}
        else:
            for n, p in net.collect_params().items():
                p._data[0]._set_data(saved[n])
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        loss_fn = lambda n, d, l: ((n(d) - l) ** 2).mean()
        step = trainer.compile_step(net, loss_fn)
        p0 = ops_nn.pad_channels_count()
        ls = [float(step(data, label, batch_size=4).asnumpy())]
        t0, d0 = cached_step.trace_count(), cached_step.dispatch_count()
        for _ in range(3):
            ls.append(float(step(data, label, batch_size=4).asnumpy()))
        assert step.last_step_compiled, step.last_fallback_reason
        # 0 added retraces / dispatches: the pad lives INSIDE the program
        assert cached_step.trace_count() - t0 == 0
        assert cached_step.dispatch_count() - d0 == 3
        if env == "2":
            assert ops_nn.pad_channels_count() - p0 >= 1
        losses[env] = ls
    assert losses["0"] == losses["2"]          # bit-exact trajectories


def test_pad_channels_composes_with_amp(force_pad):
    """bf16 AMP + the padding pass: the padded bf16 conv is still
    bit-exact vs the unpadded bf16 conv."""
    from mxnet_tpu import amp

    x = mx.nd.array(_rand(2, 8, 8, 3))
    outs = {}
    amp.init("bfloat16")
    try:
        for env in ("0", "2"):
            os.environ["MXNET_PAD_CHANNELS"] = env
            config.refresh("MXNET_PAD_CHANNELS")
            net = _misaligned_net()
            net.initialize(mx.init.Xavier())
            net(x)
            if env == "0":
                saved = {n: p._data[0]._data
                         for n, p in net.collect_params().items()}
            else:
                for n, p in net.collect_params().items():
                    p._data[0]._set_data(saved[n])
            net.hybridize()
            with autograd.record():
                out = net(x)
            outs[env] = out.asnumpy()
    finally:
        amp.uninit()
    onp.testing.assert_array_equal(outs["0"], outs["2"])


def test_pad_channels_composes_with_spmd_mesh(force_pad):
    """kvstore='tpu' on the virtual 8-device mesh + the padding pass:
    the sharded compiled step still runs (jnp.pad partitions fine) and
    the loss matches the pass-off sharded run bit-exactly."""
    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    from mxnet_tpu import gluon

    rng = onp.random.RandomState(13)
    n_dev = len(jax.devices())
    data = mx.nd.array(rng.randn(2 * n_dev, 4, 4, 3).astype(onp.float32))
    label = mx.nd.array(rng.randn(2 * n_dev, 10).astype(onp.float32))
    losses = {}
    for env in ("0", "2"):
        os.environ["MXNET_PAD_CHANNELS"] = env
        config.refresh("MXNET_PAD_CHANNELS")
        net = nn.HybridSequential()
        net.add(nn.Conv2D(10, kernel_size=3, padding=1, use_bias=True,
                          layout="NHWC", in_channels=3))
        net.add(nn.GlobalAvgPool2D(layout="NHWC"))
        net.add(nn.Flatten())
        net.initialize(mx.init.Xavier())
        net(data)
        if env == "0":
            saved = {n: p._data[0]._data
                     for n, p in net.collect_params().items()}
        else:
            for n, p in net.collect_params().items():
                p._data[0]._set_data(saved[n])
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore="tpu")
        loss_fn = lambda n, d, l: ((n(d) - l) ** 2).mean()
        step = trainer.compile_step(net, loss_fn)
        ls = []
        for _ in range(2):
            ls.append(float(step(data, label,
                                 batch_size=2 * n_dev).asnumpy()))
        assert step.last_step_compiled, step.last_fallback_reason
        assert step.mesh is not None
        losses[env] = ls
    assert losses["0"] == losses["2"]
