"""Gluon shape-inference / deferred-init / reshape+slice-through-layer
scenarios — mirrors the reference's ``test_gluon.py`` families
(test_deferred_init, test_fill_shape_deferred, test_fill_shape_load,
test_dtype, test_split_data, test_flatten, and the
test_{reshape,slice}_{conv,dense,batchnorm,pooling} matrix).

The reshape/slice matrix asserts the load-bearing Gluon contract: a
hybridized (whole-graph-compiled) forward containing shape surgery between
layers is numerically identical to the eager run, and gradients flow.
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn

_R = onp.random.RandomState(11)


# ---------------------------------------------------------------------------
# deferred initialization / shape fill
# ---------------------------------------------------------------------------

def test_deferred_init_conv():
    layer = nn.Conv2D(10, 2)        # in_channels unknown
    layer.initialize()
    out = layer(nd.ones((5, 4, 10, 10)))
    assert out.shape == (5, 10, 9, 9)
    assert layer.weight.shape == (10, 4, 2, 2)


def test_fill_shape_deferred_hybridized():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(64, kernel_size=2, padding=1),
            nn.BatchNorm(),
            nn.Dense(10))
    net.hybridize()
    net.initialize()
    net(nd.ones((2, 3, 5, 7)))
    assert net[0].weight.shape[1] == 3
    assert net[1].gamma.shape[0] == 64
    assert net[2].weight.shape[1] == 64 * 6 * 8


def test_fill_shape_load(tmp_path):
    path = str(tmp_path / "net_fill.params")
    net1 = nn.HybridSequential()
    net1.add(nn.Conv2D(64, kernel_size=2, padding=1),
             nn.BatchNorm(),
             nn.Dense(10))
    net1.hybridize()
    net1.initialize()
    net1(nd.ones((2, 3, 5, 7)))
    net1.save_parameters(path)

    net2 = nn.HybridSequential()
    net2.add(nn.Conv2D(64, kernel_size=2, padding=1),
             nn.BatchNorm(),
             nn.Dense(10))
    net2.hybridize()
    net2.initialize()
    net2.load_parameters(path)
    assert net2[0].weight.shape[1] == 3
    assert net2[1].gamma.shape[0] == 64
    # loaded net computes the same function
    x = nd.array(_R.rand(2, 3, 5, 7).astype("float32"))
    onp.testing.assert_allclose(net1(x).asnumpy(), net2(x).asnumpy(),
                                rtol=1e-5, atol=1e-5)


def test_deferred_init_error_before_first_call():
    layer = nn.Dense(4)
    layer.initialize()
    with pytest.raises(Exception):
        layer.weight.data()         # shape unknown until first forward


def test_infer_shape_explicit():
    layer = nn.Dense(4)
    layer.initialize()
    layer.infer_shape(nd.ones((3, 7)))
    assert layer.weight.shape == (4, 7)


# ---------------------------------------------------------------------------
# dtype casting (reference test_dtype; float64 is truncated on TPU-default
# jax, so the cast matrix uses the dtypes the platform really serves)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [
    "float16",
    "bfloat16",                     # the TPU-native dtype
])
def test_cast_then_forward_backward(dtype):
    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=10)
    net.initialize()
    net.cast(dtype)
    x = nd.ones((2, 3, 32, 32), dtype=dtype)
    with autograd.record():
        y = net(x)
        loss = (y.astype("float32") ** 2).sum()
    loss.backward()
    assert str(y.dtype) == dtype or dtype in str(y.dtype)


def test_cast_after_hybridize_retraces():
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.Dense(3))
    net.initialize()
    net.hybridize()
    y32 = net(nd.ones((2, 5)))
    net.cast("bfloat16")
    y16 = net(nd.ones((2, 5), dtype="bfloat16"))
    assert "bfloat16" in str(y16.dtype)
    onp.testing.assert_allclose(y16.asnumpy().astype("float32"),
                                y32.asnumpy(), rtol=2e-2, atol=2e-2)


def test_embedding_dense_dtype_flow():
    class Net(gluon.Block):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(5, 10, dtype="float16")
            self.dense = nn.Dense(2, dtype="float16")

        def forward(self, x):
            e = self.embed(x)
            assert "float16" in str(e.dtype)
            return self.dense(e)

    net = Net()
    net.initialize()
    out = net(nd.array([1, 2, 3], dtype="int32"))
    assert "float16" in str(out.dtype)


# ---------------------------------------------------------------------------
# split_data / split_and_load / clip_global_norm / Flatten (gluon.utils)
# ---------------------------------------------------------------------------

def _check_split(x, num_slice, batch_axis, **kwargs):
    res = gluon.utils.split_data(x, num_slice, batch_axis, **kwargs)
    assert len(res) == num_slice
    joined = nd.concatenate(res, axis=batch_axis)
    onp.testing.assert_array_equal(joined.asnumpy(), x.asnumpy())
    want = onp.array_split(x.asnumpy(), num_slice, axis=batch_axis)
    for r, w in zip(res, want):
        onp.testing.assert_array_equal(r.asnumpy(), w)


def test_split_data_matrix():
    x = nd.array(_R.rand(128, 33, 64).astype("float32"))
    _check_split(x, 8, 0)
    _check_split(x, 3, 1)
    _check_split(x, 4, 1, even_split=False)
    _check_split(x, 15, 1, even_split=False)
    with pytest.raises(ValueError):
        gluon.utils.split_data(x, 4, 1)     # 33 % 4 != 0, even_split=True


def test_split_and_load():
    x = nd.array(_R.rand(16, 4).astype("float32"))
    parts = gluon.utils.split_and_load(x, [mx.cpu(0), mx.cpu(0)])
    assert len(parts) == 2 and parts[0].shape == (8, 4)
    onp.testing.assert_array_equal(
        onp.concatenate([p.asnumpy() for p in parts]), x.asnumpy())


def test_clip_global_norm():
    arrays = [nd.array(_R.rand(3, 4).astype("float32")),
              nd.array(_R.rand(5).astype("float32"))]
    host = [a.asnumpy().copy() for a in arrays]
    want_norm = onp.sqrt(sum((h ** 2).sum() for h in host))
    got_norm = gluon.utils.clip_global_norm(arrays, 1.0)
    onp.testing.assert_allclose(got_norm, want_norm, rtol=1e-5)
    clipped = onp.sqrt(sum((a.asnumpy().astype("float64") ** 2).sum()
                           for a in arrays))
    assert clipped <= 1.0 + 1e-4
    for a, h in zip(arrays, host):      # direction preserved
        onp.testing.assert_allclose(a.asnumpy() * want_norm, h, rtol=1e-3)


def test_clip_global_norm_no_clip_when_small():
    arrays = [nd.array(onp.array([0.01, 0.02], dtype="float32"))]
    before = arrays[0].asnumpy().copy()
    gluon.utils.clip_global_norm(arrays, 10.0)
    onp.testing.assert_array_equal(arrays[0].asnumpy(), before)


def test_flatten_shapes():
    flatten = nn.Flatten()
    assert flatten(nd.zeros((3, 4, 5, 6))).shape == (3, 120)
    assert flatten(nd.zeros((3, 6))).shape == (3, 6)
    assert flatten(nd.zeros((3,))).shape == (3, 1)


# ---------------------------------------------------------------------------
# reshape/slice between layers, eager vs hybridized (reference
# test_reshape_conv / test_slice_dense / test_reshape_batchnorm family)
# ---------------------------------------------------------------------------

class _SurgeryNet(gluon.HybridBlock):
    """Applies shape surgery, a layer, more surgery, another layer."""

    def __init__(self, layer1, surgery, layer2=None):
        super().__init__()
        self.l1 = layer1
        self.l2 = layer2
        self._surgery = surgery

    def forward(self, x):
        x = self._surgery(x)
        x = self.l1(x)
        if self.l2 is not None:
            x = self.l2(x)
        return x


def _check_eager_vs_hybrid(net, x):
    net.initialize()
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()           # trace + compile
    hybrid2 = net(x).asnumpy()          # steady-state cached path
    onp.testing.assert_allclose(eager, hybrid, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(eager, hybrid2, rtol=1e-5, atol=1e-5)
    # gradients flow through the compiled graph
    x.attach_grad()
    with autograd.record():
        y = net(x)
        loss = (y ** 2).sum()
    loss.backward()
    assert x.grad is not None and onp.isfinite(x.grad.asnumpy()).all()


def test_reshape_conv():
    net = _SurgeryNet(nn.Conv2D(8, (3, 3)),
                      lambda x: x.reshape((0, 0, 32, 8)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(2, 3, 16, 16).astype("float32")))


def test_slice_conv():
    net = _SurgeryNet(nn.Conv2D(4, (3, 3)),
                      lambda x: x.slice(begin=(0, 1, 0, 0),
                                        end=(2, 3, 12, 12)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(2, 4, 16, 16).astype("float32")))


def test_reshape_conv_slice_conv():
    net = _SurgeryNet(
        nn.Conv2D(8, (3, 3)),
        lambda x: x.reshape((0, 0, 32, 8)),
        layer2=None)
    x = nd.array(_R.rand(2, 3, 16, 16).astype("float32"))
    _check_eager_vs_hybrid(net, x)


def test_reshape_dense():
    net = _SurgeryNet(nn.Dense(10), lambda x: x.reshape((8, -1)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(4, 6, 8).astype("float32")))


def test_slice_dense():
    net = _SurgeryNet(nn.Dense(10),
                      lambda x: x.slice(begin=(1, 2), end=(4, 10)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(6, 12).astype("float32")))


def test_slice_dense_reshape_dense():
    net = _SurgeryNet(nn.Dense(10),
                      lambda x: x.slice(begin=(0, 0),
                                        end=(4, 8)).reshape((2, -1)),
                      layer2=nn.Dense(5))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(6, 12).astype("float32")))


def test_reshape_batchnorm():
    net = _SurgeryNet(nn.BatchNorm(),
                      lambda x: x.reshape((0, 16, 8, -1)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(2, 32, 8, 4).astype("float32")))


def test_slice_batchnorm():
    net = _SurgeryNet(nn.BatchNorm(),
                      lambda x: x.slice(begin=(0, 0, 0, 0),
                                        end=(2, 8, 4, 4)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(4, 16, 4, 4).astype("float32")))


def test_reshape_pooling():
    net = _SurgeryNet(nn.MaxPool2D(pool_size=2),
                      lambda x: x.reshape((0, 0, 8, 8)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(2, 4, 16, 4).astype("float32")))


def test_slice_pooling():
    net = _SurgeryNet(nn.AvgPool2D(pool_size=2),
                      lambda x: x.slice(begin=(0, 0, 2, 2),
                                        end=(2, 4, 10, 10)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(2, 6, 12, 12).astype("float32")))


def test_reshape_activation_chain():
    net = _SurgeryNet(nn.Activation("relu"),
                      lambda x: x.reshape((0, -1)),
                      layer2=nn.Dense(6))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(3, 4, 5).astype("float32") - 0.5))


def test_mxnet_reshape_special_codes_through_layers():
    """MXNet reshape code 0 = copy input dim, -1 = infer: must behave the
    same through the hybridized graph."""
    net = _SurgeryNet(nn.Conv2D(4, (1, 1)),
                      lambda x: x.reshape((0, 0, -1, 4)))
    _check_eager_vs_hybrid(net, nd.array(
        _R.rand(2, 3, 8, 4).astype("float32")))
