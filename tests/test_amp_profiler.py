"""amp / profiler / runtime tests (reference
tests/python/gpu/test_contrib_amp.py, tests/python/unittest/test_profiler.py,
test_runtime.py)."""
import json
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, nd, profiler, runtime
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _amp_off():
    yield
    amp.uninit()


def test_amp_init_casts_matmul_inputs():
    import jax.numpy as jnp

    amp.init("bfloat16")
    x = nd.ones((4, 8))
    w = nd.ones((16, 8))
    out = nd.FullyConnected(x, w, None, num_hidden=16, no_bias=True)
    assert out._data.dtype == jnp.bfloat16
    # fp32-pinned op casts back up
    s = nd.softmax(out)
    assert s._data.dtype == jnp.float32
    amp.uninit()
    out2 = nd.FullyConnected(x, w, None, num_hidden=16, no_bias=True)
    assert out2._data.dtype == jnp.float32


def test_amp_training_converges():
    import jax.numpy as jnp

    amp.init("bfloat16")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize()
    rng = onp.random.RandomState(0)
    X = nd.array(rng.rand(32, 4))
    y = nd.array((X.asnumpy() @ rng.rand(4, 1)))
    tr = mx.gluon.Trainer(net.collect_params(), "adam",
                          {"learning_rate": 0.02})
    l2 = mx.gluon.loss.L2Loss()
    first = None
    for _ in range(150):
        with mx.autograd.record():
            loss = l2(net(X), y).mean()
        loss.backward()
        tr.step(32)
        if first is None:
            first = float(loss.asscalar())
    assert float(loss.asscalar()) < 0.05 * first


def test_amp_training_loss_decreases_smoke():
    """Tier-1 smoke for the slow convergence test above: same
    amp.init + Trainer path, 25 steps, loss must clearly decrease."""
    amp.init("bfloat16")
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize()
    rng = onp.random.RandomState(0)
    X = nd.array(rng.rand(32, 4))
    y = nd.array((X.asnumpy() @ rng.rand(4, 1)))
    tr = mx.gluon.Trainer(net.collect_params(), "adam",
                          {"learning_rate": 0.02})
    l2 = mx.gluon.loss.L2Loss()
    first = None
    for _ in range(25):
        with mx.autograd.record():
            loss = l2(net(X), y).mean()
        loss.backward()
        tr.step(32)
        if first is None:
            first = float(loss.asscalar())
    assert float(loss.asscalar()) < 0.5 * first


def test_fp16_loss_scaling_end_to_end():
    """Overflowed steps are skipped and the scale adapts; gradients are
    unscaled exactly once (trainer rescale path)."""
    amp.init("float16")
    net = nn.Dense(1)
    net.initialize()
    X = nd.ones((4, 3))
    y = nd.ones((4, 1))
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1})
    amp.init_trainer(tr)
    tr._amp_loss_scaler.loss_scale = 4.0  # small, no overflow expected
    l2 = mx.gluon.loss.L2Loss()
    net(X)  # complete deferred shape inference
    w_before = net.weight.data().asnumpy().copy()
    with mx.autograd.record():
        with amp.scale_loss(l2(net(X), y).mean(), tr) as scaled:
            scaled.backward()
    tr.step(4)
    w_after = net.weight.data().asnumpy()
    assert not onp.allclose(w_before, w_after)  # clean step applied

    # force an overflow: scaler must skip the update and halve the scale
    net.weight.grad(mx.cpu())._set_data(
        (nd.full(net.weight.shape, onp.inf))._data)
    w_before = net.weight.data().asnumpy().copy()
    scale_before = tr._amp_loss_scaler.loss_scale
    tr.step(4)
    onp.testing.assert_allclose(net.weight.data().asnumpy(), w_before)
    assert tr._amp_loss_scaler.loss_scale == scale_before / 2


def test_loss_scaler_policy():
    sc = amp.LossScaler(init_scale=8.0, scale_factor=2.0, scale_window=2)
    sc.update_scale(False)
    sc.update_scale(False)
    assert sc.loss_scale == 16.0
    sc.update_scale(True)
    assert sc.loss_scale == 8.0
    g = nd.array([onp.inf, 1.0])
    assert sc.has_overflow([g])
    assert not sc.has_overflow([nd.array([1.0, 2.0])])


def test_convert_hybrid_block():
    import jax.numpy as jnp

    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    net(nd.ones((2, 4)))
    amp.convert_hybrid_block(net, "bfloat16")
    params = net.collect_params()
    assert params["0.weight"].data().dtype == jnp.bfloat16
    # norm params stay fp32
    assert params["1.gamma"].data().dtype == onp.float32


def test_convert_hybrid_block_rehomed_ctx():
    # convert_hybrid_block(ctx=...) re-homes the params; a hybridized call
    # on the new device must trace against the CALLER's ctx, not the
    # process default (caught live: the bench's bf16 inference reference
    # failed replica lookup after reset_ctx to the accelerator)
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 2:
        import pytest
        pytest.skip("needs >=2 devices")
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    x0 = nd.ones((2, 4))
    net(x0)
    bnet = amp.convert_hybrid_block(net, "bfloat16", ctx=mx.cpu(1))
    bnet.hybridize()
    out = bnet(nd.array(x0, ctx=mx.cpu(1)))
    assert out.ctx == mx.cpu(1)
    assert out.dtype == jnp.bfloat16
    assert list(out._data.devices()) == [jax.devices()[1]]


def test_profiler_scopes_and_dump(tmp_path):
    fn = str(tmp_path / "trace.json")
    profiler.set_config(filename=fn)
    profiler.set_state("run")
    with profiler.Task("stepA"):
        nd.ones((8, 8)).wait_to_read()
    with profiler.Frame("frameB"):
        pass
    cnt = profiler.Counter("imgs")
    cnt.set_value(5)
    cnt += 3
    profiler.Marker("mark").mark()
    profiler.pause()
    with profiler.Task("ignored"):
        pass
    profiler.resume()
    table = profiler.dumps()
    assert "stepA" in table
    profiler.set_state("stop")
    path = profiler.dump()
    with open(path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"stepA", "frameB", "imgs", "mark"} <= names
    assert "ignored" not in names


def test_runtime_features():
    feats = runtime.feature_list()
    names = {f.name for f in feats}
    assert {"XLA", "BF16", "CPU"} <= names
    fs = runtime.Features()
    assert fs.is_enabled("XLA")
    with pytest.raises(RuntimeError):
        fs.is_enabled("NOT_A_FEATURE")


def test_amp_lists_exhaustive_over_registry():
    """Every registered op is classified into exactly one AMP list
    (reference per-op list-file parity); new ops cannot land
    unclassified."""
    from mxnet_tpu.amp import lists
    from mxnet_tpu.ops.registry import list_ops

    all_lists = (lists.LOW_PRECISION_FUNCS, lists.FP32_FUNCS,
                 lists.WIDEST_TYPE_CASTS, lists.FP16_FP32_FUNCS)
    union = set().union(*all_lists)
    import mxnet_tpu.operator as custom_operator

    # session-registered escape hatches are exempt: library.load
    # extensions ("ext_*"/example names) and mx.operator CustomOps
    # (host callbacks — AMP cast policy never wraps them)
    runtime_custom = set(custom_operator.get_all_registered())
    core = {n for n in list_ops()
            if n != "_np_call" and not n.startswith(("ext_", "test_"))
            and n not in ("my_gemm", "my_relu")
            and n not in runtime_custom}
    missing = sorted(core - union)
    assert not missing, f"ops missing an AMP classification: {missing}"
    # no op sits in two lists (ambiguous policy)
    seen = set()
    dups = set()
    for lst in all_lists:
        for n in lst:
            (dups if n in seen else seen).add(n)
    assert not dups, f"ops in multiple AMP lists: {dups}"
    # batch norm runs in the type that arrives (the reference's placement:
    # float32 statistics inside the operator); the norms that reduce over
    # the features of one sample, the softmax family and the losses stay
    # pinned to float32
    neutral, pinned = set(lists.FP16_FP32_FUNCS), set(lists.FP32_FUNCS)
    assert {"BatchNorm", "SyncBatchNorm", "BatchNormWithReLU"} <= neutral
    assert {"LayerNorm", "GroupNorm", "InstanceNorm", "LRN", "softmax",
            "log_softmax", "softmax_cross_entropy", "SoftmaxOutput",
            "CTCLoss"} <= pinned


LOW_TYPES = pytest.mark.parametrize("low", ["bfloat16", "float16"])
# one rounding to the type: half a unit in its last place
ROUNDING = {"bfloat16": 2.0 ** -8, "float16": 2.0 ** -11}


def _bn_low_count():
    return mx.telemetry.snapshot()["amp.batch_norm.low_precision"]


@LOW_TYPES
def test_amp_batch_norm_returns_the_type_that_arrives(low):
    """``Conv2D -> BatchNorm -> Activation`` under AMP: the activation stays
    in the convolution's type; the batch statistics, the running statistics
    and the gradients of gamma and beta are float32."""
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray.ndarray import invoke

    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, in_channels=3), nn.BatchNorm(),
            nn.Activation("relu"))
    net.initialize()
    x = nd.array(onp.random.RandomState(0).randn(4, 3, 8, 8)
                 .astype("float32"))
    amp.init(low)
    before = _bn_low_count()
    with autograd.record():
        out = net(x)
        loss = out.astype("float32").sum()
    loss.backward()
    assert _bn_low_count() == before + 1
    assert str(out.dtype) == low
    bn = net[1]
    for p in (bn.gamma, bn.beta):
        assert p.data().dtype == onp.float32
        assert p.grad().dtype == onp.float32
        assert onp.isfinite(p.grad().asnumpy()).all()
    for p in (bn.running_mean, bn.running_var):
        assert p.data().dtype == onp.float32
    assert onp.abs(bn.running_mean.data().asnumpy()).max() > 0
    # the operator's own outputs: the low type, float32 batch statistics
    conv_out = net[0](x)
    y, mean, var = invoke(
        "BatchNorm", [conv_out, bn.gamma.data(), bn.beta.data(),
                      bn.running_mean.data(), bn.running_var.data()],
        {"eps": 1e-5, "fix_gamma": False, "training": True})
    assert str(conv_out.dtype) == low and str(y.dtype) == low
    assert mean.dtype == onp.float32 and var.dtype == onp.float32
    # without AMP a float32 operand is no low-precision call
    amp.uninit()
    before = _bn_low_count()
    assert net(x).dtype == onp.float32
    assert _bn_low_count() == before


def _bn_operands(low, seed=0):
    import jax.numpy as jnp

    rng = onp.random.RandomState(seed)
    x = jnp.asarray(rng.randn(8, 6, 6, 16).astype("float32") * 3 + 1)
    vec = [jnp.asarray(v.astype("float32")) for v in (
        rng.rand(16) + 0.5, rng.randn(16), rng.randn(16), rng.rand(16) + 0.5)]
    return x.astype(low), vec


@LOW_TYPES
@pytest.mark.parametrize("attrs", [{"training": True},
                                   {"use_global_stats": True}],
                         ids=["training", "use_global_stats"])
def test_batch_norm_low_operand_is_one_rounding_from_float32(low, attrs):
    """The operator on a bf16/fp16 operand against the float32 operator on
    the SAME operand: float32 statistics and one float32 multiply-add, so
    the only difference is the output's one rounding."""
    from mxnet_tpu.ops.nn import batch_norm

    x, vec = _bn_operands(low)
    got = batch_norm([x] + vec, eps=1e-5, fix_gamma=False, axis=3, **attrs)
    want = batch_norm([x.astype("float32")] + vec, eps=1e-5, fix_gamma=False,
                      axis=3, **attrs)
    assert str(got[0].dtype) == low and want[0].dtype == onp.float32
    w = onp.asarray(want[0])
    err = onp.abs(onp.asarray(got[0].astype("float32")) - w)
    assert (err <= ROUNDING[low] * onp.abs(w) + 1e-30).all(), err.max()
    for g, w in zip(got[1:], want[1:]):         # batch mean and variance
        assert g.dtype == onp.float32
        onp.testing.assert_array_equal(onp.asarray(g), onp.asarray(w))


@pytest.mark.parametrize("attrs", [{"training": True},
                                   {"use_global_stats": True}],
                         ids=["training", "use_global_stats"])
def test_batch_norm_float32_operand_is_the_parents_bit_for_bit(attrs):
    """For a float32 operand the multiply-add in float32, rounded to the
    operand's type, IS the line it replaced (``data * scale + shift`` in
    the operand's type): replayed here."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import batch_norm

    x, (gamma, beta, mean, var) = _bn_operands("float32", seed=1)
    out = batch_norm([x, gamma, beta, mean, var], eps=1e-5, fix_gamma=False,
                     axis=3, **attrs)
    if attrs.get("training"):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.maximum(jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean, 0.0)
    sc = jax.lax.rsqrt(var + jnp.float32(1e-5)) * gamma
    bi = beta - mean * sc
    onp.testing.assert_array_equal(
        onp.asarray(out[0]),
        onp.asarray(x * sc.reshape(1, 1, 1, 16) + bi.reshape(1, 1, 1, 16)))


@LOW_TYPES
@pytest.mark.parametrize("op", ["SyncBatchNorm", "BatchNormWithReLU"])
def test_amp_graph_parity_batch_norms_are_float32_inside(op, low):
    """The two graph-parity batch norms moved with ``BatchNorm``: a low
    operand comes back in its type, one rounding from the float32 run."""
    x, (gamma, beta, mean, var) = _bn_operands(low, seed=2)
    x = x.transpose(0, 3, 1, 2)                      # these two are NCHW
    args = [nd.array(onp.asarray(a.astype("float32"))) for a in
            (x, gamma, beta, mean, var)]
    want = getattr(nd, op)(*args, eps=1e-5, fix_gamma=False).asnumpy()
    amp.init(low)
    before = _bn_low_count()
    got = getattr(nd, op)(args[0].astype(low), *args[1:], eps=1e-5,
                          fix_gamma=False)
    assert _bn_low_count() == before + 1
    assert str(got.dtype) == low
    err = onp.abs(got.astype("float32").asnumpy() - want)
    assert (err <= ROUNDING[low] * onp.abs(want) + 1e-30).all(), err.max()


@pytest.mark.parametrize("low,count", [("bfloat16", 53), (None, 0)],
                         ids=["amp", "no_amp"])
def test_resnet50_traces_53_low_precision_batch_norms(low, count):
    """``amp.batch_norm.low_precision`` counts a site once a trace:
    ``resnet50_v1`` under bf16 AMP hands every one of its 53 batch norms the
    convolution's bf16; without AMP none."""
    import jax
    from mxnet_tpu.gluon import block as gblock
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet50_v1(classes=10, layout="NHWC", input_layout="NHWC")
    net.initialize()
    x = nd.zeros((1, 32, 32, 3))
    net(x)                                  # resolves the deferred shapes
    params = net.collect_params()
    raw_fn, _, _ = gblock._stage_fn(net, params, list(params),
                                    gblock._flatten_args((x,))[1], True,
                                    x.ctx)
    if low:
        amp.init(low)
    before = _bn_low_count()
    jax.eval_shape(raw_fn, [p.data()._data for p in params.values()],
                   [x._data], jax.random.PRNGKey(0))
    assert _bn_low_count() == before + count


def test_memory_summary_attributes_params():
    """profiler.memory_summary labels live buffers with parameter names
    (reference storage-profiler attribution, storage_profiler.h:131)."""
    import mxnet_tpu as mx
    from mxnet_tpu import profiler

    net = mx.gluon.nn.Dense(8)
    net.initialize()
    net(mx.nd.ones((2, 4)))
    s = profiler.memory_summary(net)
    assert "weight" in s and "bias" in s and "TOTAL" in s


def test_bandwidth_tool_runs():
    import json
    import os
    import subprocess
    import sys

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bandwidth.py"),
         "--mb", "4", "--iters", "2", "--mesh", "dp=8"],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    for k in ("h2d_GBps", "d2h_GBps", "hbm_GBps", "allreduce_GBps"):
        assert res[k] > 0
