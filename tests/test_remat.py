"""Rematerialization (recompute-in-backward) — the TPU-native analog of
the reference's gradient mirroring (MXNET_BACKWARD_DO_MIRROR,
src/nnvm/gradient.cc mirror path), implemented with jax.checkpoint.
The testable contract on CPU is bit-level equivalence: remat changes the
schedule, never the math."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import nn


def _net(seed):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"),
            nn.Dense(8, in_units=16, activation="tanh"),
            nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    return net


def _grads(net, x):
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    return (float(loss.asnumpy()),
            {k: p.grad().asnumpy().copy()
             for k, p in net.collect_params().items()
             if p.grad_req != "null"})


def test_hybridize_remat_matches_plain():
    x = nd.array(onp.random.RandomState(0).rand(4, 8).astype(onp.float32))
    net_a, net_b = _net(11), _net(11)
    net_a.hybridize()
    net_b.hybridize(remat=True)
    la, ga = _grads(net_a, x)
    lb, gb = _grads(net_b, x)
    assert abs(la - lb) < 1e-6
    for k in ga:
        onp.testing.assert_allclose(gb[k], ga[k], rtol=1e-6, atol=1e-7)


def test_remat_policy_accepted():
    x = nd.ones((2, 8))
    net = _net(3)
    net.hybridize(remat=True, remat_policy="dots_saveable")
    la, _ = _grads(net, x)
    net2 = _net(3)
    net2.hybridize()
    lb, _ = _grads(net2, x)
    assert abs(la - lb) < 1e-6


def test_mirror_env_var_default(monkeypatch):
    from mxnet_tpu import config

    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    config.refresh("MXNET_BACKWARD_DO_MIRROR")
    try:
        net = _net(5)
        # a net constructed under the env var remats by default…
        assert net._remat is True
        # …and still matches the plain math
        net.hybridize()
        x = nd.ones((2, 8))
        la, ga = _grads(net, x)
        net2 = _net(5)
        net2.hybridize(remat=False)
        lb, gb = _grads(net2, x)
        assert abs(la - lb) < 1e-6
    finally:
        config.refresh("MXNET_BACKWARD_DO_MIRROR")


def test_sharded_trainer_remat_equivalence():
    import jax.numpy as jnp

    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    rng = onp.random.RandomState(2)
    data = rng.rand(8, 8).astype(onp.float32)
    label = rng.randint(0, 2, (8,)).astype(onp.int32)
    ce = SoftmaxCrossEntropyLoss()

    losses = []
    for remat in (False, True):
        net = _net(21)
        mesh = par.make_mesh({"dp": 1})
        tr = par.ShardedTrainer(net, lambda o, l: ce(o, l).mean(), mesh,
                                optimizer="sgd",
                                optimizer_params={"lr": 0.1},
                                remat=remat)
        d, l = tr.stage(data, label)
        run = []
        for _ in range(3):
            loss = tr.step(d, l)
            run.append(float(loss.asnumpy() if hasattr(loss, "asnumpy")
                             else loss))
        losses.append(run)
    onp.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)


def test_sharded_trainer_remat_with_accum():
    import jax.numpy as jnp

    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    rng = onp.random.RandomState(4)
    data = rng.rand(8, 8).astype(onp.float32)
    label = rng.randint(0, 2, (8,)).astype(onp.int32)
    ce = SoftmaxCrossEntropyLoss()

    losses = []
    for remat in (False, True):
        net = _net(23)
        mesh = par.make_mesh({"dp": 1})
        tr = par.ShardedTrainer(net, lambda o, l: ce(o, l).mean(), mesh,
                                optimizer="sgd",
                                optimizer_params={"lr": 0.1},
                                grad_accum=2, remat=remat)
        d, l = tr.stage(data, label)
        out = [float(tr.step(d, l)) for _ in range(2)]
        losses.append(out)
    onp.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)


def test_executor_fresh_dropout_mask_per_forward():
    # reference engine RNG: each forward draws fresh randomness; a bound
    # executor must not freeze the bind-time key (review-caught)
    from mxnet_tpu import sym

    out = sym.Dropout(sym.var("data"), p=0.5, training=True)
    exe = out.simple_bind(mx.cpu(), data=(256,))
    a = exe.forward(data=nd.ones((256,)))[0].asnumpy()
    b = exe.forward(data=nd.ones((256,)))[0].asnumpy()
    assert (a != b).any(), "dropout mask frozen across forwards"
    # reshape keeps the key machinery intact
    exe2 = exe.reshape(data=(64,))
    c = exe2.forward(data=nd.ones((64,)))[0].asnumpy()
    d = exe2.forward(data=nd.ones((64,)))[0].asnumpy()
    assert c.shape == (64,) and (c != d).any()
    assert not (set(exe2.grad_dict) & set(out._rng_key_vars()))


# -- a run of children recomputed inside a traced parent --------------------
def _conv_bn_net(seed, recompute):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1, in_channels=3), nn.BatchNorm(),
            nn.Activation("relu"), nn.MaxPool2D(2, 2),
            nn.Conv2D(2, 1, in_channels=4))
    if recompute:
        net.recompute(*recompute)
    net.initialize(mx.init.Xavier())
    return net


def _stats(net):
    return {k: p.data().asnumpy().copy()
            for k, p in net.collect_params().items() if "running" in k}


@pytest.mark.parametrize("run", [(1, 4), (0, 4), (0, 5)],
                         ids=["bn_relu_pool", "conv_too", "whole"])
@pytest.mark.parametrize("mode", ["eager", "hybridized", "compile_step"])
def test_recomputed_run_changes_no_number(run, mode, monkeypatch):
    """``HybridSequential.recompute``: the run is one ``jax.checkpoint``
    segment in a staged program and a plain run of calls in an eager one;
    loss, gradients and the batch norm's running statistics (written INSIDE
    the segment, carried out of it as outputs) are the plain net's."""
    x = nd.array(onp.random.RandomState(1).rand(4, 3, 8, 8)
                 .astype(onp.float32))
    got, want = _conv_bn_net(7, run), _conv_bn_net(7, None)
    if mode == "compile_step":
        from mxnet_tpu import gluon

        monkeypatch.setenv("MXNET_SPMD_MESH", "off")
        out = []
        for net in (got, want):
            net(x)
            trainer = gluon.Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 1e-3}, kvstore="tpu")
            step = trainer.compile_step(net, lambda n, a: (n(a) ** 2).sum())
            losses = [float(step(x, batch_size=4).asnumpy())
                      for _ in range(3)]
            assert step.last_step_compiled
            out.append((losses, {k: p.data().asnumpy() for k, p in
                                 net.collect_params().items()}))
        (la, pa), (lb, pb) = out
        onp.testing.assert_allclose(la, lb, rtol=1e-5)
        assert la[-1] < la[0]
        for k in pa:
            onp.testing.assert_allclose(pa[k], pb[k], rtol=1e-5, atol=1e-6)
        assert onp.abs(pa["1.running_mean"]).max() > 0
        return
    if mode == "hybridized":
        got.hybridize()
        want.hybridize()
    la, ga = _grads(got, x)
    lb, gb = _grads(want, x)
    assert abs(la - lb) <= 1e-6 * abs(lb)
    for k in gb:
        onp.testing.assert_allclose(ga[k], gb[k], rtol=1e-5, atol=1e-6)
    sa, sb = _stats(got), _stats(want)
    assert onp.abs(sa["1.running_mean"]).max() > 0
    for k in sb:
        onp.testing.assert_allclose(sa[k], sb[k], rtol=1e-6)


def test_recomputed_run_is_a_checkpoint_only_when_staged():
    import jax

    from mxnet_tpu.gluon import block as gblock

    net = _conv_bn_net(3, (1, 4))
    x = nd.ones((2, 3, 8, 8))
    net(x)
    params = net.collect_params()
    raw_fn, _, mutated = gblock._stage_fn(
        net, params, list(params), gblock._flatten_args((x,))[1], True,
        x.ctx)
    jaxpr = str(jax.make_jaxpr(raw_fn)(
        [p.data()._data for p in params.values()], [x._data],
        jax.random.PRNGKey(0)))
    assert jaxpr.count("remat2[") == 1         # jax.checkpoint's primitive
    # the running statistics still leave the staged function as mutations
    assert sorted(mutated) == ["1.running_mean", "1.running_var"]
    # eager: no tracer, no checkpoint, the plain blocks
    assert not isinstance(net(x)._data, jax.core.Tracer)


def test_resnet_stem_is_the_recomputed_run():
    from mxnet_tpu.gluon.model_zoo import vision

    for make, run in ((vision.resnet18_v1, (0, 4)),
                      (vision.resnet18_v2, (1, 5))):
        for kwargs in ({}, {"stem_s2d": True, "layout": "NHWC"}):
            assert make(**kwargs).features._recomputed == run
    assert vision.resnet18_v1(thumbnail=True).features._recomputed is None
