"""The sparse-label cross-entropy as one operator
(``sparse_softmax_cross_entropy``): it picks the label's logit before it
normalises, so its value is ``-pick(log_softmax(x), y)``'s bit for bit, its
hand-written VJP is autodiff's of that expression, neither pass holds a
scatter or a gather from a float32 copy of the logits, and
``SoftmaxCrossEntropyLoss`` takes it on the sparse-label path alone."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, nd, telemetry
from mxnet_tpu.models import transformer_lm
from mxnet_tpu.ops.registry import get_op

sparse_ce = get_op("sparse_softmax_cross_entropy").fn
pick = get_op("pick").fn

DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["float32", "bfloat16"])


def reference(x, y, axis=-1):
    """The expression the operator replaces, statistics in float32 as the
    AMP policy runs ``log_softmax``."""
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=axis)
    return -pick(logp, y, axis=axis)


def _logits(shape, dtype, seed=0, scale=3.0):
    rng = onp.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype("float32") * scale, dtype)


def _labels(shape, classes, seed=1, dtype="int32"):
    return jnp.asarray(onp.random.RandomState(seed)
                       .randint(0, classes, shape).astype(dtype))


def _fused():
    return telemetry.snapshot()["loss.sparse_ce.fused"]


# -- the operator -----------------------------------------------------------
@DTYPES
@pytest.mark.parametrize("shape", [(16, 1000), (3, 5, 257)],
                         ids=["rank2", "rank3"])
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_forward_is_the_old_expression_bit_for_bit(dtype, shape, jit):
    x, y = _logits(shape, dtype), _labels(shape[:-1], shape[-1])
    new, old = (jax.jit(sparse_ce), jax.jit(reference)) if jit \
        else (sparse_ce, reference)
    got, want = new(x, y), old(x, y)
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == shape[:-1]
    onp.testing.assert_array_equal(onp.asarray(got), onp.asarray(want))


def _grads(fn, x, y, ct, **kw):
    g = jax.grad(lambda x: (fn(x, y, **kw) * ct).sum())(x)
    assert g.dtype == x.dtype
    return onp.asarray(g.astype(jnp.float32))


@DTYPES
def test_gradient_is_autodiffs_of_the_old_expression(dtype):
    x, y = _logits((16, 1000), dtype), _labels((16,), 1000)
    ct = jnp.asarray(onp.random.RandomState(2).rand(16).astype("float32"))
    got, want = _grads(sparse_ce, x, y, ct), _grads(reference, x, y, ct)
    if dtype == jnp.float32:
        onp.testing.assert_allclose(got, want, rtol=1e-6,
                                    atol=1e-7 * float(ct.max()))
    else:
        # one ulp of bfloat16 (8 bits of significand)
        onp.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-30)


@DTYPES
def test_axis_1_of_a_rank_3_tensor(dtype):
    x, y = _logits((4, 37, 6), dtype), _labels((4, 6), 37)
    onp.testing.assert_array_equal(
        onp.asarray(sparse_ce(x, y, axis=1)),
        onp.asarray(reference(x, y, axis=1)))
    ct = jnp.ones((4, 6), jnp.float32)
    onp.testing.assert_allclose(
        _grads(sparse_ce, x, y, ct, axis=1),
        _grads(reference, x, y, ct, axis=1),
        rtol=1e-6 if dtype == jnp.float32 else 2.0 ** -7, atol=1e-7)


@pytest.mark.parametrize("label_dtype", ["int32", "int64", "float32"])
def test_any_label_dtype(label_dtype):
    x = nd.array(onp.asarray(_logits((8, 11), jnp.float32)))
    y = onp.asarray(_labels((8,), 11))
    want = gluon.loss.SoftmaxCrossEntropyLoss()(
        x, nd.array(y, dtype="int32")).asnumpy()
    got = gluon.loss.SoftmaxCrossEntropyLoss()(
        x, nd.array(y.astype(label_dtype), dtype=label_dtype)).asnumpy()
    onp.testing.assert_array_equal(got, want)
    onp.testing.assert_array_equal(
        got, onp.asarray(reference(x._data, jnp.asarray(y))))


@pytest.mark.parametrize("mode", ["clip", "wrap"])
def test_out_of_range_labels_go_where_picks_go(mode):
    x = _logits((6, 9), jnp.float32)
    y = jnp.asarray([-20, -1, 0, 8, 9, 40], jnp.int32)
    inside = jnp.clip(y, 0, 8) if mode == "clip" else y % 9
    onp.testing.assert_array_equal(
        onp.asarray(pick(x, y, mode=mode)), onp.asarray(pick(x, inside)))
    onp.testing.assert_array_equal(
        onp.asarray(sparse_ce(x, y, mode=mode)),
        onp.asarray(-pick(jax.nn.log_softmax(x), y, mode=mode)))
    ct = jnp.ones((6,), jnp.float32)
    onp.testing.assert_array_equal(_grads(sparse_ce, x, y, ct, mode=mode),
                                   _grads(sparse_ce, x, inside, ct))
    with pytest.raises(ValueError, match="clip.*wrap"):
        sparse_ce(x, y, mode="raise")


def test_second_order_gradient():
    x, y = _logits((4, 7), jnp.float32), _labels((4,), 7)
    got = jax.hessian(lambda x: sparse_ce(x, y).sum())(x)
    want = jax.hessian(lambda x: reference(x, y).sum())(x)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("which", ["forward", "vjp"])
def test_no_scatter_and_no_gather_from_a_float32_copy(which):
    """bf16 logits: the one gather reads the bf16 array itself, and the
    backward's one-hot is a compare, not the gather's transpose."""
    x, y = _logits((16, 1000), jnp.bfloat16), _labels((16,), 1000)
    fn = sparse_ce if which == "forward" else jax.grad(
        lambda x, y: sparse_ce(x, y).sum())
    eqns = list(_equations(jax.make_jaxpr(fn)(x, y).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert not [n for n in names if "scatter" in n], names
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert len(gathers) == 1
    operand = gathers[0].invars[0].aval
    assert (operand.shape, operand.dtype) == (x.shape, jnp.bfloat16)
    # and the old expression is what the test can tell apart
    old = jax.make_jaxpr(jax.grad(lambda x, y: reference(x, y).sum()))(x, y)
    old_names = [e.primitive.name for e in _equations(old.jaxpr)]
    assert any("scatter" in n for n in old_names)


def test_counter_counts_a_site_once_a_trace():
    x, y = _logits((4, 9), jnp.float32), _labels((4,), 9)
    fn = jax.jit(lambda x, y: sparse_ce(x, y) + sparse_ce(x * 2, y))
    before = _fused()
    fn(x, y)
    assert _fused() == before + 2             # two sites, one trace
    fn(x, y)
    assert _fused() == before + 2             # a cache hit traces nothing


# -- SoftmaxCrossEntropyLoss ------------------------------------------------
def test_sample_weight():
    x = nd.array(onp.asarray(_logits((8, 11), jnp.float32)))
    y = nd.array(onp.asarray(_labels((8,), 11)), dtype="int32")
    w = nd.array(onp.random.RandomState(3).rand(8).astype("float32"))
    got = gluon.loss.SoftmaxCrossEntropyLoss(weight=0.5)(x, y, w).asnumpy()
    want = onp.asarray(reference(x._data, y._data)) * w.asnumpy() * 0.5
    onp.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", ["from_logits", "dense_labels"])
def test_the_other_paths_are_what_they_were(kind):
    x = nd.array(onp.asarray(_logits((8, 11), jnp.float32)))
    y = onp.asarray(_labels((8,), 11))
    logp = nd.log_softmax(x)
    before = _fused()
    if kind == "from_logits":
        got = gluon.loss.SoftmaxCrossEntropyLoss(from_logits=True)(
            logp, nd.array(y, dtype="int32"))
    else:
        got = gluon.loss.SoftmaxCrossEntropyLoss(sparse_label=False)(
            x, nd.array(onp.eye(11, dtype="float32")[y]))
    assert _fused() == before
    onp.testing.assert_allclose(
        got.asnumpy(), -logp.asnumpy()[onp.arange(8), y], rtol=1e-6)


def test_amp_hands_the_operator_the_bf16_logits():
    """Under bf16 AMP the policy casts ``log_softmax``'s input up and
    leaves this operator's alone: the same float32 value either way."""
    x = nd.array(onp.asarray(_logits((8, 3, 50), jnp.bfloat16)),
                 dtype="bfloat16")
    y = nd.array(onp.asarray(_labels((8, 3), 50)), dtype="int32")
    amp.init("bfloat16")
    try:
        x.attach_grad()
        with autograd.record():
            new = gluon.loss.SoftmaxCrossEntropyLoss()(x, y)
        new.backward()
        old = -nd.pick(nd.log_softmax(x), y)
    finally:
        amp.uninit()
    assert new.dtype == onp.float32 and str(x.grad.dtype) == "bfloat16"
    onp.testing.assert_array_equal(new.asnumpy(), old.mean(axis=1).asnumpy())


def _dense_net(seed=4):
    net = gluon.nn.Dense(13, in_units=5)
    net.initialize(mx.init.Xavier())
    net.weight.set_data(nd.array(
        onp.random.RandomState(seed).randn(13, 5).astype("float32")))
    return net


def test_eager_hybridized_and_compiled_give_one_loss(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")
    rng = onp.random.RandomState(5)
    x = nd.array(rng.randn(8, 5).astype("float32"))
    y = nd.array(rng.randint(0, 13, (8,)), dtype="int32")
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    net = _dense_net()
    with autograd.record():
        eager = ce(net(x), y).mean()
    eager.backward()
    eager_grad = net.weight.grad().asnumpy()

    net = _dense_net()
    net.hybridize()
    hybrid_ce = gluon.loss.SoftmaxCrossEntropyLoss()
    hybrid_ce.hybridize()
    with autograd.record():
        hybrid = hybrid_ce(net(x), y).mean()
    hybrid.backward()

    net2 = _dense_net()
    net2.hybridize()
    trainer = gluon.Trainer(net2.collect_params(), "sgd",
                            {"learning_rate": 0.5}, kvstore="tpu")
    before = _fused()
    step = trainer.compile_step(net2, lambda n, x, y: ce(n(x), y).mean())
    compiled = step(x, y, batch_size=1)
    assert step.last_step_compiled, step.last_fallback_reason
    assert _fused() == before + 1

    onp.testing.assert_allclose(hybrid.asnumpy(), eager.asnumpy(), rtol=1e-6)
    onp.testing.assert_allclose(compiled.asnumpy(), eager.asnumpy(),
                                rtol=1e-6)
    onp.testing.assert_allclose(net.weight.grad().asnumpy(), eager_grad,
                                rtol=1e-5, atol=1e-7)
    # the compiled step applied the same gradient
    onp.testing.assert_allclose(
        net2.weight.data().asnumpy(),
        _dense_net().weight.data().asnumpy() - 0.5 * eager_grad,
        rtol=1e-5, atol=1e-6)


# -- transformer_lm ----------------------------------------------------------
def test_masked_nll_takes_the_same_function():
    logits = _logits((2, 6, 19), jnp.float32)
    labels = jnp.asarray(onp.where(
        onp.random.RandomState(6).rand(2, 6) < 0.4, -1,
        onp.asarray(_labels((2, 6), 19))), jnp.int32)
    before = _fused()
    nll, valid = transformer_lm._masked_nll(logits, labels)
    assert _fused() == before + 1
    want = jnp.where(labels >= 0,
                     reference(logits, jnp.maximum(labels, 0)), 0.0)
    onp.testing.assert_array_equal(onp.asarray(valid),
                                   onp.asarray(labels >= 0))
    onp.testing.assert_array_equal(onp.asarray(nll), onp.asarray(want))
    grad = jax.grad(lambda l: transformer_lm._masked_nll(l, labels)[0].sum())(
        logits)
    assert not onp.asarray(grad)[onp.asarray(labels) < 0].any()
