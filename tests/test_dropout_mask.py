"""The program's one dropout keep mask (``ops.random.keep_mask``): a pure
function of (key, index).  Its statistics, that ``Dropout`` gives the same
mask for a key eager, hybridized, through ``Trainer.compile_step`` and under
a ``dp=4`` mesh, that the backward regenerates it and keeps no array of the
operand's shape, and that no threefry bits are drawn for it anywhere."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import rnn as rnn_ops
from mxnet_tpu.ops.random import keep_mask

KEY = jnp.asarray([0x1234ABCD, 0x0F1E2D3C], jnp.uint32)
HIDDEN = (16384, 768)             # a BERT cell's tokens x width
RANK4 = (4, 6, 512, 256)


def _mask(shape, keep, key=KEY):
    return onp.asarray(keep_mask(key, shape, keep))


# -- the mask alone ---------------------------------------------------------
@pytest.mark.parametrize("shape", [HIDDEN, RANK4], ids=["hidden", "rank4"])
@pytest.mark.parametrize("p", [0.1, 0.5])
def test_keep_rate_inside_four_sigma(p, shape):
    mask = _mask(shape, 1.0 - p)
    assert mask.shape == shape and mask.dtype == onp.bool_
    sigma = math.sqrt(p * (1.0 - p) / mask.size)
    assert abs(mask.mean() - (1.0 - p)) < 4.0 * sigma


def _corr(a, b):
    a = a.astype(onp.float64) - a.mean()
    b = b.astype(onp.float64) - b.mean()
    return float((a * b).mean() / math.sqrt((a * a).mean() * (b * b).mean()))


@pytest.mark.parametrize("p", [0.1, 0.5])
@pytest.mark.parametrize("pair", ["rows", "columns", "leading", "keys"])
def test_neighbours_and_sites_are_uncorrelated(pair, p):
    if pair == "keys":                 # two sites of one step: split keys
        k1, k2 = jax.random.split(jax.random.PRNGKey(11))
        a, b = _mask(HIDDEN, 1.0 - p, k1), _mask(HIDDEN, 1.0 - p, k2)
    elif pair == "leading":
        mask = _mask(RANK4, 1.0 - p)
        a, b = mask[:, :-1], mask[:, 1:]
    else:
        mask = _mask(HIDDEN, 1.0 - p)
        a, b = ((mask[:-1], mask[1:]) if pair == "rows"
                else (mask[:, :-1], mask[:, 1:]))
    assert abs(_corr(a, b)) < 5e-3


@pytest.mark.parametrize("shape", [(), (7,), (5, 3), (2, 3, 8, 16),
                                   (2, 1, 3, 8, 16)],
                         ids=lambda s: "x".join(map(str, s)) or "scalar")
def test_leading_axes_fold_into_the_head_word(shape):
    """Any rank is the rank-3 mask of the kernels with the leading axes
    flattened row-major: no two elements share an index triple."""
    mask = _mask(shape, 0.5)
    assert mask.shape == shape
    rows, cols = ((1,) * 2 + shape)[-2:]
    flat = onp.asarray(pk.dropout_keep_mask(
        KEY, int(onp.prod(shape[:-2], dtype=onp.int64)), rows, cols, 0.5))
    assert (mask.reshape(flat.shape) == flat).all()


def test_typed_and_raw_keys_agree_and_keys_differ():
    typed = jax.random.key(3)
    raw = jax.random.key_data(typed)
    assert (_mask((64, 128), 0.9, typed) == _mask((64, 128), 0.9, raw)).all()
    assert (_mask((64, 128), 0.9, raw)
            != _mask((64, 128), 0.9, raw + jnp.uint32(1))).any()


# -- the operator -----------------------------------------------------------
def _drop(x, key=KEY, **attrs):
    attrs.setdefault("training", True)
    return nn_ops.dropout(x, key, **attrs)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kept_elements_are_scaled_in_the_operands_dtype(dtype):
    x = jnp.asarray(onp.random.RandomState(0).randn(6, 32, 64), dtype)
    y = _drop(x, p=0.1)
    assert y.dtype == dtype
    want = jnp.where(keep_mask(KEY, x.shape, 0.9), x, 0) * (1.0 / 0.9)
    assert (onp.asarray(y, onp.float32) == onp.asarray(want, onp.float32)).all()


@pytest.mark.parametrize("axes", [(0,), (1,), (0, 2)], ids=str)
def test_axes_broadcast_one_mask(axes):
    x = jnp.ones((4, 6, 32), jnp.float32)
    y = onp.asarray(_drop(x, p=0.5, axes=axes))
    shape = tuple(1 if i in axes else s for i, s in enumerate(x.shape))
    want = onp.broadcast_to(_mask(shape, 0.5), x.shape)
    assert ((y != 0) == want).all() and 0 < want.mean() < 1


@pytest.mark.parametrize("attrs,drops", [
    (dict(p=0.0, training=True), False),
    (dict(p=0.5, training=False), False),
    (dict(p=0.5, training=False, mode="always"), True),
    (dict(p=0.5, training=True), True),
], ids=["p0", "inference", "always", "training"])
def test_when_it_drops(attrs, drops):
    x = jnp.ones((8, 128), jnp.float32)
    y = onp.asarray(_drop(x, **attrs))
    assert (y == 0).any() == drops
    if not drops:
        assert (y == 1).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gradient_is_the_cotangent_under_the_same_mask(dtype):
    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 32, 64), dtype)
    ct = jnp.asarray(rng.randn(4, 32, 64), dtype)
    y, vjp = jax.vjp(lambda x: _drop(x, p=0.1), x)
    (grad,) = vjp(ct)
    want = jnp.where(keep_mask(KEY, x.shape, 0.9), ct, 0) * (1.0 / 0.9)
    assert grad.dtype == dtype
    assert (onp.asarray(grad, onp.float32)
            == onp.asarray(want, onp.float32)).all()
    # second order: dropout is linear, so its vjp differentiates too
    hess = jax.grad(lambda c: (vjp(c)[0].astype(jnp.float32) ** 2).sum())(ct)
    assert onp.isfinite(onp.asarray(hess, onp.float32)).all()


def test_backward_keeps_no_array_of_the_operands_shape():
    x = jnp.ones((16, 256), jnp.bfloat16)
    _, vjp = jax.vjp(lambda x: _drop(x, p=0.1), x)
    kept = [leaf.shape for leaf in jax.tree_util.tree_leaves(vjp)
            if hasattr(leaf, "shape")]
    assert kept and all(onp.prod(s) <= 2 for s in kept), kept


# -- one mask for a key, however the program runs ---------------------------
class _Scaled(gluon.HybridBlock):
    """``Dropout(w * x)``: the output shows the mask, w's gradient the
    backward's."""

    def __init__(self, p):
        super().__init__()
        self.w = gluon.Parameter("w", shape=(1,), init=mx.init.One())
        self.drop = gluon.nn.Dropout(p)

    def forward(self, x):
        return self.drop(x * self.w.data())


def _site_key(seed, staged):
    """The key ``gluon.nn.Dropout`` draws first after ``mx.random.seed``:
    the chain's first subkey, split once more by every staged program it
    passes through on its way to the site (the hybridized block; the
    compiled step around it)."""
    key = jax.random.split(jax.random.PRNGKey(seed))[1]
    for _ in range(staged):
        key = jax.random.split(key)[1]
    return key


@pytest.mark.parametrize("how", ["eager", "hybridized", "compile_step",
                                 "compile_step_dp4"])
def test_same_key_same_mask_however_it_runs(how, monkeypatch):
    p, shape, seed = 0.3, (8, 16, 128), 41
    x = nd.array(onp.random.RandomState(2).rand(*shape).astype("float32")
                 + 0.5)
    net = _Scaled(p)
    net.initialize()
    if how != "eager":
        net.hybridize()
    if how.startswith("compile_step"):
        monkeypatch.setenv("MXNET_SPMD_MESH",
                           "dp=4" if how.endswith("dp4") else "off")
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 1.0}, kvstore="tpu")
        step = trainer.compile_step(net, lambda n, x: n(x))
        step(x, batch_size=1)              # staging draws keys of its own
        assert step.last_step_compiled, step.last_fallback_reason
        if how.endswith("dp4"):
            assert step.mesh.shape["dp"] == 4
        net.w.set_data(nd.ones((1,)))
        mx.random.seed(seed)
        out = step(x, batch_size=1).asnumpy()
        grad = 1.0 - float(net.w.data().asscalar())   # lr 1, w was 1
    else:
        mx.random.seed(seed)
        with autograd.record():
            y = net(x)
        y.backward()
        out, grad = y.asnumpy(), float(net.w.grad().asscalar())
    staged = {"eager": 0, "hybridized": 1}.get(how, 2)
    mask = _mask(shape, 1.0 - p, _site_key(seed, staged))
    want = onp.where(mask, x.asnumpy(), 0.0) * onp.float32(1.0 / (1.0 - p))
    onp.testing.assert_array_equal(out != 0, mask)
    onp.testing.assert_allclose(out, want, rtol=1e-6)
    onp.testing.assert_allclose(grad, want.sum(), rtol=1e-4)


# -- no threefry bits for a mask --------------------------------------------
def _primitives(jaxpr, found=None):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _bits_drawn(closed):
    """Equations that draw random bits (a key split or fold is not one)."""
    return [e for e in _primitives(closed.jaxpr)
            if e.primitive.name in ("random_bits", "threefry2x32",
                                    "rng_bit_generator", "rng_uniform")]


def _rnn_with_dropout(x, key):
    rng = onp.random.RandomState(3)
    h0 = jnp.zeros((2, 4, 8), jnp.float32)
    weights = []
    for layer in range(2):
        for shape in ((8, 16 if layer == 0 else 8), (8, 8), (8,), (8,)):
            weights.append(jnp.asarray(rng.randn(*shape) * 0.1, jnp.float32))
    return rnn_ops.rnn_fused([x, h0, *weights, key], mode="rnn_tanh",
                             hidden_size=8, num_layers=2, dropout=0.5)[0]


@pytest.mark.parametrize("op", ["Dropout", "RNN"])
def test_the_operators_draw_no_random_bits(op):
    if op == "Dropout":
        fn, x = (lambda x, k: _drop(x, k, p=0.1)), jnp.ones((16, 128))
    else:
        fn, x = _rnn_with_dropout, jnp.ones((5, 4, 16), jnp.float32)
    closed = jax.make_jaxpr(jax.value_and_grad(
        lambda x, k: fn(x, k).sum()))(x, KEY)
    assert not _bits_drawn(closed)
    assert onp.isfinite(onp.asarray(fn(x, KEY))).all()


def test_bert_train_step_holds_only_key_splits(monkeypatch):
    """The lowered one-layer BERT step: every site's key is a split of the
    step's key (two words each), and nothing draws bits of an activation's
    shape from them."""
    from mxnet_tpu import program_store

    monkeypatch.setenv("MXNET_SPMD_MESH", "off")
    built = []
    real_build = program_store.build

    def build(name, jitted, lower_args, *a, **kw):
        built.append((jitted, lower_args))
        return real_build(name, jitted, lower_args, *a, **kw)

    monkeypatch.setattr(program_store, "build", build)
    net = bert.BERTModel(vocab_size=64, units=128, mlp_units=256,
                         num_layers=1, num_heads=2, max_len=16, dropout=0.1)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3}, kvstore="tpu")
    step = trainer.compile_step(net, lambda n, x: (n(x) ** 2).mean())
    toks = nd.array(onp.random.RandomState(4).randint(0, 64, (4, 16)),
                    dtype="int32")
    assert onp.isfinite(float(step(toks, batch_size=4).asscalar()))
    assert step.last_step_compiled, step.last_fallback_reason
    (jitted, lower_args), = built
    closed = jax.make_jaxpr(jitted)(*lower_args)
    assert not _bits_drawn(closed)
    # the hybridized net's key, then one a site: embeddings, the attention
    # core and the two hidden dropouts
    splits = [e for e in _primitives(closed.jaxpr)
              if e.primitive.name == "random_split"]
    assert len(splits) == 5
    # in the lowered module threefry works on a key's words only
    text = jitted.lower(*lower_args).as_text()
    calls = [line for line in text.splitlines()
             if "call @threefry2x32" in line]
    assert calls
    for line in calls:
        assert set(re.findall(r"tensor<([^>]*)>", line)) <= {"ui32", "2xui32"}
