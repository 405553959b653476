"""Per-op eager jit cache (round-5 VERDICT Weak #4; SURVEY §7 "per-op
jit-compiled XLA computation with a compilation cache").

MXNET_EAGER_JIT=2 forces the path on CPU.  The battery asserts: numeric
equivalence with plain dispatch across representative op families, cache
reuse (one trace per (op, attrs) across calls), permanent fallback for
ops whose python body cannot trace, autograd equivalence through the
jitted forward, and that hybridized traces never route through an inner
jit (fusion preservation).  Reference analog: engine operator bulking,
``src/engine/threaded_engine.h:507-528``.
"""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, config, nd
from mxnet_tpu.ndarray import ndarray as ndmod


@pytest.fixture
def eager_jit(monkeypatch):
    monkeypatch.setenv("MXNET_EAGER_JIT", "2")
    config.refresh("MXNET_EAGER_JIT")
    for store in (ndmod._EAGER_JIT_CACHE, ndmod._EAGER_JIT_BAD,
                  ndmod._EAGER_JIT_KEYCOUNT):
        store.clear()
    yield
    import os

    os.environ.pop("MXNET_EAGER_JIT", None)    # tests flip it mid-test
    config.refresh("MXNET_EAGER_JIT")
    for store in (ndmod._EAGER_JIT_CACHE, ndmod._EAGER_JIT_BAD,
                  ndmod._EAGER_JIT_KEYCOUNT):
        store.clear()


def _battery():
    rng = onp.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(onp.float32))
    w = nd.array(rng.randn(3, 8).astype(onp.float32))
    b = nd.array(rng.randn(3).astype(onp.float32))
    img = nd.array(rng.randn(2, 3, 8, 8).astype(onp.float32))
    k = nd.array(rng.randn(4, 3, 3, 3).astype(onp.float32))
    return [
        ("add", lambda: x + x),
        ("fc", lambda: nd.FullyConnected(x, w, b, num_hidden=3)),
        ("softmax", lambda: nd.softmax(x, axis=-1)),
        ("conv", lambda: nd.Convolution(img, k, kernel=(3, 3), pad=(1, 1),
                                        num_filter=4, no_bias=True)),
        ("norm", lambda: nd.norm(x, ord=2)),
        ("topk", lambda: nd.topk(x, k=3)),
        ("mean", lambda: x.mean(axis=1)),
    ]


def test_jitted_eager_matches_plain_dispatch(eager_jit):
    import os

    jitted = {}
    for name, fn in _battery():
        jitted[name] = fn().asnumpy()
    os.environ["MXNET_EAGER_JIT"] = "0"
    config.refresh("MXNET_EAGER_JIT")
    for name, fn in _battery():
        onp.testing.assert_allclose(fn().asnumpy(), jitted[name],
                                    rtol=1e-5, atol=1e-6, err_msg=name)


def test_cache_reuse_one_trace_per_attrs(eager_jit):
    from mxnet_tpu.ops.registry import get_op

    schema = get_op("softmax")
    traces = {"n": 0}
    orig = schema.fn

    def counting(*a, **k):
        traces["n"] += 1
        return orig(*a, **k)

    schema.fn = counting
    try:
        x = nd.array(onp.random.RandomState(1).randn(4, 6).astype(onp.float32))
        for _ in range(5):
            nd.softmax(x, axis=-1)
        # one jit trace total, not five executions of the python body
        assert traces["n"] == 1
        nd.softmax(x, axis=0)          # different attrs: one more trace
        assert traces["n"] == 2
        nd.softmax(x, axis=0)
        assert traces["n"] == 2
    finally:
        schema.fn = orig
        ndmod._EAGER_JIT_CACHE.clear()


def test_unjittable_op_falls_back_permanently(eager_jit):
    from mxnet_tpu.ops import registry

    calls = {"n": 0}

    @registry.register("_test_dynamic_shape_op", num_inputs=1,
                       differentiable=False)
    def _dyn(data):
        calls["n"] += 1
        import numpy as np

        host = np.asarray(data)          # concretization: fails under trace
        import jax.numpy as jnp

        return jnp.asarray(host[host > 0])

    try:
        x = nd.array(onp.array([-1.0, 2.0, -3.0, 4.0], onp.float32))
        from mxnet_tpu.ndarray.ndarray import invoke

        out = invoke("_test_dynamic_shape_op", [x], {})
        onp.testing.assert_allclose(out.asnumpy(), [2.0, 4.0])
        assert "_test_dynamic_shape_op" in ndmod._EAGER_JIT_BAD
        # second call goes straight to plain dispatch (no re-jit attempt)
        invoke("_test_dynamic_shape_op", [x], {})
    finally:
        registry._OPS.pop("_test_dynamic_shape_op", None)


def test_autograd_through_jitted_forward(eager_jit):
    x = nd.array(onp.random.RandomState(2).randn(4, 5).astype(onp.float32))
    x.attach_grad()
    with autograd.record():
        y = (nd.softmax(x, axis=-1) * nd.softmax(x, axis=-1)).sum()
    y.backward()
    g_jit = x.grad.asnumpy().copy()
    import os

    os.environ["MXNET_EAGER_JIT"] = "0"
    config.refresh("MXNET_EAGER_JIT")
    x.attach_grad()
    with autograd.record():
        y = (nd.softmax(x, axis=-1) * nd.softmax(x, axis=-1)).sum()
    y.backward()
    onp.testing.assert_allclose(g_jit, x.grad.asnumpy(), rtol=1e-5,
                                atol=1e-6)


def test_tracer_inputs_bypass_inner_jit(eager_jit):
    """Inside a hybridized trace the lookup must return None so ops stay
    inline (XLA fusion across op boundaries)."""
    from mxnet_tpu.gluon import nn

    net = nn.Dense(4)
    net.initialize()
    x = nd.array(onp.random.RandomState(3).randn(2, 8).astype(onp.float32))
    net(x)                     # eager shape probe MAY add cache entries
    net.hybridize()
    before_trace = set(ndmod._EAGER_JIT_CACHE)
    net(x)                     # builds + runs the hybridized trace
    net(x)                     # cached-graph re-execution
    # the trace and its re-execution added NO per-op jit entries
    assert set(ndmod._EAGER_JIT_CACHE) == before_trace


def test_input_error_does_not_ban_op(eager_jit):
    """A bad user call (shape mismatch) must not permanently disable the
    jit cache for that op (review finding)."""
    x = nd.array(onp.ones((2, 3), onp.float32))
    y = nd.array(onp.ones((5, 7), onp.float32))
    with pytest.raises(Exception):
        (x + y).asnumpy()
    assert "broadcast_add" not in ndmod._EAGER_JIT_BAD
    out = (x + x).asnumpy()               # still jitted after the bad call
    onp.testing.assert_allclose(out, 2 * onp.ones((2, 3)))
    assert any(k[0] == "broadcast_add" for k in ndmod._EAGER_JIT_CACHE)


def test_attr_cardinality_cutoff(eager_jit):
    """Ops whose attrs vary every call stop being jitted after the
    per-op cutoff instead of compiling forever (review finding)."""
    x = nd.array(onp.random.RandomState(5).randn(200, 4).astype(onp.float32))
    for i in range(ndmod._EAGER_JIT_MAX_PER_OP + 5):
        nd.slice_axis(x, axis=0, begin=i, end=i + 2)
    assert "slice_axis" in ndmod._EAGER_JIT_BAD
    n_keys = sum(1 for k in ndmod._EAGER_JIT_CACHE if k[0] == "slice_axis")
    assert n_keys <= ndmod._EAGER_JIT_MAX_PER_OP


def test_cache_lru_bounded(eager_jit):
    cap = ndmod._EAGER_JIT_MAX_ENTRIES
    assert len(ndmod._EAGER_JIT_CACHE) <= cap


def test_higher_order_grad_through_jitted_ops(eager_jit):
    """create_graph replay must agree with the plain path (the TapeNode
    replay fn is the unjitted body — review finding)."""
    import os

    def d2(flag):
        os.environ["MXNET_EAGER_JIT"] = flag
        config.refresh("MXNET_EAGER_JIT")
        x = nd.array(onp.array([0.3, -0.7, 1.2], onp.float32))
        x.attach_grad()
        with autograd.record():
            y = nd.tanh(x * x)
            g = autograd.grad(y.sum(), [x], create_graph=True)[0]
            gg = g.sum()
        gg.backward()
        return x.grad.asnumpy().copy()

    onp.testing.assert_allclose(d2("2"), d2("0"), rtol=1e-4, atol=1e-5)


def test_multi_output_op_jitted(eager_jit):
    x = nd.array(onp.random.RandomState(4).randn(6, 4).astype(onp.float32))
    outs = nd.split_v2(x, sections=2, axis=0)
    assert len(outs) == 2
    onp.testing.assert_allclose(
        onp.concatenate([o.asnumpy() for o in outs]), x.asnumpy())


def test_default_mode_off_on_cpu():
    """mode 1 (default) must not jit on the CPU backend: the test suite's
    eager path stays plain dispatch (no per-shape compile storms)."""
    if os.environ.get("MXNET_EAGER_JIT") == "2":
        pytest.skip("suite running with eager jit forced on")
    config.refresh("MXNET_EAGER_JIT")
    ndmod._EAGER_JIT_CACHE.clear()
    x = nd.array(onp.ones((3, 3), onp.float32))
    nd.softmax(x, axis=-1)
    assert not ndmod._EAGER_JIT_CACHE


def test_keyless_rng_ops_never_jitted(eager_jit):
    """Ops that draw from the global PRNG chain when ``key`` is omitted
    (the samplers' ``key=None`` default) must stay on plain dispatch:
    tracing the draw would leak a tracer into the chain and bake the key
    into the cached executable (every cache hit returning identical
    "random" numbers).  Caught live on the TPU backend where eager jit
    defaults on."""
    a = nd.random.normal(shape=(16,))
    b = nd.random.normal(shape=(16,))      # second call: chain must be intact
    assert not onp.allclose(a.asnumpy(), b.asnumpy())
    assert not any(k[0] in ("normal", "uniform") for k in ndmod._EAGER_JIT_CACHE)
    u1 = nd.random.uniform(shape=(16,))
    u2 = nd.random.uniform(shape=(16,))
    assert not onp.allclose(u1.asnumpy(), u2.asnumpy())
    # an explicit key is static data: jit is fine there, and the same key
    # must reproduce the same sample through whichever path runs
    import jax

    k = jax.random.PRNGKey(7)
    s1 = nd.random.normal(shape=(8,), key=k)
    s2 = nd.random.normal(shape=(8,), key=k)
    onp.testing.assert_allclose(s1.asnumpy(), s2.asnumpy())


def test_reduction_opt_out_default_and_override(eager_jit, monkeypatch):
    """Single-primitive reductions stay OUT of the per-op cache by
    default (one primitive is already one dispatch, so the cache only
    adds lookup overhead) and the list is overridable through
    MXNET_EAGER_JIT_EXCLUDE (config.py)."""
    x = nd.array(onp.random.RandomState(2).randn(4, 6).astype(onp.float32))
    x.mean(axis=1)
    x.sum(axis=0)
    assert not any(k[0] in ("mean", "sum") for k in ndmod._EAGER_JIT_CACHE)
    nd.softmax(x, axis=-1)               # non-excluded ops still cache
    assert any(k[0] == "softmax" for k in ndmod._EAGER_JIT_CACHE)
    # empty override re-admits the reductions (knob is uncached: takes
    # effect immediately)
    monkeypatch.setenv("MXNET_EAGER_JIT_EXCLUDE", "")
    m_jit = x.mean(axis=1)
    assert any(k[0] == "mean" for k in ndmod._EAGER_JIT_CACHE)
    monkeypatch.delenv("MXNET_EAGER_JIT_EXCLUDE")
    onp.testing.assert_allclose(m_jit.asnumpy(),
                                x.asnumpy().mean(axis=1),
                                rtol=1e-6, atol=1e-7)
