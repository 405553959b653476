"""Extension/plugin system tests.

Reference analog: tests/python/unittest/test_extensions.py (MXLoadLib
custom ops / passes / subgraph backends from example/extensions/*).  Here
the extension surface is mx.library: register_op (custom op with optional
custom VJP, visible in mx.nd immediately, working eagerly + under autograd
+ hybridized), register_backend (optimize_for transform), and load()
(import an extension module by path).
"""
import os
import subprocess
import sys
import textwrap

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import library
from mxnet_tpu.gluon import nn


def test_register_custom_op_eager_and_namespaces():
    import jax.numpy as jnp

    library.register_op("ext_square_plus", num_inputs=1)(
        lambda x, c=0.0: x * x + c)
    x = mx.nd.array(onp.array([1.0, 2.0, 3.0], onp.float32))
    out = mx.nd.ext_square_plus(x, c=1.0)
    assert onp.allclose(out.asnumpy(), [2.0, 5.0, 10.0])
    # visible in npx too (already-imported module gets poked)
    assert onp.allclose(mx.npx.ext_square_plus(x).asnumpy(), [1.0, 4.0, 9.0])


def test_custom_op_autograd_default_vjp():
    """No explicit grad: jax autodiff supplies the VJP through the tape."""
    library.register_op("ext_cube", num_inputs=1)(lambda x: x * x * x)
    x = mx.nd.array(onp.array([1.0, 2.0], onp.float32))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.ext_cube(x)
    y.backward()
    assert onp.allclose(x.grad.asnumpy(), 3.0 * onp.array([1.0, 4.0]))


def test_custom_op_custom_vjp():
    """Explicit grad callback (the lib_custom_op backward analog)."""
    import jax.numpy as jnp

    calls = []

    def grad(res, ct):
        (x,), _out = res
        calls.append(1)
        return (ct * 2.0 * x,)          # d/dx x^2

    library.register_op("ext_sq_customgrad", grad=grad, num_inputs=1)(
        lambda x: x * x)
    x = mx.nd.array(onp.array([3.0, 4.0], onp.float32))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.ext_sq_customgrad(x)
    y.backward()
    assert onp.allclose(x.grad.asnumpy(), [6.0, 8.0])
    assert calls, "custom grad was not invoked"


def test_custom_vjp_op_with_attr_kwargs():
    """Custom-VJP ops accept attr kwargs (attrs close over the vjp core)."""
    def grad(res, ct):
        (x,), _out = res
        return (ct * 2.0 * x,)

    scaled_sq = library.register_op("ext_sq_attr", grad=grad, num_inputs=1)(
        lambda x, s=1.0: x * x * s)
    x = mx.nd.array(onp.array([2.0, 3.0], onp.float32))
    assert onp.allclose(mx.nd.ext_sq_attr(x, s=3.0).asnumpy(), [12.0, 27.0])
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.ext_sq_attr(x, s=3.0)
    y.backward()
    assert onp.allclose(x.grad.asnumpy(), [4.0, 6.0])

    # the returned module-level symbol carries the custom VJP too
    import jax
    import jax.numpy as jnp

    g = jax.grad(lambda a: jnp.sum(scaled_sq(a, s=5.0)))(
        jnp.asarray([1.0, 2.0]))
    assert onp.allclose(onp.asarray(g), [2.0, 4.0])  # custom grad ignores s


def test_custom_op_hybridized_block():
    library.register_op("ext_shift", num_inputs=1)(lambda x, s=1.0: x + s)

    from mxnet_tpu import gluon

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.dense = nn.Dense(3, in_units=3)

        def forward(self, x):
            return mx.nd.ext_shift(self.dense(x), s=2.0)

    net = Net()
    net.initialize(mx.init.Constant(0.1))
    x = mx.nd.ones((2, 3))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    assert onp.allclose(eager, hybrid, atol=1e-6)
    assert onp.allclose(hybrid, 0.3 + 2.0, atol=1e-6)


def test_register_backend_optimize_for():
    """optimize_for('testback') routes compilation through the registered
    transform (the subgraph-backend plugin analog)."""
    seen_flags = {}

    @library.register_backend("testback")
    def testback(fn, **flags):
        seen_flags.update(flags)

        def wrapped(param_arrays, input_arrays, rng_key):
            outs, muts = fn(param_arrays, input_arrays, rng_key)
            return [o * 2.0 for o in outs], muts

        return wrapped

    net = nn.Dense(2, in_units=2)
    net.initialize(mx.init.Constant(0.5))
    x = mx.nd.ones((1, 2))
    base = net(x).asnumpy()
    out = net.optimize_for(x, backend="testback", myflag=7)
    assert onp.allclose(out.asnumpy(), base * 2.0, atol=1e-6)
    assert seen_flags.get("myflag") == 7


def test_backend_unknown_raises():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    x = mx.nd.ones((1, 2))
    with pytest.raises(KeyError):
        net.optimize_for(x, backend="no_such_backend")


def test_load_extension_module(tmp_path):
    ext = tmp_path / "my_ext.py"
    ext.write_text(textwrap.dedent("""
        from mxnet_tpu import library

        @library.register_op("ext_loaded_scale", num_inputs=1)
        def ext_loaded_scale(x, k=3.0):
            return x * k
    """))
    mod = library.load(str(ext), verbose=False)
    assert hasattr(mod, "ext_loaded_scale")
    x = mx.nd.array(onp.array([1.0, 2.0], onp.float32))
    assert onp.allclose(mx.nd.ext_loaded_scale(x).asnumpy(), [3.0, 6.0])


def test_load_missing_path_raises():
    with pytest.raises(ValueError):
        library.load("/nonexistent/ext.py")


def test_example_extension_loads_and_runs():
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "example", "extensions",
        "custom_op_ext.py")
    library.load(path, verbose=False)
    a = mx.nd.ones((2, 3))
    b = mx.nd.ones((3, 4))
    assert onp.allclose(mx.nd.my_gemm(a, b).asnumpy(), 3.0)
    x = mx.nd.array(onp.array([-1.0, 2.0], onp.float32))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.my_relu(x)
    y.backward()
    assert onp.allclose(y.asnumpy(), [0.0, 2.0])
    assert onp.allclose(x.grad.asnumpy(), [0.0, 1.0])

    # the example bf16 backend compiles and approximates the fp32 result
    net = nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    xin = mx.nd.random.normal(shape=(2, 8))
    ref = net(xin).asnumpy()
    out = net.optimize_for(xin, backend="example_bf16")
    assert onp.allclose(out.asnumpy(), ref, atol=3e-2)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(os.environ)
_ENV["JAX_PLATFORMS"] = "cpu"
_ENV.pop("PYTHONPATH", None)


def test_graph_pass_extension_example():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "example", "extensions",
                                      "graph_pass_ext.py")],
        capture_output=True, text=True, timeout=420, env=_ENV)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "OK" in out.stdout


def test_subgraph_extension_example():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "example", "extensions",
                                      "subgraph_ext.py")],
        capture_output=True, text=True, timeout=420, env=_ENV)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "Activation" not in out.stdout.split("fused graph ops")[-1]
    assert "OK" in out.stdout
