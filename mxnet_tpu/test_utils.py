"""Testing utilities (reference ``python/mxnet/test_utils.py``, 2,602 LoC).

The load-bearing pieces reproduced per SURVEY.md §4: ``default_context`` so
one test file runs on any device, dtype-aware ``assert_almost_equal``,
``rand_ndarray``, finite-difference ``check_numeric_gradient`` against the
autograd tape, and ``check_symbolic_forward/backward`` as the
symbolic-vs-reference oracle.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, List, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as onp

from .base import MXNetError
from .context import Context, cpu, current_context
from .ndarray import NDArray, array

__all__ = [
    "default_context", "set_default_context", "default_dtype",
    "assert_almost_equal", "almost_equal", "same", "rand_ndarray",
    "rand_shape_2d", "rand_shape_3d", "rand_shape_nd", "random_arrays",
    "check_numeric_gradient", "check_symbolic_forward",
    "check_symbolic_backward", "numeric_grad", "environment",
    "default_rtols", "default_atols", "effective_dtype",
    "get_rtol", "get_atol", "get_etol", "get_tolerance", "get_tols",
    "default_numeric_eps", "assert_allclose", "almost_equal_ignore_nan",
    "assert_almost_equal_ignore_nan", "assert_almost_equal_with_err",
    "assert_exception", "same_array", "list_gpus", "np_reduce",
    "random_sample", "random_uniform_arrays", "rand_coord_2d",
    "create_vector", "create_2d_tensor", "compare_ndarray_tuple",
    "compare_optimizer", "check_speed", "assign_each", "assign_each2",
    "collapse_sum_like", "check_gluon_hybridize_consistency",
    "gen_buckets_probs_with_ppf", "chi_square_check", "verify_generator",
    "mean_check", "var_check", "discard_stderr",
]

_DEFAULT_CTX: Optional[Context] = None

# dtype-aware default tolerances (reference test_utils.py:650 rtol/atol maps)
_RTOLS = {onp.dtype(onp.float16): 1e-2, onp.dtype(onp.float32): 1e-4,
          onp.dtype(onp.float64): 1e-7, onp.dtype(onp.int32): 0,
          onp.dtype(onp.int64): 0, onp.dtype(onp.bool_): 0}
_ATOLS = {onp.dtype(onp.float16): 1e-3, onp.dtype(onp.float32): 1e-5,
          onp.dtype(onp.float64): 1e-9, onp.dtype(onp.int32): 0,
          onp.dtype(onp.int64): 0, onp.dtype(onp.bool_): 0}


def default_rtols():
    return dict(_RTOLS)


def default_atols():
    return dict(_ATOLS)


def default_context() -> Context:
    """The context tests run on; switch with set_default_context or the
    MXNET_TEST_DEVICE env var (reference default_context():57)."""
    global _DEFAULT_CTX
    if _DEFAULT_CTX is not None:
        return _DEFAULT_CTX
    from . import config as _config

    dev = _config.get("MXNET_TEST_DEVICE")
    if dev:
        from . import context as ctx_mod

        kind, _, idx = dev.partition(":")
        return getattr(ctx_mod, kind)(int(idx or 0))
    return current_context()


def set_default_context(ctx: Context):
    global _DEFAULT_CTX
    _DEFAULT_CTX = ctx


def default_dtype():
    return onp.float32


def effective_dtype(a):
    if isinstance(a, NDArray):
        return onp.dtype("float16") if str(a.dtype) == "bfloat16" \
            else onp.dtype(a.dtype)
    return onp.asarray(a).dtype


def _host(a):
    if isinstance(a, NDArray):
        return a.asnumpy()
    return onp.asarray(a)


def same(a, b) -> bool:
    return onp.array_equal(_host(a), _host(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False) -> bool:
    a_h, b_h = _host(a), _host(b)
    dt = max(effective_dtype(a), effective_dtype(b),
             key=lambda d: _RTOLS.get(d, 1e-4))
    rtol = _RTOLS.get(dt, 1e-4) if rtol is None else rtol
    atol = _ATOLS.get(dt, 1e-5) if atol is None else atol
    return onp.allclose(a_h, b_h, rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False, use_broadcast=True):
    """Dtype-aware closeness assertion (reference
    test_utils.py:650 assert_almost_equal)."""
    a_h, b_h = _host(a), _host(b)
    dt = max(effective_dtype(a), effective_dtype(b),
             key=lambda d: _RTOLS.get(d, 1e-4))
    rtol = _RTOLS.get(dt, 1e-4) if rtol is None else rtol
    atol = _ATOLS.get(dt, 1e-5) if atol is None else atol
    if not onp.allclose(a_h, b_h, rtol=rtol, atol=atol, equal_nan=equal_nan):
        diff = onp.abs(a_h - b_h)
        rel = diff / (onp.abs(b_h) + atol)
        idx = onp.unravel_index(onp.argmax(rel), rel.shape) if rel.size \
            else ()
        raise AssertionError(
            f"Items are not equal (rtol={rtol}, atol={atol}):\n"
            f" max abs diff {diff.max() if diff.size else 0} "
            f"max rel diff {rel.max() if rel.size else 0} at {idx}\n"
            f" {names[0]}: {a_h.flat[:8]}...\n {names[1]}: {b_h.flat[:8]}...")


def rand_shape_2d(dim0=10, dim1=10, allow_zero_size=False):
    low = 0 if allow_zero_size else 1
    return (onp.random.randint(low, dim0 + 1),
            onp.random.randint(low, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10, allow_zero_size=False):
    low = 0 if allow_zero_size else 1
    return (onp.random.randint(low, dim0 + 1),
            onp.random.randint(low, dim1 + 1),
            onp.random.randint(low, dim2 + 1))


def rand_shape_nd(num_dim, dim=10, allow_zero_size=False):
    low = 0 if allow_zero_size else 1
    return tuple(onp.random.randint(low, dim + 1, size=num_dim))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None, scale=1.0, distribution="uniform") -> NDArray:
    """Random NDArray (reference rand_ndarray:479; sparse stypes fall back
    to dense with zeros at the requested density)."""
    dtype = dtype or onp.float32
    if distribution == "normal":
        data = onp.random.normal(scale=scale, size=shape)
    else:
        data = onp.random.uniform(-scale, scale, size=shape)
    if stype in ("row_sparse", "csr"):
        density = 0.1 if density is None else density
        mask = onp.random.uniform(size=shape) < density
        data = data * mask
    return array(data.astype(dtype), ctx=ctx or default_context())


def random_arrays(*shapes):
    arrays = [onp.random.randn(*s).astype(onp.float32) if s else
              onp.float32(onp.random.randn()) for s in shapes]
    return arrays[0] if len(arrays) == 1 else arrays


def numeric_grad(f, location: Dict[str, onp.ndarray], eps=1e-4):
    """Central finite differences of scalar-valued f (reference
    numeric_grad inside check_numeric_gradient)."""
    grads = {}
    for name, arr in location.items():
        arr = arr.astype(onp.float64)
        g = onp.zeros_like(arr)
        flat = arr.reshape(-1)
        gflat = g.reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            fp = f({k: (arr if k == name else v)
                    for k, v in location.items()})
            flat[i] = orig - eps
            fm = f({k: (arr if k == name else v)
                    for k, v in location.items()})
            flat[i] = orig
            gflat[i] = (fp - fm) / (2 * eps)
        grads[name] = g
    return grads


def check_numeric_gradient(op_name_or_fn, location, aux_states=None,
                           numeric_eps=1e-2, rtol=1e-2, atol=1e-3,
                           grad_nodes=None, ctx=None, attrs=None):
    """Verify autograd gradients against finite differences (reference
    check_numeric_gradient:1038).

    ``op_name_or_fn``: registry op name, or fn(*NDArrays) -> NDArray.
    ``location``: list of numpy arrays or dict name->array.
    ``numeric_eps`` defaults to 1e-2 (not the reference's 1e-4): forward
    evals run in float32 on device, so smaller eps is roundoff-dominated.
    """
    from . import autograd
    from .ndarray.ndarray import invoke

    ctx = ctx or default_context()
    if isinstance(location, dict):
        names = list(location)
        arrays = [onp.asarray(location[n], onp.float64) for n in names]
    else:
        names = [f"arg_{i}" for i in range(len(location))]
        arrays = [onp.asarray(a, onp.float64) for a in location]
    grad_nodes = grad_nodes or names

    if isinstance(op_name_or_fn, str):
        def fn(*nds):
            return invoke(op_name_or_fn, list(nds), dict(attrs or {}))
    else:
        fn = op_name_or_fn

    # analytic grads via the tape (sum(output) as the scalar head)
    nds = [array(a.astype(onp.float32), ctx=ctx) for a in arrays]
    for nd_arr, n in zip(nds, names):
        if n in grad_nodes:
            nd_arr.attach_grad()
    with autograd.record():
        out = fn(*nds)
        if isinstance(out, (list, tuple)):
            out = out[0]
        head = out.sum()
    head.backward()
    analytic = {n: nd_arr.grad.asnumpy()
                for nd_arr, n in zip(nds, names) if n in grad_nodes}

    # numeric grads on host float64
    def scalar_f(loc):
        outs = fn(*[array(loc[n].astype(onp.float32), ctx=ctx)
                    for n in names])
        if isinstance(outs, (list, tuple)):
            outs = outs[0]
        return float(outs.sum().asscalar())

    numeric = numeric_grad(scalar_f, dict(zip(names, arrays)),
                           eps=numeric_eps)
    for n in grad_nodes:
        assert_almost_equal(analytic[n], numeric[n], rtol=rtol, atol=atol,
                            names=(f"analytic d/d{n}", f"numeric d/d{n}"))


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=1e-5,
                           ctx=None, dtype=onp.float32):
    """Bind a symbol, run forward, compare with expected numpy outputs
    (reference check_symbolic_forward)."""
    ctx = ctx or default_context()
    args = sym.list_arguments()
    if isinstance(location, dict):
        arg_arrays = {k: array(onp.asarray(v, dtype), ctx=ctx)
                      for k, v in location.items()}
    else:
        arg_arrays = {a: array(onp.asarray(v, dtype), ctx=ctx)
                      for a, v in zip(args, location)}
    exe = sym.bind(ctx, arg_arrays, grad_req="null")
    outputs = exe.forward(is_train=False)
    if not isinstance(expected, (list, tuple)):
        expected = [expected]
    for out, exp in zip(outputs, expected):
        assert_almost_equal(out, exp, rtol=rtol, atol=atol)
    return outputs


def check_symbolic_backward(sym, location, out_grads, expected, rtol=1e-4,
                            atol=1e-5, grad_req="write", ctx=None,
                            dtype=onp.float32):
    """Bind, forward+backward, compare arg grads (reference
    check_symbolic_backward)."""
    from .ndarray import zeros

    ctx = ctx or default_context()
    args = sym.list_arguments()
    if isinstance(location, dict):
        arg_arrays = {k: array(onp.asarray(v, dtype), ctx=ctx)
                      for k, v in location.items()}
    else:
        arg_arrays = {a: array(onp.asarray(v, dtype), ctx=ctx)
                      for a, v in zip(args, location)}
    grads = {a: zeros(arg_arrays[a].shape, ctx=ctx) for a in args}
    exe = sym.bind(ctx, arg_arrays, args_grad=grads, grad_req=grad_req)
    exe.forward(is_train=True)
    exe.backward([array(onp.asarray(g, dtype), ctx=ctx)
                  for g in (out_grads if isinstance(out_grads, (list, tuple))
                            else [out_grads])])
    if isinstance(expected, dict):
        items = expected.items()
    else:
        items = zip(args, expected)
    for name, exp in items:
        assert_almost_equal(grads[name], exp, rtol=rtol, atol=atol,
                            names=(f"grad[{name}]", "expected"))
    return grads


def check_consistency(sym, location, dtypes=("float32", "float16",
                                             "bfloat16"),
                      grad_req="write", tol=None, with_backward=True):
    """Run the same Symbol across execution modes and dtypes and compare
    against the highest-precision result.

    TPU analog of the reference's GPU-vs-CPU oracle
    (python/mxnet/test_utils.py:1304 check_consistency — same symbol run
    per (ctx, dtype) and cross-compared).  Contexts here are execution
    MODES: eager op-by-op interpretation vs the whole-graph jit the
    hybridized path uses; dtype sweep covers fp32/fp16/bf16 with
    dtype-aware tolerances.  Ground truth = float32 whole-graph jit.

    ``location``: dict arg-name -> numpy array (float inputs get cast per
    dtype).  Returns the ground-truth outputs.
    """
    import jax

    from .symbol.symbol import execute_graph

    if tol is None:
        tol = {"float32": (1e-5, 1e-6), "float16": (1e-2, 1e-3),
               "bfloat16": (5e-2, 5e-3)}
    args = sym.list_arguments()
    base = {k: onp.asarray(v) for k, v in location.items()}
    missing = [a for a in args if a not in base]
    assert not missing, f"location missing args: {missing}"

    def run(dtype, jitted):
        feed = {}
        for k, v in base.items():
            arr = jnp.asarray(v)
            if onp.issubdtype(v.dtype, onp.floating):
                arr = arr.astype(dtype)
            feed[k] = arr
        fn = lambda f: execute_graph(sym._outputs, f)
        if jitted:
            fn = jax.jit(fn)
        outs = fn(feed)
        grads = None
        if with_backward and grad_req != "null":
            float_keys = [k for k in feed
                          if jnp.issubdtype(feed[k].dtype, jnp.floating)]

            def loss(fl):
                outs = execute_graph(sym._outputs, {**feed, **fl})
                return sum(jnp.sum(o.astype(jnp.float32)) for o in outs
                           if jnp.issubdtype(o.dtype, jnp.floating))

            gfn = jax.grad(loss)
            if jitted:
                gfn = jax.jit(gfn)
            grads = gfn({k: feed[k] for k in float_keys})
        return outs, grads

    gt_outs, gt_grads = run("float32", jitted=True)
    for dtype in dtypes:
        for jitted in (False, True):
            if dtype == "float32" and jitted:
                continue                      # that's the ground truth
            outs, grads = run(dtype, jitted)
            rtol, atol = tol.get(dtype, (1e-2, 1e-3))
            mode = "jit" if jitted else "eager"
            for i, (o, g) in enumerate(zip(outs, gt_outs)):
                assert_almost_equal(
                    onp.asarray(o, onp.float32), onp.asarray(g, onp.float32),
                    rtol=rtol, atol=atol,
                    names=(f"{dtype}/{mode} out{i}", "float32/jit"))
            if grads is not None and gt_grads is not None:
                for k in gt_grads:
                    assert_almost_equal(
                        onp.asarray(grads[k], onp.float32),
                        onp.asarray(gt_grads[k], onp.float32),
                        rtol=max(rtol, 1e-4), atol=max(atol, 1e-4),
                        names=(f"{dtype}/{mode} grad[{k}]", "float32/jit"))
    return gt_outs


@contextlib.contextmanager
def environment(*args):
    """Temporarily set env vars: environment(name, value) or
    environment({name: value, ...}) (reference common.py with_environment)."""
    if len(args) == 2:
        updates = {args[0]: args[1]}
    else:
        (updates,) = args
    # graftlint: disable=env-discipline -- save/restore of arbitrary
    # caller-chosen vars (the context manager's whole job), not a knob read
    saved = {k: os.environ.get(k) for k in updates}
    try:
        for k, v in updates.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# extended reference helpers (reference test_utils.py — the functions
# migration users' own test suites call)
# ---------------------------------------------------------------------------

def _np(a):
    return a.asnumpy() if isinstance(a, NDArray) else onp.asarray(a)


def get_rtol(rtol=None, dtype=None):
    """Dtype-aware default rtol (reference test_utils.py get_rtol)."""
    if rtol is not None:
        return rtol
    return _RTOLS.get(onp.dtype(dtype or onp.float32), 1e-4)


def get_atol(atol=None, dtype=None):
    if atol is not None:
        return atol
    return _ATOLS.get(onp.dtype(dtype or onp.float32), 1e-5)


def get_etol(etol=None):
    """Allowed fraction of mismatching elements (reference get_etol)."""
    return 0.0 if etol is None else etol


def get_tolerance(arr, rtol, atol):
    dt = getattr(arr, "dtype", onp.float32)
    return get_rtol(rtol, dt), get_atol(atol, dt)


def get_tols(x, y, rtol=None, atol=None):
    """Joint tolerance of a pair: the looser of the two dtypes
    (reference get_tols)."""
    return (max(get_rtol(rtol, x.dtype), get_rtol(rtol, y.dtype)),
            max(get_atol(atol, x.dtype), get_atol(atol, y.dtype)))


def default_numeric_eps(dtype=onp.float32):
    """Finite-difference eps per dtype (reference default_numeric_eps)."""
    return {onp.dtype(onp.float16): 1e-1, onp.dtype(onp.float32): 1e-3,
            onp.dtype(onp.float64): 1e-4}.get(onp.dtype(dtype), 1e-3)


def assert_allclose(a, b, rtol=1e-7, atol=0, equal_nan=True):
    """Thin numpy wrapper accepting NDArrays (reference assert_allclose)."""
    onp.testing.assert_allclose(_np(a), _np(b), rtol=rtol, atol=atol,
                                equal_nan=equal_nan)


def almost_equal_ignore_nan(a, b, rtol=None, atol=None):
    a, b = _np(a).copy(), _np(b).copy()
    nan = onp.isnan(a)
    if not (nan == onp.isnan(b)).all():
        return False
    a[nan] = 0
    b[nan] = 0
    return onp.allclose(a, b, get_rtol(rtol, a.dtype),
                        get_atol(atol, a.dtype))


def assert_almost_equal_ignore_nan(a, b, rtol=None, atol=None,
                                   names=("a", "b")):
    """Equality where NaNs must coincide and are otherwise ignored
    (reference assert_almost_equal_ignore_nan)."""
    a_, b_ = _np(a).copy(), _np(b).copy()
    nan_a, nan_b = onp.isnan(a_), onp.isnan(b_)
    onp.testing.assert_array_equal(nan_a, nan_b,
                                   err_msg=f"NaN patterns differ: {names}")
    a_[nan_a] = 0
    b_[nan_b] = 0
    onp.testing.assert_allclose(a_, b_, get_rtol(rtol, a_.dtype),
                                get_atol(atol, a_.dtype))


def assert_almost_equal_with_err(a, b, rtol=None, atol=None, etol=None,
                                 names=("a", "b")):
    """Allow a FRACTION etol of out-of-tolerance elements (reference
    assert_almost_equal_with_err)."""
    a_, b_ = _np(a), _np(b)
    rtol, atol, etol = get_rtol(rtol, a_.dtype), get_atol(atol, a_.dtype), \
        get_etol(etol)
    bad = ~onp.isclose(a_, b_, rtol=rtol, atol=atol, equal_nan=True)
    frac = bad.sum() / max(bad.size, 1)
    if frac > etol:
        onp.testing.assert_allclose(a_, b_, rtol=rtol, atol=atol,
                                    err_msg=f"{names}: {frac:.4f} > "
                                            f"etol {etol}")


def assert_exception(fn, exception_type, *args, **kwargs):
    """fn(*args) must raise exception_type (reference assert_exception)."""
    try:
        fn(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError(f"{fn} did not raise {exception_type.__name__}")


def same_array(a, b) -> bool:
    """True when two NDArrays share the same device buffer: mutating one
    is visible through the other (reference same_array probes by
    mutation; buffers here are functional, so identity of the backing
    jax.Array is the faithful notion of 'same array')."""
    return a is b or a._data is b._data


def list_gpus():
    """Indices of visible CUDA GPUs — none on a TPU host (reference
    list_gpus)."""
    return []


def np_reduce(dat, axis, keepdims, numpy_reduce_func):
    """Reference np_reduce: reduce with mxnet axis/keepdims semantics."""
    if isinstance(axis, int):
        axis = [axis]
    axis = list(axis) if axis is not None else range(len(dat.shape))
    ret = dat
    for i in reversed(sorted(axis)):
        ret = numpy_reduce_func(ret, axis=i)
    if keepdims:
        keepdims_shape = list(dat.shape)
        for i in axis:
            keepdims_shape[i] = 1
        ret = ret.reshape(tuple(keepdims_shape))
    return ret


def random_sample(population, k):
    """Sample without replacement preserving order semantics of the
    reference helper."""
    import random as _pyrandom

    return _pyrandom.sample(population, k)


def random_uniform_arrays(*shapes, low=0.0, high=1.0, dtype="float32"):
    return [array(onp.random.uniform(low, high, s).astype(dtype))
            for s in shapes]


def rand_coord_2d(x_low, x_high, y_low, y_high):
    x = onp.random.randint(x_low, x_high)
    y = onp.random.randint(y_low, y_high)
    return x, y


def create_vector(size, dtype=onp.int64):
    """arange vector (reference create_vector — large-tensor tests)."""
    return array(onp.arange(size, dtype=dtype))


def create_2d_tensor(rows, columns, dtype=onp.int64):
    return array(
        onp.arange(rows * columns, dtype=dtype).reshape(rows, columns))


def compare_ndarray_tuple(t1, t2, rtol=None, atol=None):
    """Recursive tuple compare (reference compare_ndarray_tuple)."""
    if t1 is None or t2 is None:
        assert t1 is t2
        return
    if isinstance(t1, tuple):
        for a, b in zip(t1, t2):
            compare_ndarray_tuple(a, b, rtol, atol)
        return
    assert_almost_equal(t1, t2, rtol=rtol, atol=atol)


def compare_optimizer(opt1, opt2, shapes, dtype, w_stype="default",
                      g_stype="default", rtol=1e-4, atol=1e-5, ntests=3):
    """Drive two optimizers along the SAME multi-step trajectory —
    shared weights and persistent states — and assert weights AND states
    stay equal at every step (reference compare_optimizer)."""
    ws1, ws2, ss1, ss2 = [], [], [], []
    for i, s in enumerate(shapes):
        w = onp.random.uniform(-1, 1, s).astype(dtype)
        w1, w2 = array(w), array(w)
        ws1.append(w1)
        ws2.append(w2)
        ss1.append(opt1.create_state(i, w1))
        ss2.append(opt2.create_state(i, w2))
    for _ in range(ntests):                 # multiple steps, states evolve
        for i, s in enumerate(shapes):
            g = onp.random.uniform(-1, 1, s).astype(dtype)
            opt1.update(i, ws1[i], array(g), ss1[i])
            opt2.update(i, ws2[i], array(g), ss2[i])
            compare_ndarray_tuple(
                ss1[i] if isinstance(ss1[i], tuple) else (ss1[i],),
                ss2[i] if isinstance(ss2[i], tuple) else (ss2[i],),
                rtol, atol)
        compare_ndarray_tuple(tuple(ws1), tuple(ws2), rtol, atol)


def check_speed(sym_or_fn, *args, n=20, **kwargs):
    """Steady-state seconds/call with a host-read fence (reference
    check_speed): a warm-up call, then the timed loop ends in a read of
    its last result, so asynchronous dispatch cannot hide device time."""
    import time as _time

    fn = sym_or_fn
    out = fn(*args, **kwargs)
    _np(out if not isinstance(out, (list, tuple)) else out[0])
    t0 = _time.time()
    for _ in range(n):
        out = fn(*args, **kwargs)
    _np(out if not isinstance(out, (list, tuple)) else out[0])
    return (_time.time() - t0) / n


def assign_each(input_arr, function):
    """Elementwise python-function application on host (reference
    assign_each — oracle builder for unary ops)."""
    return onp.vectorize(function)(_np(input_arr))


def assign_each2(input1, input2, function):
    return onp.vectorize(function)(_np(input1), _np(input2))


def collapse_sum_like(a, shape):
    """Sum ``a`` down to ``shape`` (reference collapse_sum_like — the
    broadcast-gradient oracle)."""
    a = _np(a)
    extra = a.ndim - len(shape)
    if extra:
        a = a.sum(axis=tuple(range(extra)))
    axes = tuple(i for i, (da, ds) in enumerate(zip(a.shape, shape))
                 if ds == 1 and da != 1)
    if axes:
        a = a.sum(axis=axes, keepdims=True)
    return a.reshape(shape)


def check_gluon_hybridize_consistency(net_builder, data_l, numpy_func=None,
                                      test_grad=True, rtol=1e-4, atol=1e-5):
    """Eager-vs-hybridized forward (and input-grad) equivalence for a
    Block factory (reference check_gluon_hybridize_consistency)."""
    from . import autograd

    import tempfile

    saved_out_np = None
    saved_grad_np_l = None
    saved_params = None
    for hybridize in (False, True):
        net = net_builder()
        net.initialize()
        in_data_l = [array(_np(d)) for d in data_l]
        net(*in_data_l)                 # materialize deferred shapes
        if saved_params is None:        # both runs share ONE weight set
            saved_params = os.path.join(tempfile.gettempdir(),
                                        f"hyb_consist_{os.getpid()}.params")
            net.save_parameters(saved_params)
        else:
            net.load_parameters(saved_params)
        if hybridize:
            net.hybridize()
        if test_grad:
            for d in in_data_l:
                d.attach_grad()
            with autograd.record():
                out = net(*in_data_l)
                loss = (out ** 2).sum()
            loss.backward()
            grad_np_l = [d.grad.asnumpy() for d in in_data_l]
        else:
            out = net(*in_data_l)
            grad_np_l = None
        out_np = out.asnumpy()
        if saved_out_np is None:
            saved_out_np = out_np
            saved_grad_np_l = grad_np_l
        else:
            onp.testing.assert_allclose(out_np, saved_out_np, rtol=rtol,
                                        atol=atol)
            if test_grad:
                for g, sg in zip(grad_np_l, saved_grad_np_l):
                    onp.testing.assert_allclose(g, sg, rtol=rtol,
                                                atol=atol)
    if numpy_func is not None:
        onp.testing.assert_allclose(
            saved_out_np, numpy_func(*[_np(d) for d in data_l]),
            rtol=rtol, atol=atol)


# --- statistical generator checking (reference chi_square_check /
# verify_generator / mean_check / var_check) -------------------------------

def gen_buckets_probs_with_ppf(ppf, nbuckets):
    """Equal-probability buckets from a percent-point function."""
    probs = [1.0 / nbuckets] * nbuckets
    buckets = [(float(ppf(i / nbuckets)), float(ppf((i + 1) / nbuckets)))
               for i in range(nbuckets)]
    return buckets, probs


def chi_square_check(generator, buckets, probs, nsamples=1000000):
    """Chi-square fit of generator samples against expected bucket
    probabilities (reference chi_square_check).  Continuous buckets are
    (low, high) tuples; discrete buckets are scalar values."""
    from scipy import stats as _sps

    samples = onp.asarray(generator(nsamples)).ravel()
    expected = []
    counted = []
    if isinstance(buckets[0], (tuple, list)):
        for (lo, hi), p in zip(buckets, probs):
            counted.append(((samples >= lo) & (samples < hi)).sum())
            expected.append(p * nsamples)
    else:
        for v, p in zip(buckets, probs):
            counted.append((samples == v).sum())
            expected.append(p * nsamples)
    counted = onp.asarray(counted, dtype=onp.float64)
    expected = onp.asarray(expected, dtype=onp.float64)
    # NO rescaling of expected to the observed total: mass the generator
    # puts OUTSIDE the buckets shows up as a deficit and fails the fit
    # (the reference compares raw counts against probs*nsamples too).
    # Statistic computed directly so unequal totals are allowed.
    stat = ((counted - expected) ** 2 / onp.maximum(expected, 1e-12)).sum()
    pvalue = float(_sps.chi2.sf(stat, len(probs) - 1))
    return pvalue, counted, expected


def verify_generator(generator, buckets, probs, nsamples=100000,
                     nrepeat=5, success_rate=0.25, alpha=0.05):
    """Run chi_square_check nrepeat times; pass when enough repeats have
    p-value above alpha (reference verify_generator)."""
    cs_list = []
    success = 0
    for _ in range(nrepeat):
        pvalue, *_ = chi_square_check(generator, buckets, probs, nsamples)
        cs_list.append(pvalue)
        if pvalue > alpha:
            success += 1
    if success / nrepeat < success_rate:
        raise AssertionError(
            f"generator failed chi-square: p-values {cs_list}")
    return cs_list


def mean_check(generator, mu, sigma, nsamples=1000000, alpha=0.05):
    """z-test of the sample mean against mu (reference mean_check)."""
    from scipy import stats as _sps

    samples = onp.asarray(generator(nsamples)).ravel()
    z = (samples.mean() - mu) / (sigma / onp.sqrt(len(samples)))
    return abs(z) < _sps.norm.ppf(1 - alpha / 2)


def var_check(generator, sigma, nsamples=1000000, alpha=0.05):
    """Chi-square test of the sample variance (reference var_check)."""
    from scipy import stats as _sps

    samples = onp.asarray(generator(nsamples)).ravel()
    n = len(samples)
    stat = (n - 1) * samples.var() / (sigma ** 2)
    lo = _sps.chi2.ppf(alpha / 2, n - 1)
    hi = _sps.chi2.ppf(1 - alpha / 2, n - 1)
    return lo < stat < hi


@contextlib.contextmanager
def discard_stderr():
    """Silence C-level stderr inside the block (reference
    discard_stderr)."""
    import sys

    stderr_fileno = sys.stderr.fileno()
    old = os.dup(stderr_fileno)
    try:
        with open(os.devnull, "wb") as devnull:
            os.dup2(devnull.fileno(), stderr_fileno)
        yield
    finally:
        os.dup2(old, stderr_fileno)
        os.close(old)


def load_digits_split(img_size: int = 32, test_fraction: float = 0.2,
                      seed: int = 42):
    """scikit-learn's bundled real handwritten digits, preprocessed the
    way the shipped pretrained checkpoint was trained
    (tools/publish_pretrained.py --data digits): [-1, 1] normalize,
    nearest-neighbor upsample 8->img_size, 3-channel stack, fixed
    permutation and holdout.  Returns (Xtr, Ytr, Xte, Yte) as numpy.
    Single source of truth so the published test_acc stays reproducible
    by tests/test_model_zoo.py."""
    import numpy as onp
    from sklearn.datasets import load_digits

    d = load_digits()
    rep = img_size // 8
    imgs = d.images.astype(onp.float32) / 16.0 * 2 - 1
    imgs = imgs.repeat(rep, axis=1).repeat(rep, axis=2)
    X = onp.stack([imgs] * 3, axis=1)
    Y = d.target.astype(onp.int32)
    perm = onp.random.RandomState(seed).permutation(len(X))
    X, Y = X[perm], Y[perm]
    n_te = int(len(X) * test_fraction)
    return X[n_te:], Y[n_te:], X[:n_te], Y[:n_te]
