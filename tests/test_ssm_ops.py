"""State-space operators (``ops/ssm.py``) and the causal grouped-head
attention entry, on the CPU at small sizes: the chunked scan against the
recurrence one step a token, the depthwise convolution against shifted
multiplies, the attention kernels (Pallas interpreter) against an explicit
mask."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import contrib, pallas_kernels as pk, ssm


def _scan_inputs(length, dtype, seed=0, b=2, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, length, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, length, h)) - 1.0)
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (h,)))
    B = jax.random.normal(k[3], (b, length, g, n)).astype(dtype)
    C = jax.random.normal(k[4], (b, length, g, n)).astype(dtype)
    D = jax.random.normal(k[5], (h,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("length", [32, 37, 5, 8])
def test_ssd_scan_equals_the_sequential_recurrence(length):
    """Lengths that are and are not multiples of the chunk (8), one shorter
    than a chunk; float32 agrees to rounding."""
    args = _scan_inputs(length, jnp.float32)
    got = ssm.ssd_scan(*args, chunk_size=8)
    want = ssm.ssd_scan_sequential(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("length", [32, 21])
def test_ssd_scan_gradients_equal_the_recurrences(length):
    args = _scan_inputs(length, jnp.float32, seed=1)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=tuple(range(6)))(*args)

    got = through(lambda *a: ssm.ssd_scan(*a, chunk_size=8))
    want = through(ssm.ssd_scan_sequential)
    for g, w, name in zip(got, want, "x dt A B C D".split()):
        np.testing.assert_allclose(
            g, w, atol=3e-5 * float(jnp.max(jnp.abs(w))), err_msg=name)


@pytest.mark.parametrize("length", [32, 13])
def test_ssd_scan_in_bf16_keeps_float32_state(length):
    """bf16 operands: the result is bf16 and within bf16 rounding of the
    float32 recurrence ON THE ROUNDED operands; a bf16 carried state or
    bf16 decay sums would be several times further off."""
    args = _scan_inputs(length, jnp.bfloat16, seed=2)
    got = ssm.ssd_scan(*args, chunk_size=8)
    assert got.dtype == jnp.bfloat16
    want = ssm.ssd_scan_sequential(*args)
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / scale
    assert err < 2 ** -6, err


def test_ssd_scan_does_not_depend_on_the_chunk():
    args = _scan_inputs(48, jnp.float32, seed=3)
    a = ssm.ssd_scan(*args, chunk_size=8)
    b = ssm.ssd_scan(*args, chunk_size=16)
    np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(a))))


def test_ssd_scan_through_the_registry_and_the_tape():
    x, dt, A, B, C, D = (mx.nd.array(np.asarray(t, np.float32))
                         for t in _scan_inputs(16, jnp.float32, seed=4))
    x.attach_grad()
    with mx.autograd.record():
        y = mx.nd.ssd_scan(x, dt, A, B, C, D, chunk_size=8)
        loss = (y * y).sum()
    loss.backward()
    want = jax.grad(lambda x_: jnp.sum(jnp.square(ssm.ssd_scan_sequential(
        x_, dt._data, A._data, B._data, C._data, D._data))))(x._data)
    np.testing.assert_allclose(x.grad.asnumpy(), want, rtol=2e-4, atol=2e-4)


# -- the scan's Pallas kernels, under the interpreter -------------------------
def _kernel_scan(*args, chunk_size=8):
    return ssm._ssd_scan(*args, chunk_size=chunk_size, fused=True)


def _chunked_scan(*args, chunk_size=8):
    return ssm._ssd_scan(*args, chunk_size=chunk_size, fused=False)


def _scan_grads(fn, args):
    """Gradients of all six inputs under a fixed cotangent that bf16 holds
    exactly: with bf16 operands the error is then the backward's own, not
    a nonlinear loss read at a rounded output."""
    ct = jax.random.normal(jax.random.PRNGKey(99), args[0].shape) \
        .astype(jnp.bfloat16).astype(jnp.float32)
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct),
                    argnums=tuple(range(6)))(*args)


def _worst(got, want):
    """The largest error as a share of the largest wanted value."""
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("length", [32, 37, 5, 8])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 2 ** -6)],
                         ids=["float32", "bfloat16"])
def test_scan_kernel_equals_the_sequential_recurrence(length, dtype, limit):
    """Two heads a group, whole and broken chunks, one shorter than a
    chunk; bf16 operands against the float32 recurrence on the ROUNDED
    operands, which a bf16 state or bf16 decay sums would miss."""
    args = _scan_inputs(length, dtype, seed=5)
    got = _kernel_scan(*args)
    want = ssm.ssd_scan_sequential(*args)
    assert got.shape == want.shape and got.dtype == dtype
    assert _worst(got, want) < limit


@pytest.mark.parametrize("length", [32, 21])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 3e-5),
                                         (jnp.bfloat16, 2 ** -6)],
                         ids=["float32", "bfloat16"])
def test_scan_kernel_gradients_equal_the_recurrences(length, dtype, limit):
    args = _scan_inputs(length, dtype, seed=6)
    got = _scan_grads(_kernel_scan, args)
    want = _scan_grads(ssm.ssd_scan_sequential, args)
    for g, w, a, name in zip(got, want, args, "x dt A B C D".split()):
        assert g.shape == a.shape and g.dtype == a.dtype, name
        assert _worst(g, w) < limit, name


@pytest.mark.parametrize("length,groups", [(32, 2), (19, 1), (24, 4)])
def test_scan_kernel_equals_the_chunked_expression(length, groups):
    """float32, forward and all six gradients, to rounding: the kernels and
    ``_ssd_chunked`` are the same arithmetic.  One group for all heads, and a
    group a head."""
    args = _scan_inputs(length, jnp.float32, seed=7, g=groups)
    assert _worst(_kernel_scan(*args), _chunked_scan(*args)) < 2e-6
    for g, w, name in zip(_scan_grads(_kernel_scan, args),
                          _scan_grads(_chunked_scan, args),
                          "x dt A B C D".split()):
        assert _worst(g, w) < 5e-6, name


def test_scan_kernel_does_not_depend_on_the_chunk():
    args = _scan_inputs(48, jnp.float32, seed=3)
    a = _kernel_scan(*args, chunk_size=8)
    b = _kernel_scan(*args, chunk_size=16)
    np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(a))))


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_scan_kernels_read_the_operands_where_they_lie(monkeypatch):
    """One Mosaic call forward; differentiated, two: the forward that also
    writes the state before every chunk, and the backward.  ``x``, ``B``,
    ``C`` and the cotangent go in whole, as (batch, channels, length) with
    no group or head moved to the front, and the backward never evaluates
    the chunked expression."""
    args = _scan_inputs(32, jnp.float32, seed=8)

    def no_chunked(*a, **kw):
        raise AssertionError("the kernel path evaluated _ssd_chunked")

    monkeypatch.setattr(ssm, "_ssd_chunked", no_chunked)
    # not the jitted entry: its cache may hold a trace from another test
    def scan(*a):
        return ssm._ssd_scan.__wrapped__(*a, chunk_size=8, fused=True)

    forward = list(_pallas_calls(jax.make_jaxpr(scan)(*args).jaxpr))
    assert len(forward) == 1
    shapes = [tuple(v.aval.shape) for v in forward[0].invars[:3]]
    assert shapes == [(2, 32, 32), (2, 32, 32), (2, 32, 32)]
    both = list(_pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(scan(*a)), argnums=tuple(range(6))))(*args)
        .jaxpr))
    assert len(both) == 2
    states = [tuple(v.aval.shape) for v in both[0].outvars]
    assert states == [(2, 32, 32), (2, 2, 4, 16, 16)]    # y, (b, g, c, e p, n)
    big = [tuple(v.aval.shape) for v in both[1].outvars[:3]]
    assert big == [(2, 32, 32)] * 3                       # dx, dB, dC


def test_ssd_scan_operator_both_paths(monkeypatch):
    """The registered operator on the CPU, then as a TPU trace would take it:
    a shape the kernels refuse says so and still computes, one they take
    counts a fused site."""
    from mxnet_tpu.parallel import mesh as mesh_mod

    def nd_args(args):
        return [mx.nd.array(np.asarray(t, np.float32)) for t in args]

    small = _scan_inputs(16, jnp.float32, seed=9)
    want = ssm.ssd_scan_sequential(*small)
    base = mx.telemetry.snapshot()
    out = mx.nd.ssd_scan(*nd_args(small), chunk_size=8)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-5)
    delta = mx.telemetry.delta(base)
    assert delta["ssm.scan_unfused"] >= 1
    assert not delta.get("ssm.scan_fused")

    monkeypatch.setattr(ssm, "_scan_platform", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "current_mesh", lambda: None)

    def fallbacks():
        return [e for e in mx.telemetry.events("fallback")
                if e["name"] == "ssm.scan_fused"]

    # a chunk of 8 and a state of 16 are no whole 128-lane columns
    before, base = len(fallbacks()), mx.telemetry.snapshot()
    out = mx.nd.ssd_scan(*nd_args(small), chunk_size=8)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-5)
    assert len(fallbacks()) == before + 1
    assert "128" in fallbacks()[-1]["why"]
    assert mx.telemetry.delta(base)["ssm.scan_unfused"] >= 1
    # two heads of 64 a group, state 128, chunk 128, a length that breaks
    # the second chunk: the kernels take it, and no event
    wide = _scan_inputs(150, jnp.float32, seed=10, b=1, h=4, p=64, g=2,
                        n=128)
    base = mx.telemetry.snapshot()
    out = mx.nd.ssd_scan(*nd_args(wide), chunk_size=128)
    want = ssm.ssd_scan_sequential(*wide)
    np.testing.assert_allclose(out.asnumpy(), want, atol=3e-5 * float(
        jnp.max(jnp.abs(want))))
    delta = mx.telemetry.delta(base)
    assert delta["ssm.scan_fused"] >= 1
    assert not delta.get("ssm.scan_unfused")
    assert len(fallbacks()) == before + 1


@pytest.mark.parametrize("why,kw", [
    (None, {}), ("mesh", {"devices": 4}), ("128", {"chunk": 64}),
    ("128", {"n": 64}), ("16", {"p": 8}),
    ("types", {"b_dtype": jnp.float32})],
    ids=["taken", "mesh", "chunk", "state", "head_dim", "types"])
def test_scan_kernel_refusals(why, kw, monkeypatch):
    """What a TPU trace decides from: the mesh, the shapes, the types."""
    from mxnet_tpu.parallel import mesh as mesh_mod

    class Mesh:
        size = kw.get("devices", 1)

    monkeypatch.setattr(mesh_mod, "current_mesh", Mesh)
    x = jax.ShapeDtypeStruct((1, 256, 4, kw.get("p", 64)), jnp.bfloat16)
    c = jax.ShapeDtypeStruct((1, 256, 2, kw.get("n", 128)), jnp.bfloat16)
    b = jax.ShapeDtypeStruct(c.shape, kw.get("b_dtype", jnp.bfloat16))
    refusal = ssm._fused_scan_refusal(x, b, c, kw.get("chunk", 128))
    assert refusal is None if why is None else why in refusal


@pytest.mark.parametrize("kernel,activation", [(4, "silu"), (4, None),
                                               (3, "silu")])
def test_causal_conv1d_equals_shifted_multiplies(kernel, activation):
    rng = np.random.default_rng(kernel)
    x = rng.standard_normal((2, 11, 6)).astype(np.float32)
    w = rng.standard_normal((6, kernel)).astype(np.float32)
    b = rng.standard_normal((6,)).astype(np.float32)
    got = np.asarray(ssm.causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                                       jnp.asarray(b), activation=activation))
    padded = np.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
    want = b + sum(padded[:, k:k + 11] * w[:, k] for k in range(kernel))
    if activation == "silu":
        want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and against XLA's grouped convolution, one group a channel
    conv = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w).T[:, None, :], (1,),
        [(kernel - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=6, precision="highest") + b
    if activation == "silu":
        conv = conv * jax.nn.sigmoid(conv)
    np.testing.assert_allclose(got, conv, rtol=1e-5, atol=1e-5)
    # causal: the output at t ignores everything after t
    x2 = x.copy()
    x2[:, 7:] += 1.0
    got2 = np.asarray(ssm.causal_conv1d(jnp.asarray(x2), jnp.asarray(w),
                                        jnp.asarray(b),
                                        activation=activation))
    np.testing.assert_array_equal(got[:, :7], got2[:, :7])


def test_causal_conv1d_gradients_and_bf16():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 9, 4)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4,)), jnp.float32)

    def shifted(x, w, b):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        out = b + sum(padded[:, k:k + 9] * w[:, k] for k in range(4))
        return out * jax.nn.sigmoid(out)

    for got, want in zip(
            jax.grad(lambda *a: jnp.sum(jnp.sin(ssm.causal_conv1d(
                *a, activation="silu"))), argnums=(0, 1, 2))(x, w, b),
            jax.grad(lambda *a: jnp.sum(jnp.sin(shifted(*a))),
                     argnums=(0, 1, 2))(x, w, b)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    low = ssm.causal_conv1d(x.astype(jnp.bfloat16), w, b, activation="silu")
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), shifted(x, w, b),
                               atol=0.05)


# -- the convolution's Pallas kernels, under the interpreter ------------------
def _conv_args(b, l, c, kernel, dtype, bias, seed=0, total=None):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, l, total or c)), dtype)
    w = jnp.asarray(rng.standard_normal((c, kernel)) / kernel, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((c,)), jnp.float32) if bias \
        else None
    return x, w, bias


def _kernel_conv(x, w, b, activation, begin=0, splits=None):
    out = ssm._conv_fused(x, w, b, activation, begin,
                          splits or (w.shape[0],))
    return out if splits else out[0]


def _xla_conv(x, w, b, activation, begin=0, splits=None):
    return ssm.causal_conv1d(x, w, b, activation=activation, begin=begin,
                             splits=splits)


def _conv_grads(fn, args, seed=99):
    """The output(s) and the gradients of the data, the taps and the bias
    (where there is one) under fixed cotangents that bf16 holds exactly."""
    outs = fn(*args)
    outs = outs if isinstance(outs, tuple) else (outs,)
    cts = [jax.random.normal(jax.random.PRNGKey(seed + i), o.shape)
           .astype(jnp.bfloat16).astype(jnp.float32)
           for i, o in enumerate(outs)]

    def loss(*a):
        got = fn(*a)
        got = got if isinstance(got, tuple) else (got,)
        return sum(jnp.sum(o.astype(jnp.float32) * ct)
                   for o, ct in zip(got, cts))

    wrt = (0, 1) if args[2] is None else (0, 1, 2)
    return outs, jax.grad(loss, argnums=wrt)(*args)


@pytest.mark.parametrize("kernel,bias,activation", [
    (4, True, "silu"), (4, False, "silu"), (4, True, None), (2, False, None),
    (2, True, "silu")])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2 ** -7)],
                         ids=["float32", "bfloat16"])
def test_conv_kernels_equal_xlas_expression(kernel, bias, activation, dtype,
                                            limit, monkeypatch):
    """Forward and the gradients of data, taps and bias, against XLA's
    shifted multiply-adds: 37 positions, under one 128-lane column (padded
    inside), two batch rows, three blocks of 16 channels.  float32 to
    rounding; bf16 within a rounding of the output's type (both take the
    same bf16 operands, float32 inside)."""
    monkeypatch.setattr(pk, "_CONV_BLOCK", 16 * 128)
    assert pk.conv_channel_block((48, 0, 48), 128,
                                 jnp.dtype(dtype).itemsize) == 16
    args = _conv_args(2, 37, 48, kernel, dtype, bias)
    (got,), got_grads = _conv_grads(
        lambda *a: _kernel_conv(*a, activation), args)
    (want,), want_grads = _conv_grads(
        lambda *a: _xla_conv(*a, activation), args)
    assert got.shape == want.shape and got.dtype == dtype
    assert _worst(got, want) < limit
    for g, w, a, name in zip(got_grads, want_grads, args, ("x", "w", "b")):
        assert g.shape == a.shape and g.dtype == a.dtype, name
        assert _worst(g, w) < limit, name


@pytest.mark.parametrize("channels,begin,splits,total", [
    (1440, 0, None, 1440), (2880, 0, None, 2880),
    (2304, 2048, (2048, 128, 128), 4384)],
    ids=["olmo_keys", "olmo_values", "granite_xbc"])
def test_conv_kernels_at_the_cells_channel_counts(channels, begin, splits,
                                                  total, monkeypatch):
    """The delta-rule cell's 1,440 and 2,880 channels (no multiple of 128)
    and the Granite mixer's 2,304 read out of its 4,384-wide projection and
    cut into x, B and C, at a toy length, bf16, in blocks of 16 channels
    (forced small: a block's index maps, and the cuts' blocks that stand
    still outside their own range, are run many times)."""
    monkeypatch.setattr(pk, "_CONV_BLOCK", 16 * 128)
    assert pk.conv_channel_block(
        (channels, begin) + (splits or (channels,)), 128, 2) == 16
    args = _conv_args(1, 20, channels, 4, jnp.bfloat16, begin > 0, seed=3,
                      total=total)
    got, got_grads = _conv_grads(
        lambda *a: _kernel_conv(*a, "silu", begin, splits), args)
    want, want_grads = _conv_grads(
        lambda *a: _xla_conv(*a, "silu", begin, splits), args)
    assert len(got) == len(splits or (channels,))
    for g, w in zip(got, want):
        assert g.shape == w.shape and _worst(g, w) < 2 ** -7
    for g, w, name in zip(got_grads, want_grads, ("x", "w", "b")):
        assert _worst(g, w) < 2 ** -7, name
    if begin:       # nothing flows into the projection's other channels
        dx = np.asarray(got_grads[0], np.float32)
        assert not dx[..., :begin].any() and not dx[..., begin + channels:] \
            .any()


def test_conv_kernels_read_the_projection_where_it_lies():
    """One Mosaic call forward, which reads the WHOLE projection, (batch,
    channels, length), and writes x, B and C as three arrays; differentiated,
    the backward call reads the three cotangents as they come and the
    forward keeps nothing for it but its inputs (no call writes more than
    the three pieces)."""
    args = _conv_args(2, 128, 64, 4, jnp.float32, True, total=88)

    def conv(x, w, b):
        return ssm._conv_fused.__wrapped__(x, w, b, "silu", 16, (32, 16, 16))

    forward = list(_pallas_calls(jax.make_jaxpr(conv)(*args).jaxpr))
    assert len(forward) == 1
    assert tuple(forward[0].invars[0].aval.shape) == (2, 88, 128)
    assert [tuple(v.aval.shape) for v in forward[0].outvars] == \
        [(2, 32, 128), (2, 16, 128), (2, 16, 128)]
    both = list(_pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: sum(jnp.sum(o) for o in conv(*a)), argnums=(0, 1, 2)))(
        *args).jaxpr))
    assert len(both) == 2
    assert len(both[0].outvars) == 3
    backward = both[1]
    assert [tuple(v.aval.shape) for v in backward.invars[2:]] == \
        [(2, 32, 128), (2, 16, 128), (2, 16, 128)]
    assert [tuple(v.aval.shape) for v in backward.outvars] == \
        [(2, 64, 128), (2, 64, 5)]             # dx, and taps + bias a row


def test_causal_conv1d_operator_both_paths(monkeypatch):
    """The registered operator on the CPU, then as a TPU trace would take it:
    a channel count no tile divides says so and still computes, one the
    kernels take counts a fused site, and a mesh of more than one device
    takes XLA's form and says so."""
    from mxnet_tpu.parallel import mesh as mesh_mod

    def fallbacks():
        return [e for e in mx.telemetry.events("fallback")
                if e["name"] == "ssm.conv_fused"]

    def run(args, **attrs):
        base = mx.telemetry.snapshot()
        out = mx.nd.causal_conv1d(*[mx.nd.array(np.asarray(t))
                                    for t in args], **attrs)
        moved = mx.telemetry.delta(base)
        return out, moved.get("ssm.conv_fused", 0), \
            moved.get("ssm.conv_unfused", 0)

    narrow = _conv_args(2, 11, 6, 4, jnp.float32, True, seed=5)
    want = _xla_conv(*narrow, "silu")
    out, fused, unfused = run(narrow, activation="silu")
    np.testing.assert_allclose(out.asnumpy(), want, atol=1e-6)
    assert (fused, unfused) == (0, 1)

    monkeypatch.setattr(ssm, "_conv_platform", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "current_mesh", lambda: None)
    before = len(fallbacks())
    out, fused, unfused = run(narrow, activation="silu")
    np.testing.assert_allclose(out.asnumpy(), want, atol=1e-6)
    assert (fused, unfused) == (0, 1)
    assert len(fallbacks()) == before + 1
    assert "8-row tiles" in fallbacks()[-1]["why"]
    wide = _conv_args(1, 40, 32, 4, jnp.float32, True, seed=6)
    out, fused, unfused = run(wide, activation="silu")
    np.testing.assert_allclose(out.asnumpy(), _xla_conv(*wide, "silu"),
                               atol=2e-6)
    assert (fused, unfused) == (1, 0) and len(fallbacks()) == before + 1

    class Mesh:
        size = 4

    monkeypatch.setattr(mesh_mod, "current_mesh", Mesh)
    out, fused, unfused = run(wide, activation="silu")
    np.testing.assert_allclose(out.asnumpy(), _xla_conv(*wide, "silu"),
                               atol=2e-6)
    assert (fused, unfused) == (0, 1)
    assert "mesh of 4" in fallbacks()[-1]["why"]


@pytest.mark.parametrize("mixer", ["mamba2", "delta_net"])
def test_the_mixers_take_the_conv_kernels(mixer, monkeypatch):
    """Both callers, forced onto the kernels (the scan and the rule stay on
    XLA's form): the Mamba-2 mixer hands its whole projection over and
    takes x, B and C back, the delta-rule mixer three projections; the
    layer's output and every parameter's gradient equal XLA's form's, and
    every site counts a fused convolution."""
    from mxnet_tpu.gluon.model_zoo import nemotron_h as nh

    mx.random.seed(0)
    if mixer == "mamba2":
        net = nh.NemotronHMamba2Mixer(32, 4, 8, 1, 16, chunk_size=8)
        sites = 1
    else:
        net = mx.gluon.nn.GatedDeltaNet(32, 2, 16, 24, chunk_size=8)
        sites = 3
    net.initialize()
    x = mx.nd.array(np.random.default_rng(7).standard_normal((2, 19, 32))
                    .astype(np.float32))
    params = net.collect_params()

    def run(platform):
        monkeypatch.setattr(ssm, "_conv_platform", lambda: platform)
        base = mx.telemetry.snapshot()
        with mx.autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        moved = mx.telemetry.delta(base)
        return (y.asnumpy(), {n: p.grad().asnumpy()
                              for n, p in params.items()},
                moved.get("ssm.conv_fused", 0))

    y_k, g_k, fused = run("tpu")
    y_x, g_x, unfused = run("cpu")
    assert (fused, unfused) == (sites, 0)
    np.testing.assert_allclose(y_k, y_x, atol=2e-5 * np.abs(y_x).max())
    for name in params:
        np.testing.assert_allclose(
            g_k[name], g_x[name], atol=5e-5 * max(np.abs(g_x[name]).max(), 1),
            err_msg=name)


def test_rms_norms_follow_their_input_type():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 5, 16)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((2, 5, 16)), jnp.float32)
    gamma = jnp.asarray(1.0 + 0.1 * rng.standard_normal(16), jnp.float32)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) \
        * gamma
    np.testing.assert_allclose(ssm.rms_norm(x, gamma), want, rtol=1e-5)
    gated = x * gate / (1.0 + np.exp(-gate))
    grouped = np.asarray(gated).reshape(2, 5, 4, 4)
    want = (grouped / np.sqrt(np.mean(np.square(grouped), -1, keepdims=True)
                              + 1e-5)).reshape(2, 5, 16) * gamma
    np.testing.assert_allclose(
        ssm.gated_rms_norm(x, gate, gamma, num_groups=4), want, rtol=1e-5,
        atol=1e-6)
    for fn in (lambda t: ssm.rms_norm(t, gamma),
               lambda t: ssm.gated_rms_norm(t, t, gamma, num_groups=4)):
        assert fn(x.astype(jnp.bfloat16)).dtype == jnp.bfloat16


def test_rms_norm_block_under_amp_returns_bf16():
    norm = mx.gluon.nn.RMSNorm(in_channels=8)
    norm.initialize()
    x = mx.nd.array(np.random.default_rng(2).standard_normal((3, 8)))
    mx.amp.init("bfloat16")
    try:
        assert norm(x.astype("bfloat16")).dtype == jnp.bfloat16
        assert norm(x).dtype == np.float32
    finally:
        mx.amp.uninit()


# -- causal attention with grouped key-value heads ---------------------------
def _explicit_mask_attention(q, k, v, heads, kv_heads):
    b, s, width = q.shape
    d, group = width // heads, heads // kv_heads
    q5 = q.reshape(b, s, kv_heads, group, d)
    k4, v4 = k.reshape(b, s, kv_heads, d), v.reshape(b, s, kv_heads, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k4,
                        precision="highest") / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", att, v4,
                      precision="highest").reshape(b, s, width)


def _qkv(b, s, heads, kv_heads, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, heads * d)),
            jax.random.normal(ks[1], (b, s, kv_heads * d)),
            jax.random.normal(ks[2], (b, s, kv_heads * d)),
            jax.random.normal(ks[3], (b, s, heads * d)))


@pytest.fixture(params=["fused", "split"])
def backward_form(request, monkeypatch):
    """Both forms of the grouped causal backward: the one kernel, and the dq
    and dk/dv kernels a shape past the VMEM budget takes (forced here by a
    budget of nothing)."""
    if request.param == "split":
        monkeypatch.setattr(pk, "_GQA_BWD_VMEM", 0)
    return request.param


# seq 64 at three blocks, then one, three and five blocks of 16: with three
# and more a query block's dq is revisited after other blocks ran in between
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4)],
                         ids=["grouped", "group_of_one"])
@pytest.mark.parametrize("block,seq", [(16, 64), (32, 64), (64, 64),
                                       (16, 16), (16, 48), (16, 80),
                                       (16, 128)])
def test_grouped_causal_kernels_equal_an_explicit_mask(
        block, seq, heads, kv_heads, backward_form, monkeypatch):
    """4 query heads a key-value head (they share dk and dv) and a group of
    one; several blocks a sequence, so the pairs above the diagonal, the
    index maps and the accumulators a later key block revisits are run.
    At 128 keys the fused backward takes four blocks of 32 behind a forward
    of eight blocks of 16."""
    monkeypatch.setattr(pk, "_BLOCK", block)
    monkeypatch.setattr(pk, "_GQA_BWD_BLOCK", 2 * block)
    assert pk._gqa_bwd_block(seq, block) == (32 if seq == 128 else block)
    q, k, v, ct = _qkv(2, seq, heads, kv_heads, 16)
    got = pk.flash_attention_gqa(q, k, v, heads, kv_heads)
    want = _explicit_mask_attention(q, k, v, heads, kv_heads)
    np.testing.assert_allclose(got, want, atol=2e-5)
    base = mx.telemetry.snapshot()
    grads = jax.grad(lambda *a: jnp.sum(pk.flash_attention_gqa(
        *a, heads, kv_heads) * ct), argnums=(0, 1, 2))(q, k, v)
    moved = mx.telemetry.delta(base)
    assert moved["attention.gqa_backward_" + backward_form] == 1
    wants = jax.grad(lambda *a: jnp.sum(_explicit_mask_attention(
        *a, heads, kv_heads) * ct), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(g, w, atol=5e-5)


def test_grouped_causal_backward_counts_the_form_it_took(monkeypatch):
    """A traced backward whose accumulators fit counts the fused kernel once
    and the split form never, and the program holds ONE kernel call for dq,
    dk and dv; past the budget the reverse, two calls, and a ``fallback``
    event whose ``why`` holds the bytes that did not fit."""
    monkeypatch.setattr(pk, "_BLOCK", 16)
    heads, kv_heads, d, seq = 4, 2, 16, 48
    q, k, v, ct = _qkv(1, seq, heads, kv_heads, d)

    def trace():
        base = mx.telemetry.snapshot()
        seen = max((e["seq"] for e in mx.telemetry.events("fallback")),
                   default=0)
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            pk.flash_attention_gqa(*a, heads, kv_heads) * ct),
            argnums=(0, 1, 2)))(q, k, v).jaxpr
        moved = mx.telemetry.delta(base)
        return (len(list(_pallas_calls(jaxpr))) - 1,   # less the forward
                moved["attention.gqa_backward_fused"],
                moved["attention.gqa_backward_split"],
                [e for e in mx.telemetry.events("fallback")
                 if e["seq"] > seen])

    # the fused backward's block at the cells' 8,192 keys and around them
    assert [pk._gqa_bwd_block(n, 512) for n in (2048, 4096, 8192, 32768)] \
        == [512, 1024, 1024, 1024]
    resident = pk._gqa_bwd_resident(seq, d, heads // kv_heads, 16)
    assert resident == 3 * seq * d * 4                 # dq, dk, dv: whole
    assert pk._gqa_bwd_resident(seq, d, 1, 16) == (seq + 2 * 16) * d * 4
    assert trace() == (1, 1, 0, [])
    monkeypatch.setattr(pk, "_GQA_BWD_VMEM", resident - 1)
    calls, fused, split, events = trace()
    assert (calls, fused, split) == (2, 0, 1)
    assert [e["name"] for e in events] == ["attention.gqa_backward_fused"]
    assert str(resident) in events[0]["why"] and events[0]["head_dim"] == d


def test_grouped_causal_kernels_write_no_copy_of_a_key():
    """The kernel call itself takes k and v with their own two heads:
    nothing of the query's width is made of them on the way in."""
    q, k, v, _ = _qkv(1, 32, 8, 2, 16)
    calls = list(_pallas_calls(jax.make_jaxpr(
        lambda *a: pk.flash_attention_gqa(*a, 8, 2))(q, k, v).jaxpr))
    assert len(calls) == 1
    assert [tuple(var.aval.shape) for var in calls[0].invars] == \
        [q.shape, k.shape, v.shape]


def test_causal_gqa_selfatt_operator_both_paths(monkeypatch):
    heads, kv_heads = 4, 2
    q, k, v, _ = _qkv(2, 32, heads, kv_heads, 16, seed=5)
    want = _explicit_mask_attention(q, k, v, heads, kv_heads)
    base = mx.telemetry.snapshot()
    args = [mx.nd.array(np.asarray(t)) for t in (q, k, v)]
    out = mx.nd.causal_gqa_selfatt(*args, heads=heads, kv_heads=kv_heads)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-5)
    assert mx.telemetry.delta(base)["attention.unfused"] >= 1
    # the kernel path, forced as the BERT tests force it
    monkeypatch.setattr(contrib, "_attention_platform", lambda: "tpu")
    seq_before = max((e["seq"] for e in mx.telemetry.events("fallback")),
                     default=0)
    # head_dim 16 is no whole 128-lane column: a TPU trace refuses it loudly
    out = mx.nd.causal_gqa_selfatt(*args, heads=heads, kv_heads=kv_heads)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-5)
    new = [e for e in mx.telemetry.events("fallback")
           if e["seq"] > seq_before]
    assert new and new[-1]["name"] == "attention.fused"
    # at 128 the kernels take it
    q, k, v, _ = _qkv(1, 32, 2, 1, 128, seed=6)
    base = mx.telemetry.snapshot()
    out = mx.nd.causal_gqa_selfatt(
        *[mx.nd.array(np.asarray(t)) for t in (q, k, v)], heads=2,
        kv_heads=1)
    np.testing.assert_allclose(
        out.asnumpy(), _explicit_mask_attention(q, k, v, 2, 1), atol=2e-5)
    assert mx.telemetry.delta(base)["attention.fused"] >= 1
