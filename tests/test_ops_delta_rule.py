"""The gated delta rule (``ops/delta_rule.py``) on the CPU at small sizes: the
chunked form against the recurrence one step a token, values and all five
gradients; the triangular inverse by products against a solve; the L2 norm;
the two small changes to ``ops/ssm.py`` that came with it (a convolution
without a bias, the gated norm with the norm first)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import delta_rule as dr, ssm


def _inputs(length, dtype=jnp.float32, seed=0, regime="mixed", b=2, h=3,
            dk=8, dv=12):
    """``regime``: ``mixed`` draws gates all over (0, 1) and ``beta`` over
    (0, 2); ``hard`` puts ``beta`` within 1e-3 of 2 on repeated keys and
    the gate at both ends (decays of e^-30 and of 1 - 1e-4)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = dr.l2_norm(jax.random.normal(ks[0], (b, length, h, dk)),
                   scale=dk ** -0.5)
    k = dr.l2_norm(jax.random.normal(ks[1], (b, length, h, dk)))
    v = jax.random.normal(ks[2], (b, length, h, dv))
    if regime == "mixed":
        log_alpha = -jnp.exp(2.0 * jax.random.normal(ks[3], (b, length, h))
                             - 1.0)
        beta = 2.0 * jax.nn.sigmoid(
            3.0 * jax.random.normal(ks[4], (b, length, h)))
    else:
        k = k.at[:, 1::2].set(k[:, ::2][:, :k[:, 1::2].shape[1]])
        log_alpha = jnp.where(
            jax.random.bernoulli(ks[3], 0.2, (b, length, h)), -30.0, -1e-4)
        beta = 2.0 - 1e-3 * jax.random.uniform(ks[4], (b, length, h))
    return tuple(t.astype(dtype) for t in (q, k, v)) + (log_alpha, beta)


def _worst(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("regime", ["mixed", "hard"])
@pytest.mark.parametrize("length", [40, 37, 5, 8])
def test_the_chunked_rule_equals_the_recurrence(length, regime):
    """Five chunks of 8, a length that is no multiple of the chunk, one
    shorter than a chunk and one chunk; gates near 0 and near 1, ``beta``
    near 2 on keys that repeat."""
    args = _inputs(length, regime=regime)
    got = dr.gated_delta_rule(*args, chunk_size=8)
    want = dr.gated_delta_rule_sequential(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _worst(got, want) < 2e-5


@pytest.mark.parametrize("regime", ["mixed", "hard"])
@pytest.mark.parametrize("length", [40, 21])
def test_all_five_gradients_equal_the_recurrences(length, regime):
    args = _inputs(length, seed=1, regime=regime)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def through(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                        argnums=tuple(range(5)))(*args)

    got = through(lambda *a: dr.gated_delta_rule(*a, chunk_size=8))
    want = through(dr.gated_delta_rule_sequential)
    for g, w, name in zip(got, want, "q k v log_alpha beta".split()):
        assert _worst(g, w) < 5e-5, name


def test_the_rule_does_not_depend_on_the_chunk():
    args = _inputs(48, seed=3)
    a = dr.gated_delta_rule(*args, chunk_size=8)
    b = dr.gated_delta_rule(*args, chunk_size=16)
    assert _worst(a, b) < 3e-5


@pytest.mark.parametrize("length", [32, 13])
def test_bf16_operands_keep_a_float32_state(length):
    """bf16 operands: the result is bf16 and within bf16 rounding of the
    float32 recurrence ON THE ROUNDED operands."""
    args = _inputs(length, jnp.bfloat16, seed=2)
    got = dr.gated_delta_rule(*args, chunk_size=8)
    assert got.dtype == jnp.bfloat16
    assert _worst(got, dr.gated_delta_rule_sequential(*args)) < 2 ** -6


def test_without_decay_and_correction_it_is_causal_linear_attention():
    """``alpha = 1`` and ``beta -> 0`` with ``v / beta`` written: the
    correction vanishes and the rule is ``sum_{j<=i} (q_i . k_j) v_j``."""
    q, k, v, _, _ = _inputs(24, seed=4)
    tiny = jnp.full(q.shape[:3], 1e-4)
    got = dr.gated_delta_rule(q, k, v / 1e-4, jnp.zeros_like(tiny), tiny,
                              chunk_size=8)
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) \
        * jnp.tril(jnp.ones((24, 24)))
    want = jnp.einsum("bhij,bjhe->bihe", scores, v)
    assert _worst(got, want) < 1e-2


@pytest.mark.parametrize("n", [2, 8, 64, 48])
def test_the_inverse_by_products_equals_a_solve(n):
    """Sizes that are and are not powers of two; entries up to 2, as
    ``beta`` near 2 on repeated keys gives them; and its own gradient rule
    against jax's through the series."""
    a = jnp.tril(2.0 * jax.random.uniform(jax.random.PRNGKey(n), (3, n, n),
                                          minval=-1.0), -1) / np.sqrt(n)
    eye = jnp.eye(n)
    got = dr._unit_lower_inverse(a)
    want = jax.scipy.linalg.solve_triangular(eye + a, jnp.broadcast_to(
        eye, a.shape), lower=True, unit_diagonal=True)
    assert _worst(got, want) < 1e-5
    ct = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    d_got = jax.grad(lambda t: jnp.sum(dr._unit_lower_inverse(t) * ct))(a)
    d_want = jax.grad(lambda t: jnp.sum(jnp.linalg.inv(eye + t) * ct))(a)
    assert _worst(d_got, d_want) < 1e-4


def test_through_the_registry_the_tape_and_the_counter():
    args = [mx.nd.array(np.asarray(t, np.float32))
            for t in _inputs(16, seed=5)]
    args[1].attach_grad()
    base = mx.telemetry.snapshot()
    with mx.autograd.record():
        out = mx.nd.gated_delta_rule(*args, chunk_size=8)
        loss = (out * out).sum()
    loss.backward()
    assert mx.telemetry.delta(base)["linear_attention.chunked"] == 1
    raw = [a._data for a in args]
    want = jax.grad(lambda k: jnp.sum(jnp.square(
        dr.gated_delta_rule_sequential(raw[0], k, *raw[2:]))))(raw[1])
    np.testing.assert_allclose(args[1].grad.asnumpy(), want, rtol=2e-4,
                               atol=2e-4)


def test_the_rule_runs_under_its_scope():
    text = jax.jit(lambda *a: dr.gated_delta_rule(*a, chunk_size=8)) \
        .lower(*_inputs(16)).as_text(debug_info=True)
    assert dr.RULE_SCOPE in text


def test_l2_norm_follows_its_input_type():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 3, 8)),
                    jnp.float32)
    want = x / np.sqrt(np.sum(np.square(x), -1, keepdims=True) + 1e-6) * 0.5
    np.testing.assert_allclose(dr.l2_norm(x, scale=0.5), want, rtol=1e-5)
    assert dr.l2_norm(x.astype(jnp.bfloat16)).dtype == jnp.bfloat16
    out = mx.nd.L2Norm(mx.nd.array(np.asarray(x)), scale=0.5)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-5)


def test_a_convolution_without_a_bias_is_one_with_a_zero_bias():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 9, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    np.testing.assert_array_equal(
        ssm.causal_conv1d(x, w, activation="silu"),
        ssm.causal_conv1d(x, w, jnp.zeros(6), activation="silu"))


def test_the_gated_norm_with_the_norm_first():
    """``norm_before_gate``: an RMS norm over each head's values with one
    scale of a head's width, then ``silu(gate)``; the default order (the
    Mamba-2 mixer's) is what it was."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 8)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((2, 5, 3, 8)), jnp.float32)
    gamma = jnp.asarray(1.0 + 0.1 * rng.standard_normal(8), jnp.float32)
    silu = gate / (1.0 + np.exp(-gate))
    normed = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(
        ssm.gated_rms_norm(x, gate, gamma, norm_before_gate=True),
        normed * gamma * silu, rtol=1e-5, atol=1e-6)
    gated = x * silu
    np.testing.assert_allclose(
        ssm.gated_rms_norm(x, gate, gamma),
        gated / np.sqrt(np.mean(np.square(gated), -1, keepdims=True) + 1e-5)
        * gamma, rtol=1e-5, atol=1e-6)
    assert ssm.gated_rms_norm(x.astype(jnp.bfloat16), gate, gamma,
                              norm_before_gate=True).dtype == jnp.bfloat16
