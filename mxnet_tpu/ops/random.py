"""Sampling operators (reference ``src/operator/random/sample_op.cc``).

Each op draws a fresh subkey from the global threefry chain at call time;
under jit-tracing the key is captured as a constant, so Gluon layers that
need per-step randomness (Dropout) thread keys as explicit inputs instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _onp

from .. import random as _rng
from .registry import register


def _dt(dtype):
    if dtype in (None, "None"):
        return jnp.float32
    return jnp.dtype(dtype) if isinstance(dtype, str) else dtype


# --- the keep mask of every dropout: a counter hash of (key, index) --------
#
# One function for ``Dropout``, the fused ``RNN`` op and the attention core
# (the Pallas kernels regenerate it tile by tile in their forward and
# backward, ``ops/pallas_kernels.py``).  It is a pure function of the key's
# two words and each element's global index: nothing is stored for a
# backward pass, and the mask is the same on every platform, eager or
# compiled, under any mesh and any tiling.  Plain int32 ``jax.numpy``
# (wrapping multiply, logical shift, xor), so Mosaic, the Pallas interpreter
# and XLA run the same arithmetic; the hardware generator (``pltpu.prng_*``)
# has no CPU rule, so tier-1 could not test a mask made with it.
#
# Cost per element: one add, one shift, one xor, one multiply, one compare.
# The row and column words are mixed once per row and per column (``_mix``,
# the lowbias32 finaliser) and the last round decorrelates their sum.

_MIX_1 = 0x7FEB352D
_MIX_2 = 0x846CA68B - (1 << 32)            # as a wrapped int32


def _mix(x):
    x = x ^ jax.lax.shift_right_logical(x, 16)
    x = x * jnp.int32(_MIX_1)
    x = x ^ jax.lax.shift_right_logical(x, 15)
    x = x * jnp.int32(_MIX_2)
    return x ^ jax.lax.shift_right_logical(x, 16)


def _keep(seed0, seed1, head, row, col, keep_prob):
    """Bernoulli(keep_prob) keep mask at the broadcast of ``head``, ``row``
    and ``col`` (int32, any broadcastable shapes)."""
    row_word = _mix(seed0 ^ head)
    col_word = _mix(seed1 + row_word)
    x = _mix(row_word + row) + _mix(col_word + col)
    x = x ^ jax.lax.shift_right_logical(x, 15)
    x = x * jnp.int32(_MIX_2)
    # x is uniform over int32: P(x >= t) = (2^31 - t) / 2^32 = keep_prob
    t = min(round((1 << 31) - keep_prob * (1 << 32)), (1 << 31) - 1)
    return x >= jnp.int32(t)


def _seed_words(key):
    """The two int32 words of a PRNG key (typed, or the raw ``uint32[2]`` of
    ``mxnet_tpu.random.next_key``)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return jax.lax.bitcast_convert_type(key.astype(jnp.uint32), jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def keep_mask(key, shape, keep_prob):
    """Boolean Bernoulli(``keep_prob``) mask of ``shape`` for ``key``.

    The last two axes are the row and the column of the hash and the
    leading axes fold, row-major, into its third word, so no two elements
    of one call share an index triple."""
    out_shape = tuple(shape)
    shape = (1,) * (2 - len(out_shape)) + out_shape
    rank = len(shape)

    def iota(axis):
        return jax.lax.broadcasted_iota(
            jnp.int32, (1,) * axis + (shape[axis],) + (1,) * (rank - 1 - axis),
            axis)

    head = jnp.zeros((1,) * rank, jnp.int32)
    for axis in range(rank - 2):
        head = head * shape[axis] + iota(axis)
    seed = _seed_words(key)
    keep = _keep(seed[0], seed[1], head, iota(rank - 2), iota(rank - 1),
                 keep_prob)
    return jnp.broadcast_to(keep, shape).reshape(out_shape)


@register("uniform", num_inputs=0, differentiable=False,
          aliases=["random_uniform", "_sample_uniform"], draws_key=True)
def uniform(low=0.0, high=1.0, shape=(1,), dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.uniform(key, shape, _dt(dtype), minval=low, maxval=high)


@register("normal", num_inputs=0, differentiable=False,
          aliases=["random_normal", "_sample_normal"], draws_key=True)
def normal(loc=0.0, scale=1.0, shape=(1,), dtype=None, key=None):
    if isinstance(scale, (int, float, _onp.floating, _onp.integer)) \
            and float(scale) < 0:
        # reference sample_op validates sigma >= 0 (MXNetError at sync)
        from ..error import MXNetError

        raise MXNetError(f"normal: scale must be non-negative, got {scale}")
    key = key if key is not None else _rng.next_key()
    return loc + scale * jax.random.normal(key, shape, _dt(dtype))


@register("random_gamma", num_inputs=0, differentiable=False,
          aliases=["_sample_gamma"], draws_key=True)
def random_gamma(alpha=1.0, beta=1.0, shape=(1,), dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.gamma(key, alpha, shape, _dt(dtype)) * beta


@register("exponential", num_inputs=0, differentiable=False,
          aliases=["random_exponential"], draws_key=True)
def exponential(lam=1.0, shape=(1,), dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.exponential(key, shape, _dt(dtype)) / lam


@register("poisson", num_inputs=0, differentiable=False, aliases=["random_poisson"], draws_key=True)
def poisson(lam=1.0, shape=(1,), dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.poisson(key, lam, shape).astype(_dt(dtype))


@register("negative_binomial", num_inputs=0, differentiable=False,
          aliases=["random_negative_binomial"], draws_key=True)
def negative_binomial(k=1, p=1.0, shape=(1,), dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    k1, k2 = jax.random.split(key)
    lam = jax.random.gamma(k1, k, shape) * ((1 - p) / p)
    return jax.random.poisson(k2, lam, shape).astype(_dt(dtype))


@register("randint", num_inputs=0, differentiable=False, aliases=["random_randint"], draws_key=True)
def randint(low=0, high=1, shape=(1,), dtype="int32", key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.randint(key, shape, low, high, _dt(dtype))


@register("randn", num_inputs=0, differentiable=False, draws_key=True)
def randn(shape=(1,), loc=0.0, scale=1.0, dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    return loc + scale * jax.random.normal(key, shape, _dt(dtype))


@register("multinomial", num_inputs=1, differentiable=False,
          aliases=["sample_multinomial"], draws_key=True)
def multinomial(data, shape=1, get_prob=False, dtype="int32", key=None):
    key = key if key is not None else _rng.next_key()
    n = shape if isinstance(shape, int) else int(jnp.prod(jnp.asarray(shape)))
    logits = jnp.log(jnp.maximum(data, 1e-30))
    if data.ndim == 1:
        out = jax.random.categorical(key, logits, shape=(n,))
    else:
        out = jax.random.categorical(key, logits[:, None, :], axis=-1,
                                     shape=(data.shape[0], n))
        if n == 1 and (isinstance(shape, int) and shape == 1):
            out = out[:, 0]
    return out.astype(_dt(dtype))


@register("shuffle", num_inputs=1, differentiable=False, aliases=["_shuffle"], draws_key=True)
def shuffle(data, key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.permutation(key, data, axis=0)


@register("bernoulli", num_inputs=0, differentiable=False, draws_key=True)
def bernoulli(prob=0.5, shape=(1,), dtype=None, key=None):
    key = key if key is not None else _rng.next_key()
    return jax.random.bernoulli(key, prob, shape).astype(_dt(dtype))
