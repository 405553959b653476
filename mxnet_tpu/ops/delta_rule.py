"""Linear attention with a gated delta rule (Yang, Kautz and Hatamizadeh
2024, arXiv:2412.06464): per head a state ``S`` of (value, key) size that
every token first decays, then corrects along its key, then writes to:

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                                      S_0 = 0

``alpha_t`` in (0, 1] is the gate, ``beta_t`` the writing strength; with
``beta_t`` up to 2 the transition ``I - beta k k^T`` has an eigenvalue down
to -1 along a unit key (``allow_neg_eigval``).

``gated_delta_rule`` computes it in chunks.  With ``g`` the running sum of
``log alpha`` inside a chunk and ``D_ij = exp(g_i - g_j)`` for ``j <= i``,
the tokens of a chunk see the state before it through

    T = (I + tril(diag(beta) K K^T * D, -1))^-1        unit lower triangular
    W = T diag(beta exp(g)) K       U = T diag(beta) V
    V' = U - W S^T                  the chunk's writes, corrected
    O = (Q * exp(g)) S^T + tril(Q K^T * D) V'
    S <- exp(g_last) S + V'^T (K * exp(g_last - g))

so a chunk is a handful of matrix products and the state is carried from
chunk to chunk by a scan.  ``T`` is built by products too: the matrix under
the inverse is nilpotent, so its Neumann series is the finite product
``(I - A)(I + A^2)(I + A^4)...``, a logarithm of the chunk's length in
steps where substitution takes a step a row.  Decay sums, the inverse and
the carried state are float32 (the inverse at the highest product
precision: its entries feed every other product); the other products take
the operands' type and accumulate in float32.  The backward pass is jax's,
through the scan, with the inverse's own rule (``-T^T dT T^T``) in place of
the series' transpose.

``gated_delta_rule_sequential`` is the recurrence itself, one step a token,
float32: what the chunked form is tested against, never what a program
runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from .registry import register

__all__ = ["gated_delta_rule", "gated_delta_rule_sequential", "l2_norm"]

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# the scope the device trace reads (perfbench/scope_view)
RULE_SCOPE = "DeltaRule"

_CHUNKED = _telemetry.counter(
    "linear_attention.chunked",
    "gated delta-rule sites traced as the chunked expression of XLA's "
    "products")


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., n, n),
    float32: ``(I - a)(I + a^2)(I + a^4)...`` until the power vanishes."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=_F32)
    inv, power, reach = eye - a, a, 2
    while reach < n:                       # a^n = 0
        power = jnp.matmul(power, power, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=_HIGHEST)
        reach *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, d_inv):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, d_inv, precision=_HIGHEST), t,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _delta_chunked(q, k, v, log_alpha, beta, chunk):
    """``q``/``k`` (b, l, h, dk), ``v`` (b, l, h, dv), ``log_alpha``/``beta``
    (b, l, h); ``l`` a multiple of ``chunk``.  Returns (b, l, h, dv).
    Inside, a chunk of one head is a matrix: (b, chunks, h, chunk, width)."""
    b, l, h, _ = q.shape
    nc, dtype = l // chunk, v.dtype

    def chunks(t):                      # (b, l, h, ...) -> (b, nc, h, chunk, ...)
        return jnp.moveaxis(t.reshape(b, nc, chunk, h, *t.shape[3:]), 3, 2)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(_F32))[..., None]
    g = jnp.cumsum(chunks(log_alpha.astype(_F32)), axis=-1)
    rows = jnp.arange(chunk)[:, None]
    upto, before = rows >= rows.T, rows > rows.T
    decay = jnp.exp(jnp.where(upto, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                # D, (b, nc, h, i, j)
    to_end = jnp.exp(g[..., -1:] - g)[..., None]        # (b, nc, h, chunk, 1)
    grown = jnp.exp(g)[..., None]

    k_beta = kc.astype(_F32) * beta
    inv = _unit_lower_inverse(jnp.where(
        before, _dot("bchid,bchjd->bchij", k_beta.astype(dtype), kc) * decay,
        0.0)).astype(dtype)
    w = _dot("bchij,bchjd->bchid", inv, (k_beta * grown).astype(dtype)) \
        .astype(dtype)
    u = _dot("bchij,bchje->bchie", inv, (vc.astype(_F32) * beta)
             .astype(dtype))
    within = jnp.where(upto, _dot("bchid,bchjd->bchij", qc, kc) * decay,
                       0.0).astype(dtype)
    q_grown = (qc.astype(_F32) * grown).astype(dtype)
    k_to_end = (kc.astype(_F32) * to_end).astype(dtype)
    chunk_decay = jnp.exp(g[..., -1])                   # (b, nc, h)

    def carry(state, inp):                              # (b, h, dk, dv)
        w_c, u_c, q_c, k_c, d_c = inp
        seen = state.astype(dtype)
        new = (u_c - _dot("bhid,bhde->bhie", w_c, seen)).astype(dtype)
        out = _dot("bhid,bhde->bhie", q_c, seen)
        state = state * d_c[..., None, None] \
            + _dot("bhid,bhie->bhde", k_c, new)
        return state, (new, out)

    _, (new, out) = jax.lax.scan(
        carry, jnp.zeros((b, h, q.shape[-1], v.shape[-1]), _F32),
        tuple(t.swapaxes(0, 1)
              for t in (w, u, q_grown, k_to_end, chunk_decay)))
    out = out.swapaxes(0, 1) \
        + _dot("bchij,bchje->bchie", within, new.swapaxes(0, 1))
    return jnp.moveaxis(out, 2, 3).reshape(b, l, h, -1).astype(dtype)


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def _gated_delta_rule(q, k, v, log_alpha, beta, chunk_size):
    with jax.named_scope(RULE_SCOPE):
        l = q.shape[1]
        pad = -l % chunk_size
        args = (q, k, v, log_alpha, beta)
        if pad:
            # log alpha = 0 and beta = 0 behind the end: the state stands
            # still, and the outputs there are cut off
            args = tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                                 (t.ndim - 2)) for t in args)
        # rematerialised in the backward pass: a layer's own recomputation
        # would otherwise keep every chunk's float32 masks, inverse, writes
        # and states beside the rest of the layer (1.7 GiB more of a step at
        # 8,192 tokens and 15 heads: PERF.md, PR 38)
        chunked = jax.checkpoint(_delta_chunked, static_argnums=(5,))
        return chunked(*args, chunk_size)[:, :l]


@register("gated_delta_rule", num_inputs=5)
def gated_delta_rule(q, k, v, log_alpha, beta, chunk_size=64):
    """Linear attention under the gated delta rule (module docstring).
    ``q``, ``k`` (batch, length, heads, key_dim), as they enter the rule
    (normalised and scaled by the caller); ``v`` (batch, length, heads,
    value_dim); ``log_alpha`` (batch, length, heads), the log of the gate,
    at most 0; ``beta`` (batch, length, heads).  Returns (batch, length,
    heads, value_dim) of ``v``'s type.  Any length: one that is no multiple
    of ``chunk_size`` is padded with steps that do nothing.  Counted on
    ``linear_attention.chunked``, a site once a trace."""
    _CHUNKED.inc()
    return _gated_delta_rule(q, k, v, log_alpha, beta, int(chunk_size))


def gated_delta_rule_sequential(q, k, v, log_alpha, beta):
    """The recurrence one step a token, float32, shapes as
    :func:`gated_delta_rule`."""
    b, _, h, dk = q.shape
    q, k, v, log_alpha, beta = (t.astype(_F32)
                                for t in (q, k, v, log_alpha, beta))

    def step(s, inp):                                   # (b, h, dv, dk)
        q_t, k_t, v_t, a_t, b_t = inp
        s_k = jnp.einsum("bhed,bhd->bhe", s, k_t, precision=_HIGHEST)
        s = jnp.exp(a_t)[..., None, None] \
            * (s - (b_t[..., None] * s_k)[..., None] * k_t[:, :, None, :]) \
            + (b_t[..., None] * v_t)[..., None] * k_t[:, :, None, :]
        return s, jnp.einsum("bhed,bhd->bhe", s, q_t, precision=_HIGHEST)

    _, out = jax.lax.scan(
        step, jnp.zeros((b, h, v.shape[-1], dk), _F32),
        tuple(t.swapaxes(0, 1) for t in (q, k, v, log_alpha, beta)))
    return out.swapaxes(0, 1)


@register("L2Norm", num_inputs=1)
def l2_norm(data, scale=1.0, eps=1e-6):
    """``data / sqrt(sum(data^2) + eps) * scale`` over the last axis,
    float32 inside, ``data``'s type out."""
    x = data.astype(_F32)
    return (x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
            * scale).astype(data.dtype)
