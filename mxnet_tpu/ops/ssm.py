"""State-space operators: the Mamba-2 recurrence as a chunked scan, the
causal depthwise convolution in front of it, and the RMS norms around it.

``ssd_scan`` computes, per head, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
B_t^T`` and ``y_t = S_t C_t + D x_t`` in the SSD form (Dao and Gu 2024,
arXiv:2405.21060): inside a chunk the recurrence is three matrix products
under a decay mask, between chunks one float32 state a head is carried.
Decay sums and the carried state are float32 whatever the operands' type;
the products take the operands' type and accumulate in float32.

On a TPU with no mesh of more than one device the scan is a pair of Pallas
kernels (``pallas_kernels.ssd_chunk_scan``): a chunk's decay mask is built,
used and dropped in VMEM, the state is carried in scratch, and the backward
kernel rebuilds the masks.  Anywhere else it is ``_ssd_chunked``, XLA's
products group by group with jax's own backward through a rematerialised
forward; that expression is also what the kernels are tested against.
Which one is decided from what the trace can observe, as the attention core
does (``ops/contrib.py``): ``ssm.scan_fused`` / ``ssm.scan_unfused`` count
the sites, and a TPU trace that leaves the kernels says so with a
``fallback`` event.

The norms take and return the type that arrives (bf16 under AMP), float32
inside, as ``BatchNorm`` does since PR 29 (``amp/lists.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from .registry import register

__all__ = ["ssd_scan", "ssd_scan_sequential", "causal_conv1d", "rms_norm",
           "gated_rms_norm"]

_F32 = jnp.float32
# the scopes the device trace reads (perfbench/scope_view: a component that
# starts with a capital letter is a block scope)
SCAN_SCOPE = "SsdScan"
CONV_SCOPE = "CausalConv1d"


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _ssd_group(x, dt, A, B, C, chunk):
    """One group's heads, without the ``D x`` term: ``x`` (b, l, e, p),
    ``dt`` (b, l, e), ``A`` (e,), ``B``/``C`` (b, l, n), which all ``e``
    heads of the group share; ``l`` a multiple of ``chunk``."""
    b, l, e, p = x.shape
    n = B.shape[-1]
    nc, q, dtype = l // chunk, chunk, x.dtype
    dt = dt.astype(_F32).reshape(b, nc, q, e)
    acs = jnp.cumsum(dt * A.astype(_F32), axis=2)     # decay sums, inclusive
    xc = x.reshape(b, nc, q, e, p)
    Bc, Cc = B.reshape(b, nc, q, n), C.reshape(b, nc, q, n)

    # inside a chunk: y_i += sum_{j<=i} (C_i.B_j) exp(acs_i - acs_j) dt_j x_j
    cb = _dot("bcin,bcjn->bcij", Cc, Bc)              # (b, nc, q, q)
    seg = acs[:, :, :, None] - acs[:, :, None, :]     # (b, nc, i, j, e)
    visible = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(visible[:, :, None], seg, -jnp.inf))
    w = (cb[..., None] * decay * dt[:, :, None]).astype(dtype)
    y = _dot("bcije,bcjep->bciep", w, xc)

    # a chunk's own state at its end, and the decay over the whole chunk
    to_end = (jnp.exp(acs[:, :, -1:] - acs) * dt).astype(dtype)
    states = _dot("bcjn,bcjep->bcenp", Bc, xc * to_end[..., None])
    chunk_decay = jnp.exp(acs[:, :, -1])              # (b, nc, e)

    def carry(s, inp):
        own, d = inp
        return s * d[..., None, None] + own, s        # emits the state BEFORE

    _, before = jax.lax.scan(
        carry, jnp.zeros((b, e, n, p), _F32),
        (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                    # (b, nc, e, n, p)

    # what the chunks before contribute: C_i . S_before, decayed to i
    y = y + _dot("bcin,bcenp->bciep", Cc, before.astype(dtype)) \
        * jnp.exp(acs)[..., None]
    return y.reshape(b, l, e, p).astype(dtype)


def _ssd_chunked(x, dt, A, B, C, chunk):
    """``y`` without the ``D x`` term.  ``x`` (b, l, h, p), ``dt``
    (b, l, h), ``A`` (h,), ``B``/``C`` (b, l, g, n); head ``i`` reads group
    ``i // (h // g)``; ``l`` a multiple of ``chunk``.  The groups run one
    after another, each rematerialised in the backward pass: a chunk's
    (chunk, chunk, heads) float32 decay mask is the scan's largest
    intermediate, and one group's is alive at a time."""
    b, l, h, p = x.shape
    g = B.shape[2]
    e = h // g

    def to_front(t, *tail):                           # (g, b, l, ...)
        return jnp.moveaxis(t.reshape(b, l, g, *tail), 2, 0)

    y = jax.lax.map(
        jax.checkpoint(lambda a: _ssd_group(*a, chunk=chunk)),
        (to_front(x, e, p), to_front(dt, e), A.reshape(g, e),
         jnp.moveaxis(B, 2, 0), jnp.moveaxis(C, 2, 0)))
    return jnp.moveaxis(y, 0, 2).reshape(b, l, h, p)  # (b, l, g, e, p)


def _ssd_fused(x, dt, A, B, C, D, chunk):
    """``y`` WITH the ``D x`` term from the Pallas kernels, shapes as
    ``_ssd_chunked``.  The kernels take the sequence along the last axis;
    XLA runs a mixer's projections and convolution that way round on the
    chip, so the transposes here are dimension orders, not copies.  The
    decay sums are 8 bytes a position and head, and jax differentiates
    their cumulative sum."""
    from . import pallas_kernels as _pk

    b, l, h, p = x.shape
    g, n = B.shape[2:]
    # (batch, group, chunk, head, position)
    dt = jnp.moveaxis(dt.astype(_F32).swapaxes(1, 2)
                      .reshape(b, g, h // g, l // chunk, chunk), 2, 3)
    # the sum over a chunk's steps up to each: a product with a triangle of
    # ones at the highest precision (float32 to rounding; XLA's own
    # cumulative sum along the lanes ran a millisecond a layer on the v5e)
    a = jnp.einsum("bgceq,qr->bgcer",
                   dt * A.astype(_F32).reshape(g, 1, h // g, 1),
                   jnp.triu(jnp.ones((chunk, chunk), _F32)),
                   precision=jax.lax.Precision.HIGHEST)
    y = _pk.ssd_chunk_scan(
        x.reshape(b, l, h * p).swapaxes(1, 2),
        B.reshape(b, l, g * n).swapaxes(1, 2),
        C.reshape(b, l, g * n).swapaxes(1, 2), a, dt, D)
    return y.swapaxes(1, 2).reshape(b, l, h, p)


@functools.partial(jax.jit, static_argnames=("chunk_size", "fused"))
def _ssd_scan(x, dt, A, B, C, D, chunk_size, fused):
    with jax.named_scope(SCAN_SCOPE):
        l = x.shape[1]
        pad = -l % chunk_size
        args = (x, dt, B, C)
        if pad:
            # dt = 0 behind the end: no decay, no input, outputs cut off
            args = tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                                 (t.ndim - 2)) for t in args)
        xp, dtp, Bp, Cp = args
        if fused:
            return _ssd_fused(xp, dtp, A, Bp, Cp, D, chunk_size)[:, :l]
        y = _ssd_chunked(xp, dtp, A, Bp, Cp, chunk_size)
        y = y[:, :l].astype(_F32) + D.astype(_F32)[:, None] * x.astype(_F32)
        return y.astype(x.dtype)


_SCAN_FUSED = _telemetry.counter(
    "ssm.scan_fused", "state-space scan sites traced onto the Pallas kernels")
_SCAN_UNFUSED = _telemetry.counter(
    "ssm.scan_unfused",
    "state-space scan sites traced as the chunked expression of XLA's "
    "products")


def _scan_platform() -> str:
    return jax.default_backend()


def _fused_scan_refusal(x, B, C, chunk):
    """Why a TPU trace cannot take the Pallas kernels at this site, or None:
    ``pallas_call`` has no partitioning rule, and Mosaic wants a chunk's
    positions and a state in whole 128-lane columns and a head's rows in
    whole bf16 tiles."""
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"mesh of {mesh.size} devices"
    (h, p), (g, n) = x.shape[2:], B.shape[2:]
    if chunk % 128 or n % 128 or h % g or p % 16:
        return ("chunk_size and the state must be multiples of 128, "
                "head_dim one of 16")
    if not x.dtype == B.dtype == C.dtype:
        return "x, B and C of different types"
    return None


@register("ssd_scan", num_inputs=6)
def ssd_scan(x, dt, A, B, C, D, chunk_size=128):
    """The Mamba-2 selective scan.  ``x`` (batch, length, heads, head_dim);
    ``dt`` (batch, length, heads), already positive; ``A`` (heads,),
    negative; ``B``, ``C`` (batch, length, groups, state); ``D`` (heads,).
    Returns ``y`` of ``x``'s shape and type.  Any length: one that is no
    multiple of ``chunk_size`` is padded with steps that do nothing.  On a
    TPU with no mesh the Pallas kernels, anywhere else the chunked
    expression; counted and refused as ``interleaved_selfatt`` is."""
    chunk_size = int(chunk_size)
    fused = _scan_platform() == "tpu"
    if fused:
        refusal = _fused_scan_refusal(x, B, C, chunk_size)
        if refusal is not None:
            fused = False
            _telemetry.event("fallback", "ssm.scan_fused",
                             length=x.shape[1], chunk=chunk_size,
                             why=refusal)
    (_SCAN_FUSED if fused else _SCAN_UNFUSED).inc()
    return _ssd_scan(x, dt, A, B, C, D, chunk_size, fused)


def ssd_scan_sequential(x, dt, A, B, C, D):
    """The recurrence one step a token, float32: what :func:`ssd_scan` is
    tested against (and never what a program runs)."""
    b, l, h, p = x.shape
    g, n = B.shape[2:]
    x, dt, B, C = (t.astype(_F32) for t in (x, dt, B, C))
    B, C = (jnp.repeat(t, h // g, axis=2) for t in (B, C))    # (b, l, h, n)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * b_t)[..., None] * x_t[:, :, None, :]
        return s, jnp.einsum("bhn,bhnp->bhp", c_t, s,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((b, h, n, p), _F32),
                        tuple(t.swapaxes(0, 1) for t in (x, dt, B, C)))
    return y.swapaxes(0, 1) + D.astype(_F32)[:, None] * x


@register("causal_conv1d", num_inputs=3)
def causal_conv1d(data, weight, bias=None, activation=None):
    """Depthwise causal convolution over a sequence: ``data`` (batch,
    length, channels), ``weight`` (channels, kernel), ``bias`` (channels,)
    or none:
    ``out[t] = bias + sum_k weight[:, k] * data[t - (kernel - 1) + k]``,
    positions before the start read as zero.  ``activation='silu'`` applies
    ``x * sigmoid(x)`` to the result.  Computed as ``kernel`` shifted
    multiply-adds in float32, which XLA fuses with what stands around them.
    (One grouped ``Convolution`` with as many groups as channels, the same
    arithmetic, read 5 ms a step less under its own scope on the v5e and
    cost the step 1.9% of its rate and 0.73 GiB: PERF.md, PR 31.)"""
    channels, kernel = weight.shape
    length = data.shape[1]
    with jax.named_scope(CONV_SCOPE):
        padded = jnp.pad(data, ((0, 0), (kernel - 1, 0), (0, 0))) \
            .astype(_F32)
        w = weight.astype(_F32)
        out = sum(padded[:, k:k + length] * w[:, k] for k in range(kernel))
        if bias is not None:
            out = bias.astype(_F32) + out
        if activation == "silu":
            out = out * jax.nn.sigmoid(out)
        elif activation is not None:
            raise ValueError(f"causal_conv1d: unknown activation "
                             f"{activation!r}")
        return out.astype(data.dtype)


def _rms(x32, eps):
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                               + eps)


@register("RMSNorm", num_inputs=2)
def rms_norm(data, gamma, eps=1e-5):
    """``data / sqrt(mean(data^2) + eps) * gamma`` over the last axis,
    float32 inside, ``data``'s type out."""
    return (_rms(data.astype(_F32), eps) * gamma.astype(_F32)) \
        .astype(data.dtype)


@register("GatedRMSNorm", num_inputs=3)
def gated_rms_norm(data, gate, gamma, num_groups=1, eps=1e-5,
                   norm_before_gate=False):
    """The Mamba-2 mixer's norm: ``data * silu(gate)``, then an RMS norm
    over each of ``num_groups`` equal groups of the last axis, times
    ``gamma``.  With ``norm_before_gate`` the order is the gated delta
    rule's: the norm and ``gamma`` first, ``silu(gate)`` on the result.
    float32 inside, ``data``'s type out."""
    g32 = gate.astype(_F32)
    silu = g32 * jax.nn.sigmoid(g32)
    x = data.astype(_F32)
    if not norm_before_gate:
        x = x * silu
    grouped = x.reshape(x.shape[:-1] + (num_groups, -1))
    out = _rms(grouped, eps).reshape(x.shape) * gamma.astype(_F32)
    if norm_before_gate:
        out = out * silu
    return out.astype(data.dtype)
