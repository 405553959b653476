"""Pallas TPU kernels: the attention core, the one place where a hand-written
kernel beats XLA's own fusion on the chip.

Flash attention (forward + backward) as Pallas kernels: the S×S score
matrix never materializes in HBM.  Up to 512 keys a head's whole score tile
lives in VMEM (plain softmax, one backward kernel for dq, dk and dv, several
heads a grid step); longer sequences are tiled with online softmax — O(S)
memory instead of O(S²), the enabler for long-context training.  Dropout on
the probabilities happens inside the kernels, from a counter-based hash of
the position that the backward regenerates (no mask is stored).

Reference analog: the fused transformer attention matmuls
(``src/operator/contrib/transformer.cc:650-740``,
``interleaved_matmul_selfatt_qk/valatt``) — which still materialized the
full score matrix; this is the TPU-first replacement, not a translation.
Callers: ``ops/contrib.py interleaved_selfatt`` (the Gluon BERT attention
core) and ``models/transformer_lm.py``.  Beside it, for the state-space /
sparse-expert decoder: the grouped causal kernels, the grouped matrix
product of a rank's held experts, and the Mamba-2 chunked scan (each under
its own heading below).

On the CPU the kernels run under the Pallas interpreter (slow but exact) so
the CPU test suite validates the same code path that runs on hardware; any
platform that is neither ``tpu`` nor ``cpu`` raises (:func:`_interpret`).

TPU lowering constraints honored throughout (Mosaic requires the last two
block dims divisible by (8, 128) or equal to the array dims): softmax
stats (m/l/lse/delta) are carried as COLUMN vectors with a trailing unit
dim — block (block_q, 1) passes because 1 == the array's own last dim.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as onp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _telemetry
from .random import _keep, _seed_words, keep_mask

__all__ = ["flash_attention", "flash_attention_qkv", "flash_attention_gqa",
           "qkv_heads_per_step", "gqa_block", "dropout_keep_mask",
           "grouped_matmul", "GROUP_TILE", "ssd_chunk_scan"]

_NEG_INF = -1e30


def _interpret() -> bool:
    """Mosaic on a TPU, the Pallas interpreter on the CPU (the test
    suite), and nothing else: any other platform raises instead of
    interpreting silently."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels need platform 'tpu' (Mosaic) or 'cpu' "
        f"(interpret mode); the default JAX backend is {platform!r}")


# ---------------------------------------------------------------------------
# dropout on the probabilities: the program's one keep mask (ops/random.py)
# ---------------------------------------------------------------------------
#
# A pure function of (key words, flattened batch*head index, query position,
# key position): the forward and every backward kernel regenerate it tile by
# tile, nothing is stored, and it does not depend on the tiling.


def dropout_keep_mask(key, num_heads, seq_q, seq_k, dropout_p):
    """The dense ``(num_heads, seq_q, seq_k)`` boolean keep mask that
    :func:`flash_attention` applies inside its kernels for ``key``:
    ``num_heads`` is the flattened batch*heads extent."""
    return keep_mask(key, (num_heads, seq_q, seq_k), 1.0 - dropout_p)


# ---------------------------------------------------------------------------
# pieces every attention kernel shares
# ---------------------------------------------------------------------------
#
# MXU operands stay in the caller's dtype (bf16 under AMP, float32 for
# float32 callers); products accumulate in float32 and the softmax
# statistics (m, l, lse, delta) are float32.


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):          # a @ b.T
    return _dot(a, b, (1, 1))


def _dot_nn(a, b):          # a @ b
    return _dot(a, b, (1, 0))


def _dot_tn(a, b):          # a.T @ b
    return _dot(a, b, (0, 0))


def _scores(q, k, sm_scale):
    """(rows, cols) float32 ``q k^T * sm_scale``.  A power-of-two scale
    folds into q without rounding (head_dim 64: 0.125); any other is applied
    to the float32 scores."""
    if math.frexp(sm_scale)[0] == 0.5:
        return _dot_nt(q * jnp.asarray(sm_scale, q.dtype), k)
    return _dot_nt(q, k) * sm_scale


def _masks(seed_ref, head, q0, k0, rows, cols, causal, dropout_p):
    """``(visible, keep)`` for the tile at (q0, k0); each is None when the
    kernel was built without it."""
    if not causal and not dropout_p:
        return None, None
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    visible = (q_pos >= k_pos) if causal else None
    keep = _keep(seed_ref[0], seed_ref[1], head, q_pos, k_pos,
                 1.0 - dropout_p) if dropout_p else None
    return visible, keep


def _where(mask, x, other=0.0):
    return x if mask is None else jnp.where(mask, x, other)


# ---------------------------------------------------------------------------
# whole-row kernels (seq <= _ROW_SEQ_MAX): every key of a head at once
# ---------------------------------------------------------------------------
#
# A (seq, seq) float32 score tile fits VMEM, so the softmax is the plain one
# (no online rescaling), nothing but the output leaves the forward, and ONE
# backward kernel recomputes the tile once for dq, dk and dv.  A grid step
# takes several heads: short sequences would otherwise pay the per-step
# cost a thousand times a call.  Two layouts share the per-head arithmetic:
# (batch*heads, seq, head_dim) operands, and the interleaved projection
# (seq, batch*heads*3*head_dim) read and written in place, whose blocks are
# whole 128-lane rows and which needs no copy of q, k, v around the call.


def _row_softmax(seed_ref, head, q, k, sm_scale, causal, dropout_p):
    """``(e, keep, l)``: the unnormalised probabilities of one head, the
    dropout keep mask (or None) and the float32 normaliser.  The normaliser
    is the undropped one: dropout acts on the normalised probabilities."""
    seq = q.shape[0]
    visible, keep = _masks(seed_ref, head, 0, 0, seq, seq, causal, dropout_p)
    s = _where(visible, _scores(q, k, sm_scale), _NEG_INF)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return e, keep, jnp.sum(e, axis=-1, keepdims=True)


def _row_head_fwd(seed_ref, head, q, k, v, *, sm_scale, causal, dropout_p):
    e, keep, l = _row_softmax(seed_ref, head, q, k, sm_scale, causal,
                              dropout_p)
    return _dot_nn(_where(keep, e).astype(v.dtype), v) \
        / (l * (1.0 - dropout_p))


def _row_head_bwd(seed_ref, head, q, k, v, o, do, *, sm_scale, causal,
                  dropout_p):
    """float32 ``(dq, dk, dv)`` of one head."""
    keep_p = 1.0 - dropout_p
    e, keep, l = _row_softmax(seed_ref, head, q, k, sm_scale, causal,
                              dropout_p)
    # per-row factors stay out of the (seq, seq) tile: with
    # w = 1 / (l * keep_p), P~ = keep * e * w and
    # dS = e * w * (keep * dP~ - keep_p * delta)
    w = 1.0 / (l * keep_p)
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * o.astype(jnp.float32), axis=-1, keepdims=True)
    dv = _dot_tn(_where(keep, e).astype(do.dtype), (do32 * w).astype(do.dtype))
    ds = (e * (_where(keep, _dot_nt(do, v)) - keep_p * delta)).astype(q.dtype)
    w = w * sm_scale
    dq = _dot_nn(ds, k) * w
    dk = _dot_tn(ds, (q.astype(jnp.float32) * w).astype(q.dtype))
    return dq, dk, dv


def _row_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, *, heads, **kw):
    for g in range(heads):
        o = _row_head_fwd(seed_ref, pl.program_id(0) * heads + g, q_ref[g],
                          k_ref[g], v_ref[g], **kw)
        o_ref[g] = o.astype(o_ref.dtype)


def _row_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, do_ref, dq_ref,
                    dk_ref, dv_ref, *, heads, **kw):
    for g in range(heads):
        grads = _row_head_bwd(seed_ref, pl.program_id(0) * heads + g,
                              q_ref[g], k_ref[g], v_ref[g], o_ref[g],
                              do_ref[g], **kw)
        for ref, grad in zip((dq_ref, dk_ref, dv_ref), grads):
            ref[g] = grad.astype(ref.dtype)


def _qkv_of_head(x_ref, g, d):
    """Head ``g`` of an interleaved block: columns (head, q|k|v, d)."""
    return (x_ref[:, (3 * g + j) * d:(3 * g + j + 1) * d] for j in range(3))


def _qkv_first_head(heads, num_heads):
    """The flattened batch*num_heads index of a grid step's first head:
    the grid is (batch, num_heads // heads)."""
    return pl.program_id(0) * num_heads + pl.program_id(1) * heads


def _qkv_fwd_kernel(seed_ref, x_ref, o_ref, *, heads, num_heads, d, **kw):
    head0 = _qkv_first_head(heads, num_heads)
    outs = [_row_head_fwd(seed_ref, head0 + g, *_qkv_of_head(x_ref, g, d),
                          **kw)
            for g in range(heads)]
    o_ref[...] = jnp.concatenate(outs, axis=-1).astype(o_ref.dtype)


def _qkv_bwd_kernel(seed_ref, x_ref, o_ref, do_ref, dx_ref, *, heads,
                    num_heads, d, **kw):
    head0 = _qkv_first_head(heads, num_heads)
    grads = []
    for g in range(heads):
        cols = slice(g * d, (g + 1) * d)
        grads.extend(_row_head_bwd(
            seed_ref, head0 + g, *_qkv_of_head(x_ref, g, d), o_ref[:, cols],
            do_ref[:, cols], **kw))
    dx_ref[...] = jnp.concatenate(grads, axis=-1).astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# blocked kernels (longer sequences): online softmax over k-blocks, the
# backward as dq over q-blocks and dk/dv over k-blocks
# ---------------------------------------------------------------------------


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                block_k, causal, block_q, seq_len, dropout_p):
    head, qi = pl.program_id(0), pl.program_id(1)
    q = q_ref[0]                                        # (block_q, d)
    d = q.shape[-1]
    # causal: only k-blocks at or before this q-block participate
    num_kb = pl.cdiv((qi + 1) * block_q, block_k) if causal \
        else seq_len // block_k

    def body(ki, carry):
        acc, m_prev, l_prev = carry                     # stats: (block_q, 1)
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = _scores(q, k, sm_scale)                     # (block_q, block_k)
        visible, keep = _masks(seed_ref, head, qi * block_q, ki * block_k,
                               block_q, block_k, causal, dropout_p)
        s = _where(visible, s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(-1, keepdims=True)
        acc = acc * alpha + _dot_nn(_where(keep, p).astype(v.dtype), v)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_kb, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / (l * (1.0 - dropout_p))).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)                         # (block_q, 1)


def _bwd_tile(seed_ref, head, q0, k0, q, k, v, do, lse, delta, *, sm_scale,
              causal, dropout_p):
    """``(P~, dS)`` of one (block_q, block_k) tile, in the operand dtype:
    the dropped probabilities and the gradient of the scaled scores."""
    s = _scores(q, k, sm_scale)
    visible, keep = _masks(seed_ref, head, q0, k0, s.shape[0], s.shape[1],
                           causal, dropout_p)
    p = jnp.exp(_where(visible, s, _NEG_INF) - lse)
    inv_keep = 1.0 / (1.0 - dropout_p)
    dp = _where(keep, _dot_nt(do, v)) * inv_keep
    ds = p * (dp - delta) * sm_scale
    return (_where(keep, p) * inv_keep).astype(do.dtype), ds.astype(q.dtype)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, block_k, block_q, seq_len, causal, **kw):
    head, qi = pl.program_id(0), pl.program_id(1)
    q, do = q_ref[0], do_ref[0]
    lse, delta = lse_ref[0], delta_ref[0]               # (block_q, 1)
    num_kb = pl.cdiv((qi + 1) * block_q, block_k) if causal \
        else seq_len // block_k

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, pl.ds(ki * block_k, block_k), :]
        _, ds = _bwd_tile(seed_ref, head, qi * block_q, ki * block_k, q, k, v,
                          do, lse, delta, causal=causal, **kw)
        return dq + _dot_nn(ds, k)

    dq = jax.lax.fori_loop(0, num_kb, body,
                           jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, block_q, block_k, seq_len,
                    causal, **kw):
    head, ki = pl.program_id(0), pl.program_id(1)
    k, v = k_ref[0], v_ref[0]                           # (block_k, d)
    start_qb = (ki * block_k) // block_q if causal else 0

    def body(qi, carry):
        dk, dv = carry
        rows = pl.ds(qi * block_q, block_q)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        pd, ds = _bwd_tile(seed_ref, head, qi * block_q, ki * block_k, q, k,
                           v, do, lse_ref[0, rows, :], delta_ref[0, rows, :],
                           causal=causal, **kw)
        return dk + _dot_tn(ds, q), dv + _dot_tn(pd, do)

    zeros = jnp.zeros(k.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(start_qb, seq_len // block_q, body,
                               (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrappers (the pallas_call entries are jitted, so a model's
# identical layers lower ONE function each way)
# ---------------------------------------------------------------------------

# The block rule (fixed in code; tuned on the v5e, PERF.md PR 25).  A
# sequence of up to _ROW_SEQ_MAX keys takes the whole-row kernels; a grid
# step holds as many heads as keep its float32 score tiles within
# _ROW_TILE_ELEMS, at most _ROW_HEADS_MAX (the kernels unroll over them).
# Longer sequences take the blocked kernels at _BLOCK: at 512 keys blocks of
# 512 ran 2.8x faster than the 128 this rule used to prefer.
_ROW_SEQ_MAX = 512
_ROW_TILE_ELEMS = 512 * 512
_ROW_HEADS_MAX = 16
_BLOCK = 512


def _divisor(n, preferred, multiple=1):
    """The largest divisor of ``n`` that is at most ``preferred``, a
    multiple of ``multiple`` if ``n`` has such a divisor."""
    fits = [b for b in range(1, min(preferred, n) + 1) if n % b == 0]
    return max([b for b in fits if b % multiple == 0] or fits)


def _plan(bh, s):
    """``("rows", heads)`` or ``("blocks", block_q, block_k)``."""
    if s > _ROW_SEQ_MAX:
        block = _divisor(s, _BLOCK, multiple=8)         # Mosaic's sublanes
        return ("blocks", block, block)
    return ("rows", _divisor(bh, min(_ROW_HEADS_MAX,
                                     max(1, _ROW_TILE_ELEMS // (s * s)))))


def _call(kernel, grid, in_specs, out_specs, out_shape, seed, *args):
    """``pallas_call`` with the two seed words as the scalar prefetch (the
    index maps take them as a trailing argument and ignore it)."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        interpret=_interpret(),
    )(seed, *args)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _forward(q, k, v, seed, causal, sm_scale, dropout_p, plan):
    """``(out, lse)``; ``lse`` is None on the whole-row path, whose
    backward recomputes the statistics."""
    bh, s, d = q.shape
    kw = dict(sm_scale=sm_scale, causal=causal, dropout_p=dropout_p)
    if plan[0] == "rows":
        heads = plan[1]
        spec = pl.BlockSpec((heads, s, d), lambda b, seed: (b, 0, 0))
        out = _call(functools.partial(_row_fwd_kernel, heads=heads, **kw),
                    (bh // heads,), [spec] * 3, spec,
                    jax.ShapeDtypeStruct(q.shape, q.dtype), seed, q, k, v)
        return out, None
    _, bq, bk = plan
    q_blk = pl.BlockSpec((1, bq, d), lambda b, i, seed: (b, i, 0))
    whole = pl.BlockSpec((1, s, d), lambda b, i, seed: (b, 0, 0))
    return _call(
        functools.partial(_fwd_kernel, block_q=bq, block_k=bk, seq_len=s,
                          **kw),
        (bh, s // bq), [q_blk, whole, whole],
        [q_blk, pl.BlockSpec((1, bq, 1), lambda b, i, seed: (b, i, 0))],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)], seed, q, k, v)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _backward(q, k, v, o, lse, do, seed, causal, sm_scale, dropout_p,
              plan):
    bh, s, d = q.shape
    kw = dict(sm_scale=sm_scale, causal=causal, dropout_p=dropout_p)
    like = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if plan[0] == "rows":
        heads = plan[1]
        spec = pl.BlockSpec((heads, s, d), lambda b, seed: (b, 0, 0))
        return _call(functools.partial(_row_bwd_kernel, heads=heads, **kw),
                     (bh // heads,), [spec] * 5, [spec] * 3, [like] * 3,
                     seed, q, k, v, o, do)
    _, bq, bk = plan
    kw.update(block_q=bq, block_k=bk, seq_len=s)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                       # (bh, s, 1)
    def blk(rows, width):
        return pl.BlockSpec((1, rows, width), lambda b, i, seed: (b, i, 0))

    whole = pl.BlockSpec((1, s, d), lambda b, i, seed: (b, 0, 0))
    whole_stat = pl.BlockSpec((1, s, 1), lambda b, i, seed: (b, 0, 0))
    dq = _call(functools.partial(_bwd_dq_kernel, **kw), (bh, s // bq),
               [blk(bq, d), whole, whole, blk(bq, d), blk(bq, 1), blk(bq, 1)],
               blk(bq, d), like, seed, q, k, v, do, lse, delta)
    dk, dv = _call(functools.partial(_bwd_dkv_kernel, **kw), (bh, s // bk),
                   [whole, blk(bk, d), blk(bk, d), whole, whole_stat,
                    whole_stat],
                   [blk(bk, d)] * 2, [like] * 2, seed, q, k, v, do, lse,
                   delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, seed, causal, sm_scale, dropout_p, plan):
    return _forward(q, k, v, seed, causal, sm_scale, dropout_p, plan)[0]


def _flash_fwd(q, k, v, seed, causal, sm_scale, dropout_p, plan):
    out, lse = _forward(q, k, v, seed, causal, sm_scale, dropout_p, plan)
    return out, (q, k, v, out, lse, seed)


def _flash_bwd(causal, sm_scale, dropout_p, plan, res, do):
    q, k, v, out, lse, seed = res
    dq, dk, dv = _backward(q, k, v, out, lse, do, seed, causal, sm_scale,
                           dropout_p, plan)
    return dq, dk, dv, onp.zeros(seed.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _dropout_args(dropout_p, dropout_key):
    """The static rate and the seed words a kernel call takes."""
    dropout_p = float(dropout_p)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p and dropout_key is None:
        raise ValueError("dropout_p > 0 needs dropout_key")
    return dropout_p, (_seed_words(dropout_key) if dropout_p
                       else jnp.zeros((2,), jnp.int32))


def flash_attention(q, k, v, causal=True, sm_scale=None, dropout_p=0.0,
                    dropout_key=None):
    """Tiled attention: ``dropout(softmax(q k^T * scale [+ causal mask])) v``.

    q/k/v: (..., num_heads, seq, head_dim); leading dims are flattened into
    the kernel grid.  Differentiable (custom VJP with flash backward).
    ``dropout_p`` is static; with ``dropout_p > 0`` the probabilities are
    dropped inside the kernels with the mask :func:`dropout_keep_mask`
    gives for ``dropout_key`` (a PRNG key, required then) and the kept ones
    scaled by ``1 / (1 - dropout_p)``.
    """
    orig_shape = q.shape
    *lead, s, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    dropout_p, seed = _dropout_args(dropout_p, dropout_key)
    bh = math.prod(lead)
    q3, k3, v3 = (t.reshape(bh, s, d) for t in (q, k, v))
    out = _flash(q3, k3, v3, seed, bool(causal), float(sm_scale), dropout_p,
                 _plan(bh, s))
    return out.reshape(orig_shape)


# -- causal attention with grouped key-value heads, in place -----------------
#
# The projections as they lie: q and the output (batch, seq, heads*head_dim),
# k and v (batch, seq, kv_heads*head_dim); query head h reads key-value head
# h // (heads // kv_heads) through the index map, so no copy of a key or a
# value is written.  A block is (block, head_dim) columns of one batch row:
# head_dim must be whole 128-lane columns, one (32 query heads of 128 over 2
# key-value heads: Nemotron-H) or several (20 over 20 at 256, a group of
# one: latent attention, whose scores and values share the width).  Blocks
# above the diagonal are never fetched.
#
# The forward holds three (block, head_dim) operands and a float32
# accumulator of that shape a step, the key blocks a grid dimension: nothing
# of a whole sequence is resident, so its length is bounded by HBM alone.
#
# The backward is ONE kernel where its accumulators fit ``_GQA_BWD_VMEM``
# (``_gqa_bwd_resident``; both cells' shapes do: 10 MiB at 8,192 keys of 256
# in groups of one, 12 MiB at 8,192 of 128 in groups of 16).  Its grid is
# (batch, key-value head, head of the group, visible (key block, query
# block) pair), the pairs key block by key block from two prefetched tables,
# so a tile above the diagonal is no grid step at all.  A pair's ``(P, dS)``
# is built once (``_bwd_tile``) and feeds all three gradients.  What is
# resident, in float32 VMEM scratch: ``dq`` of the current head over the
# WHOLE sequence, because a query block's gradient is revisited once a key
# block; ``dk`` and ``dv`` of the current key block, or of the whole
# sequence where a group's heads share them (they are revisited once a
# head).  An output block is written exactly once, in the one run of steps
# that maps to it: Mosaic writes an output block back when its index
# changes and does NOT read it back on a later visit (the interpreter does,
# so only the accumulators may be revisited).  ``dq`` of query block i is
# complete at the diagonal pair (i, i), the first step of key block i, and
# goes out under key block i's index; ``dk`` and ``dv`` of key block j are
# complete at the group's last head's pair (j, last) and until that head
# their output index rests on block 0, which nothing writes before.  The
# scratch is over Mosaic's default scoped limit, so the call states
# ``vmem_limit_bytes``.
#
# Past the budget (32,768 keys of 256: 34 MiB) the backward is the two
# kernels it was before: dq over (query head, query block) with the key
# blocks inside, dk and dv over (key-value head, key block) with the group's
# query heads and their query blocks inside; every tile is then built twice.
# Nothing of a whole sequence is resident there.  Which form a trace took is
# counted (``attention.gqa_backward_fused`` / ``..._split``).

# what the fused backward's float32 accumulators may take of VMEM, and what
# Mosaic is told the call may use in all: the accumulators, the pipeline's
# copies of nine (block, head_dim) blocks and a tile's float32 intermediates
_GQA_BWD_VMEM = 24 << 20
_GQA_BWD_VMEM_LIMIT = 64 << 20
# the fused backward's block.  A tile's fixed costs (the grid step, three
# accumulators read and written, nine blocks handed over) are paid once for
# four times the work of the forward's 512.  On the v5e at 8,192 keys, the
# delta reduction included (tools/gqa_backward_check.py; PERF.md, PR 36):
# 20 heads of 256 in groups of one 12.3 ms a call against 14.1 at 512 and
# 20.9 at 256 (the two kernels: 17.6); 32 heads of 128 over 2 11.0 against
# 14.3 and 29.3 (17.2).  The forward keeps _BLOCK.
_GQA_BWD_BLOCK = 1024

_GQA_BWD_FUSED = _telemetry.counter(
    "attention.gqa_backward_fused",
    "backward passes of the grouped causal core traced as the one kernel "
    "that builds a score tile once for dq, dk and dv")
_GQA_BWD_SPLIT = _telemetry.counter(
    "attention.gqa_backward_split",
    "backward passes of the grouped causal core traced as the dq kernel and "
    "the dk/dv kernel: the fused kernel's accumulators did not fit VMEM")


def _gqa_visible(qi, ki):
    """Does key block ``ki`` hold a key that query block ``qi`` sees?
    (Query and key blocks have one size.)"""
    return ki <= qi


def _gqa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                    sm_scale, block):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)
        m[...] = jnp.full(m.shape, _NEG_INF, m.dtype)
        l[...] = jnp.zeros(l.shape, l.dtype)

    @pl.when(_gqa_visible(qi, ki))
    def _():
        s = _scores(q_ref[...], k_ref[...], sm_scale)
        visible, _ = _masks(None, 0, qi * block, ki * block, block, block, True,
                            0.0)
        s = jnp.where(visible, s, _NEG_INF)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l[...] = l[...] * alpha + p.sum(-1, keepdims=True)
        acc[...] = acc[...] * alpha + _dot_nn(p.astype(v_ref.dtype),
                                              v_ref[...])
        m[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc[...] / l[...]).astype(o_ref.dtype)
        lse_ref[...] = m[...] + jnp.log(l[...])


def _gqa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, acc, *, sm_scale, block):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(_gqa_visible(qi, ki))
    def _():
        _, ds = _bwd_tile(None, 0, qi * block, ki * block, q_ref[...], k_ref[...],
                          v_ref[...], do_ref[...], lse_ref[...],
                          delta_ref[...], sm_scale=sm_scale, causal=True,
                          dropout_p=0.0)
        acc[...] += _dot_nn(ds, k_ref[...])

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = acc[...].astype(dq_ref.dtype)


def _gqa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, block,
                        nq):
    ki, t = pl.program_id(2), pl.program_id(3)
    qi = t % nq

    @pl.when(t == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)

    @pl.when(_gqa_visible(qi, ki))
    def _():
        pd, ds = _bwd_tile(None, 0, qi * block, ki * block, q_ref[...], k_ref[...],
                           v_ref[...], do_ref[...], lse_ref[...],
                           delta_ref[...], sm_scale=sm_scale, causal=True,
                           dropout_p=0.0)
        dk_acc[...] += _dot_tn(ds, q_ref[...])
        dv_acc[...] += _dot_tn(pd, do_ref[...])

    @pl.when(t == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _gqa_bwd_fused_kernel(kb_ref, qb_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                          dk_acc, dv_acc, *, sm_scale, block, group):
    g, t = pl.program_id(2), pl.program_id(3)
    ki, qi = kb_ref[t], qb_ref[t]
    # a group of one visits a key block's dk and dv in one run of steps
    slot = ki if group > 1 else 0
    pd, ds = _bwd_tile(None, 0, qi * block, ki * block, q_ref[...],
                       k_ref[...], v_ref[...], do_ref[...], lse_ref[...],
                       delta_ref[...], sm_scale=sm_scale, causal=True,
                       dropout_p=0.0)

    def add(acc, at, first, term):
        @pl.when(first)
        def _():
            acc[at] = term

        @pl.when(jnp.logical_not(first))
        def _():
            acc[at] += term

    add(dq_acc, qi, ki == 0, _dot_nn(ds, k_ref[...]))
    opens = (qi == ki) & (g == 0)
    add(dk_acc, slot, opens, _dot_tn(ds, q_ref[...]))
    add(dv_acc, slot, opens, _dot_tn(pd, do_ref[...]))

    @pl.when(qi == ki)                      # no later key block is visible
    def _():
        dq_ref[...] = dq_acc[qi].astype(dq_ref.dtype)

    @pl.when((qi == dq_acc.shape[0] - 1) & (g == group - 1))
    def _():
        dk_ref[...] = dk_acc[slot].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[slot].astype(dv_ref.dtype)


def _gqa_call(kernel, grid, in_specs, out_specs, out_shape, scratch, *args):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=_interpret())(*args)


def gqa_block(seq):
    """Rows a block of the grouped causal kernels, or None when they cannot
    take the length (Mosaic tiles bf16 rows in sixteens)."""
    block = _divisor(seq, _BLOCK, multiple=16)
    return block if block % 16 == 0 else None


def _gqa_specs(heads, kv_heads, d, block):
    """Block specs over the grid (batch, head, query block, key block):
    ``(q-like, k-like, lse-like)``; a key block above the diagonal maps to
    the diagonal's."""
    group = heads // kv_heads
    q_spec = pl.BlockSpec((None, block, d), lambda b, h, i, j: (b, i, h))
    k_spec = pl.BlockSpec(
        (None, block, d),
        lambda b, h, i, j: (b, jnp.minimum(j, i), h // group))
    stat_spec = pl.BlockSpec((None, None, block, 1),
                             lambda b, h, i, j: (b, h, i, 0))
    return q_spec, k_spec, stat_spec


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gqa_forward(q, k, v, heads, kv_heads, sm_scale, block):
    bsz, s, width = q.shape
    d = width // heads
    q_spec, k_spec, stat_spec = _gqa_specs(heads, kv_heads, d, block)
    return _gqa_call(
        functools.partial(_gqa_fwd_kernel, sm_scale=sm_scale, block=block),
        (bsz, heads, s // block, s // block), [q_spec, k_spec, k_spec],
        [q_spec, stat_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((bsz, heads, s, 1), jnp.float32)],
        [pltpu.VMEM((block, d), jnp.float32),
         pltpu.VMEM((block, 1), jnp.float32),
         pltpu.VMEM((block, 1), jnp.float32)], q, k, v)


def _gqa_bwd_block(seq, block):
    """Rows a block of the fused backward where the forward's is ``block``:
    up to ``_GQA_BWD_BLOCK`` where the sequence is four of them or more
    (with fewer, the masked half of the diagonal tiles costs what the
    larger tile saves), else the forward's."""
    wide = _divisor(seq, _GQA_BWD_BLOCK, multiple=16)
    return wide if wide % 16 == 0 and 4 * wide <= seq else block


def _gqa_bwd_resident(seq, d, group, block):
    """Bytes the fused backward's float32 accumulators take of VMEM: ``dq``
    of one head over the whole sequence, and ``dk`` and ``dv`` of one key
    block, or of the whole sequence where a group's heads share them."""
    return (seq + 2 * (seq if group > 1 else block)) * d * 4


def _gqa_bwd_fused(q, k, v, do, lse, delta, heads, kv_heads, sm_scale, block):
    bsz, s, width = q.shape
    d, group, nq = width // heads, heads // kv_heads, s // block
    # the visible (key block, query block) pairs, key block by key block
    kb, qb = (t.astype(onp.int32) for t in onp.triu_indices(nq))

    def head(h, g):
        return h * group + g

    q_spec = pl.BlockSpec(
        (None, block, d), lambda b, h, g, t, kb, qb: (b, qb[t], head(h, g)))
    k_spec = pl.BlockSpec(
        (None, block, d), lambda b, h, g, t, kb, qb: (b, kb[t], h))
    stat_spec = pl.BlockSpec(
        (None, None, block, 1),
        lambda b, h, g, t, kb, qb: (b, head(h, g), qb[t], 0))
    dq_spec = pl.BlockSpec(
        (None, block, d), lambda b, h, g, t, kb, qb: (b, kb[t], head(h, g)))
    dk_spec = pl.BlockSpec(
        (None, block, d),
        lambda b, h, g, t, kb, qb: (b, jnp.where(g == group - 1, kb[t], 0), h))
    kv_acc = pltpu.VMEM((nq if group > 1 else 1, block, d), jnp.float32)
    return pl.pallas_call(
        functools.partial(_gqa_bwd_fused_kernel, sm_scale=sm_scale,
                          block=block, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bsz, kv_heads, group, len(kb)),
            in_specs=[q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec],
            out_specs=[dq_spec, dk_spec, dk_spec],
            scratch_shapes=[pltpu.VMEM((nq, block, d), jnp.float32), kv_acc,
                            kv_acc]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_GQA_BWD_VMEM_LIMIT),
        interpret=_interpret())(kb, qb, q, k, v, do, lse, delta)


def _gqa_bwd_split(q, k, v, do, lse, delta, heads, kv_heads, sm_scale, block):
    bsz, s, width = q.shape
    d, group, nq = width // heads, heads // kv_heads, s // block
    q_spec, k_spec, stat_spec = _gqa_specs(heads, kv_heads, d, block)
    kw = dict(sm_scale=sm_scale, block=block)
    dq = _gqa_call(
        functools.partial(_gqa_bwd_dq_kernel, **kw),
        (bsz, heads, nq, nq),
        [q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec], q_spec,
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block, d), jnp.float32)], q, k, v, do, lse, delta)

    # grid (batch, kv head, key block, group's heads x query blocks): a
    # query block before the key block maps to the first one that sees it
    def head(h, t):
        return h * group + t // nq

    def first_query(j, t):
        return jnp.maximum(t % nq, j)

    qd_spec = pl.BlockSpec(
        (None, block, d),
        lambda b, h, j, t: (b, first_query(j, t), head(h, t)))
    kd_spec = pl.BlockSpec((None, block, d), lambda b, h, j, t: (b, j, h))
    sd_spec = pl.BlockSpec(
        (None, None, block, 1),
        lambda b, h, j, t: (b, head(h, t), first_query(j, t), 0))
    dk, dv = _gqa_call(
        functools.partial(_gqa_bwd_dkv_kernel, nq=nq, **kw),
        (bsz, kv_heads, nq, group * nq),
        [qd_spec, kd_spec, kd_spec, qd_spec, sd_spec, sd_spec],
        [kd_spec, kd_spec],
        [jax.ShapeDtypeStruct(k.shape, k.dtype)] * 2,
        [pltpu.VMEM((block, d), jnp.float32)] * 2, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _gqa_backward(q, k, v, o, lse, do, heads, kv_heads, sm_scale, block,
                  fused):
    bsz, s, width = q.shape
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(bsz, s, heads, width // heads), axis=-1) \
        .transpose(0, 2, 1)[..., None]                   # (bsz, heads, s, 1)
    return (_gqa_bwd_fused if fused else _gqa_bwd_split)(
        q, k, v, do, lse, delta, heads, kv_heads, sm_scale, block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_gqa(q, k, v, heads, kv_heads, sm_scale, block):
    return _gqa_forward(q, k, v, heads, kv_heads, sm_scale, block)[0]


def _flash_gqa_fwd(q, k, v, heads, kv_heads, sm_scale, block):
    out, lse = _gqa_forward(q, k, v, heads, kv_heads, sm_scale, block)
    return out, (q, k, v, out, lse)


def _flash_gqa_bwd(heads, kv_heads, sm_scale, block, res, do):
    _, s, width = res[0].shape
    d = width // heads
    fused_block = _gqa_bwd_block(s, block)
    resident = _gqa_bwd_resident(s, d, heads // kv_heads, fused_block)
    fused = resident <= _GQA_BWD_VMEM
    if fused:
        _GQA_BWD_FUSED.inc()
        block = fused_block
    else:
        _GQA_BWD_SPLIT.inc()
        _telemetry.event(
            "fallback", "attention.gqa_backward_fused", seq=s, head_dim=d,
            why=f"{resident} bytes of float32 accumulators do not fit the "
                f"{_GQA_BWD_VMEM} the fused backward may keep in VMEM")
    return _gqa_backward(*res, do, heads, kv_heads, sm_scale, block, fused)


_flash_gqa.defvjp(_flash_gqa_fwd, _flash_gqa_bwd)


def flash_attention_gqa(q, k, v, num_heads, num_kv_heads, sm_scale=None):
    """Causal self-attention straight from grouped projections: ``q``
    (batch, seq, num_heads * head_dim), ``k`` and ``v`` (batch, seq,
    num_kv_heads * head_dim); query head ``h`` reads key-value head
    ``h // (num_heads // num_kv_heads)``.  The result has ``q``'s shape.
    Nothing is copied around the kernels, forward or backward, and the
    backward keeps ``q``, ``k``, ``v``, the output and one float32
    statistic a query.  Only for lengths :func:`gqa_block` accepts."""
    bsz, s, width = q.shape
    if num_heads % num_kv_heads or width % num_heads:
        raise ValueError(f"flash_attention_gqa: {num_heads} query heads "
                         f"over {num_kv_heads} key-value heads, width "
                         f"{width}")
    block = gqa_block(s)
    if block is None:
        raise ValueError(f"flash_attention_gqa cannot take seq {s}")
    d = width // num_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    return _flash_gqa(q, k, v, num_heads, num_kv_heads, float(sm_scale),
                      block)


# -- self-attention over the interleaved projection, in place ---------------
#
# The kernels take the projection batch-major, (batch, seq, heads*3*head_dim):
# a block is one batch row's (seq, heads*3*head_dim) columns, whole (8, 128)
# tiles of the array as it lies in HBM.  The (seq, batch, width) the operator
# speaks is the same buffer under another dimension order, which XLA's layout
# assignment gives the producing product directly: no relayout runs.  A
# (seq, batch*width) view of the seq-major array, the first in-place form,
# cost four untiled copies of the projection a layer (7.8% of the step at 128
# keys; PERF.md PR 25).

_QKV_HEADS = 4          # heads a grid step (v5e: 2, 4 and 6 within 3%)


def qkv_heads_per_step(seq, num_heads, head_dim):
    """Heads a grid step of :func:`flash_attention_qkv`, or None when its
    kernels cannot take the shape: whole-row kernels hold at most
    ``_ROW_SEQ_MAX`` keys, Mosaic tiles rows in eights, and a block must be
    whole 128-lane columns of one batch row."""
    if seq > _ROW_SEQ_MAX or seq % 8 or head_dim % 8:
        return None
    unit = 128 // math.gcd(128, head_dim)
    for heads in range(max(unit, _QKV_HEADS) // unit * unit, 0, -unit):
        if num_heads % heads == 0:
            return heads
    return None


def _qkv_grid(x, heads, d):
    """``(grid, kernel arguments, projection spec, output spec)`` for the
    batch-major projection ``x``: a grid step is ``heads`` heads of one
    batch row."""
    bsz, s, width = x.shape
    num_heads = width // (3 * d)
    x_spec, o_spec = (pl.BlockSpec((None, s, heads * n * d),
                                   lambda b, i, seed: (b, 0, i))
                      for n in (3, 1))
    return ((bsz, num_heads // heads),
            dict(heads=heads, num_heads=num_heads, d=d, causal=False),
            x_spec, o_spec)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _qkv_forward(x, seed, d, heads, sm_scale, dropout_p):
    grid, kw, x_spec, o_spec = _qkv_grid(x, heads, d)
    bsz, s, width = x.shape
    return _call(
        functools.partial(_qkv_fwd_kernel, sm_scale=sm_scale,
                          dropout_p=dropout_p, **kw),
        grid, [x_spec], o_spec,
        jax.ShapeDtypeStruct((bsz, s, width // 3), x.dtype), seed, x)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _qkv_backward(x, o, do, seed, d, heads, sm_scale, dropout_p):
    grid, kw, x_spec, o_spec = _qkv_grid(x, heads, d)
    return _call(
        functools.partial(_qkv_bwd_kernel, sm_scale=sm_scale,
                          dropout_p=dropout_p, **kw),
        grid, [x_spec, o_spec, o_spec], x_spec,
        jax.ShapeDtypeStruct(x.shape, x.dtype), seed, x, o, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _flash_qkv(x, seed, d, heads, sm_scale, dropout_p):
    return _qkv_forward(x, seed, d, heads, sm_scale, dropout_p)


def _flash_qkv_fwd(x, seed, d, heads, sm_scale, dropout_p):
    out = _qkv_forward(x, seed, d, heads, sm_scale, dropout_p)
    return out, (x, out, seed)


def _flash_qkv_bwd(d, heads, sm_scale, dropout_p, res, do):
    x, out, seed = res
    dx = _qkv_backward(x, out, do, seed, d, heads, sm_scale, dropout_p)
    return dx, onp.zeros(seed.shape, jax.dtypes.float0)


_flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


def flash_attention_qkv(qkv, num_heads, dropout_p=0.0, dropout_key=None):
    """Self-attention straight from the interleaved projection: ``qkv`` is
    (seq, batch, num_heads * 3 * head_dim), per head its query, key and
    value; the result is (seq, batch, num_heads * head_dim).  The whole-row
    kernels read and write these arrays in place: no (batch*heads, seq,
    head_dim) copy of q, k, v is made, forward or backward, and the
    backward keeps nothing but ``qkv`` and the output.  Only for shapes
    :func:`qkv_heads_per_step` accepts; dropout as :func:`flash_attention`
    (flattened index ``batch * num_heads + head``)."""
    seq, bsz, width = qkv.shape
    d = width // (3 * num_heads)
    heads = qkv_heads_per_step(seq, num_heads, d)
    if heads is None:
        raise ValueError(f"flash_attention_qkv cannot take seq {seq}, "
                         f"{num_heads} heads of {d}")
    dropout_p, seed = _dropout_args(dropout_p, dropout_key)
    # batch-major for the kernels: a dimension order, not a copy (above)
    out = _flash_qkv(qkv.transpose(1, 0, 2), seed, d, heads,
                     1.0 / math.sqrt(d), dropout_p)
    return out.transpose(1, 0, 2)


# -- grouped matrix product: rows sorted by group, a weight matrix a group ----
#
# ``x`` (rows, K) holds whole TILES of ``GROUP_TILE`` rows, each tile's rows
# of ONE group (``tile_group[t]``, ascending; rows a group does not fill are
# zeros), and ``w`` (groups, K, N) one matrix a group: ``out[tile t] =
# x[tile t] @ w[tile_group[t]]``.  The tile's group and ``tiles_used`` reach
# the index maps as scalar prefetches, so a tile fetches its group's matrix
# and nothing is gathered or copied.
#
# What is resident: the tiles are the INNER grid dimension and a step
# contracts the whole K, so a group's matrix block has one index over all the
# group's consecutive tiles and crosses HBM once a group, not once a tile,
# and no accumulator is read and written between steps.  The block is the
# whole (K, N) matrix where two copies of it (the pipeline's) fit
# ``_GROUP_VMEM``: 10.3 MB, 12.6 MB and 6.3 MB at the widths the two
# sparse-expert cells run, over Mosaic's default limit, which is why the
# calls state ``vmem_limit_bytes``.  A wider matrix is cut into column blocks
# on the OUTER dimension (``_group_cols``; the rows are then read once a
# column block).
#
# What a skipped tile costs: tiles at or behind ``tiles_used`` take the
# blocks of the last used tile (``_held_tile``), which are already in VMEM,
# so nothing is fetched for them; their output block is still written, as
# zeros (the caller's ``filled`` mask relies on finite values there): 0.4 to
# 0.8 MB, a microsecond.
#
# Why a tile has 128 rows: with the matrix resident the product's intensity
# does not depend on the tile's rows any more, so the tile is as small as
# one pass of the MXU's 128 rows.  An expert loses half a tile to rounding on
# average and owns one when it gets no row, and the dispatch around the
# products pays for those rows too.  On the v5e the three kernels take the
# same time at 128 and at 256 rows a tile for the same held rows (0.98 and
# 1.00 ms at 2688 x 1920, 1.78 and 1.71 at 2048 x 3072), and both cells'
# steps are 0.4% faster at 128 (PERF.md, PR 34).
#
# The backward is the same kernel with the matrices read transposed (the
# MXU takes a transposed operand; no transposed copy of the weights is
# written: it cost as much as the product) for the rows' gradient, and one
# kernel for the matrices': a group's tiles are consecutive, so its gradient
# is accumulated in float32 in VMEM over them and written once, in blocks of
# as many of the K rows as ``_GROUP_VMEM`` holds (``_group_rows``: all of
# them at the cells' widths; fewer, and ``dy`` is read once a block).
# Every group owns at least one tile (the caller's layout), so every gradient
# block is written.  For a sparse-expert layer's held experts
# (``parallel/moe.py``): XLA's own ragged product ran the same work 3 to 5
# times slower on the v5e and carries no scope (PERF.md, PR 31).

GROUP_TILE = 128
# what a call's resident blocks may take of VMEM, pipeline copies and
# accumulator included (48 MiB is exactly the 2048 x 3072 gradient's float32
# accumulator and two bf16 copies; half of it cost up to 9% of the
# matrices' gradient, more bought nothing), and what Mosaic is told it may
# use in all (the v5e's, v5p's and v6e's cores have 128 MiB)
_GROUP_VMEM = 48 << 20
_GROUP_VMEM_LIMIT = 100 << 20


def _group_cols(k, n, itemsize):
    """Columns of a group's (K, N) matrix a forward step holds: all of them,
    or the most whole 128-lane columns dividing N that fit twice."""
    return _divisor(n, max(128, _GROUP_VMEM // (2 * k * itemsize)),
                    multiple=128)


def _group_rows(k, n, itemsize):
    """Rows of a group's (K, N) gradient a step accumulates: a float32
    accumulator and the output's two copies."""
    return _divisor(k, max(128, _GROUP_VMEM // (n * (4 + 2 * itemsize))),
                    multiple=128)


def _held_tile(i, used):
    """The tile whose blocks tile ``i``'s step reads: itself, or the last
    used one when ``i`` holds nothing (then no block changes: no fetch)."""
    return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))


# index maps over (outer block, tile, tile_group, tiles_used)
def _gmm_x_map(j, i, group, used):
    return _held_tile(i, used), 0


def _gmm_w_map(j, i, group, used):
    return group[_held_tile(i, used)], 0, j


def _gmm_wt_map(j, i, group, used):         # the matrix read transposed
    return group[_held_tile(i, used)], j, 0


def _gmm_out_map(j, i, group, used):
    return i, j


def _tgmm_x_map(kk, i, group, used):
    return _held_tile(i, used), kk


def _tgmm_dy_map(kk, i, group, used):
    return _held_tile(i, used), 0


def _tgmm_dw_map(kk, i, group, used):
    return group[i], kk, 0


def _gmm_kernel(dot, group_ref, used_ref, x_ref, w_ref, o_ref):
    held = pl.program_id(1) < used_ref[0]

    @pl.when(held)
    def _():
        o_ref[...] = dot(x_ref[...], w_ref[...]).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(held))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _tgmm_kernel(group_ref, used_ref, x_ref, dy_ref, dw_ref, acc):
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1
    group = group_ref[i]

    @pl.when((i == 0) | (group != group_ref[jnp.maximum(i - 1, 0)]))
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(i < used_ref[0])
    def _():
        acc[...] += _dot_tn(x_ref[...], dy_ref[...])

    @pl.when((i == last) | (group != group_ref[jnp.minimum(i + 1, last)]))
    def _():
        dw_ref[...] = acc[...].astype(dw_ref.dtype)


def _grouped_call(kernel, grid, in_specs, out_spec, out_shape, scratch,
                  *args):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GROUP_VMEM_LIMIT),
        interpret=_interpret())(*args)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _gmm(x, w, tile_group, tiles_used, tile, tn, transposed):
    """``x[tile] @ w[group]``, or ``x[tile] @ w[group].T`` (``transposed``:
    ``w`` is (groups, N, K)), ``tn`` of the N columns a step."""
    rows, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    if transposed:
        w_spec = pl.BlockSpec((None, tn, k), _gmm_wt_map)
    else:
        w_spec = pl.BlockSpec((None, k, tn), _gmm_w_map)
    return _grouped_call(
        functools.partial(_gmm_kernel, _dot_nt if transposed else _dot_nn),
        (n // tn, rows // tile),
        [pl.BlockSpec((tile, k), _gmm_x_map), w_spec],
        pl.BlockSpec((tile, tn), _gmm_out_map),
        jax.ShapeDtypeStruct((rows, n), x.dtype), [],
        tile_group, tiles_used, x, w)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _tgmm(x, dy, tile_group, tiles_used, groups, tile, tk):
    """``dw[group] = sum over the group's tiles of x[tile].T @ dy[tile]``,
    ``tk`` of the K rows a pass over the tiles."""
    rows, k = x.shape
    n = dy.shape[1]
    return _grouped_call(
        _tgmm_kernel, (k // tk, rows // tile),
        [pl.BlockSpec((tile, tk), _tgmm_x_map),
         pl.BlockSpec((tile, n), _tgmm_dy_map)],
        pl.BlockSpec((None, tk, n), _tgmm_dw_map),
        jax.ShapeDtypeStruct((groups, k, n), x.dtype),
        [pltpu.VMEM((tk, n), jnp.float32)],
        tile_group, tiles_used, x, dy)


def _product(x, w, tile_group, tiles_used, transposed=False):
    # the tile and the budget as the trace finds them: static to the jitted
    # calls, so a changed one is never served a cached trace
    n = w.shape[1] if transposed else w.shape[2]
    return _gmm(x, w, tile_group, tiles_used, GROUP_TILE,
                _group_cols(x.shape[1], n, x.dtype.itemsize), transposed)


@jax.custom_vjp
def _grouped(x, w, tile_group, tiles_used):
    return _product(x, w, tile_group, tiles_used)


def _grouped_fwd(x, w, tile_group, tiles_used):
    return (_product(x, w, tile_group, tiles_used),
            (x, w, tile_group, tiles_used))


def _grouped_bwd(res, dy):
    x, w, tile_group, tiles_used = res
    dx = _product(dy, w, tile_group, tiles_used, transposed=True)
    dw = _tgmm(x, dy, tile_group, tiles_used, w.shape[0], GROUP_TILE,
               _group_rows(*w.shape[1:], x.dtype.itemsize))
    zero = onp.zeros(tile_group.shape, jax.dtypes.float0)
    return dx, dw, zero, onp.zeros(tiles_used.shape, jax.dtypes.float0)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, tile_group, tiles_used):
    """``out[tile t] = x[tile t] @ w[tile_group[t]]`` over tiles of
    ``GROUP_TILE`` rows (above).  ``x`` (tiles * GROUP_TILE, K), ``w``
    (groups, K, N) with K and N multiples of 128, ``tile_group`` (tiles,)
    int32 ascending and naming every group at least once, ``tiles_used``
    (1,) int32.  Differentiable in ``x`` and ``w``."""
    rows, k = x.shape
    if rows % GROUP_TILE or k % 128 or w.shape[2] % 128 or w.shape[1] != k:
        raise ValueError(f"grouped_matmul cannot take x {x.shape}, "
                         f"w {w.shape}")
    return _grouped(x, w, tile_group, tiles_used)


# -- the Mamba-2 scan: one chunk of one group a grid step --------------------
#
# ``ops/ssm.py`` states the recurrence and its chunked form; this is that
# form with nothing of a chunk but its inputs and its output in HBM.  The
# kernels take the sequence ALONG THE LANES: ``x`` as (batch, heads *
# head_dim, length), ``B`` and ``C`` as (batch, groups * state, length).
# That is how XLA lays a Mamba-2 mixer out on the v5e when left alone (the
# projection's output, the depthwise convolution over the positions and the
# grouped norm behind all run position-minor), so the transposes around the
# call cost nothing, and everything a position owns (its decay, its time
# step) is a ROW that broadcasts down the sublanes of a head's rows: no
# lane broadcast, no lane reduction and no lane concatenation a head.
#
# The grid is (batch, group, chunk), the chunks in order.  A step reads the
# group's rows of ``x`` and of ``B`` and ``C`` where they lie (the index
# maps pick them), builds each head's (chunk, chunk) decay mask in VMEM,
# multiplies and drops it, and carries the group's float32 state (heads *
# head_dim, state) in scratch from one chunk to the next.  The per-chunk
# cumulative sums of ``dt A`` come in from XLA, float32, as rows
# (``rows``) and, for the mask's other index, as columns (``cols``): no
# transpose of them runs in the kernel, and their ``exp`` does.
#
# The backward walks the chunks from the last to the first with the state's
# gradient in scratch.  It rebuilds every mask, reads the state before each
# chunk that the differentiated forward wrote (float32, alive inside one
# layer's backward under ``remat_call``), and accumulates a group's ``dB``
# and ``dC`` over its heads in VMEM.  The decay sums' gradient needs no sum
# over a mask: scaling the decay into position ``i`` scales its output's
# part without ``D x``, scaling the decay out of position ``j`` scales what
# ``x_j`` feeds, and scaling a whole chunk's decay scales the state behind
# it, so they are ``<dy_i, y_i - D x_i>``, ``<x_j, dx_j - D dy_j>`` a head
# and ``<S, dS>``.  The reverse cumulative sum that turns them into ``d dt``
# and ``dA`` is jax's own, of the cumulative sum in front.
#
# Precision as ``ssm._ssd_group``: decay sums, ``exp`` and the state
# float32; a mask times ``C B^T``, the decayed inputs and the state handed to
# ``C . S`` cast to the operands' type before their products; float32
# accumulation.  A cotangent is cast the same way before a product.


def _per_head_rows(rows, p):
    """(heads, chunk) -> (heads * p, chunk): row ``k`` down head ``k``'s
    ``p`` rows."""
    e, q = rows.shape
    return jnp.concatenate([jnp.broadcast_to(rows[k:k + 1], (p, q))
                            for k in range(e)], axis=0)


def _per_head_sums(t, e):
    """(heads * p, chunk) -> (heads, chunk): the sum over each head's ``p``
    rows."""
    p = t.shape[0] // e
    return jnp.concatenate(
        [jnp.sum(t[k * p:(k + 1) * p], axis=0, keepdims=True)
         for k in range(e)], axis=0)


def _ssd_masks(cb, rows_ref, cols_ref, e):
    """Per head, ``(C B^T . decay, decay, dt_j)``: ``cb`` (chunk, chunk)
    holds ``B_j . C_i`` at [j, i], the decay ``exp(a_i - a_j)`` where
    ``i >= j`` and 0 elsewhere, float32; ``dt_j`` a (chunk, 1) column."""
    q = cb.shape[0]
    visible = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    for k in range(e):
        seg = rows_ref[k:k + 1, :] - cols_ref[:, k:k + 1]
        decay = jnp.exp(jnp.where(visible, seg, _NEG_INF))
        yield cb * decay, decay, cols_ref[:, e + k:e + k + 1]


def _ssd_decays(rows_ref, e, n):
    """From a chunk's decay sums ``a`` and ``dt`` (heads, chunk) each, the
    float32 rows a kernel scales by: ``exp(a_i)`` (the decay from the
    chunk's start into position ``i``), ``exp(a_end - a_j)`` (out of ``j``
    to its end), ``dt_j``, and the whole chunk's decay along the ``n`` lanes
    of a state."""
    a, dt = rows_ref[:e, :], rows_ref[e:, :]
    end = a[:, a.shape[1] - 1:]
    # exp behind the broadcast: Mosaic folds a row's slice into a broadcast
    # it stands on, and cannot broadcast one element down and across at once
    return (jnp.exp(a), jnp.exp(end - a), dt,
            jnp.exp(jnp.broadcast_to(end, (e, n))))


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, y_ref,
                    *rest, heads):
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, state.dtype)

    x, B, C = x_ref[...], b_ref[...], c_ref[...]
    dtype, e, p = x.dtype, heads, x.shape[0] // heads
    before = state[...]
    if len(rest) == 2:                      # the differentiated forward
        rest[0][...] = before
    into, out_of, dt, whole = _ssd_decays(rows_ref, e, B.shape[0])
    inside = [
        _dot_nn(x[k * p:(k + 1) * p], (weight * dt_col).astype(dtype))
        for k, (weight, _, dt_col) in enumerate(
            _ssd_masks(_dot_tn(B, C), rows_ref, cols_ref, e))]
    y = jnp.concatenate(inside, axis=0) \
        + _dot_nn(before.astype(dtype), C) * _per_head_rows(into, p) \
        + _per_head_rows(d_ref[...], p) * x.astype(jnp.float32)
    y_ref[...] = y.astype(dtype)
    to_end = _per_head_rows((out_of * dt).astype(dtype), p)
    state[...] = before * _per_head_rows(whole, p) + _dot_nt(x * to_end, B)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, rows_ref, cols_ref, d_ref, s_ref,
                    dy_ref, dx_ref, db_ref, dc_ref, drow_ref, dd_ref, dstate,
                    after, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, dstate.dtype)
        after[...] = jnp.zeros(after.shape, after.dtype)
        dd_ref[...] = jnp.zeros(dd_ref.shape, dd_ref.dtype)

    f32 = jnp.float32
    x, B, C, dy = x_ref[...], b_ref[...], c_ref[...], dy_ref[...]
    dtype, e, p, q = x.dtype, heads, x.shape[0] // heads, x.shape[1]
    before, d_after = s_ref[...], dstate[...]
    x32, dy32 = x.astype(f32), dy.astype(f32)
    into, out_of, dt, whole = _ssd_decays(rows_ref, e, B.shape[0])
    # per head: the forward's product again and its transpose on dy (the
    # SAME rounded mask, so what the two send to a decay sum cancels as it
    # does in exact arithmetic), the mask without dt_j on dy for d dt_j, and
    # the mask's share of the group's d(C B^T)
    inside, back, by_dt, dcb = [], [], [], jnp.zeros((q, q), f32)
    for k, (weight, decay, dt_col) in enumerate(
            _ssd_masks(_dot_tn(B, C), rows_ref, cols_ref, e)):
        xk, dyk = (t[k * p:(k + 1) * p] for t in (x, dy))
        w = (weight * dt_col).astype(dtype)
        inside.append(_dot_nn(xk, w))
        back.append(_dot_nt(dyk, w))
        by_dt.append(_dot_nt(dyk, weight.astype(dtype)))
        dcb = dcb + _dot_tn(xk, dyk) * (decay * dt_col)
    into_rows = _per_head_rows(into, p)
    y = jnp.concatenate(inside, axis=0) \
        + _dot_nn(before.astype(dtype), C) * into_rows
    to_end = _per_head_rows((out_of * dt).astype(dtype), p)
    dxdec = _dot_nn(d_after.astype(dtype), B)
    dx = jnp.concatenate(back, axis=0) + dxdec * to_end.astype(f32)
    dx_ref[...] = (dx + _per_head_rows(d_ref[...], p) * dy32).astype(dtype)
    dd_ref[...] += _per_head_sums(dy32 * x32, e)

    # the decay sums, position by position (the comment above the kernels)
    at_end = jnp.sum(_per_head_sums(d_after * after[...], e), axis=1,
                     keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    drow_ref[...] = jnp.concatenate(
        [_per_head_sums(dy32 * y - x32 * dx, e)
         + jnp.where(last, at_end, 0.0),
         _per_head_sums(x32 * (jnp.concatenate(by_dt, axis=0)
                               + dxdec * _per_head_rows(out_of, p)), e)],
        axis=0)

    dcs, dcb = (dy32 * into_rows).astype(dtype), dcb.astype(dtype)
    dc_ref[...] = (_dot_tn(before.astype(dtype), dcs)
                   + _dot_nn(B, dcb)).astype(dtype)
    db_ref[...] = (_dot_tn(d_after.astype(dtype), x * to_end)
                   + _dot_nt(C, dcb)).astype(dtype)
    dstate[...] = d_after * _per_head_rows(whole, p) + _dot_nt(dcs, C)
    after[...] = before


def _ssd_call(kernel, x, B, C, a, dt, D, more, outs, reverse):
    """``pallas_call`` over (batch, group, chunk); ``reverse`` walks the
    chunks from the last and carries two states in scratch, not one.
    ``more`` (array, kind) pairs and the outputs ``outs`` name their blocks
    by kind: ``"x"`` the group's rows of an array like ``x``, ``"bc"`` of one
    like ``B``, ``"sum"`` a float32 (heads, chunk) block a (batch, group)
    that stays put over the chunks, a tuple the trailing block of a float32
    (batch, group, chunk, ...) array.  Beside ``x``, ``B`` and ``C`` every
    kernel reads, float32, the decay sums ``a`` and ``dt`` (batch, group,
    chunk, head, position) as ``rows`` (.., 2 heads, position) and, the same
    numbers, as ``cols`` (.., position, 2 heads), and ``D`` along a chunk's
    lanes, (group, heads, position)."""
    bsz, g, nc, e, q = a.shape
    n, ep = B.shape[1] // g, x.shape[1] // g
    f32 = jnp.float32

    def chunk(ci):
        return nc - 1 - ci if reverse else ci

    def spec(kind):
        if kind in ("x", "bc"):
            return pl.BlockSpec((None, ep if kind == "x" else n, q),
                                lambda bi, gi, ci: (bi, gi, chunk(ci)))
        if kind == "sum":
            return pl.BlockSpec((None, None, e, q),
                                lambda bi, gi, ci: (bi, gi, 0, 0))
        return pl.BlockSpec((None, None, None) + kind,
                            lambda bi, gi, ci: (bi, gi, chunk(ci), 0, 0))

    def shape(kind):
        if kind in ("x", "bc"):
            like = x if kind == "x" else B
            return jax.ShapeDtypeStruct(like.shape, like.dtype)
        if kind == "sum":
            return jax.ShapeDtypeStruct((bsz, g, e, q), f32)
        return jax.ShapeDtypeStruct((bsz, g, nc) + kind, f32)

    rows = jnp.concatenate([a, dt], axis=-2)
    d_rows = jnp.broadcast_to(D.astype(f32).reshape(g, e, 1), (g, e, q))
    return pl.pallas_call(
        functools.partial(kernel, heads=e), grid=(bsz, g, nc),
        in_specs=[spec("x"), spec("bc"), spec("bc"), spec((2 * e, q)),
                  spec((q, 2 * e)),
                  pl.BlockSpec((None, e, q), lambda bi, gi, ci: (gi, 0, 0)),
                  *(spec(kind) for _, kind in more)],
        out_specs=[spec(kind) for kind in outs],
        out_shape=[shape(kind) for kind in outs],
        scratch_shapes=[pltpu.VMEM((ep, n), f32)] * (2 if reverse else 1),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=_interpret())(x, B, C, rows, rows.swapaxes(-1, -2),
                                d_rows, *(t for t, _ in more))


@functools.partial(jax.jit, static_argnums=(6,))
def _ssd_forward(x, B, C, a, dt, D, keep_states):
    """``[y]``, and the state before every chunk, (batch, group, chunk,
    heads * head_dim, state), behind it if ``keep_states``."""
    g = a.shape[1]
    states = [(x.shape[1] // g, B.shape[1] // g)] if keep_states else []
    return _ssd_call(_ssd_fwd_kernel, x, B, C, a, dt, D, [],
                     ["x"] + states, False)


@jax.jit
def _ssd_backward(x, B, C, a, dt, D, states, dy):
    e, q = a.shape[3:]
    dx, dB, dC, drow, dd = _ssd_call(
        _ssd_bwd_kernel, x, B, C, a, dt, D,
        [(states, states.shape[3:]), (dy, "x")],
        ["x", "bc", "bc", (2 * e, q), "sum"], True)
    return (dx, dB, dC, drow[..., :e, :], drow[..., e:, :],
            jnp.sum(dd, axis=(0, 3)).reshape(D.shape).astype(D.dtype))


@jax.custom_vjp
def _ssd(x, B, C, a, dt, D):
    return _ssd_forward(x, B, C, a, dt, D, False)[0]


def _ssd_fwd(x, B, C, a, dt, D):
    y, states = _ssd_forward(x, B, C, a, dt, D, True)
    return y, (x, B, C, a, dt, D, states)


def _ssd_bwd(res, dy):
    return _ssd_backward(*res, dy)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_chunk_scan(x, B, C, a, dt, D):
    """The chunked Mamba-2 scan with the ``D x`` term, the sequence along
    the last axis: ``x`` (batch, heads * head_dim, length), ``B`` and ``C``
    (batch, groups * state, length) of ``x``'s type, ``D`` (heads,), and
    float32 ``a`` and ``dt`` (batch, groups, chunks, heads of a group,
    chunk): ``dt`` and the cumulative sum of ``dt A`` inside each chunk;
    chunks * chunk is the length.  Returns ``y`` shaped and typed as ``x``;
    differentiable in all six.  On a TPU chunk and state are multiples of
    128 and head_dim one of 16."""
    bsz, g, nc, e, q = a.shape
    if x.shape[0] != bsz or x.shape[2] != nc * q or x.shape[1] % (g * e) \
            or B.shape != C.shape or B.shape[1] % g \
            or B.shape[2] != x.shape[2] or dt.shape != a.shape \
            or not x.dtype == B.dtype == C.dtype:
        raise ValueError(f"ssd_chunk_scan cannot take x {x.shape} "
                         f"{x.dtype}, B {B.shape} {B.dtype}, C {C.shape} "
                         f"{C.dtype}, a {a.shape}, dt {dt.shape}")
    return _ssd(x, B, C, a.astype(jnp.float32), dt.astype(jnp.float32), D)
