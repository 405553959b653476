"""Pallas TPU kernels for the hot ops.

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
core) and ``models/transformer_lm.py``.

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

from .random import _keep, _seed_words, keep_mask

__all__ = ["flash_attention", "flash_attention_qkv", "qkv_heads_per_step",
           "dropout_keep_mask", "matmul_bn_stats", "conv1x1_bn_stats",
           "conv1x1_bn_stats_train", "fused_blocks",
           "conv3x3_bn_stats", "conv3x3_bn_stats_train", "conv3x3_fits",
           "convkxk_bn_stats", "convkxk_bn_stats_train", "convkxk_fits",
           "matmul_stats", "matmul_epilogue", "conv1x1_bn_act_train",
           "int8_matmul", "int8_blocks"]

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


# ---------------------------------------------------------------------------
# fused matmul + BN-stats epilogue (docs/PERF.md kernel roadmap item 3)
# ---------------------------------------------------------------------------
#
# y = act(x @ w [+ bias]); per-column sum(y) and sum(y*y) accumulated in
# the SAME kernel — the producing matmul's epilogue computes the batch-norm
# statistics, removing the separate stats pass (one fewer HBM read of the
# activation).  This is exactly the fusion XLA cannot express: a reduction
# folded into a dot's output tiles.  Covers FullyConnected and 1x1-conv
# (NHWC collapsed to (N*H*W, C)) producers, which carry roughly half of
# ResNet-50's FLOPs.
#
# Reference analog: conv+BN folding exists in the reference only for
# INFERENCE (MKLDNN subgraph fuser); training-time stats fusion has no
# reference counterpart — TPU-first design.
#
# TPU grid semantics: grid iterations execute sequentially per core
# ("arbitrary" dimension semantics), so accumulating the (1, N)-tiled
# stats outputs across m-tiles is race-free by construction.


def _mm_stats_kernel(x_ref, w_ref, o_ref, s_ref, ss_ref, *, relu, k_tiles,
                     block_k):
    # m is the INNER grid dim: the same (1, block_n) stats block is then
    # revisited on consecutive grid steps, which is the only pattern whose
    # VMEM contents Pallas guarantees to persist for read-modify-write
    mi = pl.program_id(1)

    def body(ki, acc):
        xk = x_ref[:, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        wk = w_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        return acc + xk @ wk

    acc = jax.lax.fori_loop(
        0, k_tiles, body,
        jnp.zeros((x_ref.shape[0], w_ref.shape[1]), jnp.float32))
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc.astype(o_ref.dtype)
    part = jnp.sum(acc, axis=0, keepdims=True)          # (1, N_block)
    part_sq = jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when(mi == 0)
    def _init():
        s_ref[...] = part
        ss_ref[...] = part_sq

    @pl.when(mi != 0)
    def _accum():
        s_ref[...] += part
        ss_ref[...] += part_sq


def matmul_bn_stats(x, w, relu=False, block_m=256, block_n=256,
                    block_k=512):
    """``y = act(x @ w)`` plus per-column ``sum(y)``/``sum(y*y)`` in one
    kernel pass.  x: (M, K), w: (K, N) -> (y: (M, N), s: (N,), ss: (N,)),
    stats in fp32.  M/K/N must be divisible by the (clamped) block sizes.
    Wrap 1x1 convs by collapsing NHWC to (N*H*W, C)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, k, n), (block_m, block_k, block_n))
    grid = (n // block_n, m // block_m)       # m innermost (see kernel)
    kernel = functools.partial(_mm_stats_kernel, relu=relu,
                               k_tiles=k // block_k, block_k=block_k)
    y, s, ss = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((k, block_n), lambda ni, mi: (0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda ni, mi: (mi, ni)),
            pl.BlockSpec((1, block_n), lambda ni, mi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, mi: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, w)
    return y, s[0], ss[0]


def conv1x1_bn_stats(x, w, relu=False, **blocks):
    """1x1-conv producer + BN-stats epilogue: x (N,H,W,Cin) NHWC,
    w (Cout,1,1,Cin) OHWI -> (y (N,H,W,Cout), mean (Cout,), var (Cout,)).
    The mean/var are the batch statistics BatchNorm(training=True) needs —
    computed without re-reading y from HBM."""
    n, h, wd, cin = x.shape
    cout = w.shape[0]
    x2 = x.reshape(n * h * wd, cin)
    w2 = w.reshape(cout, cin).T                  # (Cin, Cout)
    y, s, ss = matmul_bn_stats(x2, w2, relu=relu, **blocks)
    cnt = jnp.float32(n * h * wd)
    mean = s / cnt
    var = jnp.maximum(ss / cnt - mean * mean, 0.0)
    return y.reshape(n, h, wd, cout), mean, var


# ---------------------------------------------------------------------------
# Differentiable fused conv1x1 + BN-stats: the model-path entry point.
#
# Round-4 left matmul_bn_stats standalone; this wires it into training.
# Forward runs the Pallas producer+stats kernel (one HBM pass over the
# conv output instead of conv-write + stats-read); backward is explicit
# XLA (dense MXU matmuls) because pallas_call has no transpose rule.
# Reference analog: train-mode BN fusion does not exist in the reference
# (src/operator/nn/batch_norm.cc computes stats in a separate pass) —
# TPU-first design, used by gluon BatchNorm when its input was produced
# by an eligible 1x1 Convolution (see gluon/nn/basic_layers.py).
# ---------------------------------------------------------------------------


def fused_blocks(m, k, n):
    """Pick Mosaic-legal block sizes for matmul_bn_stats, or None when the
    shape can't tile: block_m multiple of 8 (sublane), block_n multiple of
    128 or the whole dim (lane), block_k any divisor of k."""
    def pick(dim, target, quantum):
        if dim <= target:
            return dim
        b = (min(target, dim) // quantum) * quantum
        while b >= quantum and dim % b:
            b -= quantum
        return b if b >= quantum and dim % b == 0 else None

    bm = pick(m, 256, 8)
    bn = pick(n, 256, 128)
    bk = pick(k, 512, 128)
    if bm is None or bn is None or bk is None:
        return None
    if m % bm or n % bn or k % bk:
        return None
    return {"block_m": bm, "block_n": bn, "block_k": bk}


@jax.custom_vjp
def conv1x1_bn_stats_train(x, w):
    """Differentiable ``(z, mean, var)`` of a 1x1 NHWC conv with fused
    batch statistics.  x (N,H,W,Cin), w (Cout,1,1,Cin) OHWI.  Caller must
    pre-check :func:`fused_blocks` eligibility."""
    z, mean, var = _c1x1_fwd(x, w)
    return z, mean, var


def _c1x1_fwd(x, w):
    n, h, wd, cin = x.shape
    blocks = fused_blocks(n * h * wd, cin, w.shape[0])
    return conv1x1_bn_stats(x, w, relu=False, **blocks)


def _c1x1_fwd_vjp(x, w):
    z, mean, var = _c1x1_fwd(x, w)
    return (z, mean, var), (x, w, z, mean)


def _c1x1_bwd(res, cts):
    x, w, z, mean = res
    gz, gmean, gvar = cts
    n, h, wd, cin = x.shape
    cout = w.shape[0]
    m = n * h * wd
    # total cotangent into the conv output: the stats outputs fold back as
    #   d mean_j / d z_ij = 1/M,   d var_j / d z_ij = 2 (z_ij - mean_j) / M
    z32 = z.reshape(m, cout).astype(jnp.float32)
    g = (gz.reshape(m, cout).astype(jnp.float32)
         + gmean[None, :].astype(jnp.float32) / m
         + gvar[None, :].astype(jnp.float32) * 2.0 * (z32 - mean[None, :]) / m)
    g = g.astype(x.dtype)                         # MXU-friendly operand dtype
    x2 = x.reshape(m, cin)
    w2 = w.reshape(cout, cin)
    dx = jax.lax.dot(g, w2.astype(g.dtype),
                     preferred_element_type=jnp.float32)
    dw = jax.lax.dot(g.T, x2, preferred_element_type=jnp.float32)
    return (dx.reshape(x.shape).astype(x.dtype),
            dw.reshape(w.shape).astype(w.dtype))


conv1x1_bn_stats_train.defvjp(_c1x1_fwd_vjp, _c1x1_bwd)


# ---------------------------------------------------------------------------
# Fused conv/BN/ReLU EPILOGUE family (round 9, ROADMAP item 2).
#
# The round-5 lesson (docs/PERF.md): a pallas_call is an opaque custom
# call XLA cannot fuse INTO, so a kernel that leaves ANY of the epilogue
# outside (scale/shift/relu/residual-add) breaks the surrounding fusion
# and loses.  These kernels take the other branch of that fork: put the
# ENTIRE consumer chain of the dominant ResNet 1x1 convs in-register —
#
#   matmul_stats     x @ w reduced DIRECTLY to per-column (sum, sumsq):
#                    the conv output is never written to HBM at all
#                    (the batch-norm statistics pass at 0 activation
#                    bytes);
#   matmul_epilogue  x @ w recomputed with bias -> BN scale-shift ->
#                    residual-add -> ReLU applied in-register, writing
#                    only the FINAL activation.
#
# Training conv+BN+ReLU(+residual) = stats pass + epilogue pass: ONE
# HBM pass over the conv output (the final write) instead of three
# (conv write, stats read, normalize read+write), at 2x matmul FLOPs —
# the flash-attention recompute trade applied to the conv path.  The
# backward (conv1x1_bn_act_train's custom_vjp) recomputes z with one
# dense MXU matmul, exactly like flash recomputes attention scores.
# No reference analog; wired via ops/nn.py _fused_conv1x1_bn_act into
# the model-zoo BottleneckV1 behind MXNET_FUSED_EPILOGUE.
# ---------------------------------------------------------------------------


def _mm_statsonly_kernel(x_ref, w_ref, s_ref, ss_ref, *, k_tiles, block_k):
    # m innermost (same revisit pattern as _mm_stats_kernel): the (1, bn)
    # stats tiles accumulate race-free across sequential m steps
    mi = pl.program_id(1)

    def body(ki, acc):
        xk = x_ref[:, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        wk = w_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        return acc + xk @ wk

    acc = jax.lax.fori_loop(
        0, k_tiles, body,
        jnp.zeros((x_ref.shape[0], w_ref.shape[1]), jnp.float32))
    part = jnp.sum(acc, axis=0, keepdims=True)
    part_sq = jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when(mi == 0)
    def _init():
        s_ref[...] = part
        ss_ref[...] = part_sq

    @pl.when(mi != 0)
    def _accum():
        s_ref[...] += part
        ss_ref[...] += part_sq


def matmul_stats(x, w, block_m=256, block_n=256, block_k=512):
    """Per-column ``(sum(x@w), sum((x@w)**2))`` in fp32 WITHOUT writing
    the product: x (M, K), w (K, N) -> (s (N,), ss (N,)).  The
    activation-free half of the fused-epilogue pair."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, k, n), (block_m, block_k, block_n))
    grid = (n // block_n, m // block_m)        # m innermost (see kernel)
    kernel = functools.partial(_mm_statsonly_kernel,
                               k_tiles=k // block_k, block_k=block_k)
    s, ss = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((k, block_n), lambda ni, mi: (0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda ni, mi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, mi: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, w)
    return s[0], ss[0]


def _mm_epilogue_kernel(x_ref, w_ref, sc_ref, bi_ref, r_ref, o_ref, *,
                        k_tiles, block_k, relu, has_res):
    def body(ki, acc):
        xk = x_ref[:, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        wk = w_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        return acc + xk @ wk

    acc = jax.lax.fori_loop(
        0, k_tiles, body,
        jnp.zeros((x_ref.shape[0], w_ref.shape[1]), jnp.float32))
    out = acc * sc_ref[...] + bi_ref[...]       # BN scale-shift, (1, bn)
    if has_res:
        out = out + r_ref[...].astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out.astype(o_ref.dtype)


def matmul_epilogue(x, w, scale, shift, residual=None, relu=False,
                    block_m=256, block_n=256, block_k=512):
    """``act((x @ w) * scale + shift [+ residual])`` in ONE kernel pass:
    x (M, K), w (K, N), scale/shift per-column fp32 (N,), residual
    (M, N) in the output dtype.  The residual adds BEFORE the relu —
    the ResNet block order ``relu(bn(conv(h)) + shortcut)``.  A conv
    bias folds into ``shift`` host-side (it is per-column affine)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, k, n), (block_m, block_k, block_n))
    has_res = residual is not None
    r = residual if has_res else jnp.zeros((1, 1), x.dtype)
    r_spec = (pl.BlockSpec((block_m, block_n), lambda ni, mi: (mi, ni))
              if has_res else pl.BlockSpec((1, 1), lambda ni, mi: (0, 0)))
    kernel = functools.partial(_mm_epilogue_kernel, k_tiles=k // block_k,
                               block_k=block_k, relu=relu, has_res=has_res)
    return pl.pallas_call(
        kernel,
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((k, block_n), lambda ni, mi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, mi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, mi: (0, ni)),
            r_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda ni, mi: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=_interpret(),
    )(x, w, scale.astype(jnp.float32).reshape(1, n),
      shift.astype(jnp.float32).reshape(1, n), r)


@functools.lru_cache(maxsize=None)
def _c1x1_act_train_for(relu, has_res, eps, fix_gamma):
    """One custom_vjp core per static (relu, has_residual, eps,
    fix_gamma) — jax.custom_vjp cannot take non-array args positionally."""

    def _fwd_impl(x, w, gamma, beta, *rs):
        n, h, wd, cin = x.shape
        cout = w.shape[0]
        m = n * h * wd
        x2 = x.reshape(m, cin)
        w2 = w.reshape(cout, cin).T
        blocks = fused_blocks(m, cin, cout)
        s, ss = matmul_stats(x2, w2, **blocks)
        cnt = jnp.float32(m)
        mean = s / cnt
        var = jnp.maximum(ss / cnt - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + jnp.float32(eps))
        g = jnp.ones_like(inv) if fix_gamma else gamma.astype(jnp.float32)
        sc = inv * g
        bi = beta.astype(jnp.float32) - mean * sc
        r2 = rs[0].reshape(m, cout) if has_res else None
        out = matmul_epilogue(x2, w2, sc, bi, residual=r2, relu=relu,
                              **blocks)
        return out.reshape(n, h, wd, cout), mean, var

    @jax.custom_vjp
    def f(x, w, gamma, beta, *rs):
        return _fwd_impl(x, w, gamma, beta, *rs)

    def fwd(x, w, gamma, beta, *rs):
        out, mean, var = _fwd_impl(x, w, gamma, beta, *rs)
        return (out, mean, var), (x, w, gamma, beta,
                                  rs[0] if has_res else None, mean, var)

    def bwd(res, cts):
        x, w, gamma, beta, r, mean, var = res
        gout, gmean, gvar = cts
        n, h, wd, cin = x.shape
        cout = w.shape[0]
        m = n * h * wd
        x2 = x.reshape(m, cin)
        w2 = w.reshape(cout, cin)
        # recompute z on the MXU (the flash-style trade: z never hit HBM
        # in forward; one dense matmul rebuilds it here)
        z = jax.lax.dot(x2, w2.T, preferred_element_type=jnp.float32)
        z = z.astype(jnp.float32)
        f32 = jnp.float32
        inv = jax.lax.rsqrt(var + f32(eps))
        g = jnp.ones_like(inv) if fix_gamma else gamma.astype(f32)
        sc = inv * g
        xhat = (z - mean[None, :]) * inv[None, :]
        y = sc[None, :] * z + (beta.astype(f32) - mean * sc)[None, :]
        ga = gout.reshape(m, cout).astype(f32)
        if has_res:
            a = y + r.reshape(m, cout).astype(f32)
        else:
            a = y
        if relu:
            ga = jnp.where(a > 0, ga, 0.0)
        # d residual: the add sits under the relu, so it shares ga
        dr = (ga.astype(r.dtype).reshape(r.shape) if has_res else None)
        dbeta_f = jnp.sum(ga, axis=0)
        dgamma_f = jnp.sum(ga * xhat, axis=0)
        # BN backward into z (mean/var chains folded), per column:
        #   dz = sc * (ga - mean_M(ga) - xhat * mean_M(ga * xhat))
        dz = sc[None, :] * (ga - dbeta_f[None, :] / m
                            - xhat * dgamma_f[None, :] / m)
        # plus the DIRECT cotangents on the returned stats outputs
        #   d mean_j / d z_ij = 1/M,  d var_j / d z_ij = 2 (z_ij - mu_j)/M
        dz = (dz + gmean[None, :].astype(f32) / m
              + gvar[None, :].astype(f32) * 2.0 * (z - mean[None, :]) / m)
        dz = dz.astype(x.dtype)                  # MXU-friendly operands
        dx = jax.lax.dot(dz, w2.astype(dz.dtype),
                         preferred_element_type=jnp.float32)
        dw = jax.lax.dot(dz.T, x2, preferred_element_type=jnp.float32)
        dgamma = (jnp.zeros_like(gamma) if fix_gamma
                  else dgamma_f.astype(gamma.dtype))
        dbeta = dbeta_f.astype(beta.dtype)
        outs = (dx.reshape(x.shape).astype(x.dtype),
                dw.reshape(w.shape).astype(w.dtype), dgamma, dbeta)
        return outs + ((dr,) if has_res else ())

    f.defvjp(fwd, bwd)
    return f


def conv1x1_bn_act_train(x, w, gamma, beta, residual=None, eps=1e-5,
                         relu=True, fix_gamma=False):
    """Differentiable fused 1x1-conv + train-mode BN + residual-add +
    ReLU: x (N,H,W,Cin) NHWC, w (Cout,1,1,Cin) OHWI, ``residual``
    (N,H,W,Cout) added before the relu -> ``(out, mean, var)``, stats
    fp32.  The conv output never materializes in HBM (stats pass +
    in-register epilogue pass); the backward recomputes it with one
    dense matmul.  Caller pre-checks :func:`fused_blocks`."""
    core = _c1x1_act_train_for(bool(relu), residual is not None,
                               float(eps), bool(fix_gamma))
    if residual is not None:
        return core(x, w, gamma, beta, residual)
    return core(x, w, gamma, beta)


# ---------------------------------------------------------------------------
# int8 matmul with s32 accumulation — the MEASUREMENT kernel (round 9).
#
# History: round 5 shipped whole-K-row int8 kernels (x block (bm, K)
# resident, fori over K slices) plus conv1x1/conv3x3 wrappers wired into
# contrib/quantization.py behind MXNET_INT8_PALLAS.  The chip bench
# measured that route at 0.345x of plain lax.conv s8 (BENCH_builder_r05
# pallas_vs_lax) with int8 itself losing to bf16 at matched batch — so
# round 9 DELETED the conv wrappers and the production routing (the knob
# now refuses, contrib/quantization.py), and rebuilt the matmul itself in
# the canonical Pallas shape so the microbench keeps an honest A/B
# vehicle: full (m, n, k) grid with k innermost, an s32 VMEM scratch
# accumulator revisited across k steps (VMEM footprint bm*bk + bk*bn +
# bm*bn instead of bm*K whole rows — the round-5 kernel's K-resident rows
# are what starved double-buffering), and the fp32 dequant / relu / s8
# requantize epilogue applied IN REGISTER on the last k step only.
# benchmark/microbench_tpu.py section_int8_pallas re-measures it against
# lax; production re-entry requires that bench to win on chip.
# ---------------------------------------------------------------------------


def _int8_mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_tiles, scale, relu,
                    out_scale):
    ki = pl.program_id(2)                     # k innermost: the same
                                              # (m, n) tile is revisited
    @pl.when(ki == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == k_tiles - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * scale
        if relu:
            out = jnp.maximum(out, 0.0)
        if out_scale is not None:
            q = jnp.clip(jnp.round(out * out_scale), -127, 127)
            o_ref[...] = q.astype(jnp.int8)
        else:
            o_ref[...] = out.astype(o_ref.dtype)


def int8_blocks(m, k, n):
    """Mosaic-legal tiles for s8 operands: sublane quantum 32, lane 128
    (or whole-dimension blocks)."""
    def pick(dim, target, quantum):
        if dim <= target:
            return dim
        b = (min(target, dim) // quantum) * quantum
        while b >= quantum and dim % b:
            b -= quantum
        return b if b >= quantum and dim % b == 0 else None

    bm = pick(m, 256, 32)
    bn = pick(n, 256, 128)
    bk = pick(k, 512, 128)
    if bm is None or bn is None or bk is None:
        return None
    if m % bm or n % bn or k % bk:
        return None
    return {"block_m": bm, "block_n": bn, "block_k": bk}


def int8_matmul(x, w, scale, relu=False, out_scale=None,
                block_m=256, block_n=256, block_k=512):
    """``dequant(x_s8 @ w_s8)``: x (M, K) s8, w (K, N) s8 -> fp32 (M, N)
    scaled by ``scale`` (= data_scale * w_scale), with the optional relu
    and s8 requantize (``out_scale``: fp32 -> s8 multiplier) fused
    in-register on the final k step.  s32 accumulation in a VMEM scratch
    tile on the MXU int8 path; (m, n, k) grid, k innermost."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, k, n), (block_m, block_k, block_n))
    k_tiles = k // block_k
    kernel = functools.partial(
        _int8_mm_kernel, k_tiles=k_tiles, scale=float(scale), relu=relu,
        out_scale=None if out_scale is None else float(out_scale))
    out_dtype = jnp.int8 if out_scale is not None else jnp.float32
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, k_tiles),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=_interpret(),
    )(x, w)


# ---------------------------------------------------------------------------
# 3x3 conv + BN-stats epilogue (round-5 VERDICT #2 second half).
#
# ResNet-50's 16 bottleneck 3x3 convs (stride 1, pad 1) are the BN sites
# the 1x1 fusion can't reach.  Every ResNet geometry keeps a full padded
# image tile resident in VMEM (56x56x64 -> 430 KB ... 7x7x2048 -> 230 KB),
# so the kernel grids over (cout-tiles, batch), pads in VMEM, and
# accumulates the conv as 9 statically-shifted matmuls on the MXU, with
# the same race-free batch-accumulated sum/sumsq epilogue as
# matmul_bn_stats (batch is the inner, sequential grid dim).
# No reference analog (src/operator/nn/batch_norm.cc stats are a
# separate pass) — TPU-first fusion.
# ---------------------------------------------------------------------------


def _tap_accumulate(xp_ref, w_ref, kh, kw, ho, wo, acc_dtype, w_cast=None):
    """Sum of shifted-window matmuls over the kh*kw taps: xp_ref a
    (Hp,Wp,Cin) already-padded VMEM ref, w_ref a (kh*kw,Cin,bn)
    taps-leading ref -> (ho*wo, bn).

    A fori_loop over the kh row shifts, NOT a fully unrolled Python
    loop: Mosaic's scoped-VMEM stack allocator keeps each unrolled
    iteration's shifted window + accumulator live simultaneously
    (kh*kw copies — the round-5 on-chip compile OOM); the loop body
    reuses one row block.  The row shift is a dynamic REF load
    (``pl.ds`` on the untiled leading dim — this Pallas TPU lowering
    has no ``dynamic_slice`` on values, and Mosaic requires sublane-dim
    dynamic starts to be 8-aligned, so the kw column shifts stay as
    static slices unrolled inside the body)."""
    cin = xp_ref.shape[-1]
    bn = w_ref.shape[-1]

    def row(dy, acc):
        xr = xp_ref[pl.ds(dy, ho), :, :]            # (ho, Wp, cin)
        for dx in range(kw):
            xs = xr[:, dx:dx + wo, :].reshape(ho * wo, cin)
            wt = w_ref[pl.ds(dy * kw + dx, 1), :, :].reshape(cin, bn)
            if w_cast is not None:
                wt = wt.astype(w_cast)
            acc = acc + jax.lax.dot_general(
                xs, wt, (((1,), (0,)), ((), ())),
                preferred_element_type=acc_dtype)
        return acc

    return jax.lax.fori_loop(0, kh, row,
                             jnp.zeros((ho * wo, bn), acc_dtype))


def _ckxk_kernel(x_ref, w_ref, o_ref, s_ref, ss_ref, xp_ref, *, ho, wo,
                 kh, kw, ph, pw):
    bi = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)                  # (H, W, Cin)
    xp_ref[...] = (jnp.pad(x, ((ph, ph), (pw, pw), (0, 0)))
                   if (ph or pw) else x)
    bn = w_ref.shape[-1]
    acc = _tap_accumulate(xp_ref, w_ref, kh, kw, ho, wo, jnp.float32,
                          w_cast=jnp.float32)
    o_ref[0] = acc.reshape(ho, wo, bn).astype(o_ref.dtype)
    part = jnp.sum(acc, axis=0, keepdims=True)        # (1, bn)
    part_sq = jnp.sum(acc * acc, axis=0, keepdims=True)

    @pl.when(bi == 0)
    def _init():
        s_ref[...] = part
        ss_ref[...] = part_sq

    @pl.when(bi != 0)
    def _accum():
        s_ref[...] += part
        ss_ref[...] += part_sq


def convkxk_fits(xshape, cout, kernel=(3, 3), pad=(1, 1), block_n=128,
                 vmem_budget=12 * 2 ** 20 + 2 ** 19, itemsize=2):
    """Eligibility for the full-image-tile KxK stride-1 kernel: NHWC
    geometry whose tiles stay inside the VMEM budget, with a
    Mosaic-friendly cout tiling.  ``itemsize`` is the storage dtype's
    byte width (2 for bf16, 4 for fp32, 1 for the s8 kernel — which
    also switches the in-kernel buffer dtypes to what
    ``_c3x3_int8_kernel`` really allocates: s8 image/window/weights,
    s32 accumulator, fp32 output).

    The byte model counts buffers as Mosaic actually allocates them:
    the last dim padded to 128 lanes, the second-to-last to the dtype's
    sublane quantum (8 f32 / 16 bf16 / 32 s8).  Un-padded estimates
    under-count tiny-channel geometries ~10x — the s2d stem's cin=12
    pads to 128 lanes, which is how the round-5 on-chip compile blew the
    16 MB scoped-VMEM limit; with honest accounting the stem is simply
    ineligible and falls back to the unfused conv+BN pair."""
    n, h, w, cin = xshape
    kh, kw = kernel
    ph, pw = pad
    ho, wo = h + 2 * ph - kh + 1, w + 2 * pw - kw + 1
    if ho <= 0 or wo <= 0:
        return None
    bn = min(block_n, cout)
    if cout % bn or (bn % 128 and bn != cout):
        return None

    def up(v, q):
        return -(-v // q) * q

    def sub(isz):
        return {1: 32, 2: 16, 4: 8}.get(isz, 8)

    # per-buffer dtypes: the bf16/fp32 kernel pads+computes in fp32 and
    # stores the conv output in the input dtype; the s8 kernel keeps the
    # image/window/weights in s8, accumulates s32, and emits fp32.
    int8 = itemsize == 1
    img_isz = 1 if int8 else 4          # padded image + tap window
    w_isz = 1 if int8 else 4            # weight taps as computed with
    out_isz = 4 if int8 else itemsize   # output tile
    m = up(ho * wo, sub(img_isz))
    cl = up(cin, 128)
    bl = up(bn, 128)
    wp = w + 2 * pw
    vmem = (h * up(w, sub(itemsize)) * cl * itemsize  # input tile as loaded
            + (h + 2 * ph) * up(wp, sub(img_isz)) * cl * img_isz  # scratch
            + ho * up(wp, sub(img_isz)) * cl * img_isz  # row-shift block
            + 2 * m * cl * img_isz                  # live column windows
            + 2 * m * bl * 4                        # accumulator in/out
            + kh * kw * up(cin, sub(w_isz)) * bl * w_isz  # weight taps
            + ho * up(wo, sub(out_isz)) * bl * out_isz)   # output tile
    if vmem > vmem_budget:
        return None
    return {"block_n": bn, "out_hw": (ho, wo)}


def convkxk_bn_stats(x, w, pad=(1, 1), block_n=128):
    """x (N,H,W,Cin) NHWC, w (Cout,kh,kw,Cin) OHWI, stride 1, symmetric
    per-dim ``pad`` -> (z (N,Ho,Wo,Cout), mean, var), stats fp32."""
    n, h, wd, cin = x.shape
    cout, kh, kw, _ = w.shape
    fit = convkxk_fits(x.shape, cout, (kh, kw), pad, block_n,
                       itemsize=jnp.dtype(x.dtype).itemsize)
    assert fit is not None, (x.shape, w.shape, pad)
    bn = fit["block_n"]
    ho, wo = fit["out_hw"]
    grid = (cout // bn, n)                        # batch innermost
    kernel = functools.partial(_ckxk_kernel, ho=ho, wo=wo, kh=kh, kw=kw,
                               ph=pad[0], pw=pad[1])
    # taps-leading weight layout so the in-loop per-tap slice is on the
    # (cheap, untiled) leading dim
    wr = jnp.transpose(w, (1, 2, 3, 0)).reshape(kh * kw, cin, cout)
    z, s, ss = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, wd, cin), lambda ci, b: (b, 0, 0, 0)),
            pl.BlockSpec((kh * kw, cin, bn), lambda ci, b: (0, 0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, ho, wo, bn), lambda ci, b: (b, 0, 0, ci)),
            pl.BlockSpec((1, bn), lambda ci, b: (0, ci)),
            pl.BlockSpec((1, bn), lambda ci, b: (0, ci)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, cout), x.dtype),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h + 2 * pad[0], wd + 2 * pad[1], cin),
                       jnp.float32),
        ],
        interpret=_interpret(),
    )(x, wr)
    cnt = jnp.float32(n * ho * wo)
    mean = s[0] / cnt
    var = jnp.maximum(ss[0] / cnt - mean * mean, 0.0)
    return z, mean, var


def _ref_convkxk(x, w, pad):
    dn = jax.lax.conv_dimension_numbers(
        x.shape, w.shape, ("NHWC", "OHWI", "NHWC"))
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), [(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=dn)


@functools.lru_cache(maxsize=None)
def _ckxk_train_for(pad):
    """One custom_vjp core per static pad (jax.custom_vjp cannot take
    non-array args positionally)."""

    @jax.custom_vjp
    def f(x, w):
        return convkxk_bn_stats(x, w, pad)

    def fwd(x, w):
        z, mean, var = convkxk_bn_stats(x, w, pad)
        return (z, mean, var), (x, w, z, mean)

    def bwd(res, cts):
        x, w, z, mean = res
        gz, gmean, gvar = cts
        n, ho, wo, _ = z.shape
        m = n * ho * wo
        z32 = z.astype(jnp.float32)
        g = (gz.astype(jnp.float32)
             + gmean.astype(jnp.float32) / m
             + gvar.astype(jnp.float32) * 2.0 * (z32 - mean) / m)
        # conv input/weight grads through XLA's own transposed convs (MXU)
        _, vjp = jax.vjp(lambda x_, w_: _ref_convkxk(x_, w_, pad), x, w)
        dx, dw = vjp(g.astype(z.dtype))
        return dx.astype(x.dtype), dw.astype(w.dtype)

    f.defvjp(fwd, bwd)
    return f


def convkxk_bn_stats_train(x, w, pad=(1, 1)):
    """Differentiable (z, mean, var) of a stride-1 KxK NHWC conv with
    fused batch statistics.  Caller pre-checks :func:`convkxk_fits`."""
    return _ckxk_train_for((int(pad[0]), int(pad[1])))(x, w)


# 3x3 compatibility surface (the original round-5 entry points)
def conv3x3_fits(xshape, cout, block_n=128, vmem_budget=10 * 2 ** 20,
                 itemsize=2):
    return convkxk_fits(xshape, cout, (3, 3), (1, 1), block_n,
                        vmem_budget, itemsize)


def conv3x3_bn_stats(x, w, block_n=128):
    return convkxk_bn_stats(x, w, (1, 1), block_n)


def conv3x3_bn_stats_train(x, w):
    return convkxk_bn_stats_train(x, w, (1, 1))


def _ref_conv3x3(x, w):
    return _ref_convkxk(x, w, (1, 1))


# The round-5 int8 conv wrappers (int8_conv1x1 / int8_conv3x3 and the
# _c3x3_int8_kernel full-image-tile body) were DELETED in round 9: the
# chip bench measured the route at 0.345x of plain lax.conv s8
# (BENCH_builder_r05 pallas_vs_lax) and contrib/quantization.py now
# refuses MXNET_INT8_PALLAS with a pointer to that measurement.  The
# rebuilt int8_matmul above stays as the microbench's A/B vehicle.
