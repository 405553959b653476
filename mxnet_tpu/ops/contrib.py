"""Contrib operators — transformer fused attention matmuls, detection ops,
resampling (reference ``src/operator/contrib/``).

The interleaved self-attention ops mirror the reference BERT kernels
(``src/operator/contrib/transformer.cc:650-740``): projections stored
interleaved as (qkv) so QK^T and attn*V run as single batched matmuls on the
MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..base import enable_x64 as _enable_x64
from .registry import register


def _split_heads(qkv, heads):
    """(seq, batch, 3*embed) interleaved per head -> q, k, v, each
    (batch*heads, seq, head_dim)."""
    seq, bsz, three_embed = qkv.shape
    x = qkv.reshape(seq, bsz, heads, 3, three_embed // (3 * heads))
    return (x[:, :, :, j, :].transpose(1, 2, 0, 3).reshape(bsz * heads, seq, -1)
            for j in range(3))


def _merge_heads(out, bsz):
    """(batch*heads, seq, head_dim) -> (seq, batch, embed)."""
    bh, seq, head_dim = out.shape
    out = out.reshape(bsz, bh // bsz, seq, head_dim).transpose(2, 0, 1, 3)
    return out.reshape(seq, bsz, -1)


@register("interleaved_matmul_selfatt_qk", num_inputs=1)
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """Input (seq, batch, 3*embed) interleaved per head; output
    (batch*heads, seq, seq) scaled QK^T."""
    q, k, _ = _split_heads(queries_keys_values, heads)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    return jnp.matmul(q * scale, k.transpose(0, 2, 1))


@register("interleaved_matmul_selfatt_valatt", num_inputs=2)
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1):
    """attention (batch*heads, seq, seq) x V -> (seq, batch, embed)."""
    _, _, v = _split_heads(queries_keys_values, heads)
    return _merge_heads(jnp.matmul(attention, v),
                        queries_keys_values.shape[1])


# --- the attention core as ONE operator ------------------------------------
#
# scores -> softmax -> probability dropout -> value product.  On a TPU the
# Pallas flash kernels compute it without the (batch*heads, seq, seq) tensor
# ever reaching HBM (ops/pallas_kernels.py); everywhere else the same
# operator computes the unfused expression with the SAME dropout mask
# (ops.random.keep_mask), so a key means one mask on every path.
# Which path is decided from what the trace can observe, never by a switch:
# the platform, the active mesh, and the shape.

_ATTN_FUSED = _telemetry.counter(
    "attention.fused",
    "attention sites traced onto the Pallas flash kernels")
_ATTN_UNFUSED = _telemetry.counter(
    "attention.unfused",
    "attention sites traced as the unfused scores/softmax/dropout/value "
    "expression")


def _attention_platform() -> str:
    return jax.default_backend()


def _fused_attention_refusal(seq, heads, head_dim):
    """Why a TPU trace cannot take the Pallas kernels at this site, or None.

    ``pallas_call`` has no partitioning rule, so a mesh of more than one
    device keeps the unfused expression (ROADMAP S3: ``shard_map``).  Up to
    512 keys the whole-row kernels read the interleaved array in place and
    state their own rule (``pallas_kernels.qkv_heads_per_step``); longer
    sequences go to the blocked kernels, which Mosaic tiles in eights."""
    from ..parallel.mesh import current_mesh
    from . import pallas_kernels as _pk

    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        return f"mesh of {mesh.size} devices"
    if seq % 8 or head_dim % 8:
        return "seq and head_dim must be multiples of 8"
    if seq <= _pk._ROW_SEQ_MAX and _pk.qkv_heads_per_step(
            seq, heads, head_dim) is None:
        return (f"{heads} heads of {head_dim} do not split into whole "
                "128-lane columns")
    return None


def _unfused_selfatt(qkv, key, heads, p):
    """The two interleaved products around a float32 softmax, the kept
    probabilities scaled by 1 / (1 - p) under the kernels' own mask."""
    from . import pallas_kernels as _pk

    scores = interleaved_matmul_selfatt_qk(qkv, heads)
    att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    if p:
        att = jnp.where(_pk.dropout_keep_mask(key, *att.shape, p), att,
                        0.0) / (1.0 - p)
    return interleaved_matmul_selfatt_valatt(qkv, att.astype(qkv.dtype),
                                             heads)


@register("interleaved_selfatt", num_inputs=2, rng_input=True)
def interleaved_selfatt(queries_keys_values, key=None, heads=1, p=0.0,
                        training=False):
    """Self-attention core over the interleaved projection: input
    (seq, batch, 3*embed) as ``interleaved_matmul_selfatt_qk`` takes it,
    output (seq, batch, embed) as ``interleaved_matmul_selfatt_valatt``
    gives it: ``dropout(softmax(q k^T / sqrt(head_dim))) v``.

    ``p`` is the dropout rate on the normalised probabilities, applied only
    when ``training``; ``key`` is the uint32 PRNG key of the mask (unused
    otherwise).  Softmax statistics are float32 and the products take the
    input's dtype on either path."""
    from . import pallas_kernels as _pk

    qkv = queries_keys_values
    seq, bsz, three_embed = qkv.shape
    head_dim = three_embed // (3 * heads)
    p = float(p) if training else 0.0
    if _attention_platform() != "tpu":
        _ATTN_UNFUSED.inc()
        return _unfused_selfatt(qkv, key, heads, p)
    refusal = _fused_attention_refusal(seq, heads, head_dim)
    if refusal is not None:
        _ATTN_UNFUSED.inc()
        _telemetry.event("fallback", "attention.fused", seq=seq,
                         head_dim=head_dim, why=refusal)
        return _unfused_selfatt(qkv, key, heads, p)
    _ATTN_FUSED.inc()
    if seq <= _pk._ROW_SEQ_MAX:
        return _pk.flash_attention_qkv(qkv, heads, dropout_p=p,
                                       dropout_key=key)
    return _merge_heads(_pk.flash_attention(
        *_split_heads(qkv, heads), causal=False, dropout_p=p,
        dropout_key=key), bsz)


def _unfused_causal_gqa(q, k, v, heads, kv_heads, scale=None):
    """Grouped causal attention as two products around a float32 softmax
    under an explicit lower-triangular mask, the scores times ``scale``
    (``1 / sqrt(head_dim)`` where None).  The values' head may be wider or
    narrower than the scores'."""
    bsz, seq, width = q.shape
    d, group = width // heads, heads // kv_heads
    q5 = q.reshape(bsz, seq, kv_heads, group, d)
    k4, v4 = (t.reshape(bsz, seq, kv_heads, -1) for t in (k, v))
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q5, k4,
                        preferred_element_type=jnp.float32)
    scores = scores / (d ** 0.5) if scale is None else scores * scale
    visible = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    att = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", att.astype(v.dtype), v4,
                     preferred_element_type=jnp.float32)
    return out.reshape(bsz, seq, -1).astype(q.dtype)


def _causal_core(q, k, v, heads, kv_heads, fused, unfused, event, misfit,
                 scale=None):
    """The causal grouped core of one site: the Pallas kernels on a TPU
    with no mesh of more than one device when the shape fits (``misfit`` is
    None, else why it does not), the unfused expression anywhere else; the
    scores times ``scale`` (``1 / sqrt(head_dim)`` where None).  Counted on
    ``fused`` / ``unfused``; a TPU trace that leaves the kernels emits the
    ``fallback`` event ``event``."""
    from ..parallel.mesh import current_mesh
    from . import pallas_kernels as _pk

    if _attention_platform() != "tpu":
        unfused.inc()
        return _unfused_causal_gqa(q, k, v, heads, kv_heads, scale)
    mesh = current_mesh()
    refusal = f"mesh of {mesh.size} devices" \
        if mesh is not None and mesh.size > 1 else misfit
    if refusal is not None:
        unfused.inc()
        _telemetry.event("fallback", event, seq=q.shape[1],
                         head_dim=q.shape[2] // heads, why=refusal)
        return _unfused_causal_gqa(q, k, v, heads, kv_heads, scale)
    fused.inc()
    return _pk.flash_attention_gqa(q, k, v, heads, kv_heads, scale)


@register("causal_gqa_selfatt", num_inputs=3)
def causal_gqa_selfatt(queries, keys, values, heads=1, kv_heads=1,
                       scale=None):
    """Causal self-attention core with grouped key-value heads:
    ``queries`` (batch, seq, heads * head_dim), ``keys`` and ``values``
    (batch, seq, kv_heads * head_dim), as the three projections give them;
    query head ``h`` reads key-value head ``h // (heads // kv_heads)``.
    ``softmax(q k^T * scale + causal mask) v``, shaped as ``queries``;
    ``scale`` is ``1 / sqrt(head_dim)`` where None (a model may state its
    own: Granite's ``attention_multiplier``).  On a TPU with no mesh the
    Pallas kernels (``pallas_kernels.flash_attention_gqa``): heads of whole
    128-lane columns read in place, with no copy of a key or a value; heads
    of 64 moved head-major around the call.  Anywhere else the unfused
    expression.  Counted and refused as ``interleaved_selfatt`` is."""
    from . import pallas_kernels as _pk

    seq, head_dim = queries.shape[1], queries.shape[2] // heads
    misfit = None
    if head_dim % 64 or _pk.gqa_block(seq) is None:
        misfit = ("head_dim must be a multiple of 64 and seq a multiple "
                  "of 16")
    return _causal_core(queries, keys, values, heads, kv_heads, _ATTN_FUSED,
                        _ATTN_UNFUSED, "attention.fused", misfit,
                        None if scale is None else float(scale))


# --- latent attention: low-rank keys and values, one rotary key a token ----
#
# DeepSeek-V2's multi-head latent attention (arXiv:2405.04434, section 2.1)
# as a model trains it: a head's key is ``[k_nope; k_rope]``, ``k_nope`` its
# own rows of the key-value up-projection and ``k_rope`` ONE rotary vector a
# token that every head uses (its gradient is the sum over the heads); a
# head's query is ``[q_nope; q_rope]`` with rotary positions on ``q_rope``;
# scores are scaled by the whole query width.  The products of the absorbed
# form are a serving matter (the latent as the cache) and are not built.

_LATENT_FUSED = _telemetry.counter(
    "attention.latent_fused",
    "latent-attention sites whose core was traced onto the Pallas flash "
    "kernels")
_LATENT_UNFUSED = _telemetry.counter(
    "attention.latent_unfused",
    "latent-attention sites whose core was traced as the unfused "
    "scores/softmax/value expression")
# the scopes the device trace reads (perfbench/scope_view)
LATENT_QK_SCOPE = "LatentQK"
LATENT_CORE_SCOPE = "LatentCore"


@register("causal_latent_selfatt", num_inputs=3)
def causal_latent_selfatt(queries, keys_values, rope_key, heads=1,
                          rope_dim=0, theta=10000.0):
    """Causal latent-attention core.  ``queries`` (batch, seq, heads *
    (nope + rope_dim)), a head's ``[q_nope; q_rope]``; ``keys_values``
    (batch, seq, heads * (nope + v_dim)), a head's ``[k_nope; v]``;
    ``rope_key`` (batch, seq, rope_dim), the token's one ``k_rope``; both
    as the up-projections give them, before rotary positions.
    ``q_rope`` and ``k_rope`` take rotary positions (``rope``, base
    ``theta``), every head's key is ``[k_nope; k_rope]``, and the result is
    ``softmax(q k^T / sqrt(nope + rope_dim) + causal mask) v``, (batch, seq,
    heads * v_dim).  On a TPU with no mesh, where scores and values have one
    head width of whole 128-lane columns, the core is
    ``pallas_kernels.flash_attention_gqa`` (as many key-value heads as query
    heads); anywhere else the unfused expression.  Counted
    (``attention.latent_fused`` / ``attention.latent_unfused``) and refused
    as ``interleaved_selfatt`` is."""
    from . import pallas_kernels as _pk
    from .rotary import rope

    bsz, seq, width = queries.shape
    qk_dim = width // heads
    nope = qk_dim - rope_dim
    with jax.named_scope(LATENT_QK_SCOPE):
        q4 = queries.reshape(bsz, seq, heads, qk_dim)
        q = jnp.concatenate(
            [q4[..., :nope], rope(q4[..., nope:], theta=theta)],
            axis=-1).reshape(bsz, seq, width)
        kv4 = keys_values.reshape(bsz, seq, heads, -1)
        k_rope = jnp.broadcast_to(
            rope(rope_key, theta=theta)[:, :, None, :],
            (bsz, seq, heads, rope_dim))               # one key, every head
        k = jnp.concatenate([kv4[..., :nope], k_rope.astype(kv4.dtype)],
                            axis=-1).reshape(bsz, seq, width)
        v = kv4[..., nope:].reshape(bsz, seq, -1)
    misfit = None
    if v.shape[-1] != width or qk_dim % 128 or _pk.gqa_block(seq) is None:
        misfit = ("scores and values must share a head width of whole "
                  "128-lane columns and seq be a multiple of 16")
    with jax.named_scope(LATENT_CORE_SCOPE):
        return _causal_core(q, k, v, heads, heads, _LATENT_FUSED,
                            _LATENT_UNFUSED, "attention.latent_fused",
                            misfit)


@register("held_experts", num_inputs=5, num_outputs=2)
def held_experts(data, router_weight, select_bias, up_weight, down_weight,
                 held=(), k=1, scaling=1.0, hidden_act="relu2",
                 capacity_factor=2.0):
    """One expert-parallel rank's part of a sparse-expert layer
    (``parallel.moe.held_experts_layer``): sigmoid scores over ALL the
    experts, the ``k`` largest of score + bias, the rows of the ``held``
    experts sorted into a static buffer, two grouped products with the
    model's ``hidden_act`` between them (``relu2``: ``relu(.)^2``;
    ``silu``: the gated form, ``up_weight`` holding gate and up side by
    side), a weighted scatter back.  The buffer holds ``capacity_factor``
    times the held experts' mean share of the rows.  Outputs: the routed
    result, shaped and typed as ``data``, and the call's float32 counts
    ``moe.HELD_STATS``."""
    from ..parallel.moe import held_experts_layer

    return held_experts_layer(
        data, router_weight, select_bias, up_weight, down_weight,
        held=tuple(held), k=int(k), scaling=float(scaling),
        hidden_act=str(hidden_act), capacity_factor=float(capacity_factor))


@register("interleaved_matmul_encdec_qk", num_inputs=2)
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1):
    seq_q, bsz, embed = queries.shape
    seq_kv = keys_values.shape[0]
    head_dim = embed // heads
    q = queries.reshape(seq_q, bsz, heads, head_dim).transpose(1, 2, 0, 3)
    q = q.reshape(bsz * heads, seq_q, head_dim)
    kv = keys_values.reshape(seq_kv, bsz, heads, 2, head_dim)
    k = kv[:, :, :, 0, :].transpose(1, 2, 0, 3).reshape(bsz * heads, seq_kv, head_dim)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, q.dtype))
    return jnp.matmul(q * scale, k.transpose(0, 2, 1))


@register("interleaved_matmul_encdec_valatt", num_inputs=2)
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1):
    seq_kv, bsz, two_embed = keys_values.shape
    embed = two_embed // 2
    head_dim = embed // heads
    kv = keys_values.reshape(seq_kv, bsz, heads, 2, head_dim)
    v = kv[:, :, :, 1, :].transpose(1, 2, 0, 3).reshape(bsz * heads, seq_kv, head_dim)
    out = jnp.matmul(attention, v)
    seq_q = attention.shape[1]
    out = out.reshape(bsz, heads, seq_q, head_dim).transpose(2, 0, 1, 3)
    return out.reshape(seq_q, bsz, embed)


@register("div_sqrt_dim")
def div_sqrt_dim(data):
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


@register("boolean_mask", num_inputs=2, differentiable=False)
def boolean_mask(data, index, axis=0, size=None):
    """Compact the rows of ``data`` where ``index`` is non-zero (reference
    ``src/operator/contrib/boolean_mask.cc`` — the canonical dynamic-shape
    op, gated by CheckDynamicShapeExists in cached_op.cc:820).

    Dynamic-shape policy on TPU (SURVEY §7 "hard parts"): XLA needs static
    shapes, so inside jit/hybridized graphs the op REQUIRES the
    pad-and-mask contract: pass ``size=k`` (an upper bound on selected
    rows) and the output has static leading size ``k`` — selected rows
    first, in order, then zero padding (same contract as
    ``jnp.nonzero(size=...)``).  Downstream reductions are unaffected by
    the zero rows for sum/mean-style math; pair with ``sum(index)`` when
    the true count matters.  Eagerly (no jit), omitting ``size`` keeps the
    reference's exact compacted-shape semantics.
    """
    mask = index.astype(bool)
    if size is None:
        try:
            idx = jnp.nonzero(mask)[0]
        except jax.errors.ConcretizationTypeError as e:
            from ..base import MXNetError

            raise MXNetError(
                "boolean_mask has a data-dependent output shape and cannot "
                "trace into a jit/hybridized graph without the pad-and-mask "
                "contract: pass size=<max rows> to fix the output's leading "
                "dimension (selected rows first, zero-padded)"
            ) from e
        return jnp.take(data, idx, axis=axis)
    idx = jnp.nonzero(mask, size=int(size), fill_value=data.shape[axis])[0]
    return jnp.take(data, idx, axis=axis, mode="fill", fill_value=0)


@register("index_copy", num_inputs=3, differentiable=False)
def index_copy(old_tensor, index_vector, new_tensor):
    return old_tensor.at[index_vector.astype(jnp.int32)].set(new_tensor)


@register("index_array", num_inputs=1, differentiable=False)
def index_array(data, axes=None):
    shape = data.shape
    axes = tuple(axes) if axes else tuple(range(len(shape)))
    grids = jnp.meshgrid(*[jnp.arange(shape[a]) for a in axes], indexing="ij")
    with _enable_x64(True):   # reference index_array emits int64
        return jnp.stack(grids, axis=-1).astype(jnp.int64)


@register("allclose", num_inputs=2, differentiable=False)
def allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=True):
    return jnp.asarray(
        jnp.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan),
        dtype=jnp.float32,
    )


@register("arange_like", num_inputs=1, differentiable=False)
def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = 1
        for s in data.shape:
            n *= s
        out = start + step * jnp.arange(n, dtype=data.dtype)
        return out.reshape(data.shape)
    n = data.shape[axis]
    return start + step * jnp.arange(n, dtype=data.dtype)


@register("quadratic", num_inputs=1)
def quadratic(data, a=0.0, b=0.0, c=0.0):
    """The reference's tutorial op (src/operator/contrib/quadratic_op.cc)."""
    return a * jnp.square(data) + b * data + c


# --- detection / vision contrib -------------------------------------------

@register("BilinearResize2D")
def bilinear_resize2d(data, height=1, width=1, scale_height=None,
                      scale_width=None, mode="size", align_corners=True):
    n, c, h, w = data.shape
    if scale_height is not None:
        height = int(round(h * scale_height))
        width = int(round(w * scale_width))
    return jax.image.resize(data, (n, c, height, width), method="bilinear")


@register("AdaptiveAvgPooling2D")
def adaptive_avg_pooling2d(data, output_size=None):
    n, c, h, w = data.shape
    if output_size is None:
        oh = ow = 1
    elif isinstance(output_size, int):
        oh = ow = output_size
    else:
        oh, ow = output_size
    # decompose into reduce_window when divisible, else resize-avg
    if h % oh == 0 and w % ow == 0:
        kh, kw = h // oh, w // ow
        out = jax.lax.reduce_window(
            data, 0.0, jax.lax.add, (1, 1, kh, kw), (1, 1, kh, kw), "VALID"
        )
        return out / (kh * kw)
    return jax.image.resize(data, (n, c, oh, ow), method="linear")


@register("ROIAlign", num_inputs=2)
def roi_align(data, rois, pooled_size=(7, 7), spatial_scale=1.0,
              sample_ratio=-1, position_sensitive=False, aligned=False):
    """ROIAlign (reference src/operator/contrib/roi_align.cc) via bilinear
    gather — vectorized over rois."""
    ph, pw = pooled_size
    n, c, h, w = data.shape

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        offset = 0.5 if aligned else 0.0
        x1 = roi[1] * spatial_scale - offset
        y1 = roi[2] * spatial_scale - offset
        x2 = roi[3] * spatial_scale - offset
        y2 = roi[4] * spatial_scale - offset
        roi_w = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
        roi_h = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
        bin_h = roi_h / ph
        bin_w = roi_w / pw
        sr = sample_ratio if sample_ratio > 0 else 2
        ys = y1 + bin_h * (jnp.arange(ph)[:, None] + (jnp.arange(sr)[None, :] + 0.5) / sr)
        xs = x1 + bin_w * (jnp.arange(pw)[:, None] + (jnp.arange(sr)[None, :] + 0.5) / sr)
        ys = ys.reshape(-1)  # (ph*sr,)
        xs = xs.reshape(-1)  # (pw*sr,)
        img = data[batch_idx]  # (c, h, w)
        y0 = jnp.clip(jnp.floor(ys), 0, h - 1)
        x0 = jnp.clip(jnp.floor(xs), 0, w - 1)
        y1i = jnp.clip(y0 + 1, 0, h - 1)
        x1i = jnp.clip(x0 + 1, 0, w - 1)
        wy1 = ys - y0
        wx1 = xs - x0
        y0 = y0.astype(jnp.int32); x0 = x0.astype(jnp.int32)
        y1i = y1i.astype(jnp.int32); x1i = x1i.astype(jnp.int32)
        v00 = img[:, y0][:, :, x0]
        v01 = img[:, y0][:, :, x1i]
        v10 = img[:, y1i][:, :, x0]
        v11 = img[:, y1i][:, :, x1i]
        val = (
            v00 * ((1 - wy1)[:, None] * (1 - wx1)[None, :])
            + v01 * ((1 - wy1)[:, None] * wx1[None, :])
            + v10 * (wy1[:, None] * (1 - wx1)[None, :])
            + v11 * (wy1[:, None] * wx1[None, :])
        )  # (c, ph*sr, pw*sr)
        val = val.reshape(c, ph, sr, pw, sr).mean(axis=(2, 4))
        return val

    return jax.vmap(one_roi)(rois)


@register("box_iou", num_inputs=2, differentiable=False)
def box_iou(lhs, rhs, format="corner"):
    def to_corner(b):
        if format == "center":
            cx, cy, w2, h2 = b[..., 0], b[..., 1], b[..., 2] / 2, b[..., 3] / 2
            return jnp.stack([cx - w2, cy - h2, cx + w2, cy + h2], axis=-1)
        return b

    a = to_corner(lhs)[..., :, None, :]
    b = to_corner(rhs)[..., None, :, :]
    tl = jnp.maximum(a[..., :2], b[..., :2])
    br = jnp.minimum(a[..., 2:], b[..., 2:])
    wh = jnp.maximum(br - tl, 0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = (a[..., 2] - a[..., 0]) * (a[..., 3] - a[..., 1])
    area_b = (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1])
    return inter / (area_a + area_b - inter + 1e-12)


# ---------------------------------------------------------------------------
# Spatial transform family (reference src/operator/spatial_transformer.cc,
# bilinear_sampler.cc, grid_generator.cc) — all fully differentiable.
# ---------------------------------------------------------------------------

def _bilinear_sample_2d(img, gx, gy):
    """Sample img [C,H,W] at normalized grid coords gx/gy [-1,1] of shape
    [Ho,Wo]; zero padding outside (matches reference BilinearSampler)."""
    C, H, W = img.shape
    x = (gx + 1.0) * (W - 1) / 2.0
    y = (gy + 1.0) * (H - 1) / 2.0
    x0 = jnp.floor(x)
    y0 = jnp.floor(y)
    wx1 = x - x0
    wy1 = y - y0

    def gather(yi, xi):
        inb = (yi >= 0) & (yi < H) & (xi >= 0) & (xi < W)
        yc = jnp.clip(yi, 0, H - 1).astype(jnp.int32)
        xc = jnp.clip(xi, 0, W - 1).astype(jnp.int32)
        v = img[:, yc, xc]                   # [C,Ho,Wo]
        return jnp.where(inb[None], v, 0.0)

    v00 = gather(y0, x0)
    v01 = gather(y0, x0 + 1)
    v10 = gather(y0 + 1, x0)
    v11 = gather(y0 + 1, x0 + 1)
    return (v00 * (1 - wy1) * (1 - wx1) + v01 * (1 - wy1) * wx1
            + v10 * wy1 * (1 - wx1) + v11 * wy1 * wx1)


@register("BilinearSampler", num_inputs=2, aliases=["bilinear_sampler"])
def bilinear_sampler(data, grid, cudnn_off=None):
    """data [B,C,H,W] sampled at grid [B,2,Ho,Wo] (channel 0 = x, 1 = y,
    normalized to [-1,1]) -> [B,C,Ho,Wo].  Reference
    src/operator/bilinear_sampler.cc."""
    return jax.vmap(lambda d, g: _bilinear_sample_2d(d, g[0], g[1]))(
        data, grid)


def _affine_grid(theta, Ho, Wo):
    """theta [6] row-major 2x3 -> normalized sampling grid [2,Ho,Wo]."""
    t = theta.reshape(2, 3)
    ys = jnp.linspace(-1.0, 1.0, Ho)
    xs = jnp.linspace(-1.0, 1.0, Wo)
    xg, yg = jnp.meshgrid(xs, ys)            # [Ho,Wo]
    ones = jnp.ones_like(xg)
    coords = jnp.stack([xg, yg, ones], axis=0).reshape(3, -1)
    out = t @ coords                          # [2, Ho*Wo]
    return out.reshape(2, Ho, Wo)


@register("GridGenerator", num_inputs=1, aliases=["grid_generator"])
def grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """Generate a BilinearSampler grid (reference grid_generator.cc).

    - affine: data [B,6] affine params -> grid [B,2,Ho,Wo]
    - warp: data [B,2,H,W] pixel flow field added to the identity grid,
      normalized to [-1,1]
    """
    if transform_type == "affine":
        Ho, Wo = int(target_shape[0]), int(target_shape[1])
        return jax.vmap(lambda th: _affine_grid(th, Ho, Wo))(data)
    if transform_type == "warp":
        B, _, H, W = data.shape
        xs = jnp.arange(W, dtype=data.dtype)
        ys = jnp.arange(H, dtype=data.dtype)
        xg, yg = jnp.meshgrid(xs, ys)
        gx = (xg[None] + data[:, 0]) * 2.0 / jnp.maximum(W - 1, 1) - 1.0
        gy = (yg[None] + data[:, 1]) * 2.0 / jnp.maximum(H - 1, 1) - 1.0
        return jnp.stack([gx, gy], axis=1)
    raise ValueError(f"unknown transform_type {transform_type}")


@register("SpatialTransformer", num_inputs=2,
          aliases=["spatial_transformer"])
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=None):
    """Affine spatial transformer network op (reference
    spatial_transformer.cc): loc [B,6] -> affine grid -> bilinear sample."""
    assert transform_type == "affine" and sampler_type == "bilinear"
    grid = grid_generator(loc, "affine", target_shape)
    return bilinear_sampler(data, grid)


@register("DeformableConvolution", num_inputs=-1,
          aliases=["deformable_convolution"])
def deformable_convolution(arrays, kernel=(3, 3), stride=(1, 1),
                           dilate=(1, 1), pad=(0, 0), num_filter=1,
                           num_group=1, num_deformable_group=1,
                           no_bias=False, workspace=1024, layout=None):
    """Deformable convolution v1 (reference
    src/operator/contrib/deformable_convolution.cc).

    arrays = [data [B,C,H,W], offset [B, 2*kh*kw*ndg, Ho, Wo], weight
    [O, C/g, kh, kw], (bias [O])].  TPU-native lowering: bilinear-sample
    the input at kernel+offset positions (gather; differentiable), then a
    single einsum over (C/g, kh, kw) — the im2col+GEMM split the MXU
    likes.
    """
    data, offset, weight = arrays[0], arrays[1], arrays[2]
    bias = None if no_bias or len(arrays) < 4 else arrays[3]
    return _deform_conv_impl(data, offset, weight, bias, kernel, stride,
                             dilate, pad, num_filter, num_group,
                             num_deformable_group)


def _deform_conv_impl(data, offset, weight, bias, kernel, stride, dilate,
                      pad, num_filter, num_group, ndg, mask=None):
    B, C, H, W = data.shape
    kh, kw = kernel
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    O = num_filter
    g = num_group

    # base sampling positions [kh*kw, Ho, Wo]
    oy = jnp.arange(Ho) * sh - ph
    ox = jnp.arange(Wo) * sw - pw
    ky = jnp.arange(kh) * dh
    kx = jnp.arange(kw) * dw
    base_y = oy[None, :, None] + ky[:, None, None]          # [kh,Ho,1]
    base_x = ox[None, None, :] + kx[:, None, None]          # [kw,1,Wo]
    base_y = jnp.broadcast_to(base_y[:, None], (kh, kw, Ho, Wo))
    base_x = jnp.broadcast_to(base_x[None, :, :, :], (kh, kw, Ho, Wo))

    def sample_one(dat, off, msk):
        # dat [C,H,W]; off [2*kh*kw*ndg, Ho, Wo] layout: per deform group,
        # per kernel point, (dy, dx); msk [ndg*kh*kw, Ho, Wo] or None
        off = off.reshape(ndg, kh * kw, 2, Ho, Wo)
        if msk is not None:
            msk = msk.reshape(ndg, kh * kw, Ho, Wo)
        cs = C // ndg
        outs = []
        for dg in range(ndg):
            dy = base_y.reshape(kh * kw, Ho, Wo) + off[dg, :, 0]
            dx = base_x.reshape(kh * kw, Ho, Wo) + off[dg, :, 1]
            # normalize to [-1,1] for the shared bilinear sampler
            gx = dx * 2.0 / jnp.maximum(W - 1, 1) - 1.0
            gy = dy * 2.0 / jnp.maximum(H - 1, 1) - 1.0
            sub = dat[dg * cs:(dg + 1) * cs]
            # sample all kernel points: [C/ndg, kh*kw, Ho, Wo]
            samp = jax.vmap(
                lambda xg, yg: _bilinear_sample_2d(sub, xg, yg),
                in_axes=(0, 0), out_axes=1)(gx, gy)
            if msk is not None:     # DCNv2 modulation per kernel point
                samp = samp * msk[dg][None]
            outs.append(samp)
        return jnp.concatenate(outs, axis=0)    # [C, kh*kw, Ho, Wo]

    if mask is None:
        cols = jax.vmap(lambda d, o: sample_one(d, o, None))(data, offset)
    else:
        cols = jax.vmap(sample_one)(data, offset, mask)
    cols = cols.reshape(B, g, C // g, kh, kw, Ho, Wo)
    wgt = weight.reshape(g, O // g, C // g, kh, kw)
    out = jnp.einsum("bgchkxy,gochk->bgoxy", cols, wgt,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, O, Ho, Wo).astype(data.dtype)
    if bias is not None:
        out = out + bias.reshape(1, O, 1, 1)
    return out


@register("ModulatedDeformableConvolution", num_inputs=-1,
          aliases=["modulated_deformable_convolution",
                   "_npx_modulated_deformable_convolution"])
def modulated_deformable_convolution(arrays, kernel=(3, 3), stride=(1, 1),
                                     dilate=(1, 1), pad=(0, 0),
                                     num_filter=1, num_group=1,
                                     num_deformable_group=1, no_bias=False,
                                     workspace=1024, layout=None):
    """Deformable convolution v2 (reference
    src/operator/contrib/modulated_deformable_convolution.cc): v1 sampling
    plus a learned per-sample-point modulation mask.

    arrays = [data, offset [B,2*kh*kw*ndg,Ho,Wo], mask [B,kh*kw*ndg,Ho,Wo]
    (already sigmoided by the layer), weight, (bias)].
    """
    data, offset, mask, weight = arrays[0], arrays[1], arrays[2], arrays[3]
    bias = None if no_bias or len(arrays) < 5 else arrays[4]
    return _deform_conv_impl(data, offset, weight, bias, kernel, stride,
                             dilate, pad, num_filter, num_group,
                             num_deformable_group, mask=mask)


# ---------------------------------------------------------------------------
# FFT + count_sketch (reference src/operator/contrib/fft.cc, ifft.cc,
# count_sketch.cc — cuFFT-based there, jnp.fft on TPU here)
# ---------------------------------------------------------------------------

@register("fft")
def fft(data, compute_size=128):
    """Batched 1D FFT of real input [..., d] -> [..., 2*d] with real/imag
    interleaved (reference fft-inl.h:80-130 output layout)."""
    c = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    out = jnp.stack([c.real, c.imag], axis=-1)
    return out.reshape(data.shape[:-1] + (2 * data.shape[-1],)).astype(
        data.dtype)


@register("ifft")
def ifft(data, compute_size=128):
    """Inverse of :func:`fft`: [..., 2*d] interleaved -> [..., d] real.
    Like cuFFT (reference ifft.cc), the transform is UNNORMALIZED — scale
    by 1/d to invert ``fft``."""
    d = data.shape[-1] // 2
    x = data.reshape(data.shape[:-1] + (d, 2)).astype(jnp.float32)
    c = jax.lax.complex(x[..., 0], x[..., 1])
    out = jnp.fft.ifft(c, axis=-1).real * d
    return out.astype(data.dtype)


@register("count_sketch", num_inputs=3)
def count_sketch(data, h, s, out_dim=0, processing_batch_size=32):
    """Count-sketch projection (reference count_sketch.cc): out[..., h[i]]
    += s[i] * data[..., i]; h in [0, out_dim), s in {+1,-1}."""
    out_dim = int(out_dim)
    idx = h.reshape(-1).astype(jnp.int32)
    sign = s.reshape(-1).astype(data.dtype)
    flat = data.reshape(-1, data.shape[-1])
    contrib = flat * sign[None, :]
    out = jnp.zeros((flat.shape[0], out_dim), data.dtype)
    out = out.at[:, idx].add(contrib)
    return out.reshape(data.shape[:-1] + (out_dim,))
