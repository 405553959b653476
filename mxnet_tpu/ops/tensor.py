"""Tensor manipulation operators.

Reference analog: ``src/operator/tensor/matrix_op.cc`` (reshape/transpose/
slice/concat/take/...), ``indexing_op.cc``, ``cast_storage`` etc.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as onp

from .. import base as _base
from ..base import S64_DEMOTING_PLATFORMS, bounded_cache_put, pow2_col_factor
from ..base import int32_overflow_dim as _concrete_big
from .registry import register


@register("reshape", aliases=["Reshape"])
def reshape(data, shape=None, reverse=False):
    # Support MXNet's special codes 0 (copy dim) and -1 (infer)
    shape = tuple(shape)
    if 0 in shape or -2 in shape or -3 in shape or -4 in shape:
        shape = _expand_reshape_codes(tuple(data.shape), shape)
    return jnp.reshape(data, shape)


@register("npx_reshape", aliases=["_npx_reshape"])
def npx_reshape(data, newshape=None, reverse=False, order="C"):
    """npx.reshape — the NUMPY-EXTENSION special codes (reference
    _numpy_op_doc.py:563): -1 infer, -2 copy dim, -3 drop a size-1 dim,
    -4 copy ALL remaining dims, -5 merge two consecutive dims, -6 split
    a dim into the two factors that follow."""
    src = tuple(data.shape)
    shape = list(newshape if isinstance(newshape, (list, tuple))
                 else [newshape])
    if reverse:
        # right-to-left SHAPE resolution only (data stays C-order): expand
        # the mirrored spec against the mirrored src, mirror the result
        out_rev = _expand_npx_codes(src[::-1], _reverse_npx_spec(shape),
                                    mirror_splits=True)
        return jnp.reshape(data, tuple(out_rev)[::-1])
    return jnp.reshape(data, tuple(_expand_npx_codes(src, shape)))


def _expand_npx_codes(src, shape, mirror_splits=False):
    out = []
    i = 0
    j = 0
    while j < len(shape):
        s = shape[j]
        if s == -2:
            out.append(src[i]); i += 1
        elif s == -3:
            if src[i] != 1:
                raise ValueError(
                    f"npx.reshape -3 requires a size-1 dim, got {src[i]}")
            i += 1
        elif s == -4:
            out.extend(src[i:]); i = len(src)
        elif s == -5:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -6:
            d1, d2 = shape[j + 1], shape[j + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            if d1 * d2 != src[i]:
                raise ValueError(
                    f"npx.reshape -6: {d1}x{d2} != {src[i]}")
            out.extend([d2, d1] if mirror_splits else [d1, d2])
            i += 1; j += 2
        elif s == -1:
            out.append(-1); i += 1
        else:
            out.append(s); i += 1
        j += 1
    return out


def _reverse_npx_spec(shape):
    """Reverse an npx-reshape spec keeping -6's factor pairs attached."""
    groups = []
    j = 0
    while j < len(shape):
        if shape[j] == -6:
            groups.append(shape[j:j + 3])
            j += 3
        else:
            groups.append([shape[j]])
            j += 1
    return [v for g in reversed(groups) for v in g]


def _expand_reshape_codes(src, shape):
    """Implements MXNet reshape special codes 0/-1/-2/-3/-4
    (reference matrix_op.cc InferReshapeShape)."""
    out = []
    i = 0  # index into src
    j = 0
    shape = list(shape)
    while j < len(shape):
        s = shape[j]
        if s == 0:
            out.append(src[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(src[i:]); i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -4:
            d1, d2 = shape[j + 1], shape[j + 2]
            if d1 == -1:
                d1 = src[i] // d2
            if d2 == -1:
                d2 = src[i] // d1
            out.extend([d1, d2]); i += 1; j += 2
        else:
            out.append(s); i += 1
        j += 1
    return tuple(out)


@register("transpose")
def transpose(data, axes=None):
    return jnp.transpose(data, axes)


@register("swapaxes", aliases=["SwapAxis"])
def swapaxes(data, dim1=0, dim2=0):
    return jnp.swapaxes(data, dim1, dim2)


@register("flatten", aliases=["Flatten"])
def flatten(data):
    return jnp.reshape(data, (data.shape[0], -1))


@register("expand_dims")
def expand_dims(data, axis=0):
    return jnp.expand_dims(data, axis)


@register("squeeze")
def squeeze(data, axis=None):
    return jnp.squeeze(data, axis)


@register("broadcast_to")
def broadcast_to(data, shape=None):
    shape = tuple(shape)
    if 0 in shape:  # 0 = keep the matching input dim, right-aligned
        offset = len(shape) - data.ndim
        shape = tuple(
            s if s != 0 else data.shape[i - offset]
            for i, s in enumerate(shape))
    return jnp.broadcast_to(data, shape)


@register("broadcast_axis", aliases=["broadcast_axes"])
def broadcast_axis(data, axis=None, size=None):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    sizes = (size,) if isinstance(size, int) else tuple(size)
    shape = list(data.shape)
    for a, s in zip(axes, sizes):
        shape[a] = s
    return jnp.broadcast_to(data, tuple(shape))


@register("tile")
def tile(data, reps=None):
    return jnp.tile(data, reps)


@register("repeat")
def repeat(data, repeats=1, axis=None):
    return jnp.repeat(data, repeats, axis)


@register("pad", aliases=["Pad"])
def pad(data, mode="constant", pad_width=None, constant_value=0.0):
    pw = list(pad_width)
    pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    jmode = {"constant": "constant", "edge": "edge", "reflect": "reflect"}[mode]
    if jmode == "constant":
        return jnp.pad(data, pairs, mode=jmode, constant_values=constant_value)
    return jnp.pad(data, pairs, mode=jmode)


@register("concat", num_inputs=-1, aliases=["Concat"])
def concat(arrays, dim=1):
    return jnp.concatenate(arrays, axis=dim)


@register("stack", num_inputs=-1)
def stack(arrays, axis=0):
    return jnp.stack(arrays, axis=axis)


@register("split", num_outputs=-1, aliases=["SliceChannel"])
def split(data, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


@register("slice", aliases=["crop"])
def slice_op(data, begin=None, end=None, step=None):
    ndim = data.ndim
    begin = list(begin) + [None] * (ndim - len(begin))
    end = list(end) + [None] * (ndim - len(end))
    step = list(step or []) + [None] * (ndim - len(step or []))
    idx = tuple(slice(b, e, s) for b, e, s in zip(begin, end, step))
    return data[idx]


@register("slice_axis")
def slice_axis(data, axis=0, begin=0, end=None):
    idx = [slice(None)] * data.ndim
    idx[axis] = slice(begin, end)
    return data[tuple(idx)]


@register("slice_like", num_inputs=2)
def slice_like(data, shape_like, axes=None):
    tgt = shape_like.shape
    idx = [slice(None)] * data.ndim
    axes = axes if axes else range(data.ndim)
    for a in axes:
        idx[a] = slice(0, tgt[a])
    return data[tuple(idx)]


@register("take", num_inputs=2)
def take(a, indices, axis=0, mode="clip"):
    jmode = {"clip": "clip", "wrap": "wrap", "raise": "clip"}[mode]
    dim = a.shape[axis] if a.ndim else 0
    if _concrete_big(dim) and not _base.s64_demoting_backend():
        # x64-native backend (cpu): s64 gathers execute natively — invoke
        # dispatches s64-typed big-dim calls under enable_x64 — so plain
        # jnp.take is exact at any offset and works traced (autograd,
        # hybridize).  The int32 factorization below and its refusals are
        # TPU-runtime constraints only (ADVICE r5).
        return jnp.take(a, indices.astype(jnp.int64), axis=axis, mode=jmode)
    if _concrete_big(dim):
        # >int32-range gather: the TPU compiler rejects s64 dynamic
        # indexing outright ("X64 rewrite ... indices exceed 32-bits"),
        # so factorize each flat index into a (row, col) int32 pair over
        # a (dim/C, C) view — per-dim extents and indices then all fit
        # int32, which the hardware gathers natively.  The s64 index
        # arithmetic runs ON HOST (the AOT compiler demotes device s64
        # types, mismatching jax's s64 buffers).
        if a.ndim != 1:
            raise NotImplementedError(
                "take along a >int32-range dim of a multi-dim array is "
                "not supported (an int32 cast would silently wrap the "
                "indices); flatten to 1-D for the exact factorized "
                "gather, or reshape so every dim fits int32")
        if isinstance(indices, jax.core.Tracer):
            raise NotImplementedError(
                "take with non-concrete indices on a >int32-range dim "
                "(inside jit/hybridize traces, or under autograd.record, "
                "which traces the op for its vjp): the TPU compiler "
                "demotes s64 index types, so the exact factorization "
                "needs concrete index values.  Gather outside record()/"
                "hybridize, or reshape to a 2-D view whose dims fit "
                "int32 — int32 gathers work everywhere, incl. autograd")
        C = pow2_col_factor(dim)
        if not C:
            # padding to a factorizable length would move data ALONG the
            # big dim — the exact pattern the runtime corrupts
            raise NotImplementedError(
                "take on an odd >int32-range dim: no power-of-two column "
                "factor exists and padding along a >2^31 dim is corrupt "
                "on the TPU runtime; pad the array to an even length at "
                "creation time")
        idx = onp.asarray(indices).astype(onp.int64)
        idx = idx % dim if jmode == "wrap" else onp.clip(idx, 0, dim - 1)
        rows = jnp.asarray((idx // C).astype(onp.int32))
        cols = jnp.asarray((idx % C).astype(onp.int32))
        ck = (a.shape, str(a.dtype), rows.shape)
        fn = _BIG_TAKE_JIT.get(ck)
        if fn is None:

            def big_take(d, r, c):
                # traced: reshape/gathers all carry static metadata
                mat = d.reshape(dim // C, C)
                picked = jnp.take(mat, r, axis=0, mode="clip")
                return jnp.take_along_axis(picked, c[..., None], axis=-1)

            fn = bounded_cache_put(_BIG_TAKE_JIT, ck, jax.jit(big_take))
        return fn(a, rows, cols).reshape(indices.shape)
    # int32 indexing otherwise (indices address an int32-range dim, so
    # every in-bounds value fits int32; out-of-bounds clip/wrap first)
    return jnp.take(a, indices.astype(jnp.int32), axis=axis, mode=jmode)


def pick_index(index, size, mode):
    """``pick``'s index as int32 inside [0, size): out-of-range values
    clip to the ends or wrap around (reference pick's ``mode``)."""
    if _concrete_big(size):
        raise NotImplementedError(
            "pick along a >int32-range dim: the int32 index cast would "
            "silently wrap; reshape so the picked dim fits int32")
    if mode not in ("clip", "wrap"):
        raise ValueError(f"pick mode must be 'clip' or 'wrap', got {mode!r}")
    index = index.astype(jnp.int32)
    return jnp.clip(index, 0, size - 1) if mode == "clip" else index % size


@register("pick", num_inputs=2)
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    index = pick_index(index, data.shape[axis], mode)
    out = jnp.take_along_axis(data, jnp.expand_dims(index, axis=axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register("gather_nd", num_inputs=2)
def gather_nd(data, indices):
    if any(_concrete_big(d) for d in data.shape[:indices.shape[0]]):
        raise NotImplementedError(
            "gather_nd over a >int32-range dim: the int32 index cast "
            "would silently wrap; reshape so indexed dims fit int32")
    idx = tuple(indices.astype(jnp.int32)[i] for i in range(indices.shape[0]))
    return data[idx]


@register("scatter_nd", num_inputs=2, differentiable=True)
def scatter_nd(data, indices, shape=None):
    shape = tuple(shape)
    if any(_concrete_big(d) for d in shape[:indices.shape[0]]):
        raise NotImplementedError(
            "scatter_nd into a >int32-range dim: the int32 index cast "
            "would silently wrap (and scatters along >2^31 dims are "
            "corrupt on the TPU runtime); reshape so scattered dims "
            "fit int32")
    if _base.s64_demoting_backend() and any(
            _concrete_big(d) for d in shape[indices.shape[0]:]):
        # non-indexed dims past int32 range are just as fatal on the TPU
        # runtime: the scatter's row copies move data ALONG the big dim,
        # which lands at corrupt offsets (docs/PERF.md) — refuse rather
        # than write garbage (ADVICE r5); x64-native cpu falls through
        raise NotImplementedError(
            "scatter_nd with a >int32-range non-indexed dim: row copies "
            "along >2^31 dims are corrupt on the TPU runtime; reshape so "
            "every dim of shape fits int32")
    idx = tuple(indices.astype(jnp.int32)[i] for i in range(indices.shape[0]))
    out = jnp.zeros(shape, dtype=data.dtype)
    return out.at[idx].add(data)


@register("one_hot", differentiable=False)
def one_hot(indices, depth=0, on_value=1.0, off_value=0.0, dtype="float32"):
    eye = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=jnp.dtype(dtype))
    return eye * on_value + (1.0 - eye) * off_value


@register("cast", aliases=["Cast"])
def cast(data, dtype=None):
    return data.astype(jnp.dtype(dtype) if not isinstance(dtype, type) else dtype)


@register("_copy", aliases=["identity", "stop_gradient_copy"])
def _copy(data):
    return jnp.asarray(data)


@register("BlockGrad", aliases=["stop_gradient"], differentiable=False)
def block_grad(data):
    return jax.lax.stop_gradient(data)


@register("where", num_inputs=3)
def where(condition, x, y):
    return jnp.where(condition.astype(bool), x, y)


_BIG_SLICE_JIT: dict = {}
_BIG_TAKE_JIT: dict = {}


def _static_slice_index(data, key):
    """Lower static int/slice indexing to one literal-bound lax.slice,
    TRACED under jit.

    For >int32-range dims the default jnp lowering materializes the
    index as an s32/s64 tensor operand — s32 wraps past 2^31 and the
    TPU compiler demotes s64 — and eager execution converts even
    lax.slice to that dynamic form.  Only a slice traced under jit
    keeps its bounds as LITERALS, which compile fine at any offset.
    Returns None for key patterns this cannot express (arrays,
    ellipsis, newaxis, strides)."""
    keys = key if isinstance(key, tuple) else (key,)
    if len(keys) > data.ndim or any(
            isinstance(k, bool) or not isinstance(k, (int, onp.integer, slice))
            for k in keys):
        # bools are ints to isinstance but mean newaxis-like masking in
        # numpy (x[True] -> shape (1, ...)) — never an element index
        return None
    starts, stops, squeeze = [], [], []
    for ax, k in enumerate(keys):
        d = data.shape[ax]
        if isinstance(k, slice):
            s, e, st = k.indices(d)
            if st != 1 or e < s:
                return None
            starts.append(s)
            stops.append(e)
        else:
            i = int(k) + (d if int(k) < 0 else 0)
            starts.append(i)
            stops.append(i + 1)
            squeeze.append(ax)
    for ax in range(len(keys), data.ndim):
        starts.append(0)
        stops.append(data.shape[ax])
    ck = (data.shape, str(data.dtype), tuple(starts), tuple(stops),
          tuple(squeeze))
    fn = _BIG_SLICE_JIT.get(ck)
    if fn is None:

        def do_slice(d):
            out = jax.lax.slice(d, starts, stops)
            if squeeze:
                out = out.reshape([dd for ax2, dd in enumerate(out.shape)
                                   if ax2 not in squeeze])
            return out

        fn = bounded_cache_put(_BIG_SLICE_JIT, ck, jax.jit(do_slice))
    return fn(data)


@register("_index", differentiable=True)
def _index(data, key=None):
    if any(_concrete_big(d) for d in data.shape):
        out = _static_slice_index(data, key)
        if out is not None:
            return out
        if isinstance(key, list) and data.ndim == 1 and key and all(
                isinstance(k, (int, onp.integer)) and not isinstance(k, bool)
                for k in key):
            key = onp.asarray(key, onp.int64)     # list of ints == index array
        # runtime integer-array index on a >int32-range 1-D array: route
        # through take's exact int32 factorization — the default jnp
        # lowering would demote the indices to int32 and gather from
        # wrapped offsets with no error.  Getitem semantics wrap
        # negatives (unlike take's clip), so normalize on host first.
        if (data.ndim == 1 and getattr(key, "dtype", None) is not None
                and onp.dtype(key.dtype).kind in ("i", "u")
                and not isinstance(key, bool)):
            if isinstance(key, jax.core.Tracer):
                raise NotImplementedError(
                    "indexing a >int32-range dim with a traced index "
                    "array (jit/hybridize): the TPU compiler demotes "
                    "s64 index types; index eagerly or use a 2-D view "
                    "whose dims fit int32")
            kh = onp.asarray(key).astype(onp.int64)
            kh = onp.where(kh < 0, kh + data.shape[0], kh)
            return take(data, kh, axis=0, mode="clip")
        # anything else (multi-dim big arrays with array keys, stepped
        # slices, masks) would reach jnp's default lowering, whose int32
        # index demotion silently gathers from wrapped offsets on
        # s64-demoting backends — refuse loudly there; cpu executes s64
        # natively (invoke dispatches it under x64), so fall through
        if jax.default_backend() in S64_DEMOTING_PLATFORMS:
            raise NotImplementedError(
                "this index pattern on a >int32-range dim would be "
                "demoted to int32 by the TPU compiler and gather from "
                "wrapped offsets; use static int/contiguous-slice keys, "
                "a 1-D integer index array, or a 2-D view whose dims "
                "fit int32")
    return data[key]


@register("reverse", aliases=["flip"])
def reverse(data, axis=None):
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return jnp.flip(data, axis=axes)


@register("roll")
def roll(data, shift=None, axis=None):
    return jnp.roll(data, shift, axis)


@register("diag")
def diag(data, k=0):
    return jnp.diag(data, k) if data.ndim <= 2 else jnp.diagonal(data, k)


@register("depth_to_space")
def depth_to_space(data, block_size=1):
    n, c, h, w = data.shape
    b = block_size
    x = data.reshape(n, b, b, c // (b * b), h, w)
    x = x.transpose(0, 3, 4, 1, 5, 2)
    return x.reshape(n, c // (b * b), h * b, w * b)


@register("space_to_depth")
def space_to_depth(data, block_size=1):
    n, c, h, w = data.shape
    b = block_size
    x = data.reshape(n, c, h // b, b, w // b, b)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return x.reshape(n, c * b * b, h // b, w // b)


@register("sequence_mask", num_inputs=2, aliases=["SequenceMask"])
def sequence_mask(data, sequence_length=None, use_sequence_length=False, value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    # data: (seq, batch, ...) when axis=0, (batch, seq, ...) when axis=1
    seq_len = data.shape[axis]
    steps = jnp.arange(seq_len)
    if axis == 0:
        mask = steps[:, None] < sequence_length[None, :].astype(jnp.int32)
    else:
        mask = steps[None, :] < sequence_length[:, None].astype(jnp.int32)
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("sequence_last", num_inputs=2, aliases=["SequenceLast"])
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        idx = -1 if axis == 0 else -1
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    last = (sequence_length.astype(jnp.int32) - 1)
    if axis == 0:
        return jnp.take_along_axis(
            data, last.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
        ).squeeze(0)
    return jnp.take_along_axis(
        data, last.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1
    ).squeeze(1)


@register("sequence_reverse", num_inputs=2, aliases=["SequenceReverse"])
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=axis)
    seq_len = data.shape[0]
    steps = jnp.arange(seq_len)
    lens = sequence_length.astype(jnp.int32)
    rev_idx = jnp.where(
        steps[:, None] < lens[None, :], lens[None, :] - 1 - steps[:, None], steps[:, None]
    )
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0
    )


@register("shape_array", differentiable=False)
def shape_array(data):
    """int64 like the reference (tensor/elemwise_unary_op.h shape_array).
    Created under a local x64 scope: the global x32 default would silently
    truncate, and a >2**31-element array's size must not wrap."""
    with _base.enable_x64(True):
        return jnp.asarray(data.shape, dtype=jnp.int64)


@register("size_array", differentiable=False)
def size_array(data):
    """int64 like the reference (see shape_array)."""
    with _base.enable_x64(True):
        return jnp.asarray([int(onp.prod(data.shape))], dtype=jnp.int64)


@register("zeros_like")
def zeros_like(data):
    return jnp.zeros_like(data)


@register("ones_like")
def ones_like(data):
    return jnp.ones_like(data)


@register("add_n", num_inputs=-1, aliases=["ElementWiseSum"])
def add_n(arrays):
    out = arrays[0]
    for a in arrays[1:]:
        out = out + a
    return out


@register("dot", num_inputs=2)
def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    a = lhs.T if transpose_a and lhs.ndim == 2 else lhs
    b = rhs.T if transpose_b and rhs.ndim == 2 else rhs
    if a.ndim == 1 and b.ndim == 1:
        return jnp.dot(a, b)
    # MXNet dot: contract last axis of a with first axis of b
    return jnp.tensordot(a, b, axes=([a.ndim - 1], [0]))


@register("batch_dot", num_inputs=2)
def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False):
    a = jnp.swapaxes(lhs, -1, -2) if transpose_a else lhs
    b = jnp.swapaxes(rhs, -1, -2) if transpose_b else rhs
    return jnp.matmul(a, b)


@register("embedding", num_inputs=2, aliases=["Embedding"])
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None, sparse_grad=False):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register("topk", differentiable=False, num_outputs=-1)
def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    neg = data if not is_ascend else -data
    vals, idx = jax.lax.top_k(jnp.moveaxis(neg, axis, -1), k)
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis)
    if is_ascend:
        vals = -vals
    if ret_typ == "indices":
        return idx.astype(jnp.dtype(dtype))
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return (vals, idx.astype(jnp.dtype(dtype)))
    if ret_typ == "mask":
        mask = jnp.zeros(jnp.moveaxis(data, axis, -1).shape, dtype=data.dtype)
        idx_last = jnp.moveaxis(idx, axis, -1)
        mask = jnp.put_along_axis(mask, idx_last, 1.0, axis=-1, inplace=False)
        return jnp.moveaxis(mask, -1, axis)
    raise ValueError(f"unknown ret_typ {ret_typ}")


@register("sort", differentiable=False)
def sort(data, axis=-1, is_ascend=True):
    out = jnp.sort(data, axis=axis)
    return out if is_ascend else jnp.flip(out, axis=axis)


@register("argsort", differentiable=False)
def argsort(data, axis=-1, is_ascend=True, dtype="float32"):
    idx = jnp.argsort(data, axis=axis)
    if not is_ascend:
        idx = jnp.flip(idx, axis=axis)
    return idx.astype(jnp.dtype(dtype))


@register("unique", differentiable=False, num_outputs=-1)
def unique(data):
    return jnp.unique(data, size=None)
