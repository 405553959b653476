"""Additional nn operators: cross-device BatchNorm, fused BN+ReLU,
ROIPooling, and the im2col/col2im pair.

Reference:
- SyncBatchNorm: ``src/operator/contrib/sync_batch_norm-inl.h`` (cross-GPU
  mean/var via an engine-coordinated reduce).  TPU-native: when executed
  inside a ``shard_map``/``pmap`` with a bound mesh axis the statistics ride
  ``lax.pmean`` over ICI; eagerly (one chip holding the full batch) plain
  batch statistics are already "synchronized".
- BatchNormWithReLU: ``src/operator/contrib/batch_norm_relu.cc`` (fused
  BN+ReLU saving one memory pass; on TPU XLA fuses the relu anyway — the op
  exists for graph parity).
- ROIPooling: ``src/operator/roi_pooling.cc`` (max-pool over quantized ROI
  grid; predecessor of ROIAlign).
- im2col/col2im: ``src/operator/nn/im2col.cc`` — patch-matrix extraction so
  user code can express convolution as GEMM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .nn import at_least_float32
from .registry import register


def _bn_stats(x, axis_name=None):
    """Per-channel mean/var over (N, spatial), optionally pmean'd over a
    mesh axis (the SyncBatchNorm cross-device reduce)."""
    x = at_least_float32(x)
    red = (0,) + tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red)
    mean_sq = jnp.mean(jnp.square(x), axis=red)
    if axis_name:
        mean = lax.pmean(mean, axis_name)
        mean_sq = lax.pmean(mean_sq, axis_name)
    var = mean_sq - jnp.square(mean)
    return mean, var


def _bn_apply(x, gamma, beta, mean, var, eps, fix_gamma):
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    inv = lax.rsqrt(var + eps).reshape(shape)
    out = (at_least_float32(x) - mean.reshape(shape)) * inv \
        * gamma.reshape(shape) + beta.reshape(shape)
    return out.astype(x.dtype)


@register("SyncBatchNorm", num_inputs=5, num_outputs=1,
          aliases=("_contrib_SyncBatchNorm",))
def sync_batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                    momentum=0.9, fix_gamma=True, use_global_stats=False,
                    output_mean_var=False, ndev=1, key="", axis_name=None):
    """Cross-device BatchNorm.  ``axis_name`` names the mesh axis to
    synchronize statistics over when the op runs inside shard_map/pmap;
    ``ndev``/``key`` are accepted for reference-signature parity (the
    engine-side device group bookkeeping has no TPU analog — the mesh axis
    is the device group)."""
    if use_global_stats:
        return _bn_apply(data, gamma, beta, moving_mean, moving_var, eps,
                         fix_gamma)
    mean, var = _bn_stats(data, axis_name)
    return _bn_apply(data, gamma, beta, mean, var, eps, fix_gamma)


@register("BatchNormWithReLU", num_inputs=5, num_outputs=1,
          aliases=("_contrib_BatchNormWithReLU",))
def batch_norm_with_relu(data, gamma, beta, moving_mean, moving_var,
                         eps=1e-3, momentum=0.9, fix_gamma=True,
                         use_global_stats=False, axis=1):
    """Fused BatchNorm+ReLU (XLA fuses the two pointwise passes into the
    normalization anyway; registered for graph parity)."""
    if use_global_stats:
        out = _bn_apply(data, gamma, beta, moving_mean, moving_var, eps,
                        fix_gamma)
    else:
        mean, var = _bn_stats(data)
        out = _bn_apply(data, gamma, beta, mean, var, eps, fix_gamma)
    return jax.nn.relu(out)


@register("ROIPooling", num_inputs=2)
def roi_pooling(data, rois, pooled_size=(7, 7), spatial_scale=1.0):
    """Max pooling over a quantized ROI grid (reference
    src/operator/roi_pooling.cc).  rois: (R, 5) of [batch_idx, x1, y1,
    x2, y2] in image coordinates."""
    ph, pw = pooled_size
    n, c, h, w = data.shape

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale)
        y1 = jnp.round(roi[2] * spatial_scale)
        x2 = jnp.round(roi[3] * spatial_scale)
        y2 = jnp.round(roi[4] * spatial_scale)
        roi_h = jnp.maximum(y2 - y1 + 1, 1.0)
        roi_w = jnp.maximum(x2 - x1 + 1, 1.0)
        bin_h = roi_h / ph
        bin_w = roi_w / pw
        img = data[batch_idx]  # (c, h, w)
        # dense grid evaluation: for each output bin take the max over the
        # pixels whose coordinates fall inside the (quantized) bin — static
        # shapes, so XLA can tile it (no per-bin dynamic slices)
        ys = jnp.arange(h, dtype=jnp.float32)
        xs = jnp.arange(w, dtype=jnp.float32)
        ybin = jnp.floor((ys - y1) / bin_h)      # (h,)
        xbin = jnp.floor((xs - x1) / bin_w)      # (w,)
        yin = (ys >= y1) & (ys <= y2)
        xin = (xs >= x1) & (xs <= x2)
        y_onehot = (ybin[None, :] == jnp.arange(ph)[:, None]) & yin[None, :]
        x_onehot = (xbin[None, :] == jnp.arange(pw)[:, None]) & xin[None, :]
        # mask (ph, h) x (pw, w) -> (ph, pw, h, w) applied to img
        mask = y_onehot[:, None, :, None] & x_onehot[None, :, None, :]
        vals = jnp.where(mask[None], img[:, None, None, :, :], -jnp.inf)
        out = vals.max(axis=(-1, -2))
        # empty bins (roi smaller than grid) -> 0, matching the reference
        return jnp.where(jnp.isfinite(out), out, 0.0)

    return jax.vmap(one_roi)(rois)


@register("im2col", num_inputs=1)
def im2col(data, kernel=(3, 3), stride=(1, 1), dilate=(1, 1), pad=(0, 0)):
    """Extract sliding patches into a column matrix (reference
    src/operator/nn/im2col.cc): (N, C, H, W) -> (N, C*kh*kw, L)."""
    kh, kw = kernel
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    n, c, h, w = data.shape
    x = jnp.pad(data, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    out_h = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    out_w = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            y0, x0 = i * dh, j * dw
            sl = x[:, :, y0:y0 + sh * out_h:sh, x0:x0 + sw * out_w:sw]
            patches.append(sl.reshape(n, c, out_h * out_w))
    # (N, C, kh*kw, L) -> (N, C*kh*kw, L) with kernel fastest-varying per
    # channel, the reference layout
    col = jnp.stack(patches, axis=2)
    return col.reshape(n, c * kh * kw, out_h * out_w)


@register("col2im", num_inputs=1)
def col2im(col, output_size=(8, 8), kernel=(3, 3), stride=(1, 1),
           dilate=(1, 1), pad=(0, 0)):
    """Scatter-add columns back to the image (adjoint of im2col; reference
    src/operator/nn/im2col.cc col2im)."""
    kh, kw = kernel
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    h, w = output_size
    n = col.shape[0]
    c = col.shape[1] // (kh * kw)
    out_h = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    out_w = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    x = jnp.zeros((n, c, h + 2 * ph, w + 2 * pw), col.dtype)
    patches = col.reshape(n, c, kh * kw, out_h, out_w)
    k = 0
    for i in range(kh):
        for j in range(kw):
            y0, x0 = i * dh, j * dw
            upd = patches[:, :, k]
            x = x.at[:, :, y0:y0 + sh * out_h:sh,
                     x0:x0 + sw * out_w:sw].add(upd)
            k += 1
    return x[:, :, ph:ph + h, pw:pw + w]
