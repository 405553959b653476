"""Neural-network operators.

Reference analog: ``src/operator/nn/`` (~31k LoC of CPU/cuDNN kernels).  On
TPU each op is a lax/jnp composition; XLA lowers convolutions and matmuls
onto the MXU and picks algorithms automatically (the reference needed the
cuDNN algo-registry ``src/operator/nn/cudnn/cudnn_algoreg-inl.h`` for that).

Layout note: MXNet defaults to NCHW.  These ops accept a ``layout`` attr and
pass it straight to XLA dimension numbers — on TPU, NHWC keeps the channel
dim minor and maps best onto the MXU, so the Gluon layers default to
computing in NHWC internally while presenting NCHW at the API boundary.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp

from .. import telemetry as _telemetry
from .random import keep_mask
from .registry import register
from .tensor import pick_index


# --- activations -----------------------------------------------------------

@register("relu")
def relu(data):
    return jax.nn.relu(data)


@register("sigmoid")
def sigmoid(data):
    return jax.nn.sigmoid(data)


@register("log_sigmoid")
def log_sigmoid(data):
    return jax.nn.log_sigmoid(data)


@register("softrelu")
def softrelu(data):
    return jax.nn.softplus(data)


@register("softsign")
def softsign(data):
    return jax.nn.soft_sign(data)


@register("mish")
def mish(data):
    return data * jnp.tanh(jax.nn.softplus(data))


@register("hard_sigmoid")
def hard_sigmoid(data, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register("Activation")
def activation(data, act_type="relu"):
    fns = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "log_sigmoid": jax.nn.log_sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
        "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    }
    return fns[act_type](data)


@register("LeakyReLU", num_inputs=-1)
def leaky_relu(arrays, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334):
    data = arrays[0]
    if act_type == "leaky":
        return jax.nn.leaky_relu(data, slope)
    if act_type == "prelu":
        gamma = arrays[1]
        # broadcast gamma over channel axis 1
        shape = [1] * data.ndim
        if gamma.ndim == 1 and data.ndim > 1:
            shape[1] = gamma.shape[0]
            gamma = gamma.reshape(shape)
        return jnp.where(data >= 0, data, gamma * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        return jax.nn.selu(data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, mid * data)
    raise ValueError(f"unknown act_type {act_type}")


@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None):
    x = data / temperature if temperature else data
    if length is not None:
        steps = jnp.arange(x.shape[axis])
        mask = steps < length[..., None].astype(jnp.int32)
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(data, axis=-1, temperature=None):
    x = -data / temperature if temperature else -data
    return jax.nn.softmax(x, axis=axis)


@register("smooth_l1")
def smooth_l1(data, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(
        jnp.abs(data) < 1.0 / s2,
        0.5 * s2 * jnp.square(data),
        jnp.abs(data) - 0.5 / s2,
    )


# --- dense / conv ----------------------------------------------------------

@register("FullyConnected", num_inputs=-1, aliases=["fully_connected"])
def fully_connected(arrays, num_hidden=0, no_bias=False, flatten=True,
                    fused_relu=False):
    """data (N, ...), weight (num_hidden, in_units) — reference
    src/operator/nn/fully_connected.cc.  ``fused_relu`` is set by the
    int8 graph pass when a following relu folded into this node."""
    data, weight = arrays[0], arrays[1]
    x = data.reshape(data.shape[0], -1) if flatten else data
    sides = _wgrad_sides(weight.shape)
    for written in sides:
        (_WGRAD_MATERIALISED if written else _WGRAD_FUSED).inc()
    out = dense_product(x, weight, *sides)
    if not no_bias:
        out = out + arrays[2]
    return jnp.maximum(out, 0) if fused_relu else out


# --- the dense product's weight gradient -----------------------------------
#
# In ``dW = dy^T x``, contracted over every token, XLA fuses an operand's
# elementwise producer (a norm with its residual, SwiGLU's product, the
# gradient of the norm behind the layer, the softmax gradient) into the
# product, and each output tile then streams that operand over all tokens
# again: the producer is re-run from its inputs, often twice the operand's
# bytes, once for every tile along the OTHER operand's width.  Written once
# behind an optimization barrier (XLA cannot fuse through one), an operand
# costs a write and a read.  On the v5e (``tools/dense_wgrad_check.py``: one
# layer's training step with its producers at every cell's shapes; PERF.md,
# section 6) writing both operands made the step faster at all five
# products that span 8 output tiles or more on each side, at three of the
# five that span 7 and at three of the fourteen narrower ones; the rule
# writes both where both widths reach 8 tiles.  The forward product, the
# data gradient and the bias gradient stay as XLA fuses them.

WGRAD_TILE = 384            # the output tile's columns in these fusions
WGRAD_MIN_TILES = 8         # on each side of a product whose operands are
                            # written


def _wgrad_sides(weight_shape):
    """(write the activation, write the output's gradient) for the weight
    gradient of a product with a weight of this shape."""
    wide = all(-(-int(w) // WGRAD_TILE) >= WGRAD_MIN_TILES
               for w in weight_shape)
    return wide, wide


_WGRAD_MATERIALISED = _telemetry.counter(
    "dense.wgrad_materialised",
    "dense weight-gradient operands written once behind a barrier (a site's "
    "activation and its output's gradient count apart, once a trace)")
_WGRAD_FUSED = _telemetry.counter(
    "dense.wgrad_fused",
    "dense weight-gradient operands left for XLA to fuse with their "
    "producers (a site's activation and its output's gradient apart, once a "
    "trace)")


def _product(x, weight):
    return jnp.matmul(x, weight.T)


def _transposed(fn, primal, cotangent):
    return jax.linear_transpose(fn, primal)(cotangent)[0]


def _dense_product_fwd(x, weight, barrier_x, barrier_dy):
    return _product(x, weight), (x, weight)


def _dense_product_bwd(barrier_x, barrier_dy, res, dy):
    x, weight = res
    dx = _transposed(lambda x: _product(x, weight), x, dy)
    if barrier_x:
        x = jax.lax.optimization_barrier(x)
    if barrier_dy:
        dy = jax.lax.optimization_barrier(dy)
    return dx, _transposed(lambda w: _product(x, w), weight, dy)


_barriered_product = jax.custom_vjp(
    lambda x, weight, barrier_x, barrier_dy: _product(x, weight),
    nondiff_argnums=(2, 3))
_barriered_product.defvjp(_dense_product_fwd, _dense_product_bwd)


def dense_product(x, weight, barrier_x=False, barrier_dy=False):
    """``x @ weight.T``.  Its weight gradient reads ``x`` (``barrier_x``)
    and the output's gradient (``barrier_dy``) as written arrays where
    asked: the same products in the same precision (bit for bit on the CPU;
    on the TPU another tiling may add the float32 partial sums in another
    order)."""
    if not (barrier_x or barrier_dy):
        return _product(x, weight)
    return _barriered_product(x, weight, barrier_x, barrier_dy)


def _conv_dimension_numbers(layout: str):
    # layouts: NCW/NWC, NCHW/NHWC, NCDHW/NDHWC; weight is O + (spatial|I) per layout
    spatial = layout.replace("N", "").replace("C", "")
    if layout.index("C") == 1:
        w = "OI" + spatial
    else:
        w = "O" + spatial + "I"
    return (layout, w, layout)


# --- MXU-alignment padding pass (round 9, ROADMAP item 2) -------------------
#
# Staged convolutions whose channel axes miss the TPU tile quanta (the
# cin=3 stem, odd-channel heads) underfill the MXU contraction.  The pass
# zero-pads Cin on BOTH operands (each padded tap contributes exactly
# 0.0 — IEEE x + 0.0 == x, so the kept lanes are bit-exact) and pads
# Cout with slice-back (output channels are independent dots, so the
# kept channels are computed identically).  It runs ONLY at trace time
# (Tracer-gated), so the pad/slice are part of the compiled program
# keyed by the UNPADDED input shapes —
# 0 added retraces and 0 added dispatches per step by construction; XLA
# folds the pads into the surrounding layout work.  This generalizes the
# stem_s2d idea (re-shaping conv0 onto the MXU) to every misaligned
# conv.  Quanta: the sublane quantum of the operand dtype — 8 for
# fp32/bf16, 32 for int8 (the int8 path applies it in
# contrib/quantization.py quantized_conv).  Bit-exactness is asserted by
# tests/test_pad_channels.py.

_PAD_CHANNELS = _telemetry.counter(
    "nn.pad_channels", "convolutions the MXU-alignment pass padded "
    "(trace-time: one per padded conv node per trace)")


def pad_channels_count() -> int:
    """Convolutions the MXU-alignment pass padded (trace-time count:
    one per padded conv node per trace).  View over the
    ``nn.pad_channels`` telemetry counter."""
    return int(_PAD_CHANNELS.value)


def _pad_up(v: int, q: int) -> int:
    return -(-v // q) * q


def maybe_pad_conv_channels(data, weight, layout: str, num_group: int):
    """Apply the MXU-alignment padding pass when eligible: returns
    ``(padded_data, padded_weight, true_cout)`` or ``None`` (aligned
    already, knob off, eager call, or grouped conv)."""
    from .. import config as _config

    mode = _config.get("MXNET_PAD_CHANNELS")
    if not mode or num_group != 1:
        return None
    if mode != 2 and jax.default_backend() != "tpu":
        return None
    if not isinstance(data, jax.core.Tracer):
        return None                      # staging-layer pass: eager
    c_axis = layout.index("C")           # dispatch never pays the pads
    cin = int(data.shape[c_axis])
    cout = int(weight.shape[0])
    q = 32 if jnp.dtype(data.dtype).itemsize == 1 else 8
    cin_p, cout_p = _pad_up(cin, q), _pad_up(cout, q)
    if cin_p == cin and cout_p == cout:
        return None
    w_in_axis = 1 if c_axis == 1 else weight.ndim - 1
    dpad = [(0, 0)] * data.ndim
    dpad[c_axis] = (0, cin_p - cin)
    wpad = [(0, 0)] * weight.ndim
    wpad[0] = (0, cout_p - cout)
    wpad[w_in_axis] = (0, cin_p - cin)
    _PAD_CHANNELS.inc()
    return (jnp.pad(data, dpad) if cin_p != cin else data,
            jnp.pad(weight, wpad), cout)


def _tup(v, n):
    if v is None:
        return (0,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register("Convolution", num_inputs=-1, aliases=["conv"])
def convolution(arrays, kernel=None, stride=None, dilate=None, pad=None,
                num_filter=0, num_group=1, no_bias=False, layout=None,
                workspace=None, cudnn_tune=None, cudnn_off=None,
                fused_relu=False):
    """N-D convolution (reference src/operator/nn/convolution.cc).

    XLA handles algorithm selection/tiling; ``workspace``/``cudnn_*`` attrs
    are accepted for API parity and ignored.
    """
    data, weight = arrays[0], arrays[1]
    nsp = len(kernel)
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nsp]
    stride = _tup(stride, nsp) if stride else (1,) * nsp
    dilate = _tup(dilate, nsp) if dilate else (1,) * nsp
    pad = _tup(pad, nsp)
    c_axis = layout.index("C")
    true_cout = None
    padded = maybe_pad_conv_channels(data, weight, layout, num_group)
    if padded is not None:
        data, weight, true_cout = padded
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, _conv_dimension_numbers(layout)
    )
    out = jax.lax.conv_general_dilated(
        data,
        weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if true_cout is not None and out.shape[c_axis] != true_cout:
        out = jax.lax.slice_in_dim(out, 0, true_cout, axis=c_axis)
    if not no_bias:
        bias = arrays[2]
        shape = [1] * out.ndim
        shape[c_axis] = bias.shape[0]
        out = out + bias.reshape(shape)
    return jnp.maximum(out, 0) if fused_relu else out


@register("Deconvolution", num_inputs=-1)
def deconvolution(arrays, kernel=None, stride=None, dilate=None, pad=None,
                  adj=None, target_shape=None, num_filter=0, num_group=1,
                  no_bias=True, layout=None, workspace=None, cudnn_tune=None,
                  cudnn_off=None):
    """Transposed convolution (reference src/operator/nn/deconvolution.cc)."""
    data, weight = arrays[0], arrays[1]
    nsp = len(kernel)
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nsp]
    stride = _tup(stride, nsp) if stride else (1,) * nsp
    dilate = _tup(dilate, nsp) if dilate else (1,) * nsp
    pad = _tup(pad, nsp)
    adj = _tup(adj, nsp) if adj else (0,) * nsp
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, _conv_dimension_numbers(layout)
    )
    # gradient-of-conv == transposed conv: lhs_dilation by stride
    k_eff = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    padding = [
        (ke - 1 - p, ke - 1 - p + a) for ke, p, a in zip(k_eff, pad, adj)
    ]
    # weight layout for deconv in MXNet is (in_c, out_c/g, *kernel): flip to OIHW
    c_axis = layout.index("C")
    if c_axis == 1:
        w = jnp.swapaxes(weight, 0, 1)
        w = jnp.flip(w, axis=tuple(range(2, 2 + nsp)))
    else:
        # channel-last: weight (in_c, *kernel, out_c) -> 'O'+spatial+'I'
        w = jnp.swapaxes(weight, 0, -1)
        w = jnp.flip(w, axis=tuple(range(1, 1 + nsp)))
    out = jax.lax.conv_general_dilated(
        data,
        w,
        window_strides=(1,) * nsp,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias:
        bias = arrays[2]
        shape = [1] * out.ndim
        shape[c_axis] = bias.shape[0]
        out = out + bias.reshape(shape)
    return out


# --- pooling ---------------------------------------------------------------

@register("Pooling")
def pooling(data, kernel=None, pool_type="max", global_pool=False,
            stride=None, pad=None, pooling_convention="valid",
            count_include_pad=True, layout=None, cudnn_off=None, p_value=2):
    """Reference src/operator/nn/pooling.cc."""
    nsp = data.ndim - 2
    if layout is None:
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[nsp]
    sp_axes = tuple(i for i, c in enumerate(layout) if c not in "NC")
    if global_pool:
        if pool_type == "max":
            return jnp.max(data, axis=sp_axes, keepdims=True)
        return jnp.mean(data, axis=sp_axes, keepdims=True)
    kernel = _tup(kernel, nsp)
    stride = _tup(stride, nsp) if stride else (1,) * nsp
    pad = _tup(pad, nsp)

    window = [1] * data.ndim
    strides = [1] * data.ndim
    padding = [(0, 0)] * data.ndim
    for ax, k, s, p in zip(sp_axes, kernel, stride, pad):
        window[ax] = k
        strides[ax] = s
        padding[ax] = (p, p)

    if pooling_convention == "full":
        # ceil-mode: extend right padding so last window fits
        for i, ax in enumerate(sp_axes):
            size = data.shape[ax] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            if rem != 0:
                extra = stride[i] - rem
                padding[ax] = (pad[i], pad[i] + extra)

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return jax.lax.reduce_window(
            data, init, jax.lax.max, window, strides, padding
        )
    if pool_type in ("avg", "sum"):
        summed = jax.lax.reduce_window(
            data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
            jax.lax.add, window, strides, padding
        )
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = float(onp.prod(kernel))
            return summed / jnp.asarray(denom, data.dtype)
        ones = jnp.ones_like(data)
        counts = jax.lax.reduce_window(
            ones, 0.0, jax.lax.add, window, strides, padding
        )
        return summed / counts
    if pool_type == "lp":
        powed = jax.lax.reduce_window(
            jnp.abs(data) ** p_value, 0.0, jax.lax.add, window, strides, padding
        )
        return powed ** (1.0 / p_value)
    raise ValueError(f"unknown pool_type {pool_type}")


# --- normalization ---------------------------------------------------------

def at_least_float32(x):
    """``x`` as batch norm computes on it: a bf16/fp16 operand (AMP hands the
    convolution's output over as it is, amp/lists.py) widened to float32,
    anything else as it is.  The output is rounded once, back to ``x``'s
    type."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


@register("BatchNorm", num_inputs=-1, num_outputs=-1)
def batch_norm(arrays, eps=1e-3, momentum=0.9, fix_gamma=True,
               use_global_stats=False, output_mean_var=False, axis=1,
               cudnn_off=None, training=False):
    """Reference src/operator/nn/batch_norm.cc.

    Returns out (+ batch mean/var when training so the layer can update
    running stats functionally — the reference mutated aux states in-place).
    """
    data, gamma, beta, moving_mean, moving_var = arrays
    red_axes = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if training and not use_global_stats:
        # Single-pass batch stats: E[x] and E[x^2] reduce the SAME operand,
        # which XLA fuses into one multi-output reduction (one HBM read of
        # the activation instead of the 2-3 passes mean-then-var costs).
        # Accumulate fp32 even for bf16 activations — the convert fuses
        # into the reduction, and the reduction still READS bf16 from HBM
        # (half the bandwidth of an fp32 materialization).
        #
        # Precision note: E[x^2]-E[x]^2 cancels when |mean| >> std (fp32
        # error ~ mean^2 * 2^-24 absolute).  This is the standard TPU BN
        # formulation (flax.linen.BatchNorm computes exactly this) and is
        # safe for normalized activations; pathological activation scales
        # can set MXNET_BN_TWO_PASS_VAR=1 to restore the two-pass
        # shifted variance at one extra HBM pass.
        from .. import config as _config
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red_axes)
        if _config.get("MXNET_BN_TWO_PASS_VAR"):
            var = jnp.var(x32, axis=red_axes)
        else:
            meansq = jnp.mean(x32 * x32, axis=red_axes)
            var = jnp.maximum(meansq - mean * mean, 0.0)
    else:
        mean, var = moving_mean, moving_var
    # Fold the affine into per-channel scale/bias vectors (C-sized, fp32):
    # the big tensor then sees ONE multiply-add, in float32 for a bf16/fp16
    # operand, rounded once to the operand's type: it is read and written
    # at its own width, only the arithmetic in between is wide.
    f32 = jnp.float32
    inv = jax.lax.rsqrt(var.astype(f32) + f32(eps))
    sc = inv * g.astype(f32)
    bi = beta.astype(f32) - mean.astype(f32) * sc
    x = at_least_float32(data)
    out = (x * sc.reshape(shape).astype(x.dtype)
           + bi.reshape(shape).astype(x.dtype)).astype(data.dtype)
    if training and not use_global_stats:
        return (out, mean.astype(moving_mean.dtype),
                var.astype(moving_var.dtype))
    return (out,)


@register("LayerNorm")
def layer_norm_op(data, gamma=None, beta=None, axis=-1, eps=1e-5):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


# register with 3 inputs
from .registry import get_op as _get_op  # noqa: E402

_get_op("LayerNorm").num_inputs = 3


@register("GroupNorm", num_inputs=-1)
def group_norm(arrays, num_groups=1, eps=1e-5):
    data, gamma, beta = arrays
    n, c = data.shape[0], data.shape[1]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = [1] * data.ndim
    shape[1] = c
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("InstanceNorm", num_inputs=-1)
def instance_norm(arrays, eps=1e-3):
    data, gamma, beta = arrays
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    x = (data - mean) * jax.lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[1] = data.shape[1]
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """Local response norm across channels (axis 1)."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    windows = sum(
        jax.lax.dynamic_slice_in_dim(padded, i, data.shape[1], axis=1)
        for i in range(nsize)
    )
    return data / jnp.power(knorm + alpha / nsize * windows, beta)


# --- dropout ---------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dropout(data, key, keep_prob, mask_shape):
    keep = keep_mask(key, mask_shape, keep_prob)
    return jnp.where(keep, data, 0) * (1.0 / keep_prob)


# Dropout is linear in ``data``: its transpose is itself under the same mask,
# which the backward regenerates from the key.  Nothing of the operand's
# shape is kept between the passes.
_dropout.defvjp(
    lambda data, key, keep_prob, mask_shape:
        (_dropout(data, key, keep_prob, mask_shape), key),
    lambda keep_prob, mask_shape, key, g:
        (_dropout(g, key, keep_prob, mask_shape), None))


@register("Dropout", num_inputs=2, rng_input=True)
def dropout(data, key, p=0.5, mode="training", axes=None, training=False,
            cudnn_off=None):
    """Reference src/operator/nn/dropout.cc.  ``key`` is a uint32 PRNG key
    array threaded explicitly so the op stays pure/traceable.

    The mask is ``ops.random.keep_mask``: a pure function of ``key`` and
    each element's index (with ``axes``, of the index in the broadcast
    shape), the same on every platform, eager or compiled, and under any
    mesh.  It is NOT the stream of ``jax.random.bernoulli(key, ...)``."""
    if not training and mode != "always":
        return data
    if p <= 0.0:
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(shape))
    return _dropout(data, key, 1.0 - p, shape)


# --- losses-as-ops ---------------------------------------------------------

@register("softmax_cross_entropy", num_inputs=2)
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    onehot = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(onehot * logp)


_SPARSE_CE_FUSED = _telemetry.counter(
    "loss.sparse_ce.fused",
    "sparse-label cross-entropy sites traced as one operator that picks "
    "the label's logit before it normalises")


def _sparse_ce_fwd(data, label, axis):
    x = data.astype(jnp.promote_types(data.dtype, jnp.float32))
    m = jnp.max(x, axis=axis, keepdims=True)
    s = jnp.sum(jnp.exp(x - m), axis=axis, keepdims=True)
    # gathered from ``data`` as it lies: the gather's operand is the array
    # the producer wrote, not a float32 copy of it
    picked = jnp.take_along_axis(data, label, axis=axis).astype(x.dtype)
    loss = -((picked - m) - jnp.log(s))
    return jnp.squeeze(loss, axis=axis), (data, m, 1.0 / s, label)


def _sparse_ce_bwd(axis, res, g):
    data, m, inv_s, label = res
    g = jnp.expand_dims(g, axis).astype(m.dtype)
    hit = jax.lax.broadcasted_iota(jnp.int32, data.shape, axis) == label
    # (softmax - onehot) * g with the row's factors multiplied first: one
    # subtract, exp, multiply, compare, select and subtract an element
    grad = jnp.exp(data.astype(m.dtype) - m) * (inv_s * g) \
        - jnp.where(hit, g, 0)
    return grad.astype(data.dtype), None


# The VJP is written by hand: the transpose of the gather is a scatter-add
# into zeros of the logits' shape, and ``iota == label`` is the same one-hot
# as an elementwise producer that a consumer fuses.
_sparse_ce = jax.custom_vjp(
    lambda data, label, axis: _sparse_ce_fwd(data, label, axis)[0],
    nondiff_argnums=(2,))
_sparse_ce.defvjp(_sparse_ce_fwd, _sparse_ce_bwd)


@register("sparse_softmax_cross_entropy", num_inputs=2)
def sparse_softmax_cross_entropy(data, label, axis=-1, mode="clip"):
    """``-pick(log_softmax(data, axis), label, axis)`` without the
    log-softmax: the label's logit is picked first and that one number
    normalised, so nothing of ``data``'s shape is written in the forward
    pass, and the backward is ``(softmax - onehot) * cotangent`` as one
    elementwise expression in ``data``'s dtype.

    Statistics (maximum, sum of exponentials) and the loss are float32
    whatever ``data``'s dtype (float64 stays float64): the value is that
    of ``log_softmax`` on a float32 copy, bit for bit.  ``label`` holds
    class indices of any dtype, with ``data``'s shape less ``axis``;
    out-of-range indices clip (``mode="clip"``) or wrap (``"wrap"``) as
    ``pick``'s do."""
    _SPARSE_CE_FUSED.inc()
    axis = axis % data.ndim
    label = jnp.expand_dims(
        pick_index(label, data.shape[axis], mode), axis)
    return _sparse_ce(data, label, axis)


def _unstack_bwd(axis, _, cotangents):
    return (jnp.stack(cotangents, axis=axis),)


# ``x[:, d]`` for every ``d``, with the backward written as ONE stack of the
# cotangents: jax's own transposes each slice into a pad and adds the pads,
# which XLA materialises (three arrays of the logits' size for two depths);
# slices of a stack, in either direction, it folds away.
_unstack = jax.custom_vjp(
    lambda data, axis: tuple(
        jax.lax.index_in_dim(data, d, axis, keepdims=False)
        for d in range(data.shape[axis])),
    nondiff_argnums=(1,))
_unstack.defvjp(lambda data, axis: (_unstack(data, axis), None), _unstack_bwd)

# the scope of a depth's loss in the device trace (perfbench/scope_view):
# depth 0 is the next token's, the others a multi-token-prediction module's
MULTI_TOKEN_LOSS_SCOPES = ("NextTokenLoss", "MultiTokenLoss")


@register("multi_token_cross_entropy", num_inputs=2)
def multi_token_cross_entropy(data, label, depth_weights=(1.0,)):
    """The training loss of a model with multi-token-prediction modules
    (DeepSeek-V3, arXiv:2412.19437 section 2.2).  ``data`` (batch, depths,
    seq, classes): depth ``d``'s logits at position ``i`` predict token
    ``i + 1 + d``; ``label`` (batch, seq) holds the NEXT token at every
    position, so depth ``d``'s target at ``i`` is ``label[i + d]`` and its
    last ``d`` positions have none and are left out.  Returns (batch,):
    ``sum_d depth_weights[d] * mean over the positions with a target`` of
    the cross-entropy, each depth one ``sparse_softmax_cross_entropy``."""
    depths, seq = data.shape[1], data.shape[2]
    if len(depth_weights) != depths:
        raise ValueError(f"{depths} depths of logits, weights "
                         f"{tuple(depth_weights)}")
    label = pick_index(label, data.shape[-1], "clip")
    total = 0.0
    for d, (logits, weight) in enumerate(zip(_unstack(data, 1),
                                             depth_weights)):
        with jax.named_scope(MULTI_TOKEN_LOSS_SCOPES[min(d, 1)]):
            _SPARSE_CE_FUSED.inc()
            target = jnp.roll(label, -d, axis=1)       # the tail is masked
            loss = _sparse_ce(logits, target[..., None], logits.ndim - 1)
            has_target = jnp.arange(seq) < seq - d
            total = total + float(weight) * jnp.sum(
                jnp.where(has_target, loss, 0.0), axis=1) / (seq - d)
    return total


@register("SoftmaxOutput", num_inputs=2, aliases=["Softmax"])
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    return jax.nn.softmax(data, axis=-1)


@register("MakeLoss", aliases=["make_loss"])
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return data


@register("CTCLoss", num_inputs=-1, aliases=["ctc_loss"])
def ctc_loss(arrays, use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """CTC loss via dynamic-programming in log space (reference
    src/operator/nn/ctc_loss.cc backed by warpctc; here a lax.scan DP)."""
    data = arrays[0]  # (seq, batch, alphabet)
    label = arrays[1]  # (batch, label_len)
    seq_len, batch, alphabet = data.shape
    blank = 0 if blank_label == "first" else alphabet - 1
    logp = jax.nn.log_softmax(data, axis=-1)

    lab = label.astype(jnp.int32)
    if blank_label == "first":
        lab = lab  # labels given 1-based? MXNet: labels are 0-based actual classes
    L = lab.shape[1]
    # extended label sequence with blanks: length 2L+1
    ext = jnp.full((batch, 2 * L + 1), blank, dtype=jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    neg_inf = -1e30

    def init_alpha():
        a = jnp.full((batch, 2 * L + 1), neg_inf)
        a = a.at[:, 0].set(logp[0, :, blank])
        a = a.at[:, 1].set(jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0])
        return a

    def step(alpha, lp):
        # lp: (batch, alphabet)
        emit = jnp.take_along_axis(lp, ext, axis=1)  # (batch, 2L+1)
        shift1 = jnp.concatenate([jnp.full((batch, 1), neg_inf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate([jnp.full((batch, 2), neg_inf), alpha[:, :-2]], axis=1)
        same = ext == jnp.concatenate([jnp.full((batch, 2), blank), ext[:, :-2]], axis=1)
        cand = jnp.logaddexp(alpha, shift1)
        cand = jnp.where(same, cand, jnp.logaddexp(cand, shift2))
        new = cand + emit
        return new, None

    alpha0 = init_alpha()
    alpha, _ = jax.lax.scan(step, alpha0, logp[1:])
    ll = jnp.logaddexp(alpha[:, -1], alpha[:, -2])
    return -ll


# --- upsampling / misc -----------------------------------------------------

@register("UpSampling", num_inputs=-1)
def upsampling(arrays, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=None):
    data = arrays[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        return out
    # bilinear
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


@register("moments", num_outputs=-1)
def moments(data, axes=None, keepdims=False):
    mean = jnp.mean(data, axis=tuple(axes) if axes else None, keepdims=keepdims)
    var = jnp.var(data, axis=tuple(axes) if axes else None, keepdims=keepdims)
    return (mean, var)
