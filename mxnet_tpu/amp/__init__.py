"""``mx.amp`` — automatic mixed precision.

Reference analog: ``python/mxnet/contrib/amp/amp.py:281-454`` (op-list
driven fp16 casting with dynamic loss scaling).  TPU-native defaults to
**bfloat16**: the MXU computes bf16 matmuls natively and bf16 shares
fp32's exponent range, so loss scaling is unnecessary (still provided for
fp16 parity).  ``init()`` installs a per-op cast policy at the operator
dispatch layer — the imperative analog of the reference's symbolic
``amp_cast`` insertion pass (src/nnvm/low_precision_pass.cc); under
hybridize the casts trace into the XLA graph and fuse away.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as onp

from .. import telemetry as _telemetry
from ..base import MXNetError
from . import lists
from .loss_scaler import LossScaler

__all__ = ["init", "uninit", "init_trainer", "scale_loss", "unscale",
           "convert_hybrid_block", "LossScaler", "lists", "target_dtype"]

_LOW = frozenset(lists.LOW_PRECISION_FUNCS)
_F32 = frozenset(lists.FP32_FUNCS)
_WIDEST = frozenset(lists.WIDEST_TYPE_CASTS)
_BATCH_NORMS = frozenset(("BatchNorm", "SyncBatchNorm", "BatchNormWithReLU"))

_BN_LOW = _telemetry.counter(
    "amp.batch_norm.low_precision",
    "batch-norm calls traced under AMP on a bf16/fp16 operand, which they "
    "return in that type (float32 statistics and arithmetic inside)")


class _AmpState:
    """Process-wide AMP state (the dispatch hook is global, so the policy
    must be too — training loops often run on worker threads)."""

    def __init__(self):
        self.target_dtype = None
        self.loss_scaler: Optional[LossScaler] = None


_STATE = _AmpState()


def _policy(op_name, arrays):
    """Cast op inputs per the op lists (invoked from ndarray dispatch)."""
    target = _STATE.target_dtype
    if target is None:
        return arrays
    if op_name in _LOW:
        return [a.astype(target)
                if hasattr(a, "dtype") and a.dtype == jnp.float32 else a
                for a in arrays]
    if op_name in _F32:
        return [a.astype(jnp.float32)
                if hasattr(a, "dtype") and a.dtype == target else a
                for a in arrays]
    if op_name in _BATCH_NORMS:
        if arrays[0].dtype in (jnp.bfloat16, jnp.float16):
            _BN_LOW.inc()
        return arrays
    if op_name in _WIDEST:
        dtypes = {a.dtype for a in arrays if hasattr(a, "dtype")}
        if jnp.float32 in dtypes and target in dtypes:
            return [a.astype(jnp.float32)
                    if hasattr(a, "dtype") and a.dtype == target else a
                    for a in arrays]
    return arrays


def init(target_dtype="bfloat16"):
    """Enable AMP globally (reference amp.init).  bfloat16 (default) or
    float16.  Bumps the AMP generation so hybridized graphs retrace under
    the new cast policy."""
    if target_dtype in ("bfloat16", jnp.bfloat16):
        _STATE.target_dtype = jnp.bfloat16
        _STATE.loss_scaler = None  # bf16 needs no scaling
    elif target_dtype in ("float16", onp.float16):
        _STATE.target_dtype = jnp.float16
        _STATE.loss_scaler = LossScaler()  # fresh scale per session
    else:
        raise ValueError("target_dtype must be bfloat16 or float16")
    from ..ndarray import ndarray as _ndmod

    _ndmod._amp_policy = _policy
    _ndmod._amp_generation += 1


def target_dtype():
    """The low-precision type of the active policy, or None without one:
    what a block casts to where it, not an operator, decides the type (a
    residual stream that follows the activations' type)."""
    return _STATE.target_dtype


def uninit():
    _STATE.target_dtype = None
    _STATE.loss_scaler = None
    from ..ndarray import ndarray as _ndmod

    _ndmod._amp_policy = None
    _ndmod._amp_generation += 1


def init_trainer(trainer):
    """Attach the loss scaler to a Trainer (reference amp.init_trainer)."""
    cfg = getattr(trainer, "_kvstore_params", {})
    if getattr(trainer, "_update_on_kvstore", None) or \
            cfg.get("update_on_kvstore"):
        raise MXNetError(
            "AMP does not support update_on_kvstore=True: overflowed "
            "updates applied server-side cannot be skipped — create the "
            "Trainer with update_on_kvstore=False")
    # lazily-resolved kvstore placement is re-checked in Trainer.step
    # (scaler present + _update_on_kvstore -> MXNetError before allreduce)
    if _STATE.target_dtype == jnp.float16 and _STATE.loss_scaler is None:
        _STATE.loss_scaler = LossScaler()
    trainer._amp_loss_scaler = _STATE.loss_scaler
    trainer._amp_original_scale = getattr(trainer, "_scale", 1.0)


class _ScaleLossCtx:
    def __init__(self, loss, trainer):
        self._loss = loss
        self._trainer = trainer

    def __enter__(self):
        scaler = getattr(self._trainer, "_amp_loss_scaler", None)
        scale = scaler.loss_scale if scaler is not None else 1.0
        if hasattr(self._trainer, "_scale"):
            # always re-derive from the saved base so the division tracks
            # the CURRENT scale (including scale == 1.0 after decay)
            base = getattr(self._trainer, "_amp_original_scale",
                           self._trainer._scale)
            self._trainer._amp_original_scale = base
            self._trainer._scale = base / scale
        if isinstance(self._loss, (list, tuple)):
            return [l * scale for l in self._loss] if scale != 1.0 \
                else list(self._loss)
        return self._loss * scale if scale != 1.0 else self._loss

    def __exit__(self, *exc):
        return False


def scale_loss(loss, trainer):
    """Context manager scaling the loss and arranging grad unscale through
    Trainer rescale (reference amp.scale_loss)."""
    return _ScaleLossCtx(loss, trainer)


def unscale(trainer):
    """Explicitly divide gradients by the current scale (e.g. before manual
    gradient clipping) and reset the Trainer rescale so the step does not
    divide again (reference amp.unscale)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        return
    inv = 1.0 / scaler.loss_scale
    for p in trainer._params:
        if p.grad_req == "null":
            continue
        for g in p.list_grad():
            if g is not None:
                g._set_data(g._data * inv)
    trainer._scale = getattr(trainer, "_amp_original_scale", trainer._scale)


_F32_LAYERS = ("BatchNorm", "SyncBatchNorm", "LayerNorm", "GroupNorm",
               "InstanceNorm")


def convert_hybrid_block(net, target_dtype="bfloat16", ctx=None):
    """Cast a Block for low-precision inference/training (reference
    amp.convert_hybrid_block).  Parameters cast to ``target_dtype`` except
    those owned by normalization layers, which stay fp32 (the op policy
    casts the inputs of layer/group/instance norm up at dispatch; batch
    norm takes its input as it arrives and is float32 inside).  ``ctx``
    additionally re-homes the parameters, matching the reference
    signature."""

    def walk(block):
        if type(block).__name__ in _F32_LAYERS:
            return
        for p in block._reg_params.values():
            if p._data is not None:
                p.cast(target_dtype)
            else:
                p.dtype = target_dtype
        for child in block._children.values():
            walk(child)

    walk(net)

    # The reference's converted symbol carries amp_cast nodes at its input
    # edges; the analog here is an input-casting forward bound on the
    # instance — hybridize traces it, so the casts land inside the compiled
    # graph exactly like the reference's graph rewrite.
    from ..ndarray.ndarray import NDArray

    jdt = jnp.bfloat16 if target_dtype in ("bfloat16", jnp.bfloat16) \
        else jnp.float16
    orig_forward = net.forward

    def _cast_in(a):
        if isinstance(a, NDArray) and jnp.issubdtype(a._data.dtype,
                                                     jnp.floating):
            return a.astype(jdt)
        return a

    def cast_forward(*args, **kwargs):
        return orig_forward(*[_cast_in(a) for a in args],
                            **{k: _cast_in(v) for k, v in kwargs.items()})

    net.forward = cast_forward
    if getattr(net, "_cached", None):
        net._cached = {}            # force a retrace under the new dtypes
    if ctx is not None:
        net.reset_ctx(ctx)
    return net
