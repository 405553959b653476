"""Gluon Parameter.

Re-design of ``python/mxnet/gluon/parameter.py`` (759 LoC).  A Parameter owns
per-context NDArray replicas of its value and (optionally) gradient buffers.
On TPU the interesting replication — data-parallel sharding over the chip
mesh — happens *inside* the compiled step function via ``jax.sharding``
(see mxnet_tpu.parallel), so per-ctx replicas here stay the simple eager
mechanism the user sees, exactly like the reference's list_data/list_grad.

Deferred initialization: shapes may contain 0 (unknown); layers complete them
on first forward (``_finish_deferred_init``), mirroring the reference's
deferred-init story (parameter.py ``DeferredInitializationError``).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as onp

from .. import initializer
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import NDArray
from ..ndarray.ndarray import _wrap

__all__ = ["Parameter", "Constant", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape is known (reference
    parameter.py:38)."""


def shape_is_known(shape) -> bool:
    if shape is None:
        return False
    return all(int(s) > 0 for s in shape)


class Parameter:
    """A settable, differentiable tensor held by Blocks.

    Reference: ``python/mxnet/gluon/parameter.py`` class Parameter.

    Sparse note: ``stype='row_sparse'`` (sparse *storage*) is rejected —
    TPU HBM + XLA gather/scatter make dense rows the fast path — but
    ``grad_stype='row_sparse'`` is accepted: the gradient is *computed*
    densely (XLA scatter-add produces the same values the reference's
    row-sparse gradient holds), and sparse-aware consumers
    (``KVStore.row_sparse_pull``, ``ops.optimizer`` lazy_update row-skip)
    still see reference semantics.
    """

    def __init__(
        self,
        name: str = "weight",
        grad_req: str = "write",
        shape=None,
        dtype="float32",
        lr_mult: float = 1.0,
        wd_mult: float = 1.0,
        init=None,
        allow_deferred_init: bool = False,
        differentiable: bool = True,
        stype: str = "default",
        grad_stype: str = "default",
    ):
        self._name = name
        self._shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            grad_req = "null"
        self._data: Optional[List[NDArray]] = None
        self._grad: Optional[List[NDArray]] = None
        self._ctx_list: Optional[List[Context]] = None
        self._grad_req = None
        self.grad_req = grad_req
        if stype not in ("default",):
            raise NotImplementedError(
                "sparse parameter storage is not supported on the TPU backend; "
                "row_sparse embedding gradients are handled densely by XLA "
                "scatter-add"
            )
        self._stype = stype
        self._grad_stype = grad_stype
        self._deferred_init = ()  # (init, ctx_list, default_init, data)
        # structural path filled in by Block registration; used in error msgs
        # and checkpoint keys
        self._structure: Optional[str] = None

    # ------------------------------------------------------------------
    def __repr__(self):
        return f"Parameter {self._name} (shape={self._shape}, dtype={self.dtype})"

    @property
    def name(self) -> str:
        return self._name

    @property
    def grad_req(self) -> str:
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req: str):
        assert req in ("write", "add", "null"), f"invalid grad_req {req}"
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                for d in self._data:
                    d._mark_variable(None, "null")
                    d._grad = None
        elif self._data is not None:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(int(s) for s in new_shape)
            return
        unknown_ok = all(
            s1 in (0, -1) or s1 == s2 for s1, s2 in zip(self._shape, new_shape)
        ) and len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise AssertionError(
                f"Expected shape {new_shape} is incompatible with given shape "
                f"{self._shape} for Parameter {self._name}"
            )
        self._shape = tuple(int(s) for s in new_shape)

    @property
    def stype(self):
        return self._stype

    # ------------------------------------------------------------------
    # initialization
    # ------------------------------------------------------------------
    def initialize(
        self,
        init=None,
        ctx=None,
        default_init=initializer.Uniform(),
        force_reinit=False,
    ):
        """Create value/grad buffers on ``ctx`` and fill them (reference
        parameter.py:380)."""
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if not shape_is_known(self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError(
                f"Cannot initialize Parameter '{self._name}' because it has "
                f"invalid shape: {self._shape}. Set allow_deferred_init=True "
                "or specify in_units/in_channels etc."
            )
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        if not shape_is_known(self._shape):
            raise DeferredInitializationError(
                f"Parameter '{self._name}' has unknown shape {self._shape} at "
                "deferred-init completion time"
            )
        self._ctx_list = list(ctx)
        if data is None:
            ref = NDArray(
                jnp.zeros(self._shape, dtype=_jax_dtype(self.dtype)), ctx=ctx[0]
            )
            if init is not None and init is not default_init:
                # parameter-specific init fills unconditionally
                init(initializer.InitDesc(self._name, {"force_weight": True}), ref)
            else:
                default_init(initializer.InitDesc(self._name), ref)
            data = ref
        self._data = [data.copyto(c) if c != data.ctx else data for c in ctx]
        # replicate value exactly across contexts
        for i, c in enumerate(ctx):
            if self._data[i]._data.dtype != _jax_dtype(self.dtype):
                self._data[i] = self._data[i].astype(self.dtype)
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = [
            _wrap(jnp.zeros(d.shape, d._data.dtype), d.ctx) for d in self._data
        ]
        for d, g in zip(self._data, self._grad):
            d._mark_variable(g, self._grad_req)

    def _release_grad(self):
        """Drop the gradient buffers, keeping ``grad_req``.  The compiled
        train step keeps gradients inside its program, so the buffers would
        be a second model in device memory that nothing reads.  The eager
        tape re-creates a buffer on its next ``backward`` and
        ``grad()``/``list_grad()`` adopt it (or hand out zeros)."""
        self._grad = None
        for d in self._data or ():
            d._grad = None

    def _live_grad(self):
        if (self._grad is None and self._data is not None
                and self._grad_req != "null"):
            # released by the compiled step: adopt what the tape wrote since
            self._grad = [
                d._grad if d._grad is not None
                else _wrap(jnp.zeros(d.shape, d._data.dtype), d.ctx)
                for d in self._data
            ]
            for d, g in zip(self._data, self._grad):
                d._mark_variable(g, self._grad_req)
        return self._grad

    def _load_init(self, data, ctx=None, cast_dtype=False, dtype_source="current"):
        """Install loaded value (reference parameter.py:280)."""
        if isinstance(data, NDArray):
            arr = data
        else:
            arr = NDArray(onp.asarray(data), ctx=ctx[0] if ctx else None)
        if self._shape is not None and shape_is_known(self._shape):
            if tuple(arr.shape) != self._shape:
                raise AssertionError(
                    f"Failed loading Parameter '{self._name}' from saved params: "
                    f"shape incompatible expected {self._shape} vs saved {arr.shape}"
                )
        else:
            self._shape = tuple(arr.shape)
        if cast_dtype and dtype_source == "current" and str(arr.dtype) != str(self.dtype):
            arr = arr.astype(self.dtype)
        elif dtype_source == "saved":
            self.dtype = arr.dtype
        if self._data is None:
            if ctx is None:
                ctx = self._deferred_init[1] if self._deferred_init else [current_context()]
            self._deferred_init = (None, ctx, initializer.Uniform(), arr)
            self._finish_deferred_init()
        else:
            self.set_data(arr)

    # ------------------------------------------------------------------
    # access
    # ------------------------------------------------------------------
    def _check_and_get(self, arr_list, ctx):
        if arr_list is None:
            if self._deferred_init:
                raise DeferredInitializationError(
                    f"Parameter '{self._name}' has not been initialized yet "
                    "because initialization was deferred. Actual initialization "
                    "happens during the first forward pass."
                )
            raise RuntimeError(
                f"Parameter '{self._name}' has not been initialized. You should "
                "initialize parameters and create a Trainer first."
            )
        if ctx is None:
            if len(arr_list) == 1:
                return arr_list[0]
            ctx = current_context()
        for c, a in zip(self._ctx_list, arr_list):
            if c == ctx:
                return a
        raise RuntimeError(
            f"Parameter '{self._name}' was not initialized on context {ctx}. "
            f"It was only initialized on {self._ctx_list}."
        )

    def data(self, ctx: Optional[Context] = None) -> NDArray:
        return self._check_and_get(self._data, ctx)

    def list_data(self) -> List[NDArray]:
        self._check_and_get(self._data, None if not self._ctx_list or
                            len(self._ctx_list) == 1 else self._ctx_list[0])
        return list(self._data)

    def grad(self, ctx: Optional[Context] = None) -> NDArray:
        if self._data is not None and self._grad_req == "null":
            raise RuntimeError(
                f"Cannot get gradient array for Parameter '{self._name}' "
                "because grad_req='null'"
            )
        return self._check_and_get(self._live_grad(), ctx)

    def list_grad(self) -> List[NDArray]:
        if self._data is not None and self._grad_req == "null":
            raise RuntimeError(
                f"Cannot get gradient array for Parameter '{self._name}' "
                "because grad_req='null'"
            )
        self._check_and_get(self._live_grad(),
                            None if not self._ctx_list or
                            len(self._ctx_list) == 1 else self._ctx_list[0])
        return list(self._grad)

    def list_ctx(self) -> List[Context]:
        if self._data is None:
            if self._deferred_init:
                return list(self._deferred_init[1])
            raise RuntimeError(
                f"Parameter '{self._name}' has not been initialized"
            )
        return list(self._ctx_list)

    def set_data(self, data):
        """Set value on all contexts (reference parameter.py:497)."""
        self.shape = tuple(data.shape)
        if self._data is None:
            assert self._deferred_init, (
                f"Parameter '{self._name}' has not been initialized"
            )
            init, ctx, default_init, _ = self._deferred_init
            self._deferred_init = (init, ctx, default_init,
                                   data if isinstance(data, NDArray) else NDArray(data))
            return
        src = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        for d in self._data:
            d._set_data(src.astype(d._data.dtype) if src.dtype != d._data.dtype else src)

    def zero_grad(self):
        if self._grad is None:
            # grad_req='null', or released (_release_grad): dropping what
            # the tape re-created since IS the zeroing
            for d in self._data or ():
                d._grad = None
            return
        for g in self._grad:
            g._set_data(jnp.zeros(g.shape, g._data.dtype))

    def reset_ctx(self, ctx):
        """Re-assign Parameter to new contexts (reference parameter.py:525)."""
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = self._reduce()
            init, _, default_init, _ = (
                self._deferred_init if self._deferred_init
                else (self.init, None, initializer.Uniform(), None)
            )
            self._data = None
            self._grad = None
            self._deferred_init = (init, ctx, default_init, data)
            self._finish_deferred_init()
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)
        else:
            raise ValueError(
                f"Cannot reset context for Parameter '{self._name}' because it "
                "has not been initialized."
            )

    def _reduce(self) -> NDArray:
        """Average value over all contexts to cpu (reference _reduce, used by
        save)."""
        data = self.data(self._ctx_list[0] if self._ctx_list else None)
        return data.copyto(cpu())

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        self._data = [d.astype(dtype) for d in self._data]
        if self._grad is not None:
            self._grad = [g.astype(dtype) for g in self._grad]
            for d, g in zip(self._data, self._grad):
                d._mark_variable(g, self._grad_req)

    def var(self):
        from ..symbol import var

        return var(self._name, shape=self._shape, dtype=self.dtype)


class Constant(Parameter):
    """Non-differentiable constant parameter (reference parameter.py:657)."""

    def __init__(self, value, name="const"):
        if not isinstance(value, NDArray):
            value = NDArray(onp.asarray(value))
        self.value = value
        super().__init__(
            name=name,
            grad_req="null",
            shape=value.shape,
            dtype=value.dtype,
            init=initializer.Constant(0),
            differentiable=False,
        )
        # exact-value init, not scalar fill
        class _Init(initializer.Initializer):
            def _init_weight(self, _, arr):
                arr._set_data(value._data.astype(arr._data.dtype))

        self.init = _Init()


def _jax_dtype(dtype):
    if dtype == jnp.bfloat16 or (isinstance(dtype, str) and dtype == "bfloat16"):
        return jnp.bfloat16
    return onp.dtype(dtype if dtype is not None else "float32")
