"""NDArray: the imperative array type.

TPU-native re-design of the reference NDArray (``include/mxnet/ndarray.h``,
``python/mxnet/ndarray/ndarray.py``).  The reference pairs each array with a
dependency-engine variable so mutation is ordered asynchronously; here the
storage is an immutable ``jax.Array`` living in device memory (HBM via PJRT)
and *mutation is modeled as replacement*: every write installs a fresh
jax.Array and bumps ``version`` (the engine-var version analog).  JAX's async
dispatch supplies the "ops return immediately / sync at asnumpy()" illusion
that the reference built the threaded engine for:

- ``wait_to_read``/``wait_to_write``  -> ``block_until_ready`` on the buffer
- exceptions thrown by device code surface at sync points (MXNetError), the
  reference's ``ExceptionRef`` story (src/engine/threaded_engine.h:64).

Operator dispatch (``invoke``) is the analog of ``MXImperativeInvokeImpl``
(src/c_api/c_api_ndarray.cc:91): unwrap arrays, run the registered pure-JAX
fn (optionally under ``jax.vjp`` when autograd is recording), wrap outputs.
"""
from __future__ import annotations

import numbers
import time as _time
from collections import OrderedDict
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as onp

from .. import autograd
from .. import engine as _engine
from .. import profiler as _profiler
from .. import program_store as _pstore
from ..base import (MXNetError, S64_DEMOTING_PLATFORMS, bounded_cache_put,
                    enable_x64 as _enable_x64, int32_overflow_dim,
                    pow2_col_factor)
from ..context import Context, current_context
from ..ops.registry import OpSchema, find_op, get_op

__all__ = ["NDArray", "invoke", "array", "_wrap", "_on_tape"]

_float_types = (onp.float16, onp.float32, onp.float64, jnp.bfloat16)

# installed by mx.amp.init(): fn(op_name, [jax arrays]) -> [jax arrays];
# _amp_generation bumps on every init/uninit so hybridized-graph caches
# keyed on it retrace under the new policy
_amp_policy = None
_amp_generation = 0


def _dtype_np(dtype) -> onp.dtype:
    if dtype is None:
        return onp.dtype("float32")
    if dtype == jnp.bfloat16 or (isinstance(dtype, str) and dtype == "bfloat16"):
        return jnp.bfloat16  # type: ignore[return-value]
    return onp.dtype(dtype)


class NDArray:
    """An n-dimensional array on a device context."""

    __slots__ = (
        "_data",
        "_ctx",
        "_version",
        "_grad",
        "_ag_grad_req",
        "_ag_node",
        "_ag_out_index",
        "_deferred_init",
        "_dc_sym",
        "__weakref__",
    )

    # numpy interop precedence (reference ndarray.py __array_priority__)
    __array_priority__ = 1000.0

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        if ctx is None:
            ctx = current_context()
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            want = _dtype_np(dtype) if dtype is not None else None
            src = getattr(data, "dtype", None)
            if onp.dtype(want or src or onp.float32) in (onp.dtype("int64"),
                                                         onp.dtype("uint64")):
                # honest 64-bit integers (same policy as shape_array):
                # the x32 default would silently truncate graph/edge ids.
                # device_put must stay INSIDE the x64 scope — outside it
                # the transfer canonicalizes through int32, wrapping
                # values past 2^31 even though the dtype reads int64
                with _enable_x64(True):
                    data = jnp.asarray(data, dtype=want)
                    data = jax.device_put(data, ctx.jax_device)
            else:
                data = jnp.asarray(data, dtype=want)
                data = jax.device_put(data, ctx.jax_device)
        elif dtype is not None and data.dtype != _dtype_np(dtype):
            data = data.astype(_dtype_np(dtype))
        self._data = data
        self._ctx = ctx
        self._version = 0
        self._grad = None
        self._ag_grad_req = "null"
        self._ag_node = None
        self._ag_out_index = 0
        self._deferred_init = None
        self._dc_sym = None

    # ------------------------------------------------------------------
    # core properties
    # ------------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        dt = self._data.dtype
        return dt if dt == jnp.bfloat16 else onp.dtype(dt)

    @property
    def size(self) -> int:
        return int(onp.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def ctx(self) -> Context:
        return self._ctx

    context = ctx

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return invoke("transpose", [self], {})

    @property
    def version(self) -> int:
        """Write-version of this array (engine var version analog)."""
        return self._version

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    # ------------------------------------------------------------------
    # mutation-as-replacement
    # ------------------------------------------------------------------
    def _set_data(self, new_data: jax.Array):
        if tuple(new_data.shape) != self.shape:
            raise MXNetError(
                f"cannot write shape {tuple(new_data.shape)} into NDArray of "
                f"shape {self.shape}"
            )
        self._data = new_data
        self._version += 1

    # ------------------------------------------------------------------
    # sync / host transfer
    # ------------------------------------------------------------------
    def wait_to_read(self):
        try:
            self._data.block_until_ready()
        except Exception as e:  # XLA runtime errors surface here
            raise MXNetError(str(e)) from e

    def wait_to_write(self):
        self.wait_to_read()

    # standard DLPack protocol (reference dlpack.py exposes the
    # to_dlpack_* helpers; the dunder makes torch.from_dlpack(nd) work)
    def __dlpack__(self, **kwargs):
        self.wait_to_read()
        # forward the consumer's protocol args (stream sync etc.)
        return self._data.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def to_dlpack_for_read(self):
        from ..dlpack import to_dlpack_for_read

        return to_dlpack_for_read(self)

    def to_dlpack_for_write(self):
        from ..dlpack import to_dlpack_for_write

        return to_dlpack_for_write(self)

    def asnumpy(self) -> onp.ndarray:
        _HOST_SYNC.inc()
        self.wait_to_read()
        return onp.asarray(self._data)

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError(
            "The truth value of an NDArray with multiple elements is ambiguous."
        )

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None):
        """Allocate a gradient buffer (reference ndarray.py attach_grad)."""
        grad = _wrap(jnp.zeros(self.shape, self._data.dtype), self._ctx)
        self._mark_variable(grad, grad_req)

    def _mark_variable(self, grad: "NDArray", grad_req: str):
        self._grad = grad
        self._ag_grad_req = grad_req
        self._ag_node = None

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad], retain_graph, train_mode)

    def detach(self) -> "NDArray":
        out = _wrap(self._data, self._ctx)
        return out

    # ------------------------------------------------------------------
    # conversion / copies
    # ------------------------------------------------------------------
    def astype(self, dtype, copy=True) -> "NDArray":
        dt = _dtype_np(dtype)
        if not copy and self._data.dtype == dt:
            return self
        return invoke("cast", [self], {"dtype": dt})

    def copy(self) -> "NDArray":
        return invoke("_copy", [self], {})

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        if isinstance(other, NDArray):
            other._set_data(
                jax.device_put(self._data, other._ctx.jax_device).astype(
                    other._data.dtype
                )
            )
            return other
        out = NDArray(jax.device_put(self._data, other.jax_device), ctx=other)
        return out

    def as_in_context(self, context: Context) -> "NDArray":
        if context == self._ctx:
            return self
        return self.copyto(context)

    as_in_ctx = as_in_context

    def as_np_ndarray(self):
        from ..numpy.multiarray import ndarray as np_ndarray

        out = np_ndarray.__new__(np_ndarray)
        NDArray.__init__(out, self._data, ctx=self._ctx)
        out._ag_node = self._ag_node
        out._ag_out_index = self._ag_out_index
        out._grad = self._grad
        out._ag_grad_req = self._ag_grad_req
        return out

    def as_nd_ndarray(self):
        out = NDArray.__new__(NDArray)
        NDArray.__init__(out, self._data, ctx=self._ctx)
        out._ag_node = self._ag_node
        out._ag_out_index = self._ag_out_index
        out._grad = self._grad
        out._ag_grad_req = self._ag_grad_req
        return out

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import cast_storage as _cast_storage

        # dense -> csr / row_sparse container (reference ndarray.py
        # tostype -> cast_storage, src/operator/tensor/cast_storage.cc)
        return _cast_storage(self, stype)

    # ------------------------------------------------------------------
    # shape ops (methods mirror reference method surface)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs) -> "NDArray":
        if "shape" in kwargs:
            shape = kwargs["shape"]
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return invoke("reshape", [self], {"shape": tuple(shape)})

    def reshape_like(self, other) -> "NDArray":
        return invoke("reshape", [self], {"shape": other.shape})

    def transpose(self, *axes) -> "NDArray":
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", [self], {"axes": axes or None})

    def swapaxes(self, dim1, dim2) -> "NDArray":
        return invoke("swapaxes", [self], {"dim1": dim1, "dim2": dim2})

    def flatten(self) -> "NDArray":
        return invoke("flatten", [self], {})

    def expand_dims(self, axis) -> "NDArray":
        return invoke("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None) -> "NDArray":
        return invoke("squeeze", [self], {"axis": axis})

    def broadcast_to(self, shape) -> "NDArray":
        return invoke("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other) -> "NDArray":
        return invoke("broadcast_to", [self], {"shape": other.shape})

    def tile(self, reps) -> "NDArray":
        return invoke("tile", [self], {"reps": reps})

    def repeat(self, repeats, axis=None) -> "NDArray":
        return invoke("repeat", [self], {"repeats": repeats, "axis": axis})

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke(
            "split",
            [self],
            {"num_outputs": num_outputs, "axis": axis, "squeeze_axis": squeeze_axis},
        )

    def slice(self, begin, end, step=None) -> "NDArray":
        return invoke("slice", [self], {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end) -> "NDArray":
        return invoke("slice_axis", [self], {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip") -> "NDArray":
        return invoke("take", [self, _as_nd(indices, self._ctx)], {"axis": axis, "mode": mode})

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return invoke("one_hot", [self], {"depth": depth, "on_value": on_value,
                                          "off_value": off_value, "dtype": dtype})

    # reductions
    def sum(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("sum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("mean", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("min", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False, **kw) -> "NDArray":
        return invoke("prod", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False) -> "NDArray":
        return invoke("norm", [self], {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False) -> "NDArray":
        return invoke("argmin", [self], {"axis": axis, "keepdims": keepdims})

    def clip(self, a_min=None, a_max=None) -> "NDArray":
        return invoke("clip", [self], {"a_min": a_min, "a_max": a_max})

    def abs(self) -> "NDArray":
        return invoke("abs", [self], {})

    def sqrt(self) -> "NDArray":
        return invoke("sqrt", [self], {})

    def square(self) -> "NDArray":
        return invoke("square", [self], {})

    def exp(self) -> "NDArray":
        return invoke("exp", [self], {})

    def log(self) -> "NDArray":
        return invoke("log", [self], {})

    def relu(self) -> "NDArray":
        return invoke("relu", [self], {})

    def sigmoid(self) -> "NDArray":
        return invoke("sigmoid", [self], {})

    def tanh(self) -> "NDArray":
        return invoke("tanh", [self], {})

    def softmax(self, axis=-1) -> "NDArray":
        return invoke("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1) -> "NDArray":
        return invoke("log_softmax", [self], {"axis": axis})

    def dot(self, other) -> "NDArray":
        return invoke("dot", [self, _as_nd(other, self._ctx)], {})

    def tolist(self):
        return self.asnumpy().tolist()

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def __getitem__(self, key) -> "NDArray":
        key = _index_unwrap(key)
        _check_int_bounds(key, self.shape)
        if _needs_x64_index(self.shape) and self._on_x64_native_backend():
            # >int32-range dims (the reference's USE_INT64_TENSOR_SIZE
            # analog): on cpu, index constants must stay s64 or XLA's
            # gather drops them as out-of-bounds after truncation.  On
            # TPU the _index op itself lowers static keys to literal-
            # bound slices (the compiler demotes s64 types wholesale).
            with _enable_x64(True):
                return invoke("_index", [self], {"key": key})
        return invoke("_index", [self], {"key": key})

    def _on_x64_native_backend(self) -> bool:
        try:
            dev = next(iter(self._data.devices()))
        except Exception:       # tracers carry no device
            return False
        return dev.platform not in S64_DEMOTING_PLATFORMS

    def __setitem__(self, key, value):
        key = _index_unwrap(key)
        if isinstance(value, NDArray):
            value = value._data
        elif isinstance(value, numbers.Number):
            pass
        else:
            value = jnp.asarray(value)
        _check_int_bounds(key, self.shape)
        if key is Ellipsis or (isinstance(key, slice) and
                               key == slice(None)):
            if isinstance(value, numbers.Number):
                self._set_data(jnp.full(self.shape, value, self._data.dtype))
            else:
                self._set_data(
                    jnp.broadcast_to(jnp.asarray(value, self._data.dtype), self.shape)
                )
        elif _needs_x64_index(self.shape):
            # NO plain-scatter path here even for small offsets: the
            # functional .at[].set implies a full-buffer copy, and any
            # copy ALONG a >2^31 dim is corrupt on the TPU runtime
            new = _big_static_set(self._data, key, value)
            if new is not None:
                self._set_data(new)
            elif self._on_x64_native_backend():
                with _enable_x64(True):
                    self._set_data(self._data.at[key].set(value))
            else:
                raise MXNetError(
                    "only static int/contiguous-slice scalar writes are "
                    "supported into a >int32-range dim on the TPU runtime "
                    "(its compiler demotes s64 indices and corrupts copies "
                    "along >2^31 dims); reshape to a 2-D view whose dims "
                    "fit int32 for general writes")
        else:
            self._set_data(self._data.at[key].set(value))

    # ------------------------------------------------------------------
    # arithmetic operators
    # ------------------------------------------------------------------
    def _binary(self, op_name, other, reverse=False):
        if isinstance(other, numbers.Number):
            args = [self]
            attrs = {"scalar": float(other), "reverse": reverse}
            return invoke(f"{op_name}_scalar", args, attrs)
        other = _as_nd(other, self._ctx)
        a, b = (other, self) if reverse else (self, other)
        return invoke(f"broadcast_{op_name}", [a, b], {})

    def _inplace(self, op_name, other):
        """In-place update.  While recording, the array takes over the
        result's tape node so gradients stay correct (mutation-as-replacement
        keeps the tape functional); in-place on a *leaf* variable during
        recording is an error, as in the reference."""
        if autograd.is_recording() and self._ag_grad_req != "null":
            raise MXNetError(
                "in-place operation on a variable with attached grad is not "
                "allowed while autograd is recording"
            )
        # snapshot: the tape must reference the pre-mutation value, not self
        # (otherwise the node's input aliases its own output -> cyclic tape)
        src = _wrap(self._data, self._ctx)
        src._ag_node = self._ag_node
        src._ag_out_index = self._ag_out_index
        out = src._binary(op_name, other)
        self._set_data(out._data)
        self._ag_node = out._ag_node
        self._ag_out_index = out._ag_out_index
        return self

    def __add__(self, other):
        return self._binary("add", other)

    def __radd__(self, other):
        return self._binary("add", other, reverse=True)

    def __iadd__(self, other):
        return self._inplace("add", other)

    def __sub__(self, other):
        return self._binary("sub", other)

    def __rsub__(self, other):
        return self._binary("sub", other, reverse=True)

    def __isub__(self, other):
        return self._inplace("sub", other)

    def __mul__(self, other):
        return self._binary("mul", other)

    def __rmul__(self, other):
        return self._binary("mul", other, reverse=True)

    def __imul__(self, other):
        return self._inplace("mul", other)

    def __truediv__(self, other):
        return self._binary("div", other)

    def __rtruediv__(self, other):
        return self._binary("div", other, reverse=True)

    def __itruediv__(self, other):
        return self._inplace("div", other)

    def __mod__(self, other):
        return self._binary("mod", other)

    def __rmod__(self, other):
        return self._binary("mod", other, reverse=True)

    def __pow__(self, other):
        return self._binary("power", other)

    def __rpow__(self, other):
        return self._binary("power", other, reverse=True)

    def __matmul__(self, other):
        return self.dot(other)

    def __neg__(self):
        return invoke("negative", [self], {})

    def __abs__(self):
        return invoke("abs", [self], {})

    def __eq__(self, other):
        if other is None:
            return False
        return self._binary("equal", other)

    def __ne__(self, other):
        if other is None:
            return True
        return self._binary("not_equal", other)

    def __gt__(self, other):
        return self._binary("greater", other)

    def __ge__(self, other):
        return self._binary("greater_equal", other)

    def __lt__(self, other):
        return self._binary("lesser", other)

    def __le__(self, other):
        return self._binary("lesser_equal", other)

    __hash__ = None  # mutable container semantics, like the reference

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = str(arr)
        except MXNetError as e:
            body = f"<error: {e}>"
        return f"{body}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def _on_tape(arr) -> bool:
    return isinstance(arr, NDArray) and (
        arr._ag_node is not None or arr._ag_grad_req != "null"
    )


def _flavor_of(inputs) -> type:
    """The array FLAVOR a computation's outputs should carry: first input
    that is an NDArray subclass (mx.np ndarray) wins, else legacy NDArray.
    One rule for the eager invoke path and the hybridized trace — flavors
    differ semantically (np comparisons yield bool; nd yields float 0/1),
    so they must never drift apart."""
    for i in inputs:
        if isinstance(i, NDArray) and type(i) is not NDArray:
            return type(i)
    return NDArray


def _wrap(data: jax.Array, ctx: Context, cls=None) -> "NDArray":
    out = (cls or NDArray).__new__(cls or NDArray)
    out._data = data
    out._ctx = ctx
    out._version = 0
    out._grad = None
    out._ag_grad_req = "null"
    out._ag_node = None
    out._ag_out_index = 0
    out._deferred_init = None
    out._dc_sym = None
    return out


def _as_nd(x, ctx: Context) -> "NDArray":
    if isinstance(x, NDArray):
        return x
    return NDArray(x, ctx=ctx)


def _index_unwrap(key):
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(k._data if isinstance(k, NDArray) else k for k in key)
    return key


def _needs_x64_index(shape):
    """True when any dim exceeds int32 range, so index constants must be
    s64 (the reference's int64-tensor-size build analog)."""
    return any(int32_overflow_dim(d) for d in shape)


_BIG_SPLICE_JIT: dict = {}


def _big_static_set(data, key, value):
    """Scalar write into a static int/contiguous-slice region of a
    >int32-range 1-D array.

    The TPU runtime moves data correctly only when every dim of the
    moved region fits int32 — ANY scatter/copy along a >2^31 dim lands
    at corrupt offsets (measured, docs/PERF.md), including the
    full-buffer copy a functional `.at[].set` implies.  So the write is
    a pure ELEMENTWISE pass over a (dim/C, C) view: reshape is
    metadata-only (verified exact past 2^31), the target region becomes
    a (row, col) iota mask, and `where` selects value vs old — no index
    tensors, no scatter, per-dim extents all int32.  Returns None for
    patterns this cannot express (the caller falls back): non-scalar
    values, stepped slices, multi-dim arrays, odd dims with no small
    factor."""
    k = key[0] if isinstance(key, tuple) and len(key) == 1 else key
    if data.ndim != 1:
        return None
    n = data.shape[0]
    if isinstance(k, bool):
        return None
    if isinstance(k, (int, onp.integer)):
        s = int(k) + (n if int(k) < 0 else 0)
        e = s + 1
    elif isinstance(k, slice):
        try:
            s, e, st = k.indices(n)
        except TypeError:
            return None
        if st != 1:
            return None
        if e <= s:
            return data                  # empty region: numpy no-op
    else:
        return None
    if isinstance(value, NDArray) or getattr(value, "ndim", 0):
        return None                      # scalar writes only on this path
    C = pow2_col_factor(n)
    if not C:
        return None
    rows = n // C
    # region bounds travel as int32 OPERANDS (they are only compared to
    # iota, never used as indices, so s64 demotion is irrelevant): one
    # executable per (shape, dtype), not one per write offset
    rs, cs = divmod(s, C)
    re_, ce = divmod(e - 1, C)           # inclusive end position
    ck = (data.shape, str(data.dtype), C)
    fn = _BIG_SPLICE_JIT.get(ck)
    if fn is None:

        def masked_set(d, v, b):
            mat = d.reshape(rows, C)
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, C), 1)
            after = (row > b[0]) | ((row == b[0]) & (col >= b[1]))
            before = (row < b[2]) | ((row == b[2]) & (col <= b[3]))
            return jnp.where(after & before, v, mat).reshape(n)

        fn = bounded_cache_put(_BIG_SPLICE_JIT, ck, jax.jit(masked_set))
    return fn(data, jnp.asarray(value, data.dtype),
              jnp.asarray([rs, cs, re_, ce], jnp.int32))


def _check_int_bounds(key, shape):
    """Raise IndexError for out-of-range CONCRETE integer indices — jax
    silently clips them, the reference raises (test_ndarray indexing
    contract).  numpy integer SCALARS count as concrete ints too: an
    out-of-range onp.int64 key must raise, not become a silently-masked
    no-op write (ADVICE r5).  Array/traced indices keep jax's clip
    semantics (that IS the documented device behavior for gather)."""
    _int_scalar = (int, onp.integer)
    ints = (key,) if isinstance(key, _int_scalar) else \
        tuple(k for k in key if isinstance(k, _int_scalar)) \
        if isinstance(key, tuple) else ()
    if not ints:
        return
    dims = iter(shape)
    keys = key if isinstance(key, tuple) else (key,)
    for k in keys:
        if k is None or k is Ellipsis:
            # newaxis consumes no dim; Ellipsis realigns dims from the
            # right — bounds past it are rare, skip the strict check
            if k is Ellipsis:
                return
            continue
        d = next(dims, None)
        if d is None:
            raise IndexError(f"too many indices for shape {shape}")
        if isinstance(k, _int_scalar) and not isinstance(k, bool) \
                and not (-d <= int(k) < d):
            raise IndexError(
                f"index {k} is out of bounds for axis with size {d}")


# operator dispatches since import: with fused.dispatch_count() this
# counts an eager step's dispatches
from .. import telemetry as _telemetry  # noqa: E402

_INVOKE = _telemetry.counter(
    "ndarray.invoke", "eager operator dispatches since import")


def invoke_count() -> int:
    """Number of eager operator dispatches since import (view over the
    ``ndarray.invoke`` registry counter)."""
    return int(_INVOKE.value)


# blocking host reads (asnumpy/item/float/bool, plus the deferred AMP
# flag read in cached_step) since import: tools/check_dispatch_budget.py
# gates the steady-state train step on this staying at 0 (non-AMP) /
# <= 1 deferred read (AMP) — the pipeline engine's host-sync budget
_HOST_SYNC = _telemetry.counter(
    "ndarray.host_sync",
    "blocking device->host value reads (asnumpy/item/float/bool + the "
    "deferred AMP flag read)")


def host_sync_count() -> int:
    """Number of blocking device->host value reads since import (view
    over the ``ndarray.host_sync`` registry counter)."""
    return int(_HOST_SYNC.value)


def count_host_sync() -> None:
    """Record one blocking host read performed outside asnumpy (e.g. a
    bool() on a raw jax scalar)."""
    _HOST_SYNC.inc()


def invoke(
    op: Union[str, OpSchema],
    inputs: Sequence[NDArray],
    attrs: dict,
    out: Optional[Union[NDArray, Sequence[NDArray]]] = None,
):
    """Imperative operator dispatch (MXImperativeInvokeImpl analog).

    - Unwraps NDArray inputs to jax.Arrays.
    - If autograd is recording and any input is tape-connected and the op is
      differentiable, runs under ``jax.vjp`` and records a TapeNode.
    - Wraps outputs; honours ``out=`` by writing into the destination
      (reference's kWriteTo into provided output arrays).
    """
    _INVOKE.inc()
    schema = get_op(op) if isinstance(op, str) else op
    ctx = inputs[0]._ctx if inputs else current_context()
    arrays = [i._data for i in inputs]

    if _profiler.ops_active():
        _t0 = _time.time_ns()       # the profiler's one clock
        try:
            return _invoke_body(schema, ctx, arrays, inputs, attrs, out)
        finally:
            _profiler.record_op(schema.name, _t0, _time.time_ns())
    return _invoke_body(schema, ctx, arrays, inputs, attrs, out)


def _make_op_fn(schema, attrs):
    if schema.num_inputs == -1:
        fn = lambda *arrs: schema.fn(list(arrs), **attrs)
    else:
        fn = lambda *arrs: schema.fn(*arrs, **attrs)

    if _amp_policy is not None:
        # mx.amp per-op cast lists: casting INSIDE fn keeps it within the
        # vjp boundary, so backward re-casts cotangents to each input's
        # original dtype (the reference amp_cast op's FGradient behavior)
        inner_fn = fn
        fn = lambda *arrs: inner_fn(*_amp_policy(schema.name, list(arrs)))
    return fn


# Per-op jit cache for the EAGER hot path (SURVEY §7: "per-op jit-compiled
# XLA computation with a compilation cache").  An op fn is typically a
# handful of jnp primitives; unjitted, each primitive is a separate device
# dispatch with its own host-side launch cost.  Jitting
# per (op, fn identity, amp generation, static attrs) collapses an op
# invocation to ONE cached executable launch (the reference engine's
# operator-bulking role, src/engine/threaded_engine.h:507-528).
# Ops whose python body cannot trace (data-dependent shapes, host
# round-trips) are detected by failure and permanently fall back.
_EAGER_JIT_BAD: set = set()
_EAGER_JIT_KEYCOUNT: dict = {}
_EAGER_JIT_MAX_ENTRIES = 512      # default namespace cap (override via
                                  # MXNET_PROGRAM_CACHE_CAPS eager_jit=N)
_EAGER_JIT_MAX_PER_OP = 64        # attr-cardinality cutoff: beyond this the
                                  # op recompiles per call (slice with a
                                  # moving begin etc.) — jit is a net loss


def _eager_jit_evicted(old_key, _fn) -> None:
    # cutoff counts LIVE entries: an evicted executable hands its op's
    # slot back so LRU churn can never accumulate into a per-op ban
    live = _EAGER_JIT_KEYCOUNT.get(old_key[0], 1) - 1
    if live > 0:
        _EAGER_JIT_KEYCOUNT[old_key[0]] = live
    else:
        _EAGER_JIT_KEYCOUNT.pop(old_key[0], None)


# the eager per-op executables are the ProgramStore 'eager_jit'
# namespace (one global scope): same LRU/metrics surface as the
# whole-program caches, values are plain shape-polymorphic jit
# callables (no AOT pinning — one (op, attrs) key serves every shape)
_EAGER_JIT_CACHE = _pstore.scope("eager_jit", on_evict=_eager_jit_evicted)

# trace-time failure types: the op BODY cannot be traced (host value
# inspection, data-dependent output shape).  Only these justify a
# permanent per-op ban; anything else (bad user input, dtype errors) must
# not disable the cache for later valid calls.
_TRACE_FAILURES = tuple(
    t for t in (
        getattr(jax.errors, "ConcretizationTypeError", None),
        getattr(jax.errors, "TracerArrayConversionError", None),
        getattr(jax.errors, "TracerBoolConversionError", None),
        getattr(jax.errors, "TracerIntegerConversionError", None),
        getattr(jax.errors, "NonConcreteBooleanIndexError", None),
        getattr(jax.errors, "UnexpectedTracerError", None),
    ) if t is not None)


def _attrs_key(v):
    if isinstance(v, (list, tuple)):
        return tuple(_attrs_key(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _attrs_key(x)) for k, x in v.items()))
    return v


# per-op cache opt-out (MXNET_EAGER_JIT_EXCLUDE): single-primitive
# reductions measured SLOWER through the cache than plain dispatch
# (mean(axis) 0.62x, measured through the old remote relay; ROADMAP D6
# still owes a run on a directly attached chip — one primitive is already
# one dispatch; the cache only adds lookup + executable-launch overhead).
# Memoized on the raw string so the per-dispatch cost is one dict read.
_EAGER_JIT_EXCLUDE_MEMO: tuple = (None, frozenset())


def _eager_jit_excluded(name: str) -> bool:
    global _EAGER_JIT_EXCLUDE_MEMO
    from .. import config as _config

    raw = _config.get("MXNET_EAGER_JIT_EXCLUDE")
    if raw != _EAGER_JIT_EXCLUDE_MEMO[0]:
        _EAGER_JIT_EXCLUDE_MEMO = (raw, frozenset(
            s.strip() for s in (raw or "").split(",") if s.strip()))
    return name in _EAGER_JIT_EXCLUDE_MEMO[1]


def _eager_jit_lookup(schema, attrs, arrays):
    from .. import config as _config

    mode = _config.get("MXNET_EAGER_JIT")
    if not mode or schema.name in _EAGER_JIT_BAD:
        return None
    if mode != 2 and jax.default_backend() != "tpu":
        return None                       # RTT-bound paths only by default
    if _eager_jit_excluded(schema.name):
        return None                       # measured net-loss ops (mean etc.)
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        # inside an outer trace an inner jit becomes a separate XLA call
        # and would break producer-consumer fusion in hybridized graphs
        return None
    try:
        key = (schema.name, id(schema.fn), _amp_generation,
               tuple(sorted((k, _attrs_key(v)) for k, v in attrs.items())))
        hash(key)
    except TypeError:
        return None                       # unhashable attr: plain dispatch
    fn = _EAGER_JIT_CACHE.lookup(key)
    if fn is not None:
        return fn
    # cutoff counts LIVE entries (decremented on eviction, see
    # _eager_jit_evicted): a hot op with few attr sets must never
    # accumulate into a ban via LRU churn or amp generation bumps
    n_keys = _EAGER_JIT_KEYCOUNT.get(schema.name, 0) + 1
    if n_keys > _EAGER_JIT_MAX_PER_OP:
        _EAGER_JIT_BAD.add(schema.name)   # attrs vary per call: jit loses
        return None
    _EAGER_JIT_KEYCOUNT[schema.name] = n_keys
    fn = jax.jit(_make_op_fn(schema, attrs))
    _EAGER_JIT_CACHE.insert(key, fn)
    return fn


def _invoke_body(schema, ctx, arrays, inputs, attrs, out):

    # Record every differentiable op while the scope is active (the reference
    # records all ops under record(), not just ones touching marked vars —
    # autograd.grad() may later differentiate w.r.t. any graph input).
    record = autograd.is_recording() and schema.differentiable and len(inputs) > 0

    # honest int64 indexing at scale: an s64-typed input (index arrays keep
    # int64 per the creation policy above) meeting a >int32-range dim must
    # dispatch under x64 on backends that execute s64 natively (cpu), or
    # jax demotes the indices to int32 with silent wraparound (gather
    # lands at the wrong offset).  NOT applied on TPU: its compiler
    # demotes s64 element types wholesale (buffers then mismatch the
    # executable), so TPU-capable ops (take, scalar get/set item) carry
    # their own int32-factorized >int32 paths instead.  The cheap dtype
    # test runs first: >99% of eager dispatches fail it in one tuple
    # check and never walk shapes.
    if (any(a.dtype in _X64_ITYPES for a in arrays)
            and any(_needs_x64_index(a.shape) for a in arrays)
            and ctx.jax_device is not None
            and ctx.jax_device.platform not in S64_DEMOTING_PLATFORMS):
        with _enable_x64(True):
            return _invoke_tail(schema, ctx, arrays, inputs, attrs, out,
                                _make_op_fn(schema, attrs), None, record)

    if schema.draws_key and attrs.get("key") is None:
        # the op body draws from the global PRNG chain: tracing it into a
        # cached executable would leak a tracer into the chain AND bake
        # the drawn key as a constant (every cache hit returning the same
        # "random" numbers) — plain dispatch only
        jitted = None
    else:
        jitted = _eager_jit_lookup(schema, attrs, arrays)
    fn = jitted if jitted is not None else _make_op_fn(schema, attrs)
    return _invoke_tail(schema, ctx, arrays, inputs, attrs, out, fn, jitted,
                        record)


_X64_ITYPES = (onp.dtype("int64"), onp.dtype("uint64"))


def _invoke_tail(schema, ctx, arrays, inputs, attrs, out, fn, jitted, record):
    while True:
        try:
            if record:
                raw_out, vjp_fn = jax.vjp(fn, *arrays)
            else:
                raw_out = fn(*arrays)
            break
        except Exception as e:
            if jitted is not None:
                # retry unjitted; ban the op ONLY for trace-time failures
                # (op body can't trace: host value inspection, dynamic
                # output shape).  Input-dependent errors (dtype, shape
                # mismatch) must not disable the cache for valid calls.
                # NotImplementedError counts as trace-time too: op bodies
                # raise it when they cannot express the pattern under a
                # trace (big-dim take with tracer indices) — without the
                # ban every call repays the failed trace (ADVICE r5).
                if isinstance(e, _TRACE_FAILURES + (NotImplementedError,)):
                    _EAGER_JIT_BAD.add(schema.name)
                jitted = None
                fn = _make_op_fn(schema, attrs)
                continue
            if record and isinstance(e, (TypeError,
                                         jax.errors.JaxRuntimeError)):
                # non-differentiable in practice (int dtypes etc.) — plain
                record = False
                continue
            raise

    multi = isinstance(raw_out, (tuple, list))
    outs_raw = list(raw_out) if multi else [raw_out]
    # outputs keep the array *flavor* of the inputs: dispatching an op on an
    # mx.np ndarray yields mx.np ndarrays (reference keeps np/nd worlds apart
    # via distinct generated namespaces; here one registry serves both)
    out_cls = _flavor_of(inputs)
    outputs = [_wrap(o, ctx, out_cls) for o in outs_raw]

    if _engine.is_naive():
        # MXNET_ENGINE_TYPE=NaiveEngine: synchronous dispatch — block per
        # op so errors surface at the faulting op, not a later sync point
        # (reference src/engine/naive_engine.cc debugging role); inside a
        # bulk scope the barrier fires every bulk_size ops instead
        _engine.naive_sync([o._data for o in outputs])

    if record:
        node = autograd.TapeNode(
            vjp_fn,
            list(inputs),
            len(outputs),
            [tuple(o.shape) for o in outs_raw],
            [o.dtype for o in outs_raw],
            name=schema.name,
            # replay (higher-order grads) runs under a trace: hand it the
            # PLAIN fn so replayed ops stay inline (an inner jit would be
            # a separate XLA call boundary, breaking fusion)
            fn=_make_op_fn(schema, attrs) if jitted is not None else fn,
            input_vals=list(arrays),
        )
        for i, o in enumerate(outputs):
            o._ag_node = node
            o._ag_out_index = i

    from .. import _deferred_compute as _dc

    if _dc.is_active():
        _dc.record(schema, list(inputs), attrs, outputs)

    if out is not None:
        dests = [out] if isinstance(out, NDArray) else list(out)
        for d, o in zip(dests, outputs):
            d._set_data(o._data.astype(d._data.dtype) if d._data.dtype != o._data.dtype else o._data)
            d._ag_node = o._ag_node
            d._ag_out_index = o._ag_out_index
            d._dc_sym = o._dc_sym
        return out

    if not multi:
        return outputs[0]
    return outputs


def array(source_array, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    """Create an NDArray from any array-like (reference mx.nd.array)."""
    if isinstance(source_array, NDArray):
        tgt = ctx or source_array._ctx
        out = NDArray(source_array._data, ctx=tgt, dtype=dtype)
        # An explicit ctx must MOVE an already-committed payload (the
        # reference mx.nd.array(nd, ctx=gpu(0)) copies device-to-device);
        # NDArray.__init__ wraps existing jax arrays in place, so the
        # placement is enforced here.  Tracers (graph capture) carry no
        # device and pass through untouched.
        if ctx is not None and not isinstance(out._data, jax.core.Tracer):
            dev = tgt.jax_device
            if dev is not None and dev not in out._data.devices():
                out._data = jax.device_put(out._data, dev)
        return out
    if dtype is None:
        np_in = onp.asarray(source_array)
        # MXNet's default dtype is float32: wide floats narrow, float16 and
        # all integer dtypes pass through.
        if np_in.dtype.kind == "f" and np_in.dtype != onp.float16:
            dtype = "float32"
        else:
            dtype = np_in.dtype
    return NDArray(onp.asarray(source_array), ctx=ctx, dtype=dtype)
