"""Python side of the C ABI (mxnet_tpu/native/src/c_api.cc).

The reference's C API marshals C arguments into its C++ runtime
(src/c_api/c_api_ndarray.cc:91 MXImperativeInvokeImpl); here the hosted
runtime *is* the Python/JAX package, so the C layer marshals buffers,
shapes and handles and calls these functions.  Everything here takes and
returns plain Python objects — the C side owns PyObject* reference
counting and the GIL.

Keep signatures in sync with c_api.cc; both cite the header entry point
they serve.
"""
from __future__ import annotations

import json

import numpy as onp


def _mx():
    import mxnet_tpu as mx

    return mx


def create(data: bytes, shape: tuple, dtype: str):
    """MXTpuNDArrayCreate: copy a host buffer into a new NDArray."""
    mx = _mx()
    npy = onp.frombuffer(data, dtype=onp.dtype(dtype)).reshape(shape)
    return mx.nd.array(npy, dtype=dtype)


def to_bytes(arr) -> bytes:
    """MXTpuNDArraySyncCopyToCPU: sync + full device->host copy."""
    return arr.asnumpy().tobytes()


def shape_of(arr) -> tuple:
    return tuple(int(d) for d in arr.shape)


def dtype_of(arr) -> str:
    return str(onp.dtype(arr.dtype).name)


def nbytes_of(arr) -> int:
    return int(onp.prod(arr.shape, dtype=onp.int64)) * onp.dtype(arr.dtype).itemsize


def wait_to_read(arr) -> None:
    arr.wait_to_read()


def wait_all() -> None:
    _mx().nd.waitall()


def _tuplify(v):
    if isinstance(v, list):
        return tuple(_tuplify(x) for x in v)
    return v


def invoke(op_name: str, inputs: list, attrs_json) -> list:
    """MXTpuImperativeInvoke: registry dispatch by name.

    JSON has no tuple type; operator attrs that are axis/kernel/stride
    tuples arrive as lists and are tuplified recursively.
    """
    from mxnet_tpu.ndarray import ndarray as _nd
    from mxnet_tpu.ops import registry

    attrs = {}
    if attrs_json:
        attrs = {k: _tuplify(v) for k, v in json.loads(attrs_json).items()}
    out = _nd.invoke(registry.get_op(op_name), list(inputs), attrs)
    return list(out) if isinstance(out, (list, tuple)) else [out]


def list_ops() -> list:
    from mxnet_tpu.ops import registry

    return registry.list_ops()


def set_recording(flag: bool) -> bool:
    from mxnet_tpu import autograd

    return autograd.set_recording(bool(flag))


def attach_grad(arr) -> None:
    arr.attach_grad()


def backward(head) -> None:
    head.backward()


def grad_of(arr):
    g = arr.grad
    if g is None:
        raise ValueError(
            "array has no gradient: call MXTpuNDArrayAttachGrad and run "
            "MXTpuAutogradBackward under recording first")
    return g


def seed(n: int) -> None:
    _mx().random.seed(int(n))


def version() -> int:
    mx = _mx()
    parts = (mx.__version__.split(".") + ["0", "0"])[:3]
    nums = [int("".join(c for c in p if c.isdigit()) or 0) for p in parts]
    return nums[0] * 10000 + nums[1] * 100 + nums[2]


def features() -> list:
    from mxnet_tpu import runtime

    return [f.name for f in runtime.feature_list() if f.enabled]
