"""Core plumbing shared across the framework.

TPU-native re-design of the reference's ``python/mxnet/base.py`` (ctypes lib
discovery, ``check_call``, handle types).  There is no C library handle here:
the compute substrate is JAX/XLA, so "base" reduces to the error type, the
registry helpers, and small utilities.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

import numpy as _np

__all__ = [
    "MXNetError",
    "NotSupportedForSparseNDArray",
    "string_types",
    "numeric_types",
    "integer_types",
    "env_bool",
    "env_int",
    "env_str",
    "data_dir",
]


def data_dir() -> str:
    """The MXNet cache root (reference mx.base.data_dir): MXNET_HOME or
    ``~/.mxnet``.  model_store/datasets build their subdirs on this."""
    import os

    from . import config

    return os.path.expanduser(config.get("MXNET_HOME"))


_INT32_MAX = 0x7FFFFFFF

# backends whose compiler demotes s64 element types wholesale (measured:
# docs/PERF.md ">int32-scale tensors on chip") — big-dim int64 indexing
# must use the int32-factorized paths there, never device s64
S64_DEMOTING_PLATFORMS = ("tpu",)


def enable_x64(new_val: bool = True):
    """The x64 scope every honest-int64 path routes through."""
    import jax

    return jax.enable_x64(new_val)


def s64_demoting_backend() -> bool:
    """True when the CURRENT default backend demotes s64 element types
    wholesale (tpu-class compilers).  Big-dim ops consult this at call
    time to pick between the int32-factorized paths (demoting backends)
    and plain s64 execution (x64-native cpu).  A function, not a constant,
    so tests can monkeypatch it to exercise the factorized machinery on
    the host."""
    import jax

    return jax.default_backend() in S64_DEMOTING_PLATFORMS


def int32_overflow_dim(d) -> bool:
    """True for a CONCRETE dim past int32 range.  Symbolic dims (AOT
    shape-polymorphic export) are never 'big' — comparing them raises
    InconclusiveDimensionOperation.  The single source of truth for the
    >int32 indexing rules in ndarray.py and ops/tensor.py."""
    return isinstance(d, (int, _np.integer)) and d > _INT32_MAX


def pow2_col_factor(n) -> int:
    """Largest power-of-two column factor (<=128) dividing n such that
    BOTH dims of the (n/C, C) view fit int32.  Returns 0 when none
    qualifies (odd n, or n so large that even n/2 overflows int32) —
    callers must refuse rather than pad: padding moves data ALONG the
    big dim, which the TPU runtime corrupts (docs/PERF.md)."""
    for c in (128, 64, 32, 16, 8, 4, 2):
        if n % c == 0 and n // c <= _INT32_MAX:
            return c
    return 0


def bounded_cache_put(cache: dict, key, val, cap: int = 64):
    """Insert into a plain-dict FIFO cache, evicting oldest past cap."""
    cache[key] = val
    while len(cache) > cap:
        cache.pop(next(iter(cache)))
    return val


class MXNetError(RuntimeError):
    """Default error type raised by the framework.

    Mirrors the reference's ``mxnet.base.MXNetError`` (raised from C via
    ``check_call``, ``python/mxnet/base.py``); here errors originate in Python
    or surface from XLA at sync points (see ``ndarray.NDArray.wait_to_read``).
    """


class NotSupportedForSparseNDArray(MXNetError):
    def __init__(self, function, alias, *args):
        super().__init__(
            f"Function {function.__name__}"
            + (f" (alias {alias})" if alias else "")
            + " is not supported for sparse NDArray"
        )


string_types = (str,)
numeric_types = (float, int)
integer_types = (int,)

_NOTHING = object()


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Read an ``MXNET_*``-style env var (reference: dmlc::GetEnv at
    point of use).  dmlc parity shim for USER code reading arbitrary
    names; in-tree knob reads must go through ``config.declare/get`` so
    docs/ENV_VARS.md stays provably complete (graftlint
    env-discipline)."""
    # graftlint: disable=env-discipline -- user-facing dmlc::GetEnv shim
    return os.environ.get(name, default)


def env_int(name: str, default: int = 0) -> int:
    try:
        # graftlint: disable=env-discipline -- user-facing dmlc shim
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def env_bool(name: str, default: bool = False) -> bool:
    # graftlint: disable=env-discipline -- user-facing dmlc shim
    val = os.environ.get(name)
    if val is None:
        return default
    return val.lower() not in ("0", "false", "off", "")


class _ThreadLocalScopeState(threading.local):
    """Small helper for thread-local nested scope flags (autograd, np-shape...)."""

    def __init__(self, **defaults):
        super().__init__()
        self._defaults = dict(defaults)
        for k, v in defaults.items():
            setattr(self, k, v)


class Registry:
    """A minimal name->object registry with alias support.

    Stands in for dmlc::Registry / ``KVStoreBase.register``-style plugin
    registries used throughout the reference.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._store: Dict[str, Any] = {}

    def register(self, name: Optional[str] = None, allow_override: bool = False):
        def _do(obj, key):
            key = key.lower()
            if key in self._store and not allow_override:
                raise ValueError(f"{self.kind} '{key}' already registered")
            self._store[key] = obj
            return obj

        if callable(name):  # used as bare decorator
            obj = name
            return _do(obj, obj.__name__)

        def deco(obj):
            return _do(obj, name or obj.__name__)

        return deco

    def get(self, name: str):
        key = name.lower()
        if key not in self._store:
            raise KeyError(
                f"{self.kind} '{name}' is not registered. "
                f"Available: {sorted(self._store)}"
            )
        return self._store[key]

    def find(self, name: str):
        return self._store.get(name.lower())

    def list(self):
        return sorted(self._store)


def classproperty(func: Callable):
    class _Desc:
        def __get__(self, obj, owner):
            return func(owner)

    return _Desc()
