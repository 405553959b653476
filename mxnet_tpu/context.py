"""Device contexts.

Re-design of the reference's ``python/mxnet/context.py`` + C++ ``Context``
(``include/mxnet/base.h:90-116``, kinds kCPU/kGPU/kCPUPinned/kCPUShared) for
TPU: the first-class accelerator is ``mx.tpu(i)`` backed by a JAX/PJRT device.
``mx.gpu(i)`` is accepted as an alias for ``mx.tpu(i)`` so reference scripts
run unchanged (the north-star requirement).

A ``Context`` resolves lazily to a concrete ``jax.Device``.

The DEFAULT context is the process's default JAX device: ``tpu(0)`` on a
machine with an accelerator, ``cpu(0)`` otherwise.  (The reference defaults
to ``cpu(0)`` and expects ``ctx=mx.gpu(0)`` everywhere; on a TPU host that
default commits every ``mx.nd.array(numpy)`` to the HOST cpu device, and one
host-committed operand pulls a whole jitted program off the chip — silently.)
``mx.cpu()`` still names the host explicitly.

``tpu(i)`` with no accelerator present resolves to the CPU (one-time warning)
ONLY when ``JAX_PLATFORMS`` names ``cpu`` explicitly — the test suite's
setting; under any other setting a missing accelerator raises.
"""
from __future__ import annotations

import threading
import warnings
from typing import Optional

import jax

__all__ = [
    "Context",
    "cpu",
    "cpu_pinned",
    "gpu",
    "tpu",
    "current_context",
    "num_gpus",
    "num_tpus",
]

_warned_fallback = set()


class Context:
    """A device context. devtype: 'cpu', 'tpu' ('gpu' aliases 'tpu')."""

    # mirror the reference's devtype ids (include/mxnet/base.h) with a new slot
    devtype2mask = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 7}
    _default_ctx = threading.local()

    __slots__ = ("device_typeid", "device_id", "_old_ctx")

    def __init__(self, device_type: str, device_id: int = 0):
        device_type = device_type.lower()
        if device_type == "gpu":
            # TPU-native build: gpu(i) is an alias for the accelerator
            device_type = "tpu"
        if device_type not in self.devtype2mask:
            raise ValueError(f"unknown device type {device_type}")
        self.device_typeid = device_type
        self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self) -> str:
        return self.device_typeid

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return f"{self.device_typeid}({self.device_id})"

    def __repr__(self):
        return f"Context({self.__str__()})"

    # --- context-manager protocol: `with mx.tpu(0):` sets default ctx ---
    def __enter__(self):
        # None = the lazy default (current_context resolves it from the
        # JAX backend on first use; entering a scope must not open one)
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        if self._old_ctx is None:
            del Context._default_ctx.value
        else:
            Context._default_ctx.value = self._old_ctx

    # --- JAX resolution ---
    @property
    def jax_device(self) -> "jax.Device":
        return _resolve_device(self.device_typeid, self.device_id)

    def empty_cache(self):
        """Reference: ``Context.empty_cache`` releases the GPU memory pool.

        PJRT owns the HBM pool; nothing to do, kept for API parity."""


def _platform_devices(platform: str):
    """Process-LOCAL devices: a Context indexes addressable devices only
    (reference semantics: ``mx.gpu(0)`` is this worker's GPU 0).  Under
    multi-controller jax.distributed, ``jax.devices()`` is the global list
    and leads with process 0's devices — non-addressable on other ranks."""
    try:
        return jax.local_devices(backend=platform)
    except RuntimeError:
        return []


def _accelerator_platform() -> Optional[str]:
    default = jax.default_backend()
    if default != "cpu":
        return default
    return None


def _resolve_device(devtype: str, device_id: int) -> "jax.Device":
    if devtype in ("cpu", "cpu_pinned", "cpu_shared"):
        devs = _platform_devices("cpu")
        if devs:
            return devs[min(device_id, len(devs) - 1)]
        # cpu platform always exists in jax, but be safe
        return jax.devices()[0]
    # tpu (or alias)
    platform = _accelerator_platform()
    if platform is None:
        requested = (jax.config.jax_platforms or "").lower().split(",")
        if requested[0].strip() != "cpu":
            raise RuntimeError(
                f"tpu({device_id}) requested but JAX found no accelerator "
                f"(default backend 'cpu', JAX_PLATFORMS="
                f"{jax.config.jax_platforms!r}).  Set JAX_PLATFORMS=cpu to "
                "run tpu() contexts on the host deliberately.")
        if "tpu" not in _warned_fallback:
            _warned_fallback.add("tpu")
            warnings.warn(
                "JAX_PLATFORMS names cpu and no accelerator is present; "
                "tpu() resolves to the host CPU."
            )
        devs = _platform_devices("cpu")
        return devs[min(device_id, len(devs) - 1)]
    devs = _platform_devices(platform)
    if device_id >= len(devs):
        raise ValueError(
            f"tpu({device_id}) requested but only {len(devs)} device(s) present"
        )
    return devs[device_id]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for :func:`tpu` — keeps reference scripts (`mx.gpu(0)`) working."""
    return Context("tpu", device_id)


def num_tpus() -> int:
    platform = _accelerator_platform()
    if platform is None:
        return 0
    return len(_platform_devices(platform))


def num_gpus() -> int:
    return num_tpus()


def current_context() -> Context:
    """The thread's default context: whatever an enclosing ``with ctx:``
    set, else the default JAX device's context (module docstring)."""
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context(
            "cpu" if _accelerator_platform() is None else "tpu", 0)
    return Context._default_ctx.value
