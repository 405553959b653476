"""Weight initializers.

Re-design of the reference ``python/mxnet/initializer.py``: same registry and
descriptor behaviour (pattern-matched per-parameter init), but the fill is a
pure-JAX computation (threefry key per call) rather than imperative RNG ops,
so initialization is reproducible across hosts/replicas — on a TPU pod every
process computes identical initial weights from the same seed, which replaces
the reference's "init on worker 0 + kvstore broadcast" step.
"""
from __future__ import annotations

import json
import logging
import math
import re
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as onp

from . import random as _random
from .ndarray import NDArray
from .ndarray.ndarray import _wrap

__all__ = [
    "InitDesc",
    "Initializer",
    "register",
    "create",
    "Zero",
    "One",
    "Constant",
    "Uniform",
    "Normal",
    "Orthogonal",
    "Xavier",
    "MSRAPrelu",
    "Bilinear",
    "LSTMBias",
    "TimeStepBias",
    "LogUniform",
    "Mixed",
    "Load",
]

_INIT_REGISTRY: Dict[str, type] = {}


def register(klass):
    """Register an initializer under its lowercased class name (reference
    ``mx.init.register``)."""
    name = klass.__name__.lower()
    _INIT_REGISTRY[name] = klass
    return klass


def create(init, **kwargs) -> "Initializer":
    if isinstance(init, Initializer):
        return init
    if init is None:
        return Uniform()
    if isinstance(init, str):
        key = init.lower()
        if key not in _INIT_REGISTRY:
            raise ValueError(
                f"unknown initializer '{init}'; registered: {sorted(_INIT_REGISTRY)}"
            )
        return _INIT_REGISTRY[key](**kwargs)
    raise TypeError(f"cannot create initializer from {init!r}")


class InitDesc(str):
    """Descriptor carrying the parameter name + attrs into the initializer
    (reference initializer.py:40)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Initializer:
    """Base class: name-pattern dispatch identical to the reference
    (initializer.py:95 ``__call__``)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose = False
        self._print_func = None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose = verbose
        if print_func is None:
            def asum_stat(x):
                return str((onp.abs(x.asnumpy()).mean(),))
            print_func = asum_stat
        self._print_func = print_func
        return self

    def dumps(self) -> str:
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __eq__(self, other):
        if not isinstance(other, Initializer):
            return NotImplemented
        return self.__class__ is other.__class__ and self._kwargs == other._kwargs

    def _verbose_print(self, desc, init, arr):
        if self._verbose and self._print_func:
            logging.info("Initialized %s as %s: %s", desc, init, self._print_func(arr))

    def __call__(self, desc, arr: NDArray):
        if not isinstance(desc, InitDesc):
            desc = InitDesc(str(desc))
        init = desc.attrs.get("__init__", "")
        if init:
            create(json.loads(init)[0], **json.loads(init)[1])._init_weight(desc, arr)
            self._verbose_print(desc, init, arr)
            return
        if desc.attrs.get("force_weight"):
            # parameter-specific initializer: fill regardless of name suffix
            # (the reference routes this through InitDesc __init__ attrs)
            self._init_weight(desc, arr)
            self._verbose_print(desc, "weight", arr)
            return
        name = desc.lower()
        if name.endswith("weight"):
            self._init_weight(desc, arr)
            self._verbose_print(desc, "weight", arr)
        elif name.endswith("bias"):
            self._init_bias(desc, arr)
            self._verbose_print(desc, "bias", arr)
        elif name.endswith("gamma"):
            self._init_gamma(desc, arr)
            self._verbose_print(desc, "gamma", arr)
        elif name.endswith("beta"):
            self._init_beta(desc, arr)
            self._verbose_print(desc, "beta", arr)
        elif name.endswith("running_mean") or name.endswith("moving_mean"):
            self._init_zero(desc, arr)
        elif name.endswith("running_var") or name.endswith("moving_var"):
            self._init_one(desc, arr)
        elif name.endswith("moving_inv_var") or name.endswith("moving_avg"):
            self._init_zero(desc, arr)
        elif name.endswith("min") or name.endswith("max"):
            self._init_zero(desc, arr)
        else:
            self._init_default(desc, arr)

    # fill helpers -------------------------------------------------------
    @staticmethod
    def _fill(arr: NDArray, data):
        arr._set_data(jnp.asarray(data, dtype=arr._data.dtype))

    def _init_zero(self, _, arr):
        self._fill(arr, jnp.zeros(arr.shape))

    def _init_one(self, _, arr):
        self._fill(arr, jnp.ones(arr.shape))

    def _init_bias(self, _, arr):
        self._init_zero(_, arr)

    def _init_gamma(self, _, arr):
        self._init_one(_, arr)

    def _init_beta(self, _, arr):
        self._init_zero(_, arr)

    def _init_weight(self, desc, arr):
        raise NotImplementedError("must override _init_weight")

    def _init_default(self, desc, arr):
        raise ValueError(
            f"Unknown initialization pattern for {desc}. Default initialization "
            "is now limited to 'weight', 'bias', 'gamma', 'beta'."
        )


@register
class Zero(Initializer):
    def _init_weight(self, _, arr):
        self._init_zero(_, arr)


_INIT_REGISTRY["zeros"] = Zero


@register
class One(Initializer):
    def _init_weight(self, _, arr):
        self._init_one(_, arr)


_INIT_REGISTRY["ones"] = One


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        self._fill(arr, jnp.full(arr.shape, self.value))


@register
class Uniform(Initializer):
    """U(-scale, scale) — reference initializer.py:427."""

    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        k = _random.next_key()
        self._fill(
            arr,
            jax.random.uniform(
                k, arr.shape, jnp.float32, minval=-self.scale, maxval=self.scale
            ),
        )


@register
class Normal(Initializer):
    """N(0, sigma) — reference initializer.py:458."""

    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        k = _random.next_key()
        self._fill(arr, self.sigma * jax.random.normal(k, arr.shape, jnp.float32))


@register
class Orthogonal(Initializer):
    """Orthogonal matrix init (reference initializer.py:487, Saxe et al.)."""

    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(onp.prod(arr.shape[1:])) if len(arr.shape) > 1 else 1
        k = _random.next_key()
        if self.rand_type == "uniform":
            tmp = jax.random.uniform(k, (nout, nin), jnp.float32, -1.0, 1.0)
        else:
            tmp = jax.random.normal(k, (nout, nin), jnp.float32)
        u, _, v = jnp.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == (nout, nin) else v
        self._fill(arr, (self.scale * q).reshape(arr.shape))


@register
class Xavier(Initializer):
    """Xavier/Glorot (reference initializer.py:540): factor_type in/out/avg,
    rnd_type uniform/gaussian."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(
            rnd_type=rnd_type, factor_type=factor_type, magnitude=magnitude
        )
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, desc, arr):
        shape = arr.shape
        if len(shape) < 2:
            raise ValueError(
                f"Xavier initializer cannot be applied to vector {desc}. "
                "It requires at least 2D."
            )
        hw_scale = 1.0
        if len(shape) > 2:
            hw_scale = onp.prod(shape[2:])
        fan_in, fan_out = shape[1] * hw_scale, shape[0] * hw_scale
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise ValueError("Incorrect factor type")
        scale = math.sqrt(self.magnitude / factor)
        k = _random.next_key()
        if self.rnd_type == "uniform":
            self._fill(
                arr, jax.random.uniform(k, shape, jnp.float32, -scale, scale)
            )
        elif self.rnd_type == "gaussian":
            self._fill(arr, scale * jax.random.normal(k, shape, jnp.float32))
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    """Kaiming-He init (reference initializer.py:601)."""

    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    """Bilinear upsampling kernel (reference initializer.py:619)."""

    def _init_weight(self, _, arr):
        shape = arr.shape
        weight = onp.zeros(int(onp.prod(shape)), dtype=onp.float32)
        f = onp.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(onp.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._fill(arr, weight.reshape(shape))


@register
class LSTMBias(Initializer):
    """Forget-gate bias init (reference initializer.py:645)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        bias = onp.zeros(arr.shape, dtype=onp.float32)
        num_hidden = int(arr.shape[0] / 4)
        bias[num_hidden : 2 * num_hidden] = self.forget_bias
        self._fill(arr, bias)


@register
class TimeStepBias(Initializer):
    """A state-space or linear-attention mixer's ``dt_bias`` as the public
    Mamba-2 code draws it: ``dt`` log-uniform in [dt_min, dt_max], floored,
    and the bias its inverse softplus."""

    def __init__(self, dt_min=0.001, dt_max=0.1, floor=1e-4):
        super().__init__(dt_min=dt_min, dt_max=dt_max, floor=floor)
        self._range = (dt_min, dt_max, floor)

    def _init_weight(self, _, arr):
        dt_min, dt_max, floor = self._range
        u = jax.random.uniform(_random.next_key(), arr.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, floor)
        self._fill(arr, dt + jnp.log(-jnp.expm1(-dt)))


@register
class LogUniform(Initializer):
    """``log(u)``, ``u`` uniform in (low, high): a decay rate kept as its
    logarithm (the gated delta rule's ``A_log``)."""

    def __init__(self, low=0.0, high=16.0):
        super().__init__(low=low, high=high)
        self._range = (low, high)

    def _init_weight(self, _, arr):
        low, high = self._range
        u = jax.random.uniform(_random.next_key(), arr.shape, jnp.float32,
                               minval=low, maxval=high)
        self._fill(arr, jnp.log(jnp.maximum(u, jnp.finfo(jnp.float32).tiny)))


class Mixed:
    """Pattern→initializer dispatcher (reference initializer.py:372)."""

    def __init__(self, patterns, initializers):
        if len(patterns) != len(initializers):
            raise ValueError("patterns and initializers must have same length")
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise ValueError(
            f"Parameter name {name} did not match any pattern. Consider adding "
            '".*" pattern at the end.'
        )


@register
class Load:
    """Init from a dict of loaded arrays, falling back to default_init
    (reference initializer.py:331)."""

    def __init__(self, param, default_init=None, verbose=False):
        self.param = {
            k[4:] if k.startswith("arg:") or k.startswith("aux:") else k: v
            for k, v in param.items()
        }
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        if name in self.param:
            src = self.param[name]
            if tuple(src.shape) != tuple(arr.shape):
                raise ValueError(
                    f"Parameter {name} cannot be initialized from loading. "
                    f"Shape mismatch, target {arr.shape} vs loaded {src.shape}"
                )
            arr._set_data(jnp.asarray(src.asnumpy() if isinstance(src, NDArray) else src,
                                      dtype=arr._data.dtype))
            if self.verbose:
                logging.info("Initialized %s by loading", name)
        else:
            if self.default_init is None:
                raise ValueError(
                    f"Cannot Initialize parameter: {name}, not found in loaded param"
                )
            self.default_init(name, arr)
