"""Basic layers: Dense, Dropout, norms, Embedding, containers.

Reference ``python/mxnet/gluon/nn/basic_layers.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from ... import autograd
from ... import random as _random
from ...ndarray import NDArray
from ...ndarray.ndarray import invoke, _wrap
from ..block import Block, HybridBlock, remat_call
from ..parameter import Parameter

__all__ = [
    "Sequential",
    "HybridSequential",
    "Dense",
    "Dropout",
    "BatchNorm",
    "BatchNormReLU",
    "SyncBatchNorm",
    "InstanceNorm",
    "LayerNorm",
    "RMSNorm",
    "LatentAttention",
    "GatedDeltaNet",
    "GroupNorm",
    "Embedding",
    "Flatten",
    "Lambda",
    "HybridLambda",
    "Identity",
    "Concatenate",
    "HybridConcatenate",
]


class Sequential(Block):
    """Stack of blocks executed sequentially (reference basic_layers.py:36)."""

    def __init__(self):
        super().__init__()

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)()
            net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    """Hybridizable Sequential (reference basic_layers.py:86)."""

    def __init__(self):
        super().__init__()
        self._recomputed = None

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def recompute(self, start, stop):
        """Children ``start`` up to ``stop`` run as one segment that a
        staged program recomputes in its backward pass and keeps nothing
        of but its input (``block.remat_call``)."""
        self._recomputed = (start, stop)

    def forward(self, x):
        blocks = list(self._children.values())
        if self._recomputed is not None:
            start, stop = self._recomputed
            for block in blocks[:start]:
                x = block(x)
            x = remat_call(blocks[start:stop], x)
            blocks = blocks[stop:]
        for block in blocks:
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)()
            net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer (reference basic_layers.py:136; op
    src/operator/nn/fully_connected.cc)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0):
        super().__init__()
        self._units = units
        self._in_units = in_units
        self._flatten = flatten
        self._use_bias = use_bias
        self.weight = Parameter(
            "weight",
            shape=(units, in_units),
            dtype=dtype,
            init=weight_initializer,
            allow_deferred_init=True,
        )
        if use_bias:
            from ... import initializer as init

            self.bias = Parameter(
                "bias",
                shape=(units,),
                dtype=dtype,
                init=init.create(bias_initializer),
                allow_deferred_init=True,
            )
        else:
            self.bias = None
        self.act = Activation(activation) if activation else None
        if self.act is not None:
            self.register_child(self.act, "act")

    def infer_shape(self, x):
        in_units = (
            int(onp.prod(x.shape[1:])) if self._flatten else int(x.shape[-1])
        )
        self.weight.shape = (self._units, in_units)

    def forward(self, x):
        args = [x, self.weight.data(x.ctx)]
        if self._use_bias:
            args.append(self.bias.data(x.ctx))
        out = invoke(
            "FullyConnected",
            args,
            {
                "num_hidden": self._units,
                "no_bias": not self._use_bias,
                "flatten": self._flatten,
            },
        )
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return f"Dense({shape[1] if shape else None} -> {self._units}, " \
               f"{'linear' if self.act is None else self.act._act_type})"


class Dropout(HybridBlock):
    """Dropout (reference basic_layers.py:226).  RNG key threaded explicitly
    so hybridized graphs stay pure (see ops/nn.py dropout)."""

    def __init__(self, rate, axes=()):
        super().__init__()
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        if self._rate == 0 or not autograd.is_training():
            return x
        key = _random.next_key()
        key_nd = _wrap(key, x.ctx)
        return invoke(
            "Dropout",
            [x, key_nd],
            {"p": self._rate, "axes": self._axes, "training": True},
        )

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """Batch normalization (reference basic_layers.py:270; op
    src/operator/nn/batch_norm.cc).

    Running statistics are updated functionally: the op returns batch
    mean/var and the layer folds them into running buffers; under
    hybridization the buffer writes become extra outputs of the compiled
    graph (block.py mutation capture).
    """

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__()
        from ... import initializer as init

        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self._in_channels = in_channels
        self.gamma = Parameter(
            "gamma", grad_req="write" if scale else "null",
            shape=(in_channels,), init=init.create(gamma_initializer),
            allow_deferred_init=True, differentiable=scale)
        self.beta = Parameter(
            "beta", grad_req="write" if center else "null",
            shape=(in_channels,), init=init.create(beta_initializer),
            allow_deferred_init=True, differentiable=center)
        self.running_mean = Parameter(
            "running_mean", grad_req="null", shape=(in_channels,),
            init=init.create(running_mean_initializer),
            allow_deferred_init=True, differentiable=False)
        self.running_var = Parameter(
            "running_var", grad_req="null", shape=(in_channels,),
            init=init.create(running_variance_initializer),
            allow_deferred_init=True, differentiable=False)

    def infer_shape(self, x):
        c = int(x.shape[self._axis])
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def forward(self, x):
        ctx = x.ctx
        training = autograd.is_training() and not self._use_global_stats
        rm, rv = self.running_mean.data(ctx), self.running_var.data(ctx)
        outs = invoke(
            "BatchNorm",
            [x, self.gamma.data(ctx), self.beta.data(ctx), rm, rv],
            {
                "eps": self._epsilon,
                "momentum": self._momentum,
                "fix_gamma": not self._scale,
                "use_global_stats": self._use_global_stats,
                "axis": self._axis,
                "training": training,
            },
        )
        if training:
            out, mean, var = outs
            m = self._momentum
            with autograd.pause():
                rm._set_data(rm._data * m + mean._data * (1 - m))
                rv._set_data(rv._data * m + var._data * (1 - m))
            return out
        return outs[0] if isinstance(outs, (list, tuple)) else outs

    def __repr__(self):
        return f"BatchNorm(axis={self._axis}, eps={self._epsilon}, " \
               f"momentum={self._momentum}, in_channels={self.gamma.shape[0] if self.gamma.shape else None})"



class BatchNormReLU(BatchNorm):
    """BatchNorm with a fused trailing ReLU (reference basic_layers.py
    BatchNormReLU / src/operator/nn/batch_norm.cc bn_relu fusion — on TPU
    XLA fuses the relu into the normalization epilogue anyway; the class
    exists for API parity and graph clarity)."""

    def forward(self, x):
        out = super().forward(x)
        return invoke("relu", [out], {})

    def __repr__(self):
        return super().__repr__().replace("BatchNorm(", "BatchNormReLU(", 1)

class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm (reference
    ``src/operator/contrib/sync_batch_norm-inl.h``).

    TPU-native: inside a pjit/shard_map data-parallel step the batch axis is
    sharded over the mesh and XLA computes global batch statistics via
    ``lax.pmean`` automatically when the layer runs under
    ``mxnet_tpu.parallel`` (see parallel/psum hooks); eager single-device
    behaviour equals BatchNorm.
    """

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, center=True, scale=True, use_global_stats=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", **kwargs):
        super().__init__(
            axis=1, momentum=momentum, epsilon=epsilon, center=center,
            scale=scale, use_global_stats=use_global_stats,
            beta_initializer=beta_initializer,
            gamma_initializer=gamma_initializer,
            running_mean_initializer=running_mean_initializer,
            running_variance_initializer=running_variance_initializer,
            in_channels=in_channels)
        self._num_devices = num_devices


class InstanceNorm(HybridBlock):
    """Reference basic_layers.py InstanceNorm."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        from ... import initializer as init

        self._axis = axis
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=(in_channels,),
                               init=init.create(gamma_initializer),
                               allow_deferred_init=True, differentiable=scale)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=(in_channels,),
                              init=init.create(beta_initializer),
                              allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x):
        c = int(x.shape[self._axis])
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def forward(self, x):
        if self._axis != 1:
            x = x.swapaxes(1, self._axis)
        out = invoke(
            "InstanceNorm",
            [x, self.gamma.data(x.ctx), self.beta.data(x.ctx)],
            {"eps": self._epsilon},
        )
        if self._axis != 1:
            out = out.swapaxes(1, self._axis)
        return out


class LayerNorm(HybridBlock):
    """Reference basic_layers.py LayerNorm; op src/operator/nn/layer_norm.cc."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        from ... import initializer as init

        self._axis = axis
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=(in_channels,),
                               init=init.create(gamma_initializer),
                               allow_deferred_init=True, differentiable=scale)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=(in_channels,),
                              init=init.create(beta_initializer),
                              allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x):
        c = int(x.shape[self._axis])
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def forward(self, x):
        return invoke(
            "LayerNorm",
            [x, self.gamma.data(x.ctx), self.beta.data(x.ctx)],
            {"axis": self._axis, "eps": self._epsilon},
        )


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + epsilon) * gamma`` over the last axis (Zhang
    and Sennrich 2019): no mean is subtracted and there is no shift.  Takes
    and returns the type that arrives, float32 inside (op ``RMSNorm``)."""

    def __init__(self, epsilon=1e-5, gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        from ... import initializer as init

        self._epsilon = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init.create(gamma_initializer),
                               allow_deferred_init=True)

    def infer_shape(self, x):
        self.gamma.shape = (int(x.shape[-1]),)

    def forward(self, x):
        return invoke("RMSNorm", [x, self.gamma.data(x.ctx)],
                      {"eps": self._epsilon})


class LatentAttention(HybridBlock):
    """Causal multi-head latent attention as a model trains it (DeepSeek-V2,
    arXiv:2405.04434 section 2.1; names follow the public code):

        c_q = RMSNorm(q_a_proj x)               the query latent, q_lora_rank
        q = q_b_proj c_q                        a head: [q_nope; q_rope]
        [c_kv; k_rope] = kv_a_proj_with_mqa x   the key-value latent and ONE
                                                rotary key a token
        kv_b_proj RMSNorm(c_kv)                 a head: [k_nope; v]

    ``q_rope`` and ``k_rope`` take rotary positions, every head's key is
    ``[k_nope; k_rope]``, scores are scaled by ``1 / sqrt(nope + rope)``,
    and ``o_proj`` maps the heads' values back.  No bias.  The core is one
    operator, ``causal_latent_selfatt``; each stage runs under a scope the
    device trace reads (``LatentDown``, ``LatentUp``, the operator's
    ``LatentQK`` and ``LatentCore``, ``LatentOut``)."""

    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, epsilon=1e-5, weight_initializer=None,
                 out_initializer=None):
        super().__init__()
        self._heads, self._rope_dim = num_heads, qk_rope_head_dim
        self._kv_rank, self._theta = kv_lora_rank, float(rope_theta)

        def proj(units, in_units, init=weight_initializer):
            return Dense(units, use_bias=False, flatten=False,
                         in_units=in_units, weight_initializer=init)

        self.q_a_proj = proj(q_lora_rank, hidden_size)
        self.q_a_layernorm = RMSNorm(epsilon=epsilon, in_channels=q_lora_rank)
        self.q_b_proj = proj(
            num_heads * (qk_nope_head_dim + qk_rope_head_dim), q_lora_rank)
        self.kv_a_proj_with_mqa = proj(kv_lora_rank + qk_rope_head_dim,
                                       hidden_size)
        self.kv_a_layernorm = RMSNorm(epsilon=epsilon,
                                      in_channels=kv_lora_rank)
        self.kv_b_proj = proj(num_heads * (qk_nope_head_dim + v_head_dim),
                              kv_lora_rank)
        self.o_proj = proj(hidden_size, num_heads * v_head_dim,
                           out_initializer or weight_initializer)

    def forward(self, x):
        with jax.named_scope("LatentDown"):
            c_q = self.q_a_layernorm(self.q_a_proj(x))
            down = self.kv_a_proj_with_mqa(x)
            c_kv = self.kv_a_layernorm(
                down.slice_axis(axis=-1, begin=0, end=self._kv_rank))
            k_rope = down.slice_axis(axis=-1, begin=self._kv_rank, end=None)
        with jax.named_scope("LatentUp"):
            q, kv = self.q_b_proj(c_q), self.kv_b_proj(c_kv)
        out = invoke("causal_latent_selfatt", [q, kv, k_rope],
                     {"heads": self._heads, "rope_dim": self._rope_dim,
                      "theta": self._theta})
        with jax.named_scope("LatentOut"):
            return self.o_proj(out)


class GatedDeltaNet(HybridBlock):
    """Linear attention under the gated delta rule (Yang, Kautz and
    Hatamizadeh 2024, arXiv:2412.06464; names follow the public
    flash-linear-attention layer), for the ``held_heads`` of ``num_heads``:

        q, k, v = silu(conv(q_proj x)), silu(conv(k_proj x)), silu(conv(v_proj x))
        q = q / |q| / sqrt(key_dim),  k = k / |k|          a head
        beta = sigmoid(b_proj x)       x 2 with ``allow_neg_eigval``
        alpha = exp(-exp(A_log) * softplus(a_proj x + dt_bias))
        o = gated_delta_rule(q, k, v, log alpha, beta)
        y = o_proj(RMSNorm_head(o) * silu(g_proj x))

    The convolutions are depthwise, causal and without bias; the norm is
    over each head's values with ONE learned scale of ``value_dim``.  Every
    projection is built for the held heads alone, and the result is their
    part of ``o_proj``'s sum over heads: with every head held (the default)
    it is the whole layer, and the parts of disjoint shares add up to it.
    No bias anywhere."""

    def __init__(self, hidden_size, num_heads, key_dim, value_dim,
                 conv_kernel=4, chunk_size=64, allow_neg_eigval=False,
                 epsilon=1e-5, held_heads=None, weight_initializer=None,
                 out_initializer=None):
        super().__init__()
        from ... import initializer as init

        self._held = held_head_ids(num_heads, held_heads)
        held = len(self._held)
        self._dims = (held, key_dim, value_dim)
        self._chunk, self._eps = chunk_size, epsilon
        self._beta_scale = 2.0 if allow_neg_eigval else 1.0

        def proj(units, in_units=hidden_size, w=weight_initializer):
            return Dense(units, use_bias=False, flatten=False,
                         in_units=in_units, weight_initializer=w)

        def conv(name, channels):
            return Parameter(name, shape=(channels, conv_kernel),
                             init=init.Uniform(conv_kernel ** -0.5))

        self.q_proj = proj(held * key_dim)
        self.k_proj = proj(held * key_dim)
        self.v_proj = proj(held * value_dim)
        self.g_proj = proj(held * value_dim)
        self.a_proj, self.b_proj = proj(held), proj(held)
        self.q_conv = conv("q_conv", held * key_dim)
        self.k_conv = conv("k_conv", held * key_dim)
        self.v_conv = conv("v_conv", held * value_dim)
        self.A_log = Parameter("A_log", shape=(held,),
                               init=init.LogUniform(0.0, 16.0), wd_mult=0.0)
        self.dt_bias = Parameter("dt_bias", shape=(held,),
                                 init=init.TimeStepBias(), wd_mult=0.0)
        self.o_norm = Parameter("o_norm", shape=(value_dim,), init=init.One())
        self.o_proj = proj(hidden_size, held * value_dim,
                           out_initializer or weight_initializer)

    @property
    def held_heads(self):
        return self._held

    def forward(self, x):
        ctx = x.ctx
        held, key_dim, value_dim = self._dims
        bsz, length = x.shape[0], x.shape[1]

        def mixed(projected, weight, width, unit=None):
            """A projection through its short convolution and SiLU, cut
            into heads; with ``unit`` each head's vector is scaled to that
            length (queries and keys)."""
            y = invoke("causal_conv1d", [projected, weight.data(ctx)],
                       {"activation": "silu"})
            y = y.reshape((bsz, length, held, width))
            return y if unit is None else invoke("L2Norm", [y],
                                                 {"scale": unit})

        q = mixed(self.q_proj(x), self.q_conv, key_dim, key_dim ** -0.5)
        k = mixed(self.k_proj(x), self.k_conv, key_dim, 1.0)
        v = mixed(self.v_proj(x), self.v_conv, value_dim)
        rate = invoke("exp", [self.A_log.data(ctx)], {})
        log_alpha = invoke("negative", [rate * invoke(
            "softrelu", [self.a_proj(x) + self.dt_bias.data(ctx)], {})], {})
        beta = invoke("sigmoid", [self.b_proj(x).astype("float32")], {}) \
            * self._beta_scale
        o = invoke("gated_delta_rule", [q, k, v, log_alpha, beta],
                   {"chunk_size": self._chunk})
        gate = self.g_proj(x).reshape((bsz, length, held, value_dim))
        y = invoke("GatedRMSNorm", [o, gate, self.o_norm.data(ctx)],
                   {"eps": self._eps, "norm_before_gate": True})
        return self.o_proj(y.reshape((bsz, length, held * value_dim)))


def held_head_ids(num_heads, held_heads):
    """The ids of the heads a token mixer holds, checked: all of them when
    ``held_heads`` is None."""
    held = tuple(range(num_heads)) if held_heads is None \
        else tuple(int(h) for h in held_heads)
    if not held or len(set(held)) != len(held) \
            or not all(0 <= h < num_heads for h in held):
        raise ValueError(f"held_heads {held}: distinct ids of the "
                         f"{num_heads} heads, at least one")
    return held


class GroupNorm(HybridBlock):
    """Reference basic_layers.py GroupNorm; op src/operator/nn/group_norm.cc."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        from ... import initializer as init

        self._num_groups = num_groups
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=(in_channels,),
                               init=init.create(gamma_initializer),
                               allow_deferred_init=True, differentiable=scale)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=(in_channels,),
                              init=init.create(beta_initializer),
                              allow_deferred_init=True, differentiable=center)

    def infer_shape(self, x):
        c = int(x.shape[1])
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def forward(self, x):
        return invoke(
            "GroupNorm",
            [x, self.gamma.data(x.ctx), self.beta.data(x.ctx)],
            {"num_groups": self._num_groups, "eps": self._epsilon},
        )


class Embedding(HybridBlock):
    """Lookup table (reference basic_layers.py Embedding).

    The reference supports ``sparse_grad`` row_sparse gradients; on TPU the
    gradient is an XLA scatter-add produced by the vjp of ``take`` — dense,
    fused, no sparse storage needed.
    """

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False):
        super().__init__()
        self._input_dim = input_dim
        self._output_dim = output_dim
        self.weight = Parameter(
            "weight", shape=(input_dim, output_dim), dtype=dtype,
            init=weight_initializer,
            grad_stype="row_sparse" if sparse_grad else "default")

    def forward(self, x):
        return invoke(
            "embedding",
            [x, self.weight.data()],
            {"input_dim": self._input_dim, "output_dim": self._output_dim},
        )

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class Flatten(HybridBlock):
    def forward(self, x):
        return invoke("flatten", [x], {})

    def __repr__(self):
        return "Flatten"


class Lambda(Block):
    """Wrap a function into a Block (reference basic_layers.py Lambda)."""

    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            from ... import ndarray as nd

            function = getattr(nd, function)
        self._func = function
        self._func_name = getattr(function, "__name__", "custom")

    def forward(self, *args):
        return self._func(*args)

    def __repr__(self):
        return f"Lambda({self._func_name})"


class HybridLambda(HybridBlock):
    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            from ... import ndarray as nd

            function = getattr(nd, function)
        self._func = function
        self._func_name = getattr(function, "__name__", "custom")

    def forward(self, *args):
        return self._func(*args)

    def __repr__(self):
        return f"HybridLambda({self._func_name})"


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Concatenate(Sequential):
    """Run children on same input, concat outputs (reference contrib →
    basic_layers in 2.0)."""

    def __init__(self, axis=-1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        out = [block(x) for block in self._children.values()]
        return invoke("concat", out, {"dim": self.axis})


class HybridConcatenate(HybridSequential):
    def __init__(self, axis=-1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        out = [block(x) for block in self._children.values()]
        return invoke("concat", out, {"dim": self.axis})


from .activations import Activation  # noqa: E402  (cycle-free tail import)
