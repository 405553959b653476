"""Nemotron-H: a hybrid decoder whose layers are each ONE mixer, chosen by a
pattern string: ``M`` a Mamba-2 state-space mixer, ``E`` sigmoid-routed
sparse experts with a shared expert, ``*`` causal attention with grouped
key-value heads (NVIDIA 2025, arXiv:2504.03624; names follow the public
``nemotron_h`` code).

    x = x + mixer(RMSNorm(x))      every layer; no bias but the convolution's
    logits = lm_head(RMSNorm(x))   an untied head

The attention layers carry no positional encoding (the Mamba-2 layers carry
position).  The expert layer is told which experts it holds (``held``): it
routes over all of them and computes the part of the result its own experts
give (``parallel.moe.held_experts_layer``); with every expert held it is the
whole layer.  Under ``amp.init`` the residual stream takes the low-precision
type (the model's ``residual_in_fp32`` is false).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax.numpy as jnp

from ... import amp, initializer
from ...ndarray.ndarray import invoke
from ..block import HybridBlock, remat_call
from ..nn import Dense, Embedding, HybridSequential, RMSNorm
from ..nn.basic_layers import held_head_ids
from ..parameter import Parameter
from .sparse_experts import SparseExperts

__all__ = ["NemotronHMamba2Mixer", "NemotronHAttention", "NemotronHMLP",
           "NemotronHMoE", "NemotronHBlock", "NemotronHModel",
           "NemotronHForCausalLM", "nemotron_h"]

_INIT_STD = 0.02


def _residual_out_init(rescale_layers):
    """``rescale_prenorm_residual``: the projections that write into the
    residual stream start smaller by ``1 / sqrt(2 x layers)``."""
    return initializer.Normal(_INIT_STD / math.sqrt(2 * rescale_layers))


class _ALog(initializer.Initializer):
    """``A_log = log(1..heads)``, so ``A = -exp(A_log)`` is -1..-heads; a
    mixer that holds some of the heads (``held``, ids) takes their own
    entries, ``log(id + 1)``."""

    def __init__(self, held=None):
        super().__init__()
        self._held = held

    def _init_weight(self, _, arr):
        if self._held is None:
            self._fill(arr, jnp.log(jnp.arange(1, arr.shape[0] + 1,
                                               dtype=jnp.float32)))
        else:
            self._fill(arr, jnp.log(jnp.asarray(self._held, jnp.float32)
                                    + 1.0))


class NemotronHMamba2Mixer(HybridBlock):
    """The Mamba-2 mixer: one input projection into gate, convolved
    ``x B C`` and time steps; a causal depthwise convolution with SiLU; the
    selective scan (``ssd_scan``, chunked); a gated grouped RMS norm; the
    output projection.

    ``held_heads`` (ids of the ``num_heads``; all by default) builds the
    mixer for those heads alone, as a tensor-parallel rank holds it: their
    rows of ``z``, ``x`` and ``dt`` in ``in_proj``, their convolution
    channels, ``A_log`` (their own entries), ``dt_bias``, ``D``, norm scale
    and columns of ``out_proj``.  With ONE group ``B`` and ``C`` and their
    channels are whole on every rank, and the gated norm's group is the held
    heads' width; the result is the held heads' part of ``out_proj``'s
    sum."""

    def __init__(self, hidden_size, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk_size=128, eps=1e-5, rescale_layers=1,
                 time_step=(0.001, 0.1, 1e-4), held_heads=None):
        super().__init__()
        held = None if held_heads is None \
            else held_head_ids(num_heads, held_heads)
        if held is not None and len(held) < num_heads and n_groups != 1:
            raise ValueError(f"held_heads: a share of the heads needs ONE "
                             f"group of B and C, not {n_groups}")
        self._held = held or tuple(range(num_heads))
        num_heads = len(self._held)
        self._heads, self._head_dim = num_heads, head_dim
        self._groups, self._state = n_groups, state_size
        self._chunk, self._eps = chunk_size, eps
        self._inner = num_heads * head_dim
        self._conv_dim = self._inner + 2 * n_groups * state_size
        normal = initializer.Normal(_INIT_STD)
        self.in_proj = Dense(self._inner + self._conv_dim + num_heads,
                             use_bias=False, flatten=False,
                             in_units=hidden_size, weight_initializer=normal)
        bound = 1.0 / math.sqrt(conv_kernel)     # a Conv1d's default range
        self.conv_weight = Parameter(
            "conv_weight", shape=(self._conv_dim, conv_kernel),
            init=initializer.Uniform(bound))
        self.conv_bias = Parameter("conv_bias", shape=(self._conv_dim,),
                                   init=initializer.Uniform(bound))
        self.dt_bias = Parameter("dt_bias", shape=(num_heads,),
                                 init=initializer.TimeStepBias(*time_step),
                                 wd_mult=0.0)
        self.A_log = Parameter("A_log", shape=(num_heads,), init=_ALog(held),
                               wd_mult=0.0)
        self.D = Parameter("D", shape=(num_heads,), init=initializer.One(),
                           wd_mult=0.0)
        self.norm_weight = Parameter("norm_weight", shape=(self._inner,),
                                     init=initializer.One())
        self.out_proj = Dense(hidden_size, use_bias=False, flatten=False,
                              in_units=self._inner,
                              weight_initializer=_residual_out_init(
                                  rescale_layers))

    @property
    def held_heads(self):
        return self._held

    def forward(self, x):
        ctx = x.ctx
        bsz, length = x.shape[0], x.shape[1]
        bc = self._groups * self._state
        proj = self.in_proj(x)
        z = proj.slice_axis(axis=-1, begin=0, end=self._inner)
        dt = proj.slice_axis(axis=-1, begin=self._inner + self._conv_dim,
                             end=None)
        # the convolution reads its channels out of the whole projection and
        # hands x, B and C over as three arrays: no slice of either side is
        # written out for it
        xs, b, c = invoke("causal_conv1d",
                          [proj, self.conv_weight.data(ctx),
                           self.conv_bias.data(ctx)],
                          {"activation": "silu", "begin": self._inner,
                           "splits": (self._inner, bc, bc)})
        dt = invoke("softrelu", [dt + self.dt_bias.data(ctx)], {})
        a = invoke("negative", [invoke("exp", [self.A_log.data(ctx)], {})],
                   {})
        y = invoke("ssd_scan", [
            xs.reshape((bsz, length, self._heads, self._head_dim)), dt, a,
            b.reshape((bsz, length, self._groups, self._state)),
            c.reshape((bsz, length, self._groups, self._state)),
            self.D.data(ctx)], {"chunk_size": self._chunk})
        y = invoke("GatedRMSNorm",
                   [y.reshape((bsz, length, self._inner)), z,
                    self.norm_weight.data(ctx)],
                   {"num_groups": self._groups, "eps": self._eps})
        return self.out_proj(y)


class NemotronHAttention(HybridBlock):
    """Causal self-attention, ``num_heads`` query heads over
    ``num_kv_heads`` key-value heads, no positional encoding.  The core is
    one operator, ``causal_gqa_selfatt``, its scores scaled by ``scale``
    (``1 / sqrt(head_dim)`` where None).

    ``held_heads`` (ids of the query heads, whole groups of a key-value
    head; all by default) builds the layer for those heads and the
    key-value heads they read, as a tensor-parallel rank holds it; the
    result is the held heads' part of ``o_proj``'s sum."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 rescale_layers=1, held_heads=None, scale=None):
        super().__init__()
        held = held_head_ids(num_heads, held_heads)
        group = num_heads // num_kv_heads
        kv_held = sorted({h // group for h in held})
        if sorted(held) != [k * group + g for k in kv_held
                            for g in range(group)]:
            raise ValueError(f"held_heads {held}: whole groups of {group} "
                             f"query heads a key-value head, in order")
        self._held = held
        self._heads, self._kv_heads = len(held), len(kv_held)
        self._scale = scale
        normal = initializer.Normal(_INIT_STD)

        def proj(units):
            return Dense(units, use_bias=False, flatten=False,
                         in_units=hidden_size, weight_initializer=normal)

        self.q_proj = proj(self._heads * head_dim)
        self.k_proj = proj(self._kv_heads * head_dim)
        self.v_proj = proj(self._kv_heads * head_dim)
        self.o_proj = Dense(hidden_size, use_bias=False, flatten=False,
                            in_units=self._heads * head_dim,
                            weight_initializer=_residual_out_init(
                                rescale_layers))

    @property
    def held_heads(self):
        return self._held

    def forward(self, x):
        attrs = {"heads": self._heads, "kv_heads": self._kv_heads}
        if self._scale is not None:
            attrs["scale"] = self._scale
        out = invoke("causal_gqa_selfatt",
                     [self.q_proj(x), self.k_proj(x), self.v_proj(x)], attrs)
        return self.o_proj(out)


class NemotronHMLP(HybridBlock):
    """``down_proj(relu(up_proj(x))^2)``."""

    def __init__(self, hidden_size, intermediate_size, rescale_layers=1):
        super().__init__()
        self.up_proj = Dense(intermediate_size, use_bias=False, flatten=False,
                             in_units=hidden_size,
                             weight_initializer=initializer.Normal(_INIT_STD))
        self.down_proj = Dense(hidden_size, use_bias=False, flatten=False,
                               in_units=intermediate_size,
                               weight_initializer=_residual_out_init(
                                   rescale_layers))

    def forward(self, x):
        h = invoke("relu", [self.up_proj(x)], {})
        return self.down_proj(h * h)


class NemotronHMoE(SparseExperts):
    """Sparse experts with a shared expert (``sparse_experts``): routed
    ``relu^2`` experts and one shared ``relu^2`` expert for every token."""

    def __init__(self, hidden_size, num_experts, top_k, moe_intermediate_size,
                 shared_intermediate_size, routed_scaling_factor=1.0,
                 held: Optional[Sequence[int]] = None, rescale_layers=1):
        super().__init__(
            hidden_size, num_experts, top_k, moe_intermediate_size,
            NemotronHMLP(hidden_size, shared_intermediate_size,
                         rescale_layers),
            routed_scaling_factor, held, hidden_act="relu2",
            init=initializer.Normal(_INIT_STD),
            down_init=_residual_out_init(rescale_layers))


class NemotronHBlock(HybridBlock):
    """``x + mixer(RMSNorm(x))``."""

    def __init__(self, hidden_size, mixer, eps=1e-5):
        super().__init__()
        self.norm = RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.mixer = mixer

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(HybridBlock):
    """Embedding, the layers of ``hybrid_override_pattern``, final norm.
    ``forward(tokens[B, S]) -> hidden [B, S, hidden_size]``."""

    def __init__(self, vocab_size, hidden_size, hybrid_override_pattern, *,
                 mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size,
                 conv_kernel=4, chunk_size=128, num_attention_heads,
                 num_key_value_heads, head_dim, n_routed_experts,
                 num_experts_per_tok, moe_intermediate_size,
                 moe_shared_expert_intermediate_size,
                 routed_scaling_factor=1.0, held_experts=None,
                 layer_norm_epsilon=1e-5,
                 rescale_layers=None, time_step=(0.001, 0.1, 1e-4),
                 recompute_layers=False):
        super().__init__()
        depth = rescale_layers or len(hybrid_override_pattern)
        self._recompute = recompute_layers
        self.embeddings = Embedding(
            vocab_size, hidden_size,
            weight_initializer=initializer.Normal(_INIT_STD))
        self.layers = HybridSequential()
        for kind in hybrid_override_pattern:
            if kind == "M":
                mixer = NemotronHMamba2Mixer(
                    hidden_size, mamba_num_heads, mamba_head_dim, n_groups,
                    ssm_state_size, conv_kernel, chunk_size,
                    layer_norm_epsilon, depth, time_step)
            elif kind == "*":
                mixer = NemotronHAttention(
                    hidden_size, num_attention_heads, num_key_value_heads,
                    head_dim, depth)
            elif kind == "E":
                mixer = NemotronHMoE(
                    hidden_size, n_routed_experts, num_experts_per_tok,
                    moe_intermediate_size,
                    moe_shared_expert_intermediate_size,
                    routed_scaling_factor, held_experts, depth)
            else:
                raise ValueError(
                    f"hybrid_override_pattern {hybrid_override_pattern!r}: "
                    f"{kind!r} is none of M, E, *")
            self.layers.add(NemotronHBlock(hidden_size, mixer,
                                           layer_norm_epsilon))
        self.norm_f = RMSNorm(epsilon=layer_norm_epsilon,
                              in_channels=hidden_size)

    def forward(self, tokens):
        x = self.embeddings(tokens)
        low = amp.target_dtype()
        if low is not None:
            x = x.astype(low)             # residual_in_fp32 is false
        if self._recompute:
            for layer in self.layers:
                x = remat_call([layer], x)
        else:
            x = self.layers(x)
        return self.norm_f(x)


class NemotronHForCausalLM(HybridBlock):
    """The backbone and an untied head: ``forward(tokens) -> logits``."""

    def __init__(self, vocab_size, hidden_size, hybrid_override_pattern,
                 **kwargs):
        super().__init__()
        self.backbone = NemotronHModel(vocab_size, hidden_size,
                                       hybrid_override_pattern, **kwargs)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             in_units=hidden_size,
                             weight_initializer=initializer.Normal(_INIT_STD))

    def forward(self, tokens):
        return self.lm_head(self.backbone(tokens))


def nemotron_h(config, **overrides):
    """A :class:`NemotronHForCausalLM` from a ``nemotron_h`` ``config.json``
    as a dict (keys this module does not read are ignored).  ``overrides``:
    ``held_experts`` (ids of the routed experts held here) and
    ``recompute_layers``."""
    c = dict(config)
    return NemotronHForCausalLM(
        c["vocab_size"], c["hidden_size"], c["hybrid_override_pattern"],
        mamba_num_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
        ssm_state_size=c["ssm_state_size"], conv_kernel=c["conv_kernel"],
        chunk_size=c["chunk_size"],
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], n_routed_experts=c["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=c[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=c["routed_scaling_factor"],
        layer_norm_epsilon=c["layer_norm_epsilon"],
        rescale_layers=c.get("rescale_layers"),
        time_step=(c["time_step_min"], c["time_step_max"],
                   c["time_step_floor"]),
        **overrides)
