"""GLM-4.7-Flash (``model_type: glm4_moe_lite``): a decoder of pre-norm
residual layers with multi-head latent attention, a leading dense gated
feed-forward layer, sigmoid-routed gated sparse experts with a shared expert
behind it, and a multi-token-prediction module that shares the embedding and
the head (the layer equations are DeepSeek-V2/V3's, arXiv:2405.04434 section
2.1 and arXiv:2412.19437 sections 2.1-2.2; names follow the public code).

    x = x + self_attn(RMSNorm(x));  x = x + mlp(RMSNorm(x))     every layer
    logits = lm_head(RMSNorm(x))                                an untied head

    the module at depth 1, for position i:
    h' = eh_proj [RMSNorm_e(Emb(t_{i+1})); RMSNorm_h(h_i)]      h_i after the
    logits' = lm_head(RMSNorm(layer(h')))                       final norm

``Emb`` and ``lm_head`` are the main model's own blocks, called a second
time: one ``Parameter`` each, gradients from both uses, one optimizer state.
The module's logits at position ``i`` predict token ``i + 2``.  The model
returns the depths stacked, (batch, 1 + modules, seq, vocabulary), which is
what ``gluon.loss.MultiTokenCrossEntropyLoss`` takes; without a module it
returns (batch, seq, vocabulary).

The expert layer is told which experts it holds (``held_experts``), as
Nemotron-H's is (``sparse_experts``).  Under ``amp.init`` the residual
stream takes the low-precision type.  Serving this model (the latent as the
cache, the absorbed products, the module as a draft) is not built.
"""
from __future__ import annotations

import functools
import math

import jax

from ... import amp, initializer
from ...ndarray.ndarray import invoke
from ..block import HybridBlock, remat_call
from ..nn import Dense, Embedding, HybridSequential, LatentAttention, RMSNorm
from .sparse_experts import SparseExperts

__all__ = ["Glm4MoeLiteMLP", "Glm4MoeLiteMoE", "Glm4MoeLiteDecoderLayer",
           "Glm4MoeLiteModel", "Glm4MoeLiteMTP", "Glm4MoeLiteForCausalLM",
           "glm4_moe_lite"]


class Glm4MoeLiteMLP(HybridBlock):
    """``down_proj(silu(gate x) * (up x))``; ``gate_up_proj`` gives gate and
    up side by side in one product."""

    def __init__(self, hidden_size, intermediate_size, init, out_init):
        super().__init__()
        self._width = intermediate_size
        self.gate_up_proj = Dense(2 * intermediate_size, use_bias=False,
                                  flatten=False, in_units=hidden_size,
                                  weight_initializer=init)
        self.down_proj = Dense(hidden_size, use_bias=False, flatten=False,
                               in_units=intermediate_size,
                               weight_initializer=out_init)

    def forward(self, x):
        both = self.gate_up_proj(x)
        gate = both.slice_axis(axis=-1, begin=0, end=self._width)
        up = both.slice_axis(axis=-1, begin=self._width, end=None)
        return self.down_proj(gate * invoke("sigmoid", [gate], {}) * up)


class Glm4MoeLiteMoE(SparseExperts):
    """Sparse experts with a shared expert (``sparse_experts``): routed
    gated experts and ``n_shared_experts`` gated experts' width of shared
    expert for every token."""

    def __init__(self, hidden_size, num_experts, top_k, moe_intermediate_size,
                 shared_intermediate_size, routed_scaling_factor, held, init,
                 out_init, hidden_act="silu", capacity_factor=2.0):
        super().__init__(
            hidden_size, num_experts, top_k, moe_intermediate_size,
            Glm4MoeLiteMLP(hidden_size, shared_intermediate_size, init,
                           out_init),
            routed_scaling_factor, held, hidden_act=hidden_act, init=init,
            down_init=out_init, capacity_factor=capacity_factor)


class Glm4MoeLiteDecoderLayer(HybridBlock):
    """``x += self_attn(RMSNorm(x)); x += mlp(RMSNorm(x))``."""

    def __init__(self, hidden_size, self_attn, mlp, eps):
        super().__init__()
        self.input_layernorm = RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.self_attn = self_attn
        self.post_attention_layernorm = RMSNorm(epsilon=eps,
                                                in_channels=hidden_size)
        self.mlp = mlp

    def forward(self, x):
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


def _layer(c, dense, *, held, capacity, init, out_init):
    """One decoder layer of config ``c``: a dense feed-forward or sparse
    experts behind the same latent attention.  ``held``: the expert ids
    held here, ``capacity`` their buffer's factor; ``init`` the matrices'
    initializer, ``out_init`` that of the projections into the residual
    stream."""
    h, eps = c["hidden_size"], c["rms_norm_eps"]
    attn = LatentAttention(
        h, c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"],
        c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
        rope_theta=c["rope_theta"], epsilon=eps, weight_initializer=init,
        out_initializer=out_init)
    if dense:
        mlp = Glm4MoeLiteMLP(h, c["intermediate_size"], init, out_init)
    else:
        mlp = Glm4MoeLiteMoE(
            h, c["n_routed_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"],
            c["moe_intermediate_size"] * c["n_shared_experts"],
            c["routed_scaling_factor"], held, init, out_init,
            hidden_act=c.get("hidden_act", "silu"), capacity_factor=capacity)
    return Glm4MoeLiteDecoderLayer(h, attn, mlp, eps)


def _low(x):
    """The residual stream follows the activations' type under AMP."""
    low = amp.target_dtype()
    return x if low is None else x.astype(low)


def _run(layers, x, recompute):
    if not recompute:
        return layers(x)
    for layer in layers:
        x = remat_call([layer], x)
    return x


class Glm4MoeLiteModel(HybridBlock):
    """Embedding, ``first_k_dense_replace`` dense layers, the sparse layers
    behind them, the final norm.  ``forward(tokens[B, S]) -> hidden``."""

    def __init__(self, config, make_layer, init, recompute_layers):
        super().__init__()
        c = config
        self._recompute = recompute_layers
        self.embed_tokens = Embedding(c["vocab_size"], c["hidden_size"],
                                      weight_initializer=init)
        self.layers = HybridSequential()
        for i in range(c["num_hidden_layers"]):
            self.layers.add(make_layer(i < c["first_k_dense_replace"]))
        self.norm = RMSNorm(epsilon=c["rms_norm_eps"],
                            in_channels=c["hidden_size"])

    def forward(self, tokens):
        x = _low(self.embed_tokens(tokens))
        return self.norm(_run(self.layers, x, self._recompute))


class Glm4MoeLiteMTP(HybridBlock):
    """One multi-token-prediction module: the two norms, the projection of
    their concatenation (embedding first), one sparse decoder layer and the
    module's own final norm.  ``forward(hidden, next_embedding)``; the
    embedding and the head stay the main model's."""

    def __init__(self, config, make_layer, init, recompute_layers):
        super().__init__()
        h, eps = config["hidden_size"], config["rms_norm_eps"]
        self._recompute = recompute_layers
        self.enorm = RMSNorm(epsilon=eps, in_channels=h)
        self.hnorm = RMSNorm(epsilon=eps, in_channels=h)
        self.eh_proj = Dense(h, use_bias=False, flatten=False, in_units=2 * h,
                             weight_initializer=init)
        self.layers = HybridSequential()
        self.layers.add(make_layer(False))
        self.norm = RMSNorm(epsilon=eps, in_channels=h)

    def forward(self, hidden, next_embedding):
        with jax.named_scope("MTPProjection"):
            x = self.eh_proj(invoke(
                "concat", [self.enorm(next_embedding), self.hnorm(hidden)],
                {"dim": -1}))
        return self.norm(_run(self.layers, x, self._recompute))


class Glm4MoeLiteForCausalLM(HybridBlock):
    """The model, an untied head and ``num_nextn_predict_layers`` modules
    (0 or 1): ``forward(tokens) -> logits`` (module docstring)."""

    def __init__(self, config, held_experts=None, recompute_layers=False,
                 init_std=0.02, rescale_layers=None,
                 expert_capacity_factor=2.0):
        super().__init__()
        c = dict(config)
        modules = c.get("num_nextn_predict_layers", 0)
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError("hidden_act: this model's feed-forwards are "
                             f"silu-gated, got {c['hidden_act']!r}")
        if modules not in (0, 1):
            raise ValueError("num_nextn_predict_layers: 0 or 1 modules, got "
                             f"{modules}")
        init = initializer.Normal(init_std)
        # the projections that write into the residual stream start smaller
        # by 1 / sqrt(2 x layers) where ``rescale_layers`` says so
        out_init = initializer.Normal(
            init_std / math.sqrt(2 * rescale_layers)) if rescale_layers \
            else init
        make_layer = functools.partial(
            _layer, c, held=held_experts, capacity=expert_capacity_factor,
            init=init, out_init=out_init)
        self.model = Glm4MoeLiteModel(c, make_layer, init, recompute_layers)
        self.lm_head = Dense(c["vocab_size"], use_bias=False, flatten=False,
                             in_units=c["hidden_size"],
                             weight_initializer=init)
        self.mtp = Glm4MoeLiteMTP(c, make_layer, init, recompute_layers) \
            if modules else None

    def forward(self, tokens):
        hidden = self.model(tokens)
        logits = self.lm_head(hidden)
        if self.mtp is None:
            return logits
        # position i of the module reads token i + 1; the last position has
        # none (what it reads there is masked out of the loss, and no
        # earlier position sees it under the causal mask)
        nxt = invoke("roll", [tokens], {"shift": -1, "axis": 1})
        with jax.named_scope("MTPModule"):
            ahead = self.mtp(hidden, _low(self.model.embed_tokens(nxt)))
            ahead = self.lm_head(ahead)
        return invoke("stack", [logits, ahead], {"axis": 1})


def glm4_moe_lite(config, **overrides):
    """A :class:`Glm4MoeLiteForCausalLM` from a ``glm4_moe_lite``
    ``config.json`` as a dict (keys this module does not read are ignored;
    ``vocab_size`` is the rows held here, a slice of the vocabulary or all
    of it).  ``overrides``: ``held_experts`` (ids of the routed experts held
    here), ``recompute_layers``, ``init_std``, ``rescale_layers`` (a
    depth: the projections into the residual stream start at ``init_std /
    sqrt(2 x rescale_layers)``) and ``expert_capacity_factor`` (the held
    experts' buffer over their mean share of the rows)."""
    return Glm4MoeLiteForCausalLM(config, **overrides)
