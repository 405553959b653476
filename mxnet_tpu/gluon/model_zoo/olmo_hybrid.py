"""Olmo-Hybrid (``model_type: olmo_hybrid``): a decoder whose layers are, by
``layer_types``, linear attention under the gated delta rule
(``gluon.nn.GatedDeltaNet``) or full causal attention with a norm on queries
and keys, each followed by a dense SiLU-gated feed-forward.  The norms stand
AFTER each sub-block, on what it adds to the residual stream (the Olmo 2
convention):

    h = x + RMSNorm(mixer(x));  out = h + RMSNorm(mlp(h))       every layer
    logits = lm_head(RMSNorm(out))                              an untied head

    full attention: q = RMSNorm(q_proj x), k = RMSNorm(k_proj x) over the
    whole projection, causal softmax(q k^T / sqrt(head_dim)) v, o_proj; no
    positional encoding (the linear layers carry position)

Both token mixers are told which heads they hold (``held_heads``): they
build their projections for those heads alone and give the held heads' part
of ``o_proj``'s sum, which is what a tensor-parallel rank holds of a layer
before its all-reduce.  The full layer's QK-norm then takes its mean square
over the held features (a deployment all-reduces one scalar a token for it).
The feed-forward is whole.  Under ``amp.init`` the residual stream takes the
low-precision type.  Serving this model (the rule's state as a cache beside
keys and values) is not built.
"""
from __future__ import annotations

import math

from ... import initializer
from ...ndarray.ndarray import invoke
from ..block import HybridBlock
from ..nn import Dense, Embedding, GatedDeltaNet, HybridSequential, RMSNorm
from ..nn.basic_layers import held_head_ids
from .decoder_parts import GatedMLP, low_stream, run_layers

__all__ = ["OlmoHybridAttention", "OlmoHybridMLP", "OlmoHybridDecoderLayer",
           "OlmoHybridModel", "OlmoHybridForCausalLM", "olmo_hybrid"]

LAYER_TYPES = ("linear_attention", "full_attention")


class OlmoHybridAttention(HybridBlock):
    """Causal self-attention over the ``held_heads`` of ``num_heads``, as
    many key-value heads as query heads, with an RMS norm over the whole
    query projection and one over the whole key projection (here: over the
    held heads' features).  The core is one operator,
    ``causal_gqa_selfatt``."""

    def __init__(self, hidden_size, num_heads, head_dim, epsilon=1e-6,
                 held_heads=None, weight_initializer=None,
                 out_initializer=None):
        super().__init__()
        self._held = held_head_ids(num_heads, held_heads)
        width = len(self._held) * head_dim

        def proj(units, in_units, init=weight_initializer):
            return Dense(units, use_bias=False, flatten=False,
                         in_units=in_units, weight_initializer=init)

        self.q_proj = proj(width, hidden_size)
        self.k_proj = proj(width, hidden_size)
        self.v_proj = proj(width, hidden_size)
        self.q_norm = RMSNorm(epsilon=epsilon, in_channels=width)
        self.k_norm = RMSNorm(epsilon=epsilon, in_channels=width)
        self.o_proj = proj(hidden_size, width,
                           out_initializer or weight_initializer)

    @property
    def held_heads(self):
        return self._held

    def forward(self, x):
        heads = len(self._held)
        out = invoke("causal_gqa_selfatt",
                     [self.q_norm(self.q_proj(x)),
                      self.k_norm(self.k_proj(x)), self.v_proj(x)],
                     {"heads": heads, "kv_heads": heads})
        return self.o_proj(out)


class OlmoHybridMLP(GatedMLP):
    """``down_proj(silu(gate x) * (up x))``, gate and up side by side in
    one product (``decoder_parts.GatedMLP`` under this model's scope)."""


class OlmoHybridDecoderLayer(HybridBlock):
    """``h = x + RMSNorm(mixer(x)); h + RMSNorm(mlp(h))``."""

    def __init__(self, hidden_size, mixer, mlp, eps):
        super().__init__()
        self.mixer = mixer
        self.post_attention_layernorm = RMSNorm(epsilon=eps,
                                                in_channels=hidden_size)
        self.mlp = mlp
        self.post_feedforward_layernorm = RMSNorm(epsilon=eps,
                                                  in_channels=hidden_size)

    def forward(self, x):
        h = x + self.post_attention_layernorm(self.mixer(x))
        return h + self.post_feedforward_layernorm(self.mlp(h))


class OlmoHybridModel(HybridBlock):
    """Embedding, the layers of ``layer_types``, the final norm.
    ``forward(tokens[B, S]) -> hidden [B, S, hidden_size]``."""

    def __init__(self, config, held_heads, init, out_init, chunk_size,
                 recompute_layers):
        super().__init__()
        c = config
        h, eps = c["hidden_size"], c["rms_norm_eps"]
        heads = c["num_attention_heads"]
        types = c["layer_types"][:c["num_hidden_layers"]]
        unknown = set(types) - set(LAYER_TYPES)
        if unknown or len(types) != c["num_hidden_layers"]:
            raise ValueError(
                f"layer_types {c['layer_types']!r}: num_hidden_layers "
                f"({c['num_hidden_layers']}) entries, each of {LAYER_TYPES}")
        if c.get("num_key_value_heads", heads) != heads \
                or c["linear_num_key_heads"] != c["linear_num_value_heads"]:
            raise ValueError("this model has as many key-value heads as "
                             "query heads, and as many linear key heads as "
                             "value heads")
        self._recompute = recompute_layers
        self.embed_tokens = Embedding(c["vocab_size"], h,
                                      weight_initializer=init)
        self.layers = HybridSequential()
        for kind in types:
            if kind == "linear_attention":
                mixer = GatedDeltaNet(
                    h, c["linear_num_value_heads"], c["linear_key_head_dim"],
                    c["linear_value_head_dim"], c["linear_conv_kernel_dim"],
                    chunk_size, c["linear_allow_neg_eigval"], eps,
                    held_heads, init, out_init)
            else:
                mixer = OlmoHybridAttention(
                    h, heads, c.get("head_dim") or h // heads, eps,
                    held_heads, init, out_init)
            self.layers.add(OlmoHybridDecoderLayer(
                h, mixer, OlmoHybridMLP(h, c["intermediate_size"], init,
                                        out_init), eps))
        self.norm = RMSNorm(epsilon=eps, in_channels=h)

    def forward(self, tokens):
        x = low_stream(self.embed_tokens(tokens))
        return self.norm(run_layers(self.layers, x, self._recompute))


class OlmoHybridForCausalLM(HybridBlock):
    """The model and an untied head: ``forward(tokens) -> logits``."""

    def __init__(self, config, held_heads=None, recompute_layers=False,
                 init_std=0.02, rescale_layers=None, chunk_size=64):
        super().__init__()
        c = dict(config)
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError("hidden_act: this model's feed-forward is "
                             f"silu-gated, got {c['hidden_act']!r}")
        init = initializer.Normal(init_std)
        # the projections that write into the residual stream start smaller
        # by 1 / sqrt(2 x layers) where ``rescale_layers`` says so
        out_init = initializer.Normal(
            init_std / math.sqrt(2 * rescale_layers)) if rescale_layers \
            else init
        self.model = OlmoHybridModel(c, held_heads, init, out_init,
                                     chunk_size, recompute_layers)
        self.lm_head = Dense(c["vocab_size"], use_bias=False, flatten=False,
                             in_units=c["hidden_size"],
                             weight_initializer=init)

    def forward(self, tokens):
        return self.lm_head(self.model(tokens))


def olmo_hybrid(config, **overrides):
    """An :class:`OlmoHybridForCausalLM` from an ``olmo_hybrid``
    ``config.json`` as a dict (keys this module does not read are ignored;
    the head counts are the PUBLISHED ones, ``vocab_size`` the rows held
    here, ``layer_types`` is read up to ``num_hidden_layers``).
    ``overrides``: ``held_heads`` (ids of the heads both kinds of token
    mixer hold here; all by default), ``recompute_layers``, ``init_std``,
    ``rescale_layers`` (a depth: the projections into the residual stream
    start at ``init_std / sqrt(2 x rescale_layers)``) and ``chunk_size`` (the
    delta rule's)."""
    return OlmoHybridForCausalLM(config, **overrides)
