"""Granite-4.0-H (``model_type: granitemoehybrid`` with no experts): a
decoder whose layers are each a token mixer, by ``layer_types`` a Mamba-2
state-space mixer or causal attention with grouped key-value heads, AND a
dense SiLU-gated feed-forward; pre-norm, every sub-block's output scaled by
``residual_multiplier`` before it joins the residual stream (names follow
the public ``granitemoehybrid`` code):

    x_0 = embedding_multiplier * E[ids]
    h = x + r * mixer(RMSNorm(x));  out = h + r * mlp(RMSNorm(h))   every layer
    logits = RMSNorm(x) E^T / logits_scaling                        a TIED head

    attention: softmax(q k^T * attention_multiplier + causal mask) v, no
    positional encoding (the Mamba-2 layers carry position)

The mixers are Nemotron-H's own blocks (``NemotronHMamba2Mixer``,
``NemotronHAttention``), told which heads they hold (``held_heads``): a
tensor-parallel rank's share, whose result is the held heads' part of the
output projection's sum before the all-reduce.  With one group the Mamba-2
mixer's gated norm takes its mean square over the held heads' width (a
deployment all-reduces one scalar a token for it).  The feed-forward is
whole.  The head is the embedding's own ``Parameter``: one weight, one
optimizer state, gradients from both uses.  Under ``amp.init`` the residual
stream takes the low-precision type.  Serving this model (the scan's state as
a cache beside keys and values) is not built.
"""
from __future__ import annotations

from ... import initializer
from ...ndarray.ndarray import invoke
from ..block import HybridBlock
from ..nn import Embedding, HybridSequential, RMSNorm
from ..nn.basic_layers import held_head_ids
from .decoder_parts import GatedMLP, low_stream, run_layers
from .nemotron_h import (_INIT_STD, NemotronHAttention, NemotronHMamba2Mixer,
                         _residual_out_init)

__all__ = ["GraniteHybridMLP", "GraniteHybridDecoderLayer",
           "GraniteHybridModel", "GraniteHybridForCausalLM", "granite_hybrid"]

LAYER_TYPES = ("mamba", "attention")


class GraniteHybridMLP(GatedMLP):
    """``down_proj(silu(a) * b)`` with ``[a, b] = gate_up_proj x``: the gate
    is the first half, as Granite's ``input_linear`` is split."""


class GraniteHybridDecoderLayer(HybridBlock):
    """``h = x + r mixer(RMSNorm(x)); h + r mlp(RMSNorm(h))``."""

    def __init__(self, hidden_size, mixer, mlp, eps, residual_multiplier):
        super().__init__()
        self._r = residual_multiplier
        self.input_layernorm = RMSNorm(epsilon=eps, in_channels=hidden_size)
        self.mixer = mixer
        self.post_attention_layernorm = RMSNorm(epsilon=eps,
                                                in_channels=hidden_size)
        self.mlp = mlp

    def forward(self, x):
        h = x + self.mixer(self.input_layernorm(x)) * self._r
        return h + self.mlp(self.post_attention_layernorm(h)) * self._r


def _attention_share(held, mamba_heads, heads):
    """The query heads that go with the held Mamba-2 heads: query head
    ``j`` with Mamba-2 heads ``j r .. j r + r - 1``, ``r = mamba_heads /
    heads``; a query head goes only with all of its own."""
    r = mamba_heads // heads
    if r * heads != mamba_heads:
        raise ValueError(f"{mamba_heads} Mamba-2 heads do not share out "
                         f"over {heads} query heads")
    share = sorted({h // r for h in held})
    if sorted(held) != [j * r + i for j in share for i in range(r)]:
        raise ValueError(f"held_heads {held}: whole runs of {r} Mamba-2 "
                         f"heads a query head")
    return share


class GraniteHybridModel(HybridBlock):
    """Embedding (times ``embedding_multiplier``), the layers of
    ``layer_types``, the final norm.  ``forward(tokens[B, S]) -> hidden
    [B, S, hidden_size]``."""

    def __init__(self, config, held_heads, rescale_layers, recompute_layers):
        super().__init__()
        c = config
        h, eps = c["hidden_size"], c["rms_norm_eps"]
        types = c["layer_types"][:c["num_hidden_layers"]]
        if set(types) - set(LAYER_TYPES) \
                or len(types) != c["num_hidden_layers"]:
            raise ValueError(
                f"layer_types {c['layer_types']!r}: num_hidden_layers "
                f"({c['num_hidden_layers']}) entries, each of {LAYER_TYPES}")
        mamba_heads, heads = c["mamba_n_heads"], c["num_attention_heads"]
        held = held_head_ids(mamba_heads, held_heads)
        attention_held = _attention_share(held, mamba_heads, heads)
        # Nemotron-H's initial values: a normal of 0.02, the projections into
        # the residual stream smaller by 1 / sqrt(2 x rescale_layers)
        depth = rescale_layers or len(types)
        init, out_init = initializer.Normal(_INIT_STD), \
            _residual_out_init(depth)
        self._multiplier = c["embedding_multiplier"]
        self._recompute = recompute_layers
        self.embed_tokens = Embedding(c["vocab_size"], h,
                                      weight_initializer=init)
        self.layers = HybridSequential()
        for kind in types:
            if kind == "mamba":
                mixer = NemotronHMamba2Mixer(
                    h, mamba_heads, c["mamba_d_head"], c["mamba_n_groups"],
                    c["mamba_d_state"], c["mamba_d_conv"],
                    c["mamba_chunk_size"], eps, depth, held_heads=held)
            else:
                mixer = NemotronHAttention(
                    h, heads, c["num_key_value_heads"], h // heads, depth,
                    held_heads=attention_held,
                    scale=c["attention_multiplier"])
            self.layers.add(GraniteHybridDecoderLayer(
                h, mixer, GraniteHybridMLP(h, c["shared_intermediate_size"],
                                           init, out_init),
                eps, c["residual_multiplier"]))
        self.norm = RMSNorm(epsilon=eps, in_channels=h)

    def forward(self, tokens):
        x = low_stream(self.embed_tokens(tokens) * self._multiplier)
        return self.norm(run_layers(self.layers, x, self._recompute))


class GraniteHybridForCausalLM(HybridBlock):
    """The model and the head tied to its embedding: ``forward(tokens) ->
    logits``, ``RMSNorm(x) E^T / logits_scaling``."""

    def __init__(self, config, held_heads=None, recompute_layers=False,
                 rescale_layers=None):
        super().__init__()
        c = dict(config)
        for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", True),
                          ("position_embedding_type", "nope"),
                          ("num_local_experts", 0)):
            if c.get(key, want) != want:
                raise ValueError(f"{key}: this model is built for {want!r}, "
                                 f"got {c[key]!r}")
        self._vocab = c["vocab_size"]
        self._logits_scale = 1.0 / c["logits_scaling"]
        self.model = GraniteHybridModel(c, held_heads, rescale_layers,
                                        recompute_layers)

    def forward(self, tokens):
        x = self.model(tokens)
        logits = invoke("FullyConnected",
                        [x, self.model.embed_tokens.weight.data(x.ctx)],
                        {"num_hidden": self._vocab, "no_bias": True,
                         "flatten": False})
        return logits * self._logits_scale


def granite_hybrid(config, **overrides):
    """A :class:`GraniteHybridForCausalLM` from a ``granitemoehybrid``
    ``config.json`` with no experts, as a dict (keys this module does not
    read are ignored; the head counts are the PUBLISHED ones,
    ``vocab_size`` the rows held here, ``layer_types`` is read up to
    ``num_hidden_layers``).  ``overrides``: ``held_heads`` (ids of the
    Mamba-2 heads held here, all by default; the attention layers hold the
    query heads of the same share, query head ``j`` with Mamba-2 heads
    ``j r`` to ``j r + r - 1`` where ``r`` is Mamba-2 heads over query
    heads, and the key-value heads those read), ``recompute_layers`` and
    ``rescale_layers`` (a depth, the layers built by default: every matrix
    starts as a normal of 0.02, as Nemotron-H's, and the projections into
    the residual stream at ``0.02 / sqrt(2 x rescale_layers)``)."""
    return GraniteHybridForCausalLM(config, **overrides)
