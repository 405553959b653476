"""Olmo-Hybrid-7B, one tensor-parallel rank of two: the first period of
``layer_types`` (three gated delta-rule layers and a full-attention layer),
15 of the 30 heads of every token mixer and an eighth of the vocabulary
held here (the JSON's ``deployment``).

What is code in this configuration: how the net is built through the
program's public API (``gluon.model_zoo.olmo_hybrid``), operations per
sequence from the sizes in the JSON, seeded Zipf tokens with next-token
labels, and the plain reference (``jax.numpy``, float32, no Gluon, no
kernel, nothing of ``mxnet_tpu/ops``: the delta rule one step a token with
its state written out, the convolution as shifted multiplies, softmax
attention under an explicit mask a block of queries at a time).
"""
from __future__ import annotations

import numpy as np

from perfbench import opcount

CHECK_INDEX = 10 ** 6        # the check batch's index in the seed's stream
QUERY_BLOCK = 512            # the reference's attention, rows at a time


def _held(sizes):
    return tuple(range(sizes["num_attention_heads"]))


# -- the system under test --------------------------------------------------
def build(mx, sizes):
    zoo = getattr(mx.gluon.model_zoo, "olmo_hybrid", None)
    if zoo is None:
        raise RuntimeError("this checkout's mxnet_tpu has no "
                           "gluon.model_zoo.olmo_hybrid: it cannot run "
                           "configuration olmo_hybrid_7b_tp2")
    published = {k: sizes["published_heads"]
                 for k in ("num_attention_heads", "num_key_value_heads",
                           "linear_num_key_heads", "linear_num_value_heads")}
    net = zoo.olmo_hybrid(
        {**sizes, **published}, held_heads=_held(sizes),
        recompute_layers=sizes["recompute_layers"],
        init_std=sizes["init_std"], rescale_layers=sizes["rescale_layers"],
        chunk_size=sizes["chunk_size"])
    net.initialize()
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    return {"net": net, "head_loss": lambda logits, y: ce(logits, y).mean(),
            "optimizer": sizes["optimizer"],
            "optimizer_params": dict(sizes["optimizer_params"])}


# -- operations from shapes -------------------------------------------------
def _layer_types(sizes):
    return sizes["layer_types"][:sizes["num_hidden_layers"]]


def delta_rule_macs(sizes, seq) -> int:
    """The gated delta rule's chunk products over one sequence of one
    layer, as the chunked form states them (``ops/delta_rule.py``), the
    same whatever implements the rule.  A chunk of ``c`` tokens of one head:
    ``K_beta K^T`` and ``Q K^T`` (c x c x key), the inverse times the decayed
    keys (c x c x key) and times the values (c x c x value), the masked
    ``Q K^T`` times the corrected values (c x c x value), and the three
    products with the carried state (c x key x value each).  The triangular
    inverse itself is not counted."""
    c, heads = sizes["chunk_size"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    a_chunk = c * c * (3 * dk + 2 * dv) + 3 * c * dk * dv
    return seq // c * heads * a_chunk


def linear_proj_macs(sizes, seq) -> int:
    """The held heads' projections of a delta-rule layer: q, k, v, the
    output gate, the two gates' rows, and ``o_proj``."""
    h, heads = sizes["hidden_size"], sizes["linear_num_value_heads"]
    key = heads * sizes["linear_key_head_dim"]
    value = heads * sizes["linear_value_head_dim"]
    return opcount.dense_macs(seq, h, 2 * key + 2 * value + 2 * heads) \
        + opcount.dense_macs(seq, value, h)


def full_core_macs(sizes, seq) -> int:
    """Score and value products at half their square: a causal kernel
    skips what lies above the diagonal."""
    return opcount.attention_macs(sizes["num_attention_heads"], seq, seq,
                                  sizes["head_dim"]) // 2


def full_proj_macs(sizes, seq) -> int:
    width = sizes["num_attention_heads"] * sizes["head_dim"]
    return 4 * opcount.dense_macs(seq, sizes["hidden_size"], width)


def ffn_macs(sizes, seq) -> int:
    return 3 * opcount.dense_macs(seq, sizes["hidden_size"],
                                  sizes["intermediate_size"])


def forward_macs(sizes, seq) -> int:
    """One sequence: every matrix product of the layers and of the head.
    The embedding lookup is a gather; the depthwise convolutions (4
    multiply-adds a channel) and the triangular inverse are not counted."""
    mixers = {"linear_attention": linear_proj_macs(sizes, seq)
              + delta_rule_macs(sizes, seq),
              "full_attention": full_proj_macs(sizes, seq)
              + full_core_macs(sizes, seq)}
    return (sum(mixers[kind] + ffn_macs(sizes, seq)
                for kind in _layer_types(sizes))
            + opcount.dense_macs(seq, sizes["hidden_size"],
                                 sizes["vocab_size"]))


def ops_per_sample(sizes, mix) -> int:
    return opcount.train_ops(forward_macs(sizes, mix["seq_len"]))


# -- traffic: Zipf tokens, labels the next token ----------------------------
def _batch(seed, index, batch, sizes, seq):
    rng = np.random.default_rng([seed, 1, index])
    ranks = rng.zipf(sizes["data"]["zipf_a"], (batch, seq + 1))
    ids = ((ranks - 1) % sizes["vocab_size"]).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def make_pool(seed, sizes, mix, batch, n):
    """``n`` host batches of int32 (tokens, next tokens)."""
    return [_batch(seed, i, batch, sizes, mix["seq_len"]) for i in range(n)]


def check_batch(seed, sizes, mix):
    return _batch(seed, CHECK_INDEX, sizes["check"]["batch"], sizes,
                  mix["seq_len"])


# -- the plain reference ----------------------------------------------------
# terms of the mathematics ``reference(without=...)`` can leave out; what the
# tests hold the tolerances against
TERMS = ("beta_double", "decay", "delta_term", "q_l2norm", "k_l2norm",
         "key_scale", "convolution", "output_gate", "head_norm", "qk_norm",
         "post_norm")


def reference(params, tokens, labels, sizes, operand_dtype=None, without=()):
    """``(loss, logits)`` in float32 at the highest matmul precision, for
    the same held heads and vocabulary slice as the program.
    ``operand_dtype`` rounds both operands of every matrix product (the
    projections, the rule's reads and writes of its state, the attention
    products, the feed-forward, the head) to that type first: what a lower
    precision than the configuration's reads, for setting the limits
    between two readings.  ``without`` names terms of the mathematics to
    leave out (``TERMS``).

    Departures, as the program's: the mixers' outputs are the held heads'
    part of ``o_proj``'s sum; QK-norm's mean square is over the held 1,920
    features."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    unknown = set(without) - set(TERMS)
    if unknown:
        raise ValueError(f"without={sorted(unknown)}: not in {TERMS}")
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    eps = sizes["rms_norm_eps"]
    heads = sizes["num_attention_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]

    def p(name):
        return jnp.asarray(params[name], f32)

    def low(t):
        return t if operand_dtype is None \
            else t.astype(operand_dtype).astype(f32)

    def dense(x, name):                           # no bias anywhere
        return jnp.einsum("...i,oi->...o", low(x), low(p(name + ".weight")),
                          precision=hi)

    def rms(x, gamma):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma

    def silu(x):
        return x * jax.nn.sigmoid(x)

    def conv(x, name):
        """Depthwise, causal, no bias: ``out[t] = sum_j w[:, j] x[t - 3 + j]``,
        then SiLU."""
        if "convolution" in without:
            return silu(x)
        w = p(name)                               # (channels, kernel)
        kernel, length = w.shape[1], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (kernel - 1, 0), (0, 0)))
        return silu(sum(padded[:, j:j + length] * w[:, j]
                        for j in range(kernel)))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def delta_net(x, at):
        bsz, seq, _ = x.shape

        def head_split(t, width):
            return t.reshape(bsz, seq, heads, width)

        q = head_split(conv(dense(x, at + ".q_proj"), at + ".q_conv"), dk)
        k = head_split(conv(dense(x, at + ".k_proj"), at + ".k_conv"), dk)
        v = head_split(conv(dense(x, at + ".v_proj"), at + ".v_conv"), dv)
        if "q_l2norm" not in without:
            q = unit(q)
        if "k_l2norm" not in without:
            k = unit(k)
        if "key_scale" not in without:
            q = q * dk ** -0.5
        beta = jax.nn.sigmoid(dense(x, at + ".b_proj"))
        if "beta_double" not in without and sizes["linear_allow_neg_eigval"]:
            beta = 2.0 * beta
        alpha = jnp.exp(-jnp.exp(p(at + ".A_log")) * jax.nn.softplus(
            dense(x, at + ".a_proj") + p(at + ".dt_bias")))
        if "decay" in without:
            alpha = jnp.ones_like(alpha)

        def token(state, inp):                    # (b, heads, dv, dk)
            q_t, k_t, v_t, a_t, b_t = inp
            read = jnp.einsum("bhed,bhd->bhe", low(state), low(k_t),
                              precision=hi)       # S k
            if "delta_term" in without:
                read = jnp.zeros_like(read)
            state = a_t[..., None, None] * (
                state - (b_t[..., None] * read)[..., None]
                * k_t[:, :, None, :]) \
                + (b_t[..., None] * v_t)[..., None] * k_t[:, :, None, :]
            return state, jnp.einsum("bhed,bhd->bhe", low(state), low(q_t),
                                     precision=hi)

        _, o = lax.scan(token, jnp.zeros((bsz, heads, dv, dk), f32),
                        tuple(t.swapaxes(0, 1)
                              for t in (q, k, v, alpha, beta)))
        o = o.swapaxes(0, 1)                      # (b, s, heads, dv)
        if "head_norm" not in without:
            o = rms(o, p(at + ".o_norm"))         # a head's 192, one scale
        if "output_gate" not in without:
            o = o * silu(head_split(dense(x, at + ".g_proj"), dv))
        return dense(o.reshape(bsz, seq, heads * dv), at + ".o_proj")

    def attention(x, at):
        bsz, seq, _ = x.shape
        d = sizes["head_dim"]
        q, k = dense(x, at + ".q_proj"), dense(x, at + ".k_proj")
        if "qk_norm" not in without:              # over the held features
            q = rms(q, p(at + ".q_norm.gamma"))
            k = rms(k, p(at + ".k_norm.gamma"))
        q, k, v = (t.reshape(bsz, seq, heads, d)
                   for t in (q, k, dense(x, at + ".v_proj")))
        block = min(QUERY_BLOCK, seq)
        pad = -seq % block
        rows = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) \
            .reshape(bsz, -1, block, heads, d).swapaxes(0, 1)
        keys = jnp.arange(seq)

        def query_block(inp):                     # a block of queries
            start, q_b = inp
            scores = jnp.einsum("bqhd,bkhd->bhqk", low(q_b), low(k),
                                precision=hi) / jnp.sqrt(f32(d))
            visible = keys[None, :] <= (start + jnp.arange(block))[:, None]
            att = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
            return jnp.einsum("bhqk,bkhd->bqhd", low(att), low(v),
                              precision=hi)

        out = lax.map(query_block,
                      (jnp.arange(rows.shape[0]) * block, rows))
        out = out.swapaxes(0, 1).reshape(bsz, -1, heads * d)[:, :seq]
        return dense(out, at + ".o_proj")

    def mlp(x, at):
        gate, up = jnp.split(dense(x, at + ".gate_up_proj"), 2, axis=-1)
        return dense(silu(gate) * up, at + ".down_proj")

    def after(x, name):                           # the norm AFTER a sub-block
        return x if "post_norm" in without else rms(x, p(name + ".gamma"))

    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    x = p("model.embed_tokens.weight")[tokens]
    for i, kind in enumerate(_layer_types(sizes)):
        at = f"model.layers.{i}"
        mixer = delta_net if kind == "linear_attention" else attention
        x = x + after(mixer(x, at + ".mixer"),
                      at + ".post_attention_layernorm")
        x = x + after(mlp(x, at + ".mlp"), at + ".post_feedforward_layernorm")
    logits = dense(rms(x, p("model.norm.gamma")), "lm_head")
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
    return loss, logits
