"""Granite-4.0-H-Micro, one tensor-parallel rank of two: the first period of
``layer_types`` (five Mamba-2 layers, an attention layer, four Mamba-2
layers), 32 of the 64 Mamba-2 heads, 16 of the 32 query heads with the 4 of
8 key-value heads they read, and an eighth of the tied vocabulary held here
(the JSON's ``deployment``).

What is code in this configuration: how the net is built through the
program's public API (``gluon.model_zoo.granite_hybrid``), operations per
sequence from the sizes in the JSON, seeded Zipf tokens with next-token
labels, and the plain reference (``jax.numpy``, float32, no Gluon, no
kernel, nothing of ``mxnet_tpu/ops``: the Mamba-2 recurrence one step a
token with its state written out, the convolution as shifted multiplies,
softmax attention under an explicit mask a block of queries at a time).
"""
from __future__ import annotations

import numpy as np

from perfbench import opcount

CHECK_INDEX = 10 ** 6        # the check batch's index in the seed's stream
QUERY_BLOCK = 512            # the reference's attention, rows at a time
HEAD_COUNTS = ("mamba_n_heads", "num_attention_heads", "num_key_value_heads")


# -- the system under test --------------------------------------------------
def build(mx, sizes):
    zoo = getattr(mx.gluon.model_zoo, "granite_hybrid", None)
    if zoo is None:
        raise RuntimeError("this checkout's mxnet_tpu has no "
                           "gluon.model_zoo.granite_hybrid: it cannot run "
                           "configuration granite_4_0_h_micro_tp2")
    published = {k: sizes["published"][k] for k in HEAD_COUNTS}
    net = zoo.granite_hybrid(
        {**sizes, **published}, held_heads=range(sizes["mamba_n_heads"]),
        recompute_layers=sizes["recompute_layers"],
        rescale_layers=sizes["rescale_layers"])
    net.initialize()
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    return {"net": net, "head_loss": lambda logits, y: ce(logits, y).mean(),
            "optimizer": sizes["optimizer"],
            "optimizer_params": dict(sizes["optimizer_params"])}


# -- operations from shapes -------------------------------------------------
def _layer_types(sizes):
    return sizes["layer_types"][:sizes["num_hidden_layers"]]


def _inner(sizes):
    return sizes["mamba_n_heads"] * sizes["mamba_d_head"]


def scan_macs(sizes, seq) -> int:
    """The chunked scan's four products over one sequence of one layer, as
    the SSD form states them at ``mamba_chunk_size``, the same whatever
    implements the scan: ``C B^T`` a group, the masked ``(C B^T . L) x`` a
    head, a chunk's state ``B^T x`` a head, and ``C S`` a head."""
    q, n = sizes["mamba_chunk_size"], sizes["mamba_d_state"]
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    return seq * (sizes["mamba_n_groups"] * q * n + heads * q * p
                  + 2 * heads * n * p)


def mamba_proj_macs(sizes, seq) -> int:
    """The held heads' projections of a Mamba-2 layer: ``in_proj`` into z,
    x, the group's B and C, and dt, and ``out_proj`` back."""
    h, inner = sizes["hidden_size"], _inner(sizes)
    bc = 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return opcount.dense_macs(seq, h, 2 * inner + bc + sizes["mamba_n_heads"]) \
        + opcount.dense_macs(seq, inner, h)


def attention_proj_macs(sizes, seq) -> int:
    h = sizes["hidden_size"]
    d = h // sizes["published"]["num_attention_heads"]
    q = sizes["num_attention_heads"] * d
    kv = sizes["num_key_value_heads"] * d
    return 2 * opcount.dense_macs(seq, h, q) + 2 * opcount.dense_macs(seq, h, kv)


def attention_core_macs(sizes, seq) -> int:
    """Score and value products at half their square: a causal kernel
    skips what lies above the diagonal."""
    d = sizes["hidden_size"] // sizes["published"]["num_attention_heads"]
    return opcount.attention_macs(sizes["num_attention_heads"], seq, seq,
                                  d) // 2


def ffn_macs(sizes, seq) -> int:
    return 3 * opcount.dense_macs(seq, sizes["hidden_size"],
                                  sizes["shared_intermediate_size"])


def head_macs(sizes, seq) -> int:
    return opcount.dense_macs(seq, sizes["hidden_size"], sizes["vocab_size"])


def forward_macs(sizes, seq) -> int:
    """One sequence: every matrix product of the layers and of the tied
    head.  The embedding lookup is a gather; the depthwise convolution (4
    multiply-adds a channel) is not counted."""
    mixers = {"mamba": mamba_proj_macs(sizes, seq) + scan_macs(sizes, seq),
              "attention": attention_proj_macs(sizes, seq)
              + attention_core_macs(sizes, seq)}
    return (sum(mixers[kind] + ffn_macs(sizes, seq)
                for kind in _layer_types(sizes))
            + head_macs(sizes, seq))


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
# terms of the mathematics ``reference(without=...)`` can leave out: each
# multiplier of the configuration (left out: 1, or for the attention's the
# usual 1 / sqrt(head_dim)) and the Mamba-2 mixer's own terms
TERMS = ("embedding_multiplier", "residual_multiplier", "logits_scaling",
         "attention_multiplier", "decay", "skip", "convolution", "conv_bias",
         "output_gate", "gated_norm")


def reference(params, tokens, labels, sizes, operand_dtype=None, without=()):
    """``(loss, logits)`` in float32 at the highest matmul precision, for
    the same held heads and vocabulary slice as the program.
    ``operand_dtype`` rounds both operands of every matrix product (the
    projections, the scan's writes into and reads of its state, the
    attention products, the feed-forward, the head) to that type first:
    what a lower precision than the configuration's reads, for setting the
    limits between two readings.  ``without`` names terms of the
    mathematics to leave out (``TERMS``).

    Departures, as the program's: the mixers' outputs are the held heads'
    part of the output projections' sums; the gated norm's mean square is
    over the held heads' width."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    unknown = set(without) - set(TERMS)
    if unknown:
        raise ValueError(f"without={sorted(unknown)}: not in {TERMS}")
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    eps = sizes["rms_norm_eps"]
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    n, inner = sizes["mamba_d_state"], _inner(sizes)

    def multiplier(name):
        return 1.0 if name in without else sizes[name]

    def par(name):
        return jnp.asarray(params[name], f32)

    def low(t):
        return t if operand_dtype is None \
            else t.astype(operand_dtype).astype(f32)

    def dense(x, name):                           # no bias anywhere
        return jnp.einsum("...i,oi->...o", low(x), low(par(name + ".weight")),
                          precision=hi)

    def rms(x, gamma):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma

    def silu(x):
        return x * jax.nn.sigmoid(x)

    def mamba(x, at):
        bsz, seq, _ = x.shape
        zxbcdt = dense(x, at + ".in_proj")
        z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-heads],
                      zxbcdt[..., -heads:])
        if "convolution" not in without:          # depthwise, causal
            w = par(at + ".conv_weight")          # (channels, kernel)
            kernel = w.shape[1]
            padded = jnp.pad(xbc, ((0, 0), (kernel - 1, 0), (0, 0)))
            xbc = sum(padded[:, j:j + seq] * w[:, j] for j in range(kernel))
        if "conv_bias" not in without:
            xbc = xbc + par(at + ".conv_bias")
        xbc = silu(xbc)
        xs = xbc[..., :inner].reshape(bsz, seq, heads, p)
        b, c = xbc[..., inner:inner + n], xbc[..., inner + n:]   # one group
        dt = jax.nn.softplus(dt + par(at + ".dt_bias"))
        a = -jnp.exp(par(at + ".A_log"))

        def token(state, inp):                    # (b, heads, p, n)
            x_t, dt_t, b_t, c_t = inp
            decay = jnp.exp(dt_t * a)[..., None, None]
            if "decay" in without:
                decay = jnp.ones_like(decay)
            write = jnp.einsum("bhp,bn->bhpn", low(dt_t[..., None] * x_t),
                               low(b_t), precision=hi)
            state = decay * state + write
            return state, jnp.einsum("bhpn,bn->bhp", low(state), low(c_t),
                                     precision=hi)

        _, y = lax.scan(token, jnp.zeros((bsz, heads, p, n), f32),
                        tuple(t.swapaxes(0, 1) for t in (xs, dt, b, c)))
        y = y.swapaxes(0, 1)                      # (b, s, heads, p)
        if "skip" not in without:
            y = y + par(at + ".D")[:, None] * xs
        y = y.reshape(bsz, seq, inner)
        if "output_gate" not in without:
            y = y * silu(z)
        if "gated_norm" not in without:           # one group: the held width
            y = rms(y, par(at + ".norm_weight"))
        return dense(y, at + ".out_proj")

    def attention(x, at):
        bsz, seq, _ = x.shape
        d = sizes["hidden_size"] // sizes["published"]["num_attention_heads"]
        q_heads, kv_heads = sizes["num_attention_heads"], \
            sizes["num_key_value_heads"]
        group = q_heads // kv_heads
        scale = d ** -0.5 if "attention_multiplier" in without \
            else sizes["attention_multiplier"]
        q = dense(x, at + ".q_proj").reshape(bsz, seq, kv_heads, group, d)
        k, v = (dense(x, at + name).reshape(bsz, seq, kv_heads, d)
                for name in (".k_proj", ".v_proj"))
        block = min(QUERY_BLOCK, seq)
        pad = -seq % block
        rows = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))) \
            .reshape(bsz, -1, block, kv_heads, group, d).swapaxes(0, 1)
        keys = jnp.arange(seq)

        def query_block(inp):                     # a block of queries
            start, q_b = inp
            scores = jnp.einsum("bqhgd,bkhd->bhgqk", low(q_b), low(k),
                                precision=hi) * scale
            visible = keys[None, :] <= (start + jnp.arange(block))[:, None]
            att = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
            return jnp.einsum("bhgqk,bkhd->bqhgd", low(att), low(v),
                              precision=hi)

        out = lax.map(query_block,
                      (jnp.arange(rows.shape[0]) * block, rows))
        out = out.swapaxes(0, 1).reshape(bsz, -1, q_heads * d)[:, :seq]
        return dense(out, at + ".o_proj")

    def mlp(x, at):
        gate, up = jnp.split(dense(x, at + ".gate_up_proj"), 2, axis=-1)
        return dense(silu(gate) * up, at + ".down_proj")

    r = multiplier("residual_multiplier")
    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    embedding = par("model.embed_tokens.weight")
    x = embedding[tokens] * multiplier("embedding_multiplier")
    for i, kind in enumerate(_layer_types(sizes)):
        at = f"model.layers.{i}"
        mixer = mamba if kind == "mamba" else attention
        x = x + r * mixer(rms(x, par(at + ".input_layernorm.gamma")),
                          at + ".mixer")
        x = x + r * mlp(rms(x, par(at + ".post_attention_layernorm.gamma")),
                        at + ".mlp")
    # the head IS the embedding; ``params`` may name a head of its own
    # (``lm_head.weight``), so that a test can take the two uses apart
    head = par("lm_head.weight") if "lm_head.weight" in params else embedding
    logits = jnp.einsum("...i,oi->...o", low(rms(x, par("model.norm.gamma"))),
                        low(head), precision=hi) \
        / multiplier("logits_scaling")
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
    return loss, logits
