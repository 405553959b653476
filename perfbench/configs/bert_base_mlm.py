"""BERT-base (Devlin et al. 2018, arXiv:1810.04805) with its masked-LM head,
as the model zoo builds it.

What is code in this configuration: how the net is built through the
program's public API, operations per sequence from the sizes in
``bert_base_mlm.json``, seeded Zipf tokens with 15% masked, and the plain
reference (``jax.numpy``, float32, no Gluon).  Where the model-zoo block
departs from the paper is listed in the JSON under ``departures``; the
reference follows the block as built, departures included.
"""
from __future__ import annotations

import numpy as np

from perfbench import opcount

LN_EPS = 1e-5


# -- the system under test --------------------------------------------------
def build(mx, sizes):
    bert = mx.gluon.model_zoo.bert

    class MLM(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.encoder = bert.BERTModel(
                vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
                mlp_units=sizes["intermediate_size"],
                num_layers=sizes["num_hidden_layers"],
                num_heads=sizes["num_attention_heads"],
                max_len=sizes["max_position_embeddings"],
                dropout=sizes["dropout"])
            self.head = bert.BERTMaskedLMHead(sizes["vocab_size"],
                                              units=sizes["hidden_size"])

        def forward(self, tokens):
            return self.head(self.encoder(tokens))

    net = MLM()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    return {"net": net, "head_loss": lambda logits, y: ce(logits, y).mean(),
            "optimizer": sizes["optimizer"],
            "optimizer_params": dict(sizes["optimizer_params"])}


# -- operations from shapes -------------------------------------------------
def layer_macs(sizes, seq) -> int:
    """One encoder layer over one sequence: the four projections, the two
    feed-forward products and attention's score and value products."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    heads = sizes["num_attention_heads"]
    return (opcount.dense_macs(seq, d, 3 * d) + opcount.dense_macs(seq, d, d)
            + opcount.dense_macs(seq, d, ff) + opcount.dense_macs(seq, ff, d)
            + opcount.attention_macs(heads, seq, seq, d // heads))


def forward_macs(sizes, seq) -> int:
    """One sequence: the layers, then the head's transform and its decoder
    over every position (as the model-zoo head computes it).  Embedding
    lookups are gathers, not products."""
    d = sizes["hidden_size"]
    return (sizes["num_hidden_layers"] * layer_macs(sizes, seq)
            + opcount.dense_macs(seq, d, d)
            + opcount.dense_macs(seq, d, sizes["vocab_size"]))


def ops_per_sample(sizes, mix) -> int:
    return opcount.train_ops(forward_macs(sizes, mix["seq_len"]))


# -- traffic: Zipf tokens, 15% masked ---------------------------------------
def _batch(seed, index, batch, sizes, seq):
    d = sizes["data"]
    rng = np.random.default_rng([seed, 1, index])
    first = d["first_token_id"]                 # ids below are special tokens
    ranks = rng.zipf(d["zipf_a"], (batch, seq))
    labels = (first + (ranks - 1) % (sizes["vocab_size"] - first)
              ).astype(np.int32)
    masked = rng.random((batch, seq)) < d["mask_share"]
    tokens = np.where(masked, np.int32(d["mask_token_id"]), labels)
    return tokens, labels


def make_pool(seed, sizes, mix, batch, n):
    """``n`` host batches of int32 (tokens, labels): the labels are the
    original tokens at EVERY position, since the model-zoo head scores all
    of them."""
    return [_batch(seed, i, batch, sizes, mix["seq_len"]) for i in range(n)]


def check_batch(seed, sizes, mix):
    return _batch(seed, 10 ** 6, sizes["check"]["batch"], sizes,
                  mix["seq_len"])


# -- the plain reference ----------------------------------------------------
def reference(params, tokens, labels, sizes):
    """``(loss, logits)`` in float32 at the highest matmul precision, dropout
    off.  ``params`` maps ``collect_params()`` names to arrays."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    heads = sizes["num_attention_heads"]

    def p(name):
        return jnp.asarray(params[name], f32)

    def dense(x, prefix):
        return jnp.einsum("...i,oi->...o", x, p(prefix + ".weight"),
                          precision=lax.Precision.HIGHEST) + p(prefix + ".bias")

    def ln(x, prefix):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return ((x - mean) * lax.rsqrt(var + LN_EPS) * p(prefix + ".gamma")
                + p(prefix + ".beta"))

    def gelu(x):
        return 0.5 * x * (1.0 + lax.erf(x / jnp.sqrt(f32(2.0))))

    def attention(x, prefix):
        b, s, d = x.shape
        # the block's interleaved layout: per head, query, key, value
        qkv = dense(x, prefix + ".qkv").reshape(b, s, heads, 3, d // heads)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            precision=lax.Precision.HIGHEST) \
            / jnp.sqrt(f32(d // heads))
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                         v, precision=lax.Precision.HIGHEST)
        return dense(out.reshape(b, s, d), prefix + ".out_proj")

    tokens = jnp.asarray(tokens)
    x = p("encoder.word_embed.weight")[tokens] \
        + p("encoder.pos_embed.weight")[jnp.arange(tokens.shape[1])]
    x = ln(x, "encoder.embed_ln")
    for i in range(sizes["num_hidden_layers"]):
        at = f"encoder.layers.{i}"
        x = x + attention(ln(x, at + ".ln1"), at + ".attn")      # pre-LN
        x = x + dense(gelu(dense(ln(x, at + ".ln2"), at + ".ffn_1")),
                      at + ".ffn_2")
    x = ln(x, "encoder.final_ln")
    x = ln(gelu(dense(x, "head.transform")), "head.ln")
    logits = dense(x, "head.decoder")
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                                axis=-1).mean()
    return loss, logits
