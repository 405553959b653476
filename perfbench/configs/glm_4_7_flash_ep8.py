"""GLM-4.7-Flash, one expert-parallel rank of eight: the leading dense layer,
the four expert layers behind it and the multi-token-prediction module, 8 of
64 routed experts in every expert layer and an eighth of the vocabulary held
here (the JSON's ``deployment``).

What is code in this configuration: how the net is built through the
program's public API (``gluon.model_zoo.glm4_moe_lite``), operations per
sequence from the sizes in the JSON, seeded Zipf tokens with next-token
labels, the plain reference (``jax.numpy``, float32, no Gluon, no kernel:
latent attention a head at a time under an explicit mask with the rotary key
written once and used by each head, the experts as a loop over the held ids,
the multi-token-prediction pass written out with the shared tables passed a
second time), and the comparison that knows what a router's near tie is
(``compare``, called by driver ``train_fixed_shape_routed``).
"""
from __future__ import annotations

import numpy as np

from perfbench import opcount

CHECK_INDEX = 10 ** 6        # the check batch's index in the seed's stream


def _held(sizes):
    return tuple(range(sizes["n_routed_experts"]))


# -- the system under test --------------------------------------------------
def build(mx, sizes):
    """The timed window's initial weights come from ``timed_seed`` whatever
    the run's ``--seed`` (the JSON's ``timed_seed_why``)."""
    zoo = getattr(mx.gluon.model_zoo, "glm4_moe_lite", None)
    if zoo is None:
        raise RuntimeError("this checkout's mxnet_tpu has no "
                           "gluon.model_zoo.glm4_moe_lite: it cannot run "
                           "configuration glm_4_7_flash_ep8")
    mx.random.seed(sizes["timed_seed"])
    config = {**sizes, "n_routed_experts": sizes["router_experts"]}
    net = zoo.glm4_moe_lite(
        config, held_experts=_held(sizes),
        recompute_layers=sizes["recompute_layers"],
        init_std=sizes["init_std"], rescale_layers=sizes["rescale_layers"],
        expert_capacity_factor=sizes["expert_capacity_factor"])
    net.initialize()
    net.hybridize()
    loss = mx.gluon.loss.MultiTokenCrossEntropyLoss(
        (1.0, sizes["mtp_loss_weight"]))
    return {"net": net, "head_loss": lambda logits, y: loss(logits, y).mean(),
            "optimizer": sizes["optimizer"],
            "optimizer_params": dict(sizes["optimizer_params"])}


# -- operations from shapes -------------------------------------------------
def latent_core_macs(sizes, seq) -> int:
    """Score and value products of one layer's attention core at half their
    square (a causal kernel skips what lies above the diagonal): every head
    scores over ``nope + rope`` and sums values of ``v_head_dim``."""
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return sizes["num_attention_heads"] * seq * seq \
        * (qk + sizes["v_head_dim"]) // 2


def latent_proj_macs(sizes, seq) -> int:
    """The two down-projections and the two up-projections."""
    h, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    return (opcount.dense_macs(seq, h, sizes["q_lora_rank"])
            + opcount.dense_macs(seq, sizes["q_lora_rank"],
                                 heads * (nope + rope))
            + opcount.dense_macs(seq, h, sizes["kv_lora_rank"] + rope)
            + opcount.dense_macs(seq, sizes["kv_lora_rank"],
                                 heads * (nope + sizes["v_head_dim"])))


def attention_macs(sizes, seq) -> int:
    return (latent_proj_macs(sizes, seq) + latent_core_macs(sizes, seq)
            + opcount.dense_macs(
                seq, sizes["num_attention_heads"] * sizes["v_head_dim"],
                sizes["hidden_size"]))


def gated_macs(sizes, rows, width) -> int:
    """``rows`` through one gated feed-forward: gate, up and down."""
    return 3 * opcount.dense_macs(rows, sizes["hidden_size"], width)


def expert_row_macs(sizes) -> int:
    """One row through one routed expert: its three products."""
    return gated_macs(sizes, 1, sizes["moe_intermediate_size"])


def mean_held_rows(sizes, seq) -> float:
    """Rows the held experts get a sequence at the MEAN share."""
    return seq * sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes["router_experts"]


def moe_macs(sizes, seq) -> int:
    return (opcount.dense_macs(seq, sizes["hidden_size"],
                               sizes["router_experts"])
            + gated_macs(sizes, seq, sizes["moe_intermediate_size"]
                         * sizes["n_shared_experts"])
            + int(mean_held_rows(sizes, seq) * expert_row_macs(sizes)))


def head_macs(sizes, seq) -> int:
    return opcount.dense_macs(seq, sizes["hidden_size"], sizes["vocab_size"])


def forward_macs(sizes, seq) -> int:
    """One sequence: every product of the layers, of the module and of BOTH
    head passes.  The embedding lookups are gathers."""
    h = sizes["hidden_size"]
    dense = sizes["first_k_dense_replace"]
    sparse = sizes["num_hidden_layers"] - dense
    main = (sizes["num_hidden_layers"] * attention_macs(sizes, seq)
            + dense * gated_macs(sizes, seq, sizes["intermediate_size"])
            + sparse * moe_macs(sizes, seq) + head_macs(sizes, seq))
    module = (opcount.dense_macs(seq, 2 * h, h) + attention_macs(sizes, seq)
              + moe_macs(sizes, seq) + head_macs(sizes, seq))
    return main + sizes["num_nextn_predict_layers"] * module


def ops_per_sample(sizes, mix) -> int:
    return opcount.train_ops(forward_macs(sizes, mix["seq_len"]))


# -- traffic: Zipf tokens, labels the next token ----------------------------
def _batch(seed, index, batch, sizes, seq):
    rng = np.random.default_rng([seed, 1, index])
    ranks = rng.zipf(sizes["data"]["zipf_a"], (batch, seq + 1))
    ids = ((ranks - 1) % sizes["vocab_size"]).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def make_pool(seed, sizes, mix, batch, n):
    """``n`` host batches of int32 (tokens, next tokens), drawn from
    ``timed_seed``: the run's ``--seed`` is NOT used (``timed_seed_why``).
    The module's target, the token after the next, is the next position's
    label: both losses come from this one pair."""
    del seed
    return [_batch(sizes["timed_seed"], i, batch, sizes, mix["seq_len"])
            for i in range(n)]


def check_batch(seed, sizes, mix):
    return _batch(seed, CHECK_INDEX, sizes["check"]["batch"], sizes,
                  mix["seq_len"])


# -- the plain reference ----------------------------------------------------
def reference_parts(params, tokens, labels, sizes, operand_dtype=None,
                    without=()):
    """``(loss, logits, margins)`` in float32 at the highest matmul
    precision.  ``logits`` (batch, 2, seq, vocabulary): depth 0 the next
    token's, depth 1 the multi-token-prediction module's.  ``margins``
    (expert layers, batch, seq), the module's layer last: in the reference's
    OWN routing, the smallest distance of a HELD expert's ``score + bias``
    from changing sides (``held_margin``); where it is small, a bf16
    rounding upstream may choose otherwise and this chip's part of the layer
    changes by an expert's whole output (``compare``).  ``operand_dtype``
    rounds both operands of every projection, attention and expert product
    to that type first (the router's stays float32): what a lower precision
    than the configuration's reads, for setting the limits between two
    readings.  ``without`` names terms of the mathematics to leave out
    (``TERMS``): what the tests hold the tolerances against."""
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
    nope, rope_dim = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, kv_rank = sizes["v_head_dim"], sizes["kv_lora_rank"]

    def p(name):
        return jnp.asarray(params[name], f32)

    def low(t):
        return t if operand_dtype is None \
            else t.astype(operand_dtype).astype(f32)

    def dense(x, name):                           # no bias anywhere
        return jnp.einsum("...i,oi->...o", low(x), low(p(name + ".weight")),
                          precision=hi)

    def rms(x, name):
        if name.rsplit(".", 1)[-1] in without:
            return x
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p(name + ".gamma")

    def rotary(x):
        """(batch, seq, dim): feature i of the first half pairs with
        feature i of the second (the JSON's ``assumed.rotary_pairing``)."""
        half = x.shape[-1] // 2
        angle = jnp.arange(x.shape[1], dtype=f32)[:, None] \
            * f32(sizes["rope_theta"]) ** (-jnp.arange(half, dtype=f32) / half)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

    def attention(x, at):
        bsz, seq, _ = x.shape
        c_q = rms(dense(x, at + ".q_a_proj"), at + ".q_a_layernorm")
        q = dense(c_q, at + ".q_b_proj").reshape(bsz, seq, heads,
                                                 nope + rope_dim)
        down = dense(x, at + ".kv_a_proj_with_mqa")
        c_kv = rms(down[..., :kv_rank], at + ".kv_a_layernorm")
        # the rotary key: ONE vector a token, written once, used by each head
        k_rope = down[..., kv_rank:]
        if "k_rotary" not in without:
            k_rope = rotary(k_rope)
        kv = dense(c_kv, at + ".kv_b_proj").reshape(bsz, seq, heads,
                                                    nope + v_dim)
        visible = jnp.tril(jnp.ones((seq, seq), bool))
        scale = nope if "score_scale" in without else nope + rope_dim

        def one_head(h):                          # a head at a time
            q_h = lax.dynamic_index_in_dim(q, h, 2, keepdims=False)
            kv_h = lax.dynamic_index_in_dim(kv, h, 2, keepdims=False)
            q_rope = q_h[..., nope:]
            if "q_rotary" not in without:
                q_rope = rotary(q_rope)
            k_r = k_rope
            if "shared_rotary_key" in without:    # a key of the head's own
                k_r = jnp.roll(k_rope, h, axis=-1)
            q_h = jnp.concatenate([q_h[..., :nope], q_rope], -1)
            k_h = jnp.concatenate([kv_h[..., :nope], k_r], -1)
            scores = jnp.einsum("bqd,bkd->bqk", low(q_h), low(k_h),
                                precision=hi) / jnp.sqrt(f32(scale))
            att = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", low(att),
                              low(kv_h[..., nope:]), precision=hi)

        out = lax.map(one_head, jnp.arange(heads))             # (h, b, s, d)
        out = out.transpose(1, 2, 0, 3).reshape(bsz, seq, heads * v_dim)
        return dense(out, at + ".o_proj")

    def gated(x, gate_up, down):
        """``down (silu(gate x) * (up x))``; ``gate_up`` (in, 2 x width)
        holds gate and up side by side."""
        both = jnp.einsum("...i,if->...f", low(x), low(gate_up), precision=hi)
        gate, up = jnp.split(both, 2, axis=-1)
        hidden = up if "expert_gate" in without else jax.nn.silu(gate) * up
        return jnp.einsum("...f,fo->...o", low(hidden), low(down),
                          precision=hi)

    def gated_mlp(x, at):
        return gated(x, p(at + ".gate_up_proj.weight").T,
                     p(at + ".down_proj.weight").T)

    def experts(x, at):
        k = sizes["num_experts_per_tok"]
        scores = jax.nn.sigmoid(jnp.einsum(
            "...i,ei->...e", x, p(at + ".router_weight"), precision=hi))
        biased = scores if "select_bias" in without \
            else scores + p(at + ".e_score_correction_bias")
        _, chosen = lax.top_k(biased, k)
        margin = held_margin(biased, k, _held(sizes))
        picked = jnp.take_along_axis(scores, chosen, -1)
        weights = picked / picked.sum(-1, keepdims=True)
        if "routed_scaling" not in without:
            weights = weights * sizes["routed_scaling_factor"]
        out = 0.0 if "shared_expert" in without \
            else gated_mlp(x, at + ".shared_expert")
        up, down = p(at + ".experts_up"), p(at + ".experts_down")
        for slot, expert in enumerate(_held(sizes)):   # the held ids alone
            mask = (chosen == expert).astype(f32)      # dense 0/1
            out = out + jnp.sum(mask * weights, -1, keepdims=True) \
                * gated(x, up[slot], down[slot])
        return out, margin

    margins = []

    def layer(x, at, dense_ffn):
        x = x + attention(rms(x, at + ".input_layernorm"), at + ".self_attn")
        h = rms(x, at + ".post_attention_layernorm")
        if dense_ffn:
            return x if "dense_layer" in without \
                else x + gated_mlp(h, at + ".mlp")
        y, margin = experts(h, at + ".mlp")
        margins.append(margin)
        return x + y

    def head(x):
        return dense(x, "lm_head")

    def ce(logits, targets):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
    embedding = p("model.embed_tokens.weight")
    x = embedding[tokens]
    for i in range(sizes["num_hidden_layers"]):
        x = layer(x, f"model.layers.{i}", i < sizes["first_k_dense_replace"])
    hidden = rms(x, "model.norm")                 # h_i, after the final norm
    logits = head(hidden)
    loss = ce(logits, labels).mean()
    # the module at depth 1: position i reads h_i and token i + 1 through
    # the SAME embedding, and predicts token i + 2 through the SAME head.
    # Token i + 1 is the next position's input; the last position has none
    # (it reads the first token's, as the program's roll does) and no
    # target, and is left out of the loss
    shift = 0 if "mtp_shift" in without else 1
    both = jnp.concatenate(
        [rms(embedding[jnp.roll(tokens, -shift, axis=1)], "mtp.enorm"),
         rms(hidden, "mtp.hnorm")], -1)
    x = layer(dense(both, "mtp.eh_proj"), "mtp.layers.0", False)
    ahead = head(rms(x, "mtp.norm"))
    ahead_loss = ce(ahead, jnp.roll(labels, -1, axis=1))[:, :-1].mean()
    if "mtp_loss" not in without:
        loss = loss + sizes["mtp_loss_weight"] * ahead_loss
    return loss, jnp.stack([logits, ahead], axis=1), jnp.stack(margins)


# terms of the mathematics ``reference_parts(without=...)`` can leave out;
# the four norms go by the last part of their parameter's name
TERMS = ("q_rotary", "k_rotary", "shared_rotary_key", "score_scale",
         "q_a_layernorm", "kv_a_layernorm", "select_bias", "routed_scaling",
         "expert_gate", "shared_expert", "dense_layer", "enorm", "hnorm",
         "mtp_loss", "mtp_shift")


def held_margin(biased, k, held):
    """How far the nearest of the ``held`` experts is from changing sides
    in a choice of the ``k`` largest of ``biased`` (..., experts): a chosen
    one from the first unchosen value, an unchosen one from the last
    chosen."""
    import jax.numpy as jnp
    from jax import lax

    top, _ = lax.top_k(biased, k + 1)
    last, first_out = top[..., k - 1:k], top[..., k:]
    mine = biased[..., jnp.asarray(held)]
    return jnp.min(jnp.where(mine >= last, mine - first_out, last - mine),
                   axis=-1)


def reference(params, tokens, labels, sizes):
    """``(loss, logits)``: the harness's plain-reference signature."""
    return reference_parts(params, tokens, labels, sizes)[:2]


def compare(logits, loss, ref_loss, ref_logits, margins, sizes):
    """The comparison that decides ``reference`` (driver
    ``train_fixed_shape_routed``).  Every position of either depth has an
    error: the largest distance of its logits from the reference's, in units
    of that depth's largest reference logit.  A program whose activations
    are bf16 may choose a held expert for a token where the float32
    reference does not (or the other way round) when that expert's ``score +
    bias`` lies close to changing sides; both answers are then legitimate
    and differ by an expert's whole output at that token.  Later positions
    see the token only through attention, one key among all they attend to,
    so what reaches them is a share of it.  So a maximum over positions
    says nothing, and:

    - the MEDIAN error of every block of ``check.block`` consecutive
      positions of either depth is held to ``logits_tol``: a lower precision
      or a missing term moves every position, and a fault local to a stretch
      of the sequence (a wrong attention block) moves its block's;
    - a position is an OUTLIER above ``outlier_err``.  Which positions may
      be outliers is the REFERENCE's word alone (never what the program
      chose): a position is EXPOSED if a layer it passed through has there a
      margin under ``tie_margin`` (``reference_parts``: the main model's
      expert layers for depth 0, those and the module's for depth 1);
      exposed positions may be outliers up to a share
      ``exposed_outlier_share_max``, the others up to
      ``unexposed_outlier_share_max``;
    - the combined loss is held to ``loss_tol``.

    The limits are ``sizes['check']``'s."""
    import jax.numpy as jnp

    import jax

    spec = sizes["check"]

    def position_errors(got, ref):
        scale = jnp.max(jnp.abs(ref), axis=(0, 2, 3), keepdims=True)
        return jnp.max(jnp.abs(got.astype(jnp.float32) - ref) / scale,
                       axis=-1)

    # one fused reduction: taken a step at a time the float32 copies of the
    # two depths' logits (1.3 GB each at the cell's size) would be the
    # process's peak of live buffers, above anything training holds
    err = jax.jit(position_errors)(logits, ref_logits)         # (b, 2, s)
    batch, depths, seq = err.shape
    block = spec["block"]
    blocks = jnp.pad(err, ((0, 0), (0, 0), (0, -seq % block)),
                     constant_values=jnp.nan).reshape(batch, depths, -1,
                                                      block)
    block_medians = jnp.nanmedian(blocks, axis=-1)
    near = margins < spec["tie_margin"]           # (layers, batch, seq)
    main = jnp.any(near[:-1], axis=0)
    exposed = jnp.stack([main, main | near[-1]], axis=1)
    outlier = err > spec["outlier_err"]

    def share(of, among):
        return float(jnp.sum(of & among) / jnp.maximum(jnp.sum(among), 1))

    worst_block = float(jnp.max(block_medians))
    exposed_outliers = share(outlier, exposed)
    unexposed_outliers = share(outlier, ~exposed)
    loss_err = abs(loss - ref_loss) / max(abs(ref_loss), 1e-6)
    return {"ok": worst_block <= spec["logits_tol"]
            and unexposed_outliers <= spec["unexposed_outlier_share_max"]
            and exposed_outliers <= spec["exposed_outlier_share_max"]
            and loss_err <= spec["loss_tol"],
            "logits_err": worst_block, "logits_tol": spec["logits_tol"],
            "block": block, "logits_err_median": float(jnp.median(err)),
            "logits_err_median_by_depth":
                [float(m) for m in jnp.median(err, axis=(0, 2))],
            "logits_err_p90": float(jnp.quantile(err, 0.9)),
            "max_logits_err": float(jnp.max(err)),
            "outlier_err": spec["outlier_err"],
            "tie_margin": spec["tie_margin"],
            "exposed_share": float(jnp.mean(exposed)),
            "exposed_outlier_share": exposed_outliers,
            "exposed_outlier_share_max": spec["exposed_outlier_share_max"],
            "unexposed_outlier_share": unexposed_outliers,
            "unexposed_outlier_share_max":
                spec["unexposed_outlier_share_max"],
            "loss": loss, "reference_loss": ref_loss,
            "loss_err": loss_err, "loss_tol": spec["loss_tol"],
            "per_position": {"logits_err": err, "margins": margins}}
