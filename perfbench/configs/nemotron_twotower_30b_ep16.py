"""Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's ``nemotron_h`` tower, one
expert-parallel rank of sixteen: the first nine layers ``MEMEM*EME``, 8 of
128 routed experts and an eighth of the vocabulary held here (the JSON's
``deployment``).

What is code in this configuration: how the net is built through the
program's public API (``gluon.model_zoo.nemotron_h``), operations per
sequence from the sizes in the JSON, seeded Zipf tokens with next-token
labels, the plain reference (``jax.numpy``, float32, no Gluon, no kernel:
the Mamba-2 recurrence one step a token, the convolution as shifted
multiplies, attention a head at a time under an explicit mask, the experts as
a loop over the held ids), and the comparison that knows what a router's
near tie is (``compare``, called by driver ``train_fixed_shape_routed``).
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
    zoo = getattr(mx.gluon.model_zoo, "nemotron_h", None)
    if zoo is None:
        raise RuntimeError("this checkout's mxnet_tpu has no "
                           "gluon.model_zoo.nemotron_h: it cannot run "
                           "configuration nemotron_twotower_30b_ep16")
    mx.random.seed(sizes["timed_seed"])
    config = {**sizes, "n_routed_experts": sizes["router_experts"]}
    net = zoo.nemotron_h(
        config, held_experts=_held(sizes),
        recompute_layers=sizes["recompute_layers"])
    net.initialize()
    net.hybridize()
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    return {"net": net, "head_loss": lambda logits, y: ce(logits, y).mean(),
            "optimizer": sizes["optimizer"],
            "optimizer_params": dict(sizes["optimizer_params"])}


# -- operations from shapes -------------------------------------------------
def scan_macs(sizes, seq) -> int:
    """The chunked scan's four products over one sequence, as the SSD form
    states them: ``C B^T`` a group, the masked ``(C B^T . L) x`` a head, a
    chunk's state ``B^T x`` a head, and ``C S`` a head."""
    q, n = sizes["chunk_size"], sizes["ssm_state_size"]
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    return seq * (sizes["n_groups"] * q * n + heads * q * p
                  + 2 * heads * n * p)


def mamba_macs(sizes, seq) -> int:
    d = sizes["hidden_size"]
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    return (opcount.dense_macs(seq, d, inner + conv_dim
                               + sizes["mamba_num_heads"])
            + seq * conv_dim * sizes["conv_kernel"]
            + scan_macs(sizes, seq)
            + opcount.dense_macs(seq, inner, d))


def attention_core_macs(sizes, seq) -> int:
    """Score and value products at half their square: a causal kernel
    skips what lies above the diagonal."""
    return opcount.attention_macs(sizes["num_attention_heads"], seq, seq,
                                  sizes["head_dim"]) // 2


def attention_macs(sizes, seq) -> int:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    q, kv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    return (opcount.dense_macs(seq, d, q) + 2 * opcount.dense_macs(seq, d, kv)
            + attention_core_macs(sizes, seq) + opcount.dense_macs(seq, q, d))


def expert_row_macs(sizes) -> int:
    """One row through one routed expert: its two products."""
    return 2 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def mean_held_rows(sizes, seq) -> float:
    """Rows the held experts get a sequence at the MEAN share."""
    return seq * sizes["num_experts_per_tok"] * sizes["n_routed_experts"] \
        / sizes["router_experts"]


def moe_macs(sizes, seq) -> int:
    d = sizes["hidden_size"]
    return (opcount.dense_macs(seq, d, sizes["router_experts"])
            + 2 * opcount.dense_macs(
                seq, d, sizes["moe_shared_expert_intermediate_size"])
            + int(mean_held_rows(sizes, seq) * expert_row_macs(sizes)))


LAYER_MACS = {"M": mamba_macs, "*": attention_macs, "E": moe_macs}


def forward_macs(sizes, seq) -> int:
    """One sequence: every product of the layers and the head.  The
    embedding lookup is a gather."""
    return (sum(LAYER_MACS[kind](sizes, seq)
                for kind in sizes["hybrid_override_pattern"])
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
    """``n`` host batches of int32 (tokens, next tokens), drawn from
    ``timed_seed``: the run's ``--seed`` is NOT used (``timed_seed_why``)."""
    del seed
    return [_batch(sizes["timed_seed"], i, batch, sizes, mix["seq_len"])
            for i in range(n)]


def check_batch(seed, sizes, mix):
    return _batch(seed, CHECK_INDEX, sizes["check"]["batch"], sizes,
                  mix["seq_len"])


# -- the plain reference ----------------------------------------------------
def reference_parts(params, tokens, labels, sizes, operand_dtype=None):
    """``(loss, logits, margins)`` in float32 at the highest matmul
    precision.  ``margins`` (expert layers, batch, seq) is, in the
    reference's OWN routing, the smallest distance of a HELD expert's
    ``score + bias`` from changing sides (a chosen one from the first
    unchosen, an unchosen one from the last chosen): where it is small, a
    bf16 rounding upstream may choose otherwise and this chip's part of the
    layer changes by an expert's whole output; a swap between two experts
    held elsewhere changes nothing here (``compare``).  ``operand_dtype`` rounds both operands
    of every projection, attention and expert product to that type first
    (the router's stays float32): what a lower precision than the
    configuration's reads, for setting the limits between two readings."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    eps = sizes["layer_norm_epsilon"]

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

    def relu2_mlp(x, at):
        return dense(jnp.square(jax.nn.relu(dense(x, at + ".up_proj"))),
                     at + ".down_proj")

    def mamba(x, at):
        heads, hd = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
        groups, n = sizes["n_groups"], sizes["ssm_state_size"]
        inner, bc = heads * hd, groups * n
        zxbcdt = dense(x, at + ".in_proj")
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], axis=-1)
        # the convolution as shifted multiplies: tap k reads k - (K-1) back
        w, taps = p(at + ".conv_weight"), sizes["conv_kernel"]
        seq = xbc.shape[1]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = p(at + ".conv_bias") + sum(
            padded[:, k:k + seq] * w[:, k] for k in range(taps))
        xbc = conv * jax.nn.sigmoid(conv)
        xs, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
        bsz = xs.shape[0]
        xs = xs.reshape(bsz, seq, heads, hd)
        b = b.reshape(bsz, seq, groups, n)
        c = c.reshape(bsz, seq, groups, n)
        dt = jax.nn.softplus(dt + p(at + ".dt_bias"))          # (b, s, h)
        a = -jnp.exp(p(at + ".A_log"))
        per = heads // groups

        def step(state, inp):                     # one token
            x_t, dt_t, b_t, c_t = inp
            b_t, c_t = (jnp.repeat(t, per, axis=1) for t in (b_t, c_t))
            state = state * jnp.exp(dt_t * a)[..., None, None] \
                + (dt_t[..., None] * b_t)[..., None] * x_t[:, :, None, :]
            return state, jnp.einsum("bhn,bhnp->bhp", c_t, state,
                                     precision=hi)

        _, y = lax.scan(step, jnp.zeros((bsz, heads, n, hd), f32),
                        tuple(t.swapaxes(0, 1) for t in (xs, dt, b, c)))
        y = y.swapaxes(0, 1) + p(at + ".D")[:, None] * xs
        y = y.reshape(bsz, seq, inner) * (z * jax.nn.sigmoid(z))
        grouped = y.reshape(bsz, seq, groups, inner // groups)
        y = (grouped * lax.rsqrt(jnp.mean(grouped * grouped, -1,
                                          keepdims=True) + eps)
             ).reshape(bsz, seq, inner) * p(at + ".norm_weight")
        return dense(y, at + ".out_proj")

    def attention(x, at):
        heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
        hd = sizes["head_dim"]
        bsz, seq, _ = x.shape
        q = dense(x, at + ".q_proj").reshape(bsz, seq, heads, hd)
        k = dense(x, at + ".k_proj").reshape(bsz, seq, kv, hd)
        v = dense(x, at + ".v_proj").reshape(bsz, seq, kv, hd)
        visible = jnp.tril(jnp.ones((seq, seq), bool))

        def one_head(h):                          # a head at a time
            q_h = lax.dynamic_index_in_dim(q, h, 2, keepdims=False)
            k_h = lax.dynamic_index_in_dim(k, h // (heads // kv), 2, False)
            v_h = lax.dynamic_index_in_dim(v, h // (heads // kv), 2, False)
            scores = jnp.einsum("bqd,bkd->bqk", low(q_h), low(k_h),
                                precision=hi) / jnp.sqrt(f32(hd))
            att = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", low(att), low(v_h),
                              precision=hi)

        out = lax.map(one_head, jnp.arange(heads))             # (h, b, s, d)
        out = out.transpose(1, 2, 0, 3).reshape(bsz, seq, heads * hd)
        return dense(out, at + ".o_proj")

    def experts(x, at):
        k = sizes["num_experts_per_tok"]
        scores = jax.nn.sigmoid(jnp.einsum(
            "...i,ei->...e", x, p(at + ".router_weight"), precision=hi))
        biased = scores + p(at + ".e_score_correction_bias")
        _, chosen = lax.top_k(biased, k)
        margin = held_margin(biased, k, _held(sizes))
        picked = jnp.take_along_axis(scores, chosen, -1)
        weights = picked / picked.sum(-1, keepdims=True) \
            * sizes["routed_scaling_factor"]
        out = relu2_mlp(x, at + ".shared_expert")
        up, down = p(at + ".experts_up"), p(at + ".experts_down")
        for slot, expert in enumerate(_held(sizes)):   # the held ids alone
            mask = (chosen == expert).astype(f32)      # dense 0/1
            h = jnp.square(jax.nn.relu(jnp.einsum(
                "...i,if->...f", low(x), low(up[slot]), precision=hi)))
            out = out + jnp.sum(mask * weights, -1, keepdims=True) \
                * jnp.einsum("...f,fo->...o", low(h), low(down[slot]),
                             precision=hi)
        return out, margin

    mixers = {"M": mamba, "*": attention}
    x = p("backbone.embeddings.weight")[jnp.asarray(tokens)]
    margins = []
    for i, kind in enumerate(sizes["hybrid_override_pattern"]):
        at = f"backbone.layers.{i}"
        h = rms(x, p(at + ".norm.gamma"))
        if kind == "E":
            y, margin = experts(h, at + ".mixer")
            margins.append(margin)
        else:
            y = mixers[kind](h, at + ".mixer")
        x = x + y
    logits = dense(rms(x, p("backbone.norm_f.gamma")), "lm_head")
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                                axis=-1).mean()
    margins = jnp.stack(margins) if margins \
        else jnp.zeros((0,) + logits.shape[:2], f32)
    return loss, logits, margins


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
    ``train_fixed_shape_routed``).  Every position has an error: the largest
    distance of its logits from the reference's, in units of the reference's
    largest logit.  A program whose activations are bf16 may choose a held
    expert for a token where the float32 reference does not (or the other
    way round) when that expert's ``score + bias`` lies close to changing
    sides; both answers are then legitimate and differ by an expert's whole
    output at that token, by a share of it at the next ``conv_kernel - 1``
    tokens (the following mixer's convolution reads them), and by a fading
    rest after that (its state).  So a maximum over positions says nothing
    (the JSON's ``check.why`` has the measured distribution), and:

    - the MEDIAN error of every block of ``chunk_size`` consecutive
      positions is held to ``logits_tol``: a lower precision or a missing
      term moves every position, and a fault local to a stretch of the
      sequence (a wrong scan chunk, a wrong attention block) moves its
      block's;
    - a position is an OUTLIER above ``outlier_err``.  Which positions may
      be outliers is the REFERENCE's word alone (never what the program
      chose): a position is EXPOSED if it, or one of the ``conv_kernel - 1``
      positions before it, has in any expert layer a margin under
      ``tie_margin`` (``reference_parts``); exposed positions may be
      outliers up to a share ``exposed_outlier_share_max``, the others up
      to ``unexposed_outlier_share_max``;
    - the loss over ALL positions is held to ``loss_tol``.

    The limits are ``sizes['check']``'s."""
    import jax.numpy as jnp

    spec = sizes["check"]

    scale = float(jnp.max(jnp.abs(ref_logits)))
    err = jnp.max(jnp.abs(logits.astype(jnp.float32) - ref_logits),
                  axis=-1) / scale                         # (batch, seq)
    batch, seq = err.shape
    block, reach = sizes["chunk_size"], sizes["conv_kernel"] - 1
    blocks = jnp.pad(err, ((0, 0), (0, -seq % block)),
                     constant_values=jnp.nan).reshape(batch, -1, block)
    block_medians = jnp.nanmedian(blocks, axis=-1)
    tied = jnp.any(margins < spec["tie_margin"], axis=0) \
        if margins.shape[0] else jnp.zeros(err.shape, bool)
    ties = jnp.pad(jnp.cumsum(tied, axis=1), ((0, 0), (reach + 1, 0)))
    exposed = ties[:, reach + 1:] > ties[:, :seq]  # a tie in [t - reach, t]
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
            "logits_err_p90": float(jnp.quantile(err, 0.9)),
            "max_logits_err": float(jnp.max(err)),
            "outlier_err": spec["outlier_err"],
            "tie_margin": spec["tie_margin"], "tie_reach": reach,
            "tied_share": float(jnp.mean(tied)),
            "exposed_share": float(jnp.mean(exposed)),
            "exposed_outlier_share": exposed_outliers,
            "exposed_outlier_share_max": spec["exposed_outlier_share_max"],
            "unexposed_outlier_share": unexposed_outliers,
            "unexposed_outlier_share_max":
                spec["unexposed_outlier_share_max"],
            "loss": loss, "reference_loss": ref_loss,
            "loss_err": loss_err, "loss_tol": spec["loss_tol"],
            "per_position": {"logits_err": err, "margins": margins}}
