"""Flagship TPU-native transformer LM (BERT-class encoder).

The reference's transformer story is a handful of fused CUDA matmul ops
(src/operator/contrib/transformer.cc:650-740) consumed by external GluonNLP
models; its parallelism story is data-parallel KVStore only (SURVEY.md §2.3).
This module is the TPU-first flagship: one model whose *training step* is a
single SPMD program exercising every mesh axis —

- ``dp``   batch sharding (gradient all-reduce inserted by XLA)
- ``fsdp`` parameter/optimizer sharding on top of dp
- ``tp``   megatron-style column/row-parallel attention + MLP
- ``sp``   ring attention over the sequence axis (parallel.ring_attention)
- ``ep``   mixture-of-experts FFN with experts sharded over ``ep``
- ``pp``   identical-stage pipeline over depth (parallel.pipeline)

Parameters are a flat ``{name: jax.Array}`` pytree (structural names match
gluon conventions so ShardingPlan rules apply unchanged); the gluon-facing
BERT lives in ``gluon/model_zoo/bert.py`` and shares nothing but math —
that one is the user-API parity surface, this one is the scale recipe.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.nn import sparse_softmax_cross_entropy
from ..parallel import moe as _moe
from ..parallel import ring_attention as _ring_mod  # noqa: F401 (module import)
from ..parallel.ring_attention import ring_attention_sharded as _ring_attention_sharded
from ..parallel.sharding import ShardingPlan, constraint

__all__ = ["TransformerLMConfig", "init_params", "forward", "loss_fn",
           "sharding_plan", "make_train_step", "init_opt_state",
           "pp_pad_batch", "flash_fallback_count"]

# Flash attention needs (seq, head_dim) divisible by 8 (TPU tiling), and the
# auto path used to fall back to the O(S^2) einsum WITHOUT saying so —
# a mis-sized config quietly trains at a fraction of the flash MFU.
# Every fallback is counted here (once per trace of each misaligned
# attention site) and logged once per process.
from .. import telemetry as _telemetry

_FLASH_FALLBACK = _telemetry.counter(
    "transformer_lm.flash_fallback",
    "attention sites that wanted the Pallas flash kernel but fell back "
    "to the O(S^2) einsum path on misaligned (seq, head_dim)")
_FLASH_FALLBACK_LOGGED = False


def flash_fallback_count() -> int:
    """Attention sites that wanted the Pallas flash kernel but fell back
    to the einsum path on misaligned (seq, head_dim).  View over the
    ``transformer_lm.flash_fallback`` telemetry counter."""
    return int(_FLASH_FALLBACK.value)


def _count_flash_fallback(seq: int, head_dim: int) -> None:
    global _FLASH_FALLBACK_LOGGED
    _FLASH_FALLBACK.inc()
    _telemetry.event("fallback", "transformer_lm.flash",
                     seq=seq, head_dim=head_dim)
    if not _FLASH_FALLBACK_LOGGED:
        _FLASH_FALLBACK_LOGGED = True
        from .. import log as _log

        _log.get_logger("mxnet_tpu.models").warning(
            "flash attention fell back to the O(S^2) einsum path: "
            f"(seq={seq}, head_dim={head_dim}) is not divisible by 8 "
            "(TPU tiling).  Pad/round the sequence length and head_dim "
            "to multiples of 8 to regain the flash kernel (BERT lane: "
            "45.6%% vs einsum's far lower MFU).  [logged once; "
            "fallbacks counted in models.transformer_lm."
            "flash_fallback_count()]")


@dataclasses.dataclass
class TransformerLMConfig:
    vocab_size: int = 30528          # bert-base vocab rounded to 64
    num_layers: int = 12
    num_heads: int = 12
    hidden: int = 768
    mlp_hidden: int = 3072
    max_len: int = 512
    dtype: Any = jnp.bfloat16        # MXU-native compute dtype
    # MoE: 0 = dense MLP everywhere; k>0 = every layer is a top-k MoE
    num_experts: int = 0
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    # parallel toggles (consumed by make_train_step)
    use_ring_attention: bool = False
    remat: bool = False              # jax.checkpoint each layer
    # None = auto (pallas flash attention on TPU, XLA einsum elsewhere);
    # True/False force the choice (True on CPU uses the slow interpreter)
    use_flash_attention: Any = None

    @property
    def head_dim(self) -> int:
        return self.hidden // self.num_heads


def _split(key, n):
    return jax.random.split(key, n)


def init_params(key, cfg: TransformerLMConfig) -> Dict[str, jax.Array]:
    """Flat param dict; truncated-normal(0.02) like BERT."""
    H, M, V = cfg.hidden, cfg.mlp_hidden, cfg.vocab_size
    p: Dict[str, jax.Array] = {}
    k_embed, k_pos, key = _split(key, 3)
    init = lambda k, shape, scale=0.02: (
        jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * scale
    ).astype(cfg.dtype)
    p["embed.weight"] = init(k_embed, (V, H))
    p["pos_embed.weight"] = init(k_pos, (cfg.max_len, H))
    for i in range(cfg.num_layers):
        ks = _split(key, 8)
        key = ks[-1]
        pre = f"layer{i}."
        p[pre + "attn.qkv.weight"] = init(ks[0], (3 * H, H))
        p[pre + "attn.qkv.bias"] = jnp.zeros((3 * H,), cfg.dtype)
        p[pre + "attn.out_proj.weight"] = init(
            ks[1], (H, H), 0.02 / math.sqrt(2 * cfg.num_layers))
        p[pre + "attn.out_proj.bias"] = jnp.zeros((H,), cfg.dtype)
        p[pre + "ln1.gamma"] = jnp.ones((H,), jnp.float32)
        p[pre + "ln1.beta"] = jnp.zeros((H,), jnp.float32)
        p[pre + "ln2.gamma"] = jnp.ones((H,), jnp.float32)
        p[pre + "ln2.beta"] = jnp.zeros((H,), jnp.float32)
        if cfg.num_experts:
            E = cfg.num_experts
            p[pre + "moe.gate.weight"] = init(ks[2], (H, E))
            p[pre + "expert.ffn_1.weight"] = init(ks[3], (E, H, M))
            p[pre + "expert.ffn_2.weight"] = init(
                ks[4], (E, M, H), 0.02 / math.sqrt(2 * cfg.num_layers))
        else:
            p[pre + "ffn_1.weight"] = init(ks[2], (M, H))
            p[pre + "ffn_1.bias"] = jnp.zeros((M,), cfg.dtype)
            p[pre + "ffn_2.weight"] = init(
                ks[3], (H, M), 0.02 / math.sqrt(2 * cfg.num_layers))
            p[pre + "ffn_2.bias"] = jnp.zeros((H,), cfg.dtype)
    p["final_ln.gamma"] = jnp.ones((H,), jnp.float32)
    p["final_ln.beta"] = jnp.zeros((H,), jnp.float32)
    return p


def _layer_norm(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps) * gamma + beta
    return out.astype(x.dtype)


def _attention(x, p, pre, cfg: TransformerLMConfig, mesh: Optional[Mesh]):
    B, S, H = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim
    qkv = x @ p[pre + "attn.qkv.weight"].T + p[pre + "attn.qkv.bias"]
    qkv = qkv.reshape(B, S, 3, nh, hd)
    q, k, v = (jnp.moveaxis(qkv[:, :, j], 2, 1) for j in range(3))  # B,nh,S,hd
    if cfg.use_ring_attention and mesh is not None and \
            mesh.shape.get("sp", 1) > 1:
        # sequence stays sharded over sp; ring rotates K/V via ICI neighbours
        out = _ring_attention_sharded(
            q, k, v, mesh, axis_name="sp",
            batch_axes=("dp", "fsdp"))
    else:
        use_flash = cfg.use_flash_attention
        if use_flash is None:
            # auto mode: single-device only — pallas_call has no SPMD
            # partitioning rule, so under a >1-device mesh the einsum path
            # keeps tp/sp shardings intact (flash-under-shard_map is the
            # future fix); explicit True overrides
            multi = mesh is not None and any(
                s > 1 for s in mesh.shape.values())
            use_flash = jax.default_backend() == "tpu" and not multi
        aligned = S % 8 == 0 and hd % 8 == 0
        if cfg.use_flash_attention is True and not aligned:
            raise ValueError(
                f"use_flash_attention=True requires seq ({S}) and head_dim "
                f"({hd}) divisible by 8 (TPU tiling)")
        if use_flash and not aligned:
            # the auto path WOULD take flash but the geometry can't tile:
            # loud one-time log + counter instead of a silent MFU cliff
            _count_flash_fallback(S, hd)
        if use_flash and aligned:
            from ..ops.pallas_kernels import flash_attention

            out = flash_attention(q, k, v, causal=False).astype(x.dtype)
        else:
            scale = 1.0 / math.sqrt(hd)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(
                jnp.float32) * scale
            out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                             v.astype(jnp.float32)).astype(x.dtype)
    out = jnp.moveaxis(out, 1, 2).reshape(B, S, H)
    return out @ p[pre + "attn.out_proj.weight"].T + p[pre + "attn.out_proj.bias"]


def _mlp(x, p, pre, cfg: TransformerLMConfig):
    if cfg.num_experts:
        B, S, H = x.shape
        out, aux = _moe.moe_layer(
            x, p[pre + "moe.gate.weight"].astype(x.dtype),
            p[pre + "expert.ffn_1.weight"], p[pre + "expert.ffn_2.weight"],
            k=cfg.moe_top_k, capacity_factor=cfg.capacity_factor)
        return out, aux
    h = jax.nn.gelu(x @ p[pre + "ffn_1.weight"].T + p[pre + "ffn_1.bias"])
    return h @ p[pre + "ffn_2.weight"].T + p[pre + "ffn_2.bias"], 0.0


def _block(params, x, i: int, cfg: TransformerLMConfig,
           mesh: Optional[Mesh] = None):
    """One pre-LN transformer block (attention + MLP/MoE residual)."""
    pre = f"layer{i}."
    h = _attention(_layer_norm(x, params[pre + "ln1.gamma"],
                               params[pre + "ln1.beta"]),
                   params, pre, cfg, mesh)
    x = x + h
    m, aux = _mlp(_layer_norm(x, params[pre + "ln2.gamma"],
                              params[pre + "ln2.beta"]),
                  params, pre, cfg)
    return x + m, aux


def forward(params, tokens, cfg: TransformerLMConfig,
            mesh: Optional[Mesh] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, S] int32 -> (logits [B, S, V] float32, moe aux loss)."""
    B, S = tokens.shape
    x = params["embed.weight"][tokens] + params["pos_embed.weight"][:S]
    x = x.astype(cfg.dtype)
    aux_total = 0.0

    def one_layer(x, i):
        return _block(params, x, i, cfg, mesh)

    layer_fn = jax.checkpoint(one_layer, static_argnums=(1,)) if cfg.remat \
        else one_layer
    for i in range(cfg.num_layers):
        x, aux = layer_fn(x, i)
        aux_total = aux_total + aux
    x = _layer_norm(x, params["final_ln.gamma"], params["final_ln.beta"])
    logits = (x @ params["embed.weight"].T.astype(cfg.dtype))
    return logits.astype(jnp.float32), jnp.asarray(aux_total, jnp.float32)


def _masked_nll(logits, labels):
    """Per-position masked NLL: labels int32, -1 = unmasked (ignored).
    Returns (nll [B,S] with zeros at masked positions, valid mask [B,S])."""
    valid = labels >= 0
    nll = sparse_softmax_cross_entropy(logits, jnp.where(valid, labels, 0))
    return jnp.where(valid, nll, 0.0), valid


def loss_fn(params, tokens, labels, cfg: TransformerLMConfig,
            mesh: Optional[Mesh] = None, aux_weight: float = 0.01):
    """Masked-LM style CE: labels [B,S] int32, -1 = unmasked (ignored)."""
    logits, aux = forward(params, tokens, cfg, mesh)
    nll, valid = _masked_nll(logits, labels)
    denom = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(nll) / denom + aux_weight * aux


def sharding_plan(cfg: TransformerLMConfig) -> ShardingPlan:
    """tp over attention/MLP (megatron), ep over experts, embeddings over tp;
    everything composes with fsdp via rule order (tp rules first, fsdp
    handled by the caller stacking plans)."""
    plan = ShardingPlan([
        (r"attn\.qkv\.weight$", P(("tp",), None)),
        (r"attn\.qkv\.bias$", P("tp")),
        (r"attn\.out_proj\.weight$", P(None, "tp")),
        (r"expert\.ffn_1\.weight$", P("ep", None, "tp")),
        (r"expert\.ffn_2\.weight$", P("ep", "tp", None)),
        (r"(^|\.)ffn_1\.weight$", P("tp", None)),
        (r"(^|\.)ffn_1\.bias$", P("tp")),
        (r"(^|\.)ffn_2\.weight$", P(None, "tp")),
        (r"embed\.weight$", P("tp", None)),
    ])
    return plan


def init_opt_state(params):
    """Adam/LAMB first+second moments, sharded like the params."""
    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    return ({n: zeros(a) for n, a in params.items()},
            {n: zeros(a) for n, a in params.items()})


# ---------------------------------------------------------------------------
# Pipeline parallelism: split the LM into heterogeneous pp stages
# ---------------------------------------------------------------------------

def pp_stages(cfg: TransformerLMConfig, params, pp: int):
    """Split flagship params/compute into ``pp`` heterogeneous stages for
    :class:`parallel.pipeline.HeteroPipeline`.

    Stage 0 = token+position embedding + first layers block; last stage =
    final layers + final LN + LM head + per-sample masked-CE reduction
    (returns ``(nll_sum[mb], valid_count[mb])`` so the caller combines
    microbatch losses exactly).  The tied embedding/head weight is split
    into two copies (``embed.weight`` on stage 0, ``head.weight`` on the
    last) — :func:`make_pp_train_step` sums their gradient slices each step
    (Megatron-style tied-embedding all-reduce), so equal-initialised copies
    stay exactly tied under any elementwise optimizer.

    No PP analog exists in the reference (SURVEY.md §2.3: DP only).
    """
    assert cfg.num_layers % pp == 0, (
        f"num_layers {cfg.num_layers} must divide pp {pp}")
    assert not cfg.num_experts, "pp path supports dense MLP stages only"
    per = cfg.num_layers // pp
    stage_params, stage_fns = [], []
    for s in range(pp):
        sp = {}
        if s == 0:
            sp["embed.weight"] = params["embed.weight"]
            sp["pos_embed.weight"] = params["pos_embed.weight"]
        for i in range(s * per, (s + 1) * per):
            pre = f"layer{i}."
            for k, v in params.items():
                if k.startswith(pre):
                    sp[k] = v
        if s == pp - 1:
            sp["final_ln.gamma"] = params["final_ln.gamma"]
            sp["final_ln.beta"] = params["final_ln.beta"]
            sp["head.weight"] = params["embed.weight"]
        stage_params.append(sp)
        stage_fns.append(_make_stage_fn(cfg, s, per, pp))
    return stage_fns, stage_params


def _make_stage_fn(cfg: TransformerLMConfig, s: int, per: int, pp: int):
    def stage(p, act, labels):
        if s == 0:
            tokens = act                       # [mb, S] int32
            S = tokens.shape[1]
            x = p["embed.weight"][tokens] + p["pos_embed.weight"][:S]
            x = x.astype(cfg.dtype)
        else:
            x = act                            # [mb, S, H]
        for i in range(s * per, (s + 1) * per):
            x, _aux = _block(p, x, i, cfg, None)
        if s == pp - 1:
            x = _layer_norm(x, p["final_ln.gamma"], p["final_ln.beta"])
            logits = (x @ p["head.weight"].T.astype(cfg.dtype)).astype(
                jnp.float32)
            nll, valid = _masked_nll(logits, labels)
            return (jnp.sum(nll, axis=-1),                 # [mb]
                    jnp.sum(valid, axis=-1).astype(jnp.float32))
        return x

    return stage


def make_pp_pipeline(cfg: TransformerLMConfig, params, mesh: Mesh, *,
                     num_microbatches: int, example_tokens,
                     remat: bool = False):
    """Build a HeteroPipeline for this LM over mesh axes pp (and dp)."""
    from ..parallel.pipeline import HeteroPipeline

    pp = mesh.shape.get("pp", 1)
    stage_fns, stage_params = pp_stages(cfg, params, pp)
    pipe = HeteroPipeline(
        stage_fns, stage_params, mesh,
        num_microbatches=num_microbatches,
        example_x=example_tokens,
        example_extras=(jax.ShapeDtypeStruct(example_tokens.shape,
                                             jnp.int32),),
        remat=remat)
    # embed (stage 0) and head (last stage) are weight-tied copies; the
    # train step sums their grads so they stay tied
    pipe.tied = (((0, "embed.weight"), (pp - 1, "head.weight")),)
    return pipe


def pp_loss_fn(pipe, packed_params, tokens, labels):
    """Exact masked-LM CE through the pipeline (matches :func:`loss_fn` for
    dense configs up to fp32 packing)."""
    nll_sum, counts = pipe.apply(packed_params, tokens, labels)
    return jnp.sum(nll_sum) / jnp.maximum(jnp.sum(counts), 1.0)


def pp_pad_batch(tokens, labels, multiple: int):
    """Pad a ragged batch up to the next multiple of ``multiple`` rows so
    it divides the pipeline's ``num_microbatches * dp`` requirement.

    Padding rows carry label ``-1`` everywhere, and the masked-CE
    normalises by the GLOBAL valid-token count — so the padded batch's
    loss and gradients are EXACTLY the unpadded batch's (the pad rows
    contribute zero nll and zero valid tokens).  This is the pad-and-mask
    contract for ragged last microbatches.
    """
    B = tokens.shape[0]
    pad = (-B) % multiple
    if pad == 0:
        return tokens, labels
    tz = jnp.zeros((pad,) + tuple(tokens.shape[1:]), tokens.dtype)
    lm = jnp.full((pad,) + tuple(labels.shape[1:]), -1, labels.dtype)
    return (jnp.concatenate([tokens, tz], axis=0),
            jnp.concatenate([labels, lm], axis=0))


def make_pp_train_step(pipe, optimizer: str = "adam", lr: float = 1e-4,
                       beta1: float = 0.9, beta2: float = 0.999,
                       epsilon: float = 1e-8, wd: float = 0.0):
    """Adam(W)/SGD on the packed per-stage parameter buffer.

    Elementwise updates are exact in packed space (padding stays zero:
    grads, moments, and decay are all zero there).  Microbatch gradient
    accumulation happens inside the pipeline's scan.  Gradients of
    weight-tied leaves (``pipe.tied``, e.g. embed/head) are summed across
    stages before the update so equal-initialised copies stay exactly tied.
    The packed-params argument is NOT donated — the pipeline object keeps a
    live reference in ``pipe.packed_params``."""
    ties = getattr(pipe, "tied", ())

    def step(packed, m, v, tokens, labels, t):
        loss, g = jax.value_and_grad(
            lambda p: pp_loss_fn(pipe, p, tokens, labels))(packed)
        if ties:
            g = pipe.tie_grads(g, ties)
        if optimizer == "sgd":
            return packed - lr * g - lr * wd * packed, m, v, loss
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * jnp.square(g)
        lr_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        upd = m / (jnp.sqrt(v) + epsilon)
        new_p = packed - lr_t * upd - lr * wd * packed
        return new_p, m, v, loss

    return jax.jit(step, donate_argnums=(1, 2))


def make_train_step(cfg: TransformerLMConfig, mesh: Mesh,
                    optimizer: str = "adam", lr: float = 1e-4,
                    beta1: float = 0.9, beta2: float = 0.999,
                    epsilon: float = 1e-8, wd: float = 0.01,
                    grad_accum: int = 1, aux_weight: float = 0.01):
    """Build the jitted SPMD train step.

    Batch is sharded over (dp, fsdp); sequence over sp; XLA derives the rest
    from the parameter shardings.  Buffer donation on params+opt state.

    ``grad_accum=k`` scans over k micro-batches inside the step, summing
    gradients before the single optimizer update (the reference's
    kAddTo/grad_req='add' accumulation).  The masked-CE is normalised by
    the GLOBAL valid-token count (computed from the labels up front), so
    for dense configs a batch of B with k-way accumulation takes exactly
    the same update as an unaccumulated batch of B.  For MoE configs the
    load-balance aux loss is computed per micro-batch and averaged — the
    balance penalty is nonlinear in batch composition, so the aux term
    (weight 0.01) differs slightly from the full-batch value; this is the
    standard accumulation semantics for MoE.
    """
    data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.shape)
    seq_axis = "sp" if "sp" in mesh.shape else None
    batch_spec = P(data_axes if data_axes else None, seq_axis)

    def step(params, opt_m, opt_v, tokens, labels, t):
        tokens = constraint(tokens, batch_spec)
        labels = constraint(labels, batch_spec)

        if grad_accum == 1:
            def lf(ps):
                return loss_fn(ps, tokens, labels, cfg, mesh,
                               aux_weight=aux_weight)

            loss, grads = jax.value_and_grad(lf)(params)
        else:
            k = grad_accum
            B = tokens.shape[0]
            assert B % k == 0, f"batch {B} must divide grad_accum {k}"
            total_valid = jnp.maximum(jnp.sum(labels >= 0), 1).astype(
                jnp.float32)

            def to_micro(x):
                x = x.reshape((k, B // k) + x.shape[1:])
                return constraint(x, P(None, *batch_spec))

            toks_m, labs_m = to_micro(tokens), to_micro(labels)

            def micro_obj(ps, tok, lab):
                logits, aux = forward(ps, tok, cfg, mesh)
                nll, _valid = _masked_nll(logits, lab)
                return jnp.sum(nll) / total_valid + aux_weight * aux / k

            def body(carry, xs):
                g_acc, loss_acc = carry
                tok, lab = xs
                l_mb, g_mb = jax.value_and_grad(micro_obj)(params, tok, lab)
                return (jax.tree_util.tree_map(jnp.add, g_acc, g_mb),
                        loss_acc + l_mb), None

            g0 = jax.tree_util.tree_map(
                lambda w: jnp.zeros(w.shape, jnp.float32), params)
            (grads, loss), _ = lax.scan(body, (g0, jnp.float32(0)),
                                        (toks_m, labs_m))
        new_p, new_m, new_v = {}, {}, {}
        lr_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        for n, w in params.items():
            g = grads[n].astype(jnp.float32)
            m = beta1 * opt_m[n] + (1 - beta1) * g
            v = beta2 * opt_v[n] + (1 - beta2) * jnp.square(g)
            upd = m / (jnp.sqrt(v) + epsilon)
            wf = w.astype(jnp.float32)
            if optimizer == "lamb":
                upd = upd + wd * wf
                r1 = jnp.linalg.norm(wf)
                r2 = jnp.linalg.norm(upd)
                trust = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
                new_w = wf - lr * trust * upd
            else:  # adamw-style decoupled decay
                new_w = wf - lr_t * upd - lr * wd * wf
            new_p[n] = new_w.astype(w.dtype)
            new_m[n], new_v[n] = m, v
        return new_p, new_m, new_v, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))
