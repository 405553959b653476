"""``gluon.model_zoo.glm4_moe_lite`` and what it is built from: the rotary
operator, the latent-attention core and block, the multi-token loss, the
gated sparse-expert block, and the whole model through
``Trainer.compile_step``.  CPU, toy widths; the comparison with the plain
reference at the configuration's own tolerances is
``tests/perfbench/test_reference_glm47.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import glm4_moe_lite as glm
from mxnet_tpu.ops import contrib
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.rotary import rope
from perfbench import manifest

TOY = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
           v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-5,
           intermediate_size=96, moe_intermediate_size=32,
           n_shared_experts=1, n_routed_experts=8, num_experts_per_tok=2,
           routed_scaling_factor=1.8, first_k_dense_replace=1,
           num_hidden_layers=3, num_nextn_predict_layers=1, vocab_size=128,
           hidden_act="silu")
REFERENCE = manifest.load_module("configs", "glm_4_7_flash_ep8")


def _tokens(seed, batch=2, seq=16, vocab=128):
    ids = np.random.default_rng(seed).integers(0, vocab, (batch, seq + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


# -- rotary positions -----------------------------------------------------------
def test_rope_turns_pairs_by_position_and_keeps_lengths():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 10, 3, 8))
    y = rope(x, theta=100.0)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)   # position 0
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # feature i pairs with feature i + 4, turned by t * theta^(-i / 4)
    t, i = 7, 1
    angle = t * 100.0 ** (-i / 4)
    np.testing.assert_allclose(
        y[0, t, 2, i], x[0, t, 2, i] * np.cos(angle)
        - x[0, t, 2, i + 4] * np.sin(angle), rtol=1e-5)
    with pytest.raises(ValueError, match="rope"):
        rope(x[..., :7])


def test_a_rotated_score_depends_on_the_distance_alone():
    q = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(1), (8,)),
                         (1, 12, 8))
    k = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(2), (8,)),
                         (1, 12, 8))
    scores = jnp.einsum("bqd,bkd->qk", rope(q), rope(k))
    for distance in (0, 1, 5):
        diagonal = jnp.diagonal(scores, -distance)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=1e-4,
                                   atol=1e-5)


def test_rope_keeps_the_type_that_arrives_and_is_an_operator():
    x = mx.nd.array(np.ones((1, 4, 8), np.float32)).astype("bfloat16")
    assert str(mx.nd.rope(x, theta=1e6).dtype) == "bfloat16"


# -- the latent-attention core ------------------------------------------------
def _latent_inputs(heads, nope, rope_dim, v_dim, seq=32, seed=4, scale=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (scale * jax.random.normal(ks[0], (2, seq,
                                              heads * (nope + rope_dim))),
            scale * jax.random.normal(ks[1], (2, seq, heads * (nope + v_dim))),
            scale * jax.random.normal(ks[2], (2, seq, rope_dim)))


def _latent_by_hand(q, kv, k_rope, heads, nope, rope_dim, theta):
    """A head at a time, the one rotary key written once."""
    b, s, _ = q.shape
    q4 = q.reshape(b, s, heads, nope + rope_dim)
    kv4 = kv.reshape(b, s, heads, -1)
    key = rope(k_rope, theta=theta)
    mask = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for h in range(heads):
        q_h = jnp.concatenate([q4[:, :, h, :nope],
                               rope(q4[:, :, h, nope:], theta=theta)], -1)
        k_h = jnp.concatenate([kv4[:, :, h, :nope], key], -1)
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h, precision="highest") \
            / np.sqrt(nope + rope_dim)
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
        outs.append(jnp.einsum("bqk,bkd->bqd", att, kv4[:, :, h, nope:],
                               precision="highest"))
    return jnp.concatenate(outs, -1)


def test_latent_core_equals_a_head_at_a_time_with_one_rotary_key():
    heads, nope, rope_dim, v_dim = 4, 12, 4, 20       # values wider than keys
    q, kv, k_rope = _latent_inputs(heads, nope, rope_dim, v_dim)
    base = mx.telemetry.snapshot()
    out = mx.nd.causal_latent_selfatt(
        *(mx.nd.array(np.asarray(t)) for t in (q, kv, k_rope)),
        heads=heads, rope_dim=rope_dim, theta=1e4)
    want = _latent_by_hand(q, kv, k_rope, heads, nope, rope_dim, 1e4)
    assert out.shape == (2, 32, heads * v_dim)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-5)
    assert mx.telemetry.delta(base)["attention.latent_unfused"] >= 1
    # the rotary key's gradient is the sum over the heads' uses
    g = jax.grad(lambda r: jnp.sum(contrib.causal_latent_selfatt(
        q, kv, r, heads=heads, rope_dim=rope_dim, theta=1e4) ** 2))(k_rope)
    w = jax.grad(lambda r: jnp.sum(_latent_by_hand(
        q, kv, r, heads, nope, rope_dim, 1e4) ** 2))(k_rope)
    np.testing.assert_allclose(g, w, atol=2e-5)


def test_latent_core_takes_the_kernels_on_a_tpu_and_says_when_it_cannot(
        monkeypatch):
    """Forced as the BERT tests force it (Pallas interpreter): heads of 128
    for scores and values take ``flash_attention_gqa``; the toy widths do
    not, and a TPU trace says so with a ``fallback`` event."""
    monkeypatch.setattr(contrib, "_attention_platform", lambda: "tpu")
    monkeypatch.setattr(pk, "_BLOCK", 16)
    heads, nope, rope_dim = 2, 96, 32
    q, kv, k_rope = _latent_inputs(heads, nope, rope_dim, 128, scale=0.1)
    base = mx.telemetry.snapshot()
    seq0 = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    args = dict(heads=heads, rope_dim=rope_dim, theta=1e6)
    got = contrib.causal_latent_selfatt(q, kv, k_rope, **args)
    want = _latent_by_hand(q, kv, k_rope, heads, nope, rope_dim, 1e6)
    np.testing.assert_allclose(got, want, atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(contrib.causal_latent_selfatt(
        *a, **args) ** 2), argnums=(0, 1, 2))(q, kv, k_rope)
    wants = jax.grad(lambda *a: jnp.sum(_latent_by_hand(
        *a, heads, nope, rope_dim, 1e6) ** 2), argnums=(0, 1, 2))(
            q, kv, k_rope)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(g, w, atol=5e-5)
    assert mx.telemetry.delta(base)["attention.latent_fused"] >= 1
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > seq0]
    q, kv, k_rope = _latent_inputs(4, 12, 4, 16)
    contrib.causal_latent_selfatt(q, kv, k_rope, heads=4, rope_dim=4)
    new = [e for e in mx.telemetry.events("fallback") if e["seq"] > seq0]
    assert [e["name"] for e in new] == ["attention.latent_fused"]
    assert "128-lane" in new[0]["why"]


# -- the loss -------------------------------------------------------------------
def test_multi_token_loss_is_two_cross_entropies_the_second_shifted():
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((2, 2, 6, 11)).astype(np.float32)
    labels = rng.integers(0, 11, (2, 6)).astype(np.int32)
    loss = mx.gluon.loss.MultiTokenCrossEntropyLoss((1.0, 0.3))
    got = loss(mx.nd.array(logits), mx.nd.array(labels)).asnumpy()
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    main = -np.take_along_axis(logp[:, 0], labels[..., None], -1)[..., 0]
    # depth 1 at position i is held to the label of position i + 1; the
    # last position has none
    ahead = -np.take_along_axis(logp[:, 1, :-1], labels[:, 1:, None],
                                -1)[..., 0]
    np.testing.assert_allclose(got, main.mean(1) + 0.3 * ahead.mean(1),
                               rtol=1e-5)
    # the backward is one stack of the two cotangents: nothing reaches the
    # last position of depth 1
    x = mx.nd.array(logits)
    x.attach_grad()
    with mx.autograd.record():
        total = loss(x, mx.nd.array(labels)).sum()
    total.backward()
    grad = x.grad.asnumpy()
    assert np.all(grad[:, 1, -1] == 0) and np.all(grad[:, 1, :-1] != 0)
    with pytest.raises(Exception, match="depths"):
        mx.gluon.loss.MultiTokenCrossEntropyLoss((1.0,))(
            mx.nd.array(logits), mx.nd.array(labels))


# -- the model ------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    mx.random.seed(3)
    net = glm.glm4_moe_lite(TOY, held_experts=(0, 1, 2, 3), init_std=0.1)
    net.initialize()
    return net


def test_the_model_is_built_from_its_config_keys(model):
    params = model.collect_params()
    # the embedding and the head are in the model ONCE: the module owns
    # neither
    assert [n for n in params if "embed" in n] == ["model.embed_tokens.weight"]
    assert [n for n in params if "lm_head" in n] == ["lm_head.weight"]
    shapes = {n: p.shape for n, p in params.items()}
    assert shapes["model.layers.0.mlp.gate_up_proj.weight"] == (192, 64)
    assert shapes["model.layers.1.mlp.experts_up"] == (4, 64, 64)
    assert shapes["model.layers.1.mlp.experts_down"] == (4, 32, 64)
    assert shapes["model.layers.1.mlp.router_weight"] == (8, 64)
    assert shapes["model.layers.2.self_attn.kv_a_proj_with_mqa.weight"] \
        == (20, 64)
    assert shapes["model.layers.2.self_attn.kv_b_proj.weight"] == (112, 16)
    assert shapes["mtp.eh_proj.weight"] == (64, 128)
    assert "mtp.layers.0.mlp.experts_up" in shapes
    x, _ = _tokens(0)
    assert model(mx.nd.array(x)).shape == (2, 2, 16, 128)
    plain = glm.glm4_moe_lite({**TOY, "num_nextn_predict_layers": 0})
    plain.initialize()
    assert plain(mx.nd.array(x)).shape == (2, 16, 128)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        glm.glm4_moe_lite({**TOY, "num_nextn_predict_layers": 2})
    with pytest.raises(ValueError, match="hidden_act"):
        glm.glm4_moe_lite({**TOY, "hidden_act": "relu2"})


def test_logits_loss_and_gradients_equal_the_plain_references(model):
    """Float32 against float32 on seeded weights: both depths' logits, the
    combined loss, and the gradient of one parameter of each kind.  The
    embedding's and the head's are the sums of both uses: the reference
    passes the same two tables to the module."""
    sizes = {**TOY, "router_experts": 8, "n_routed_experts": 4,
             "mtp_loss_weight": 0.3}     # the reference holds ids 0-3 too
    x, y = _tokens(1)
    loss_fn = mx.gluon.loss.MultiTokenCrossEntropyLoss((1.0, 0.3))
    with mx.autograd.record():
        logits = model(mx.nd.array(x))
        loss = loss_fn(logits, mx.nd.array(y)).mean()
    loss.backward()
    params = {n: jnp.asarray(p.data().asnumpy())
              for n, p in model.collect_params().items()}

    def reference(p):
        ref_loss, ref_logits, _ = REFERENCE.reference_parts(p, x, y, sizes)
        return ref_loss, ref_logits

    with jax.default_matmul_precision("highest"):
        (ref_loss, ref_logits), grads = jax.value_and_grad(
            reference, has_aux=True)(params)
    np.testing.assert_allclose(logits.asnumpy(), ref_logits, atol=2e-4)
    np.testing.assert_allclose(float(loss.asnumpy()), float(ref_loss),
                               rtol=1e-5)
    for name in ("model.embed_tokens.weight", "lm_head.weight",
                 "model.layers.0.self_attn.q_a_proj.weight",
                 "model.layers.0.self_attn.q_a_layernorm.gamma",
                 "model.layers.1.self_attn.kv_a_proj_with_mqa.weight",
                 "model.layers.1.self_attn.kv_b_proj.weight",
                 "model.layers.2.self_attn.o_proj.weight",
                 "model.layers.0.mlp.gate_up_proj.weight",
                 "model.layers.1.mlp.router_weight",
                 "model.layers.1.mlp.experts_up",
                 "model.layers.2.mlp.experts_down",
                 "model.layers.2.mlp.shared_expert.down_proj.weight",
                 "model.norm.gamma", "mtp.eh_proj.weight", "mtp.enorm.gamma",
                 "mtp.layers.0.mlp.experts_up", "mtp.norm.gamma"):
        got = model.collect_params()[name].grad().asnumpy()
        want = np.asarray(grads[name])
        assert np.abs(want).max() > 0, name
        np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max(),
                                   err_msg=name)
    # the module's use alone moves the shared tables: with its loss off
    # their gradients differ
    with jax.default_matmul_precision("highest"):
        alone = jax.grad(lambda p: REFERENCE.reference_parts(
            p, x, y, {**sizes, "mtp_loss_weight": 0.0})[0])(params)
    for name in ("model.embed_tokens.weight", "lm_head.weight"):
        assert np.abs(np.asarray(grads[name] - alone[name])).max() \
            > 1e-3 * np.abs(np.asarray(grads[name])).max()


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """The block itself: every rank's routed part of the SAME layer (each
    told its 2 of 16 experts), and what every rank computes alike (the
    shared expert) counted once, equal the layer that holds all 16."""
    mx.random.seed(5)
    init = mx.initializer.Normal(0.2)
    whole = glm.Glm4MoeLiteMoE(32, 16, 4, 24, 24, 1.8, None, init, init)
    whole.initialize()
    x = mx.nd.array(np.random.default_rng(2).standard_normal((2, 12, 32)))
    want = whole(x).asnumpy()
    total = whole.shared_expert(x).asnumpy()
    for rank in range(8):
        held = (2 * rank, 2 * rank + 1)
        part = glm.Glm4MoeLiteMoE(32, 16, 4, 24, 24, 1.8, held, init, init)
        part.initialize()
        for name in ("router_weight", "e_score_correction_bias"):
            getattr(part, name).set_data(getattr(whole, name).data())
        for name in ("experts_up", "experts_down"):
            getattr(part, name).set_data(
                getattr(whole, name).data()[2 * rank:2 * rank + 2])
        total = total + part.routed(x).asnumpy()
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_compile_step_one_dispatch_a_step_and_both_uses_reach_one_state(
        monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")    # one chip, as the cell
    mx.random.seed(7)
    net = glm.glm4_moe_lite(TOY, held_experts=(0, 1, 2, 3),
                            recompute_layers=True, init_std=0.05)
    net.initialize()
    net.hybridize()
    loss_fn = mx.gluon.loss.MultiTokenCrossEntropyLoss((1.0, 0.3))
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 3e-3}, kvstore="tpu")
    step = trainer.compile_step(
        net, lambda n, x, y: loss_fn(n(x), y).mean())
    x, y = (mx.nd.array(t) for t in _tokens(2, batch=2, seq=16))
    seq0 = max((e["seq"] for e in mx.telemetry.events("fallback")), default=0)
    losses = [float(step(x, y, batch_size=2).asnumpy()) for _ in range(3)]
    dispatches, traces = mx.cached_step.dispatch_count(), \
        mx.cached_step.trace_count()
    before = mx.telemetry.snapshot()
    losses += [float(step(x, y, batch_size=2).asnumpy()) for _ in range(12)]
    assert mx.cached_step.dispatch_count() - dispatches == 12
    assert mx.cached_step.trace_count() == traces
    assert step.last_step_compiled
    assert losses[-1] < losses[0] - 0.5 and all(map(np.isfinite, losses))
    assert not [e for e in mx.telemetry.events("fallback")
                if e["seq"] > seq0]
    # three expert layers (two of the model, one of the module) counted
    # once a step each, on the device
    after = mx.telemetry.snapshot()          # gauges: totals since birth
    assert after["moe.steps"] - before["moe.steps"] == 3 * 12
    assert after["moe.rows_overflow"] == before["moe.rows_overflow"]
    # one Adam state for the embedding and one for the head
    for shared in (net.model.embed_tokens.weight, net.lm_head.weight):
        assert sum(p is shared for p in trainer._params) == 1
        assert sum(p is shared for p in net.collect_params().values()) == 1
