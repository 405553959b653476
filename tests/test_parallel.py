"""Parallelism tests on the virtual 8-device CPU mesh.

Mirrors the reference's dist test strategy (tests/nightly/dist_sync_kvstore.py
run with the local launcher — SURVEY.md §4): numerical equality of the
distributed result against a single-device oracle.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel as par
from jax.sharding import PartitionSpec as P


def test_make_mesh_axis_order():
    mesh = par.make_mesh({"tp": 2, "dp": 4})
    assert mesh.axis_names == ("dp", "tp")
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2


def test_auto_mesh_fills_dp():
    mesh = par.auto_mesh(8, tp=2)
    assert mesh.shape["dp"] == 4


def test_sharding_plan_legalize():
    mesh = par.make_mesh({"dp": 2, "tp": 4})
    plan = par.ShardingPlan([(r"weight$", P("tp", None))])
    # 8 divisible by 4 -> sharded
    assert plan.spec_for("dense0.weight", (8, 16), mesh) == P("tp")
    # 6 not divisible by 4 -> replicated fallback
    assert plan.spec_for("dense0.weight", (6, 16), mesh) == P()
    # non-matching name -> default replicated
    assert plan.spec_for("dense0.bias", (8,), mesh) == P()


def test_fsdp_plan_shards_largest_dim():
    mesh = par.make_mesh({"fsdp": 8})
    plan = par.fsdp_plan(min_size=64)
    assert plan.spec_for("w", (16, 24), mesh) == P(None, "fsdp")
    assert plan.spec_for("tiny", (4,), mesh) == P()


def test_collectives_all_reduce_matches_sum():
    mesh = par.make_mesh({"dp": 8})
    x = jnp.arange(16.0).reshape(8, 2)

    def f(xs):
        return par.all_reduce(jnp.sum(xs), "dp")

    out = par.run_sharded(f, mesh, in_specs=(P("dp", None),), out_specs=P())(x)
    assert float(out) == float(jnp.sum(x))


def test_ring_shift_rotates():
    mesh = par.make_mesh({"sp": 8})
    x = jnp.arange(8.0)

    def f(xs):
        return par.ring_shift(xs, "sp", shift=1)

    out = par.run_sharded(f, mesh, in_specs=(P("sp"),), out_specs=P("sp"))(x)
    # shift=1 sends each shard to the next device: device j receives j-1's
    assert onp.allclose(onp.asarray(out), onp.roll(onp.arange(8.0), 1))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    B, H, S, D = 2, 4, 64, 16
    rng = onp.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D), dtype=jnp.float32)

    scale = 1.0 / onp.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        mask = onp.tril(onp.ones((S, S), dtype=bool))
        s = jnp.where(jnp.asarray(mask)[None, None], s, -jnp.inf)
    expected = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)

    mesh = par.make_mesh({"sp": 8})
    out = par.ring_attention_sharded(q, k, v, mesh, causal=causal,
                                     batch_axes=())
    assert onp.allclose(onp.asarray(out), onp.asarray(expected), atol=1e-4)


def test_moe_layer_shapes_and_routing():
    G, S, M, E, Hd = 2, 16, 8, 4, 32
    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randn(G, S, M), dtype=jnp.float32)
    gate_w = jnp.asarray(rng.randn(M, E) * 0.1, dtype=jnp.float32)
    w_in = jnp.asarray(rng.randn(E, M, Hd) * 0.1, dtype=jnp.float32)
    w_out = jnp.asarray(rng.randn(E, Hd, M) * 0.1, dtype=jnp.float32)
    out, aux = par.moe_layer(x, gate_w, w_in, w_out, k=2,
                             capacity_factor=2.0)
    assert out.shape == (G, S, M)
    assert float(aux) > 0
    assert onp.isfinite(onp.asarray(out)).all()


def test_moe_single_expert_equals_dense_ffn():
    # with E=1, k=1, ample capacity the MoE must equal the plain FFN
    G, S, M, Hd = 1, 8, 4, 16
    rng = onp.random.RandomState(2)
    x = jnp.asarray(rng.randn(G, S, M), dtype=jnp.float32)
    gate_w = jnp.zeros((M, 1), dtype=jnp.float32)
    w_in = jnp.asarray(rng.randn(1, M, Hd) * 0.3, dtype=jnp.float32)
    w_out = jnp.asarray(rng.randn(1, Hd, M) * 0.3, dtype=jnp.float32)
    out, _ = par.moe_layer(x, gate_w, w_in, w_out, k=1, capacity_factor=1.0,
                           capacity=None)
    expected = jax.nn.gelu(x @ w_in[0]) @ w_out[0]
    assert onp.allclose(onp.asarray(out), onp.asarray(expected), atol=1e-5)


def test_pipeline_matches_sequential():
    n_stage, B, Dm = 8, 16, 8
    rng = onp.random.RandomState(3)
    ws = [jnp.asarray(rng.randn(Dm, Dm) * 0.2, dtype=jnp.float32)
          for _ in range(n_stage)]
    x = jnp.asarray(rng.randn(B, Dm), dtype=jnp.float32)

    def stage(params, a):
        return jnp.tanh(a @ params["w"])

    expected = x
    for w in ws:
        expected = jnp.tanh(expected @ w)

    mesh = par.make_mesh({"pp": 8})
    stacked = par.stack_stage_params([{"w": w} for w in ws])
    fn = par.pipelined(stage, mesh, num_microbatches=4, axis_name="pp",
                       param_spec={"w": P("pp", None, None)}, x_spec=P())
    out = fn(stacked, x)
    assert onp.allclose(onp.asarray(out), onp.asarray(expected), atol=1e-5)


def test_hetero_pipeline_matches_sequential():
    """Non-shape-preserving heterogeneous stages (4 -> 16 -> 8 widths)
    through pp=2 x dp=4 must match the sequential program."""
    B = 16
    rng = onp.random.RandomState(7)
    w0 = jnp.asarray(rng.randn(4, 16) * 0.3, jnp.float32)
    b0 = jnp.asarray(rng.randn(16) * 0.1, jnp.float32)
    w1 = jnp.asarray(rng.randn(16, 8) * 0.3, jnp.float32)
    x = jnp.asarray(rng.randn(B, 4), jnp.float32)

    def stage0(p, a):
        return jax.nn.relu(a @ p["w"] + p["b"])

    def stage1(p, a):
        return a @ p["w"]

    expected = stage1({"w": w1}, stage0({"w": w0, "b": b0}, x))

    mesh = par.make_mesh({"pp": 2, "dp": 4})
    pipe = par.HeteroPipeline(
        [stage0, stage1], [{"w": w0, "b": b0}, {"w": w1}], mesh,
        num_microbatches=2, example_x=x)
    out = pipe.apply(pipe.packed_params, x)
    assert out.shape == (B, 8)
    assert onp.allclose(onp.asarray(out), onp.asarray(expected), atol=1e-5)

    # params round-trip through the packed buffer exactly
    sp0, sp1 = pipe.unpack_stage_params()
    assert onp.allclose(onp.asarray(sp0["w"]), onp.asarray(w0))
    assert onp.allclose(onp.asarray(sp1["w"]), onp.asarray(w1))


def test_hetero_pipeline_grads_match_sequential():
    """Microbatch gradient accumulation through the pp scan equals the
    unpipelined gradient."""
    B = 8
    rng = onp.random.RandomState(8)
    w0 = jnp.asarray(rng.randn(6, 12) * 0.3, jnp.float32)
    w1 = jnp.asarray(rng.randn(12, 3) * 0.3, jnp.float32)
    x = jnp.asarray(rng.randn(B, 6), jnp.float32)
    y = jnp.asarray(rng.randn(B, 3), jnp.float32)

    def stage0(p, a):
        return jnp.tanh(a @ p["w"])

    def stage1(p, a):
        return a @ p["w"]

    def seq_loss(ws):
        out = stage1({"w": ws[1]}, stage0({"w": ws[0]}, x))
        return jnp.mean((out - y) ** 2)

    g_seq = jax.grad(seq_loss)((w0, w1))

    mesh = par.make_mesh({"pp": 2, "dp": 2})
    pipe = par.HeteroPipeline(
        [stage0, stage1], [{"w": w0}, {"w": w1}], mesh,
        num_microbatches=4, example_x=x, remat=True)

    def pp_loss(packed):
        out = pipe.apply(packed, x)
        return jnp.mean((out - y) ** 2)

    g_packed = jax.grad(pp_loss)(pipe.packed_params)
    g0, g1 = pipe.unpack_stage_params(g_packed)
    assert onp.allclose(onp.asarray(g0["w"]), onp.asarray(g_seq[0]),
                        atol=1e-5)
    assert onp.allclose(onp.asarray(g1["w"]), onp.asarray(g_seq[1]),
                        atol=1e-5)


def test_hetero_pipeline_grads_smoke():
    """Tier-1 smoke for the slow remat variant above: same pack/scan/
    grad path, 2 microbatches, no remat."""
    B = 4
    rng = onp.random.RandomState(8)
    w0 = jnp.asarray(rng.randn(4, 6) * 0.3, jnp.float32)
    w1 = jnp.asarray(rng.randn(6, 2) * 0.3, jnp.float32)
    x = jnp.asarray(rng.randn(B, 4), jnp.float32)
    y = jnp.asarray(rng.randn(B, 2), jnp.float32)

    def stage0(p, a):
        return jnp.tanh(a @ p["w"])

    def stage1(p, a):
        return a @ p["w"]

    def seq_loss(ws):
        out = stage1({"w": ws[1]}, stage0({"w": ws[0]}, x))
        return jnp.mean((out - y) ** 2)

    g_seq = jax.grad(seq_loss)((w0, w1))
    mesh = par.make_mesh({"pp": 2, "dp": 2})
    pipe = par.HeteroPipeline(
        [stage0, stage1], [{"w": w0}, {"w": w1}], mesh,
        num_microbatches=2, example_x=x, remat=False)

    def pp_loss(packed):
        out = pipe.apply(packed, x)
        return jnp.mean((out - y) ** 2)

    g0, g1 = pipe.unpack_stage_params(jax.grad(pp_loss)(pipe.packed_params))
    assert onp.allclose(onp.asarray(g0["w"]), onp.asarray(g_seq[0]),
                        atol=1e-5)
    assert onp.allclose(onp.asarray(g1["w"]), onp.asarray(g_seq[1]),
                        atol=1e-5)


def _pp_transformer_setup():
    from mxnet_tpu import models

    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=2, num_heads=2, hidden=16, mlp_hidden=32,
        max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = onp.random.RandomState(0)
    B, S = 8, 16
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    labels_np = rng.randint(0, cfg.vocab_size, (B, S))
    labels_np[rng.rand(B, S) < 0.5] = -1       # mask half the positions
    labels = jnp.asarray(labels_np, jnp.int32)
    return models, cfg, params, tokens, labels


def test_pp_transformer_loss_smoke():
    """Tier-1 smoke for the flagship pp TransformerLM: the pipelined
    loss matches the unpipelined model (forward compile only; the
    grad-equality + train-step oracle rides the slow lane)."""
    models, cfg, params, tokens, labels = _pp_transformer_setup()
    ref_loss = float(models.loss_fn(params, tokens, labels, cfg))
    mesh = par.make_mesh({"pp": 2, "dp": 2})
    pipe = models.make_pp_pipeline(cfg, params, mesh, num_microbatches=2,
                                   example_tokens=tokens)
    pp_loss = float(models.pp_loss_fn(pipe, pipe.packed_params, tokens,
                                      labels))
    assert abs(pp_loss - ref_loss) < 1e-4, (pp_loss, ref_loss)


def test_pp_transformer_loss_matches_unpipelined():
    """Flagship TransformerLM through HeteroPipeline pp=2: loss and grads
    match the unpipelined model (VERDICT round-1 item 3).  11 s alone
    with a warm compile cache, 23 s cold (PR 28)."""
    from mxnet_tpu import models

    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=2, num_heads=2, hidden=16, mlp_hidden=32,
        max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = onp.random.RandomState(0)
    B, S = 8, 16
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    labels_np = rng.randint(0, cfg.vocab_size, (B, S))
    labels_np[rng.rand(B, S) < 0.5] = -1       # mask half the positions
    labels = jnp.asarray(labels_np, jnp.int32)

    ref_loss = float(models.loss_fn(params, tokens, labels, cfg))

    mesh = par.make_mesh({"pp": 2, "dp": 2})
    pipe = models.make_pp_pipeline(cfg, params, mesh, num_microbatches=2,
                                   example_tokens=tokens)
    pp_loss = float(models.pp_loss_fn(pipe, pipe.packed_params, tokens,
                                      labels))
    assert abs(pp_loss - ref_loss) < 1e-4, (pp_loss, ref_loss)

    # gradient equality: per-layer params match; tied embed grad equals
    # stage-0 embed grad + last-stage head grad
    g_ref = jax.grad(
        lambda p: models.loss_fn(p, tokens, labels, cfg))(params)
    g_packed = jax.grad(
        lambda pk: models.pp_loss_fn(pipe, pk, tokens, labels))(
        pipe.packed_params)
    g0, g1 = pipe.unpack_stage_params(g_packed)
    assert onp.allclose(onp.asarray(g0["layer0.attn.qkv.weight"]),
                        onp.asarray(g_ref["layer0.attn.qkv.weight"]),
                        atol=1e-4)
    assert onp.allclose(onp.asarray(g1["layer1.ffn_2.weight"]),
                        onp.asarray(g_ref["layer1.ffn_2.weight"]),
                        atol=1e-4)
    tied = onp.asarray(g0["embed.weight"]) + onp.asarray(g1["head.weight"])
    assert onp.allclose(tied, onp.asarray(g_ref["embed.weight"]), atol=1e-4)

    # one pp train step runs and the loss is finite
    step = models.make_pp_train_step(pipe, optimizer="adam", lr=1e-3)
    m = jnp.zeros_like(pipe.packed_params)
    v = jnp.zeros_like(pipe.packed_params)
    before = onp.asarray(jax.device_get(pipe.packed_params)).copy()
    new_packed, m, v, loss = step(pipe.packed_params, m, v, tokens, labels,
                                  jnp.float32(1))
    assert onp.isfinite(float(loss))
    assert not onp.allclose(onp.asarray(new_packed), before)

    # tied embed/head copies stay exactly tied after the update (grads are
    # summed across stages before the optimizer step)
    n0, n1 = pipe.unpack_stage_params(new_packed)
    assert onp.allclose(onp.asarray(n0["embed.weight"]),
                        onp.asarray(n1["head.weight"]))
    # the update actually incorporated the tied (summed) gradient
    assert not onp.allclose(onp.asarray(n0["embed.weight"]),
                            onp.asarray(params["embed.weight"]))


def test_sharded_trainer_data_parallel_matches_single():
    from mxnet_tpu.gluon import nn

    def build():
        net = nn.Dense(4, in_units=8)
        net.initialize(mx.init.Constant(0.05))
        return net

    def loss_fn(out, label):
        diff = out - label
        return (diff * diff).mean()

    rng = onp.random.RandomState(4)
    data = rng.randn(16, 8).astype(onp.float32)
    label = rng.randn(16, 4).astype(onp.float32)

    # single-device oracle (dp=1 mesh)
    net1 = build()
    mesh1 = par.make_mesh({"dp": 1})
    tr1 = par.ShardedTrainer(net1, loss_fn, mesh1, optimizer="sgd",
                             optimizer_params={"lr": 0.1, "momentum": 0.9})
    # dp=8
    net8 = build()
    mesh8 = par.make_mesh({"dp": 8})
    tr8 = par.ShardedTrainer(net8, loss_fn, mesh8, optimizer="sgd",
                             optimizer_params={"lr": 0.1, "momentum": 0.9})

    for _ in range(3):
        l1 = tr1.step(data, label)
        l8 = tr8.step(data, label)
        assert abs(l1 - l8) < 1e-4
    w1 = onp.asarray(tr1.params["weight"])
    w8 = onp.asarray(tr8.params["weight"])
    assert onp.allclose(w1, w8, atol=1e-5)


def test_sharded_trainer_fsdp_tp():
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"))
    net.add(nn.Dense(8, in_units=16))
    net.initialize(mx.init.Xavier())

    def loss_fn(out, label):
        diff = out - label
        return (diff * diff).mean()

    mesh = par.make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    plan = par.fsdp_plan()
    tr = par.ShardedTrainer(net, loss_fn, mesh, plan=plan, optimizer="adam",
                            optimizer_params={"lr": 1e-2})
    rng = onp.random.RandomState(5)
    data = rng.randn(8, 8).astype(onp.float32)
    label = rng.randn(8, 8).astype(onp.float32)
    losses = [tr.step(data, label) for _ in range(4)]
    assert losses[-1] < losses[0]
    tr.sync_to_block()


def test_sharded_trainer_bf16_compute_fp32_master():
    """Mixed precision: compute_dtype=bfloat16 runs fwd/bwd in bf16 (the
    MXU-native path) while params + optimizer state stay fp32 master
    copies; training still converges and tracks the fp32 run loosely."""
    import jax.numpy as jnp

    from mxnet_tpu.gluon import nn

    def build():
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=8, activation="relu"),
                nn.Dense(4, in_units=32))
        net.initialize(mx.init.Constant(0.05))
        return net

    def loss_fn(out, label):
        diff = out - label
        return (diff * diff).mean()

    rng = onp.random.RandomState(5)
    data = rng.randn(16, 8).astype(onp.float32)
    label = rng.randn(16, 4).astype(onp.float32)

    mesh = par.make_mesh({"dp": 1})
    tr32 = par.ShardedTrainer(build(), loss_fn, mesh, optimizer="sgd",
                              optimizer_params={"lr": 0.05})
    trbf = par.ShardedTrainer(build(), loss_fn, mesh, optimizer="sgd",
                              optimizer_params={"lr": 0.05},
                              compute_dtype=jnp.bfloat16)
    l32 = [float(tr32.step(data, label)) for _ in range(6)]
    lbf = [float(trbf.step(data, label)) for _ in range(6)]
    assert lbf[-1] < lbf[0]
    # bf16 tracks fp32 within bf16 resolution-scale error
    assert abs(lbf[-1] - l32[-1]) < 0.1 * max(abs(l32[0]), 1.0)
    # master state stayed fp32
    assert all(v.dtype == jnp.float32 for v in trbf.params.values())
    for st in trbf.opt_state.values():
        assert all(s.dtype == jnp.float32 for s in st)


def test_sharded_trainer_bf16_grad_accum_with_batchnorm():
    """compute_dtype + grad_accum must agree on scan-carry dtypes even
    when BatchNorm running stats (fp32 masters) chain through the bf16
    micro-batch bodies."""
    import jax.numpy as jnp

    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8), nn.BatchNorm(in_channels=16),
            nn.Activation("relu"), nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))

    def loss_fn(out, label):
        diff = out - label
        return (diff * diff).mean()

    rng = onp.random.RandomState(9)
    data = rng.randn(16, 8).astype(onp.float32)
    label = rng.randn(16, 4).astype(onp.float32)
    mesh = par.make_mesh({"dp": 1})
    tr = par.ShardedTrainer(net, loss_fn, mesh, optimizer="sgd",
                            optimizer_params={"lr": 0.05},
                            grad_accum=2, compute_dtype=jnp.bfloat16)
    losses = [float(tr.step(data, label)) for _ in range(5)]
    assert losses[-1] < losses[0]
    assert all(v.dtype == jnp.float32 for v in tr.params.values())


def _adam_ref_loop(cfg, params, batches, lr=1e-3, beta1=0.9, beta2=0.999,
                   epsilon=1e-8):
    """Unpipelined oracle: loss_fn + tree-space adam matching
    make_pp_train_step's packed-space update (wd=0)."""
    from mxnet_tpu import models

    tmap = jax.tree_util.tree_map
    m = tmap(lambda w: jnp.zeros_like(w), params)
    v = tmap(lambda w: jnp.zeros_like(w), params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, g = jax.value_and_grad(
            lambda p: models.loss_fn(p, tokens, labels, cfg))(params)
        m = tmap(lambda a, b: beta1 * a + (1 - beta1) * b, m, g)
        v = tmap(lambda a, b: beta2 * a + (1 - beta2) * jnp.square(b), v, g)
        lr_t = lr * onp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        params = tmap(
            lambda w, a, b: w - lr_t * a / (jnp.sqrt(b) + epsilon),
            params, m, v)
        losses.append(float(loss))
    return params, losses


def test_pp_multistep_convergence_matches_unpipelined():
    """VERDICT r3 item 9: ≥10 steps of pp training track the unpipelined
    loss curve — schedule bugs (stale activations, microbatch skew,
    mis-summed tied grads) compound over steps and would diverge."""
    from mxnet_tpu import models

    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=2, num_heads=2, hidden=16, mlp_hidden=32,
        max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = onp.random.RandomState(3)
    B, S, steps = 8, 16, 10
    batches = []
    for _ in range(steps):
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)),
                             jnp.int32)
        labels_np = rng.randint(0, cfg.vocab_size, (B, S))
        labels_np[rng.rand(B, S) < 0.5] = -1
        batches.append((tokens, jnp.asarray(labels_np, jnp.int32)))

    _, ref_losses = _adam_ref_loop(cfg, params, batches)

    mesh = par.make_mesh({"pp": 2, "dp": 2})
    pipe = models.make_pp_pipeline(cfg, params, mesh, num_microbatches=2,
                                   example_tokens=batches[0][0])
    step = models.make_pp_train_step(pipe, optimizer="adam", lr=1e-3)
    packed = pipe.packed_params
    m = jnp.zeros_like(packed)
    v = jnp.zeros_like(packed)
    pp_losses = []
    for t, (tokens, labels) in enumerate(batches, start=1):
        packed, m, v, loss = step(packed, m, v, tokens, labels,
                                  jnp.float32(t))
        pp_losses.append(float(loss))
    # per-step equality with the oracle is the assertion: any schedule bug
    # compounds into divergence within a few steps (each step uses fresh
    # random batches, so the curve itself need not be monotone)
    onp.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-4, atol=2e-4)


def test_pp_ragged_batch_pad_smoke():
    """Tier-1 smoke for ragged pp batches: pp_pad_batch pads rows with
    label=-1 and the global-valid-count normalization makes the padded
    pipeline's LOSS exactly the unpadded batch's (the grad oracle rides
    the slow lane)."""
    from mxnet_tpu import models

    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=2, num_heads=2, hidden=16, mlp_hidden=32,
        max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = onp.random.RandomState(4)
    B_ragged, S = 6, 16
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B_ragged, S)),
                         jnp.int32)
    labels_np = rng.randint(0, cfg.vocab_size, (B_ragged, S))
    labels_np[rng.rand(B_ragged, S) < 0.5] = -1
    labels = jnp.asarray(labels_np, jnp.int32)
    ref_loss = float(models.loss_fn(params, tokens, labels, cfg))
    mesh = par.make_mesh({"pp": 2, "dp": 2})
    ptokens, plabels = models.pp_pad_batch(tokens, labels, 4)
    assert ptokens.shape[0] == 8
    pipe = models.make_pp_pipeline(cfg, params, mesh, num_microbatches=2,
                                   example_tokens=ptokens)
    pp_loss = float(models.pp_loss_fn(pipe, pipe.packed_params, ptokens,
                                      plabels))
    assert abs(pp_loss - ref_loss) < 1e-4, (pp_loss, ref_loss)


def test_pp_ragged_batch_pad_and_mask():
    """dp x pp with a ragged batch: pp_pad_batch pads rows with label=-1;
    global-valid-count normalization makes loss/grads EXACTLY the
    unpadded batch's."""
    from mxnet_tpu import models

    cfg = models.TransformerLMConfig(
        vocab_size=64, num_layers=2, num_heads=2, hidden=16, mlp_hidden=32,
        max_len=16, dtype=jnp.float32)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = onp.random.RandomState(4)
    B_ragged, S = 6, 16          # does not divide num_micro*dp = 4
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B_ragged, S)),
                         jnp.int32)
    labels_np = rng.randint(0, cfg.vocab_size, (B_ragged, S))
    labels_np[rng.rand(B_ragged, S) < 0.5] = -1
    labels = jnp.asarray(labels_np, jnp.int32)

    ref_loss = float(models.loss_fn(params, tokens, labels, cfg))

    mesh = par.make_mesh({"pp": 2, "dp": 2})
    ptokens, plabels = models.pp_pad_batch(tokens, labels, 4)
    assert ptokens.shape[0] == 8
    pipe = models.make_pp_pipeline(cfg, params, mesh, num_microbatches=2,
                                   example_tokens=ptokens)
    pp_loss = float(models.pp_loss_fn(pipe, pipe.packed_params, ptokens,
                                      plabels))
    assert abs(pp_loss - ref_loss) < 1e-4, (pp_loss, ref_loss)

    # gradients through the padded pipeline equal the unpadded oracle's
    g_ref = jax.grad(
        lambda p: models.loss_fn(p, tokens, labels, cfg))(params)
    g_packed = jax.grad(
        lambda pk: models.pp_loss_fn(pipe, pk, ptokens, plabels))(
        pipe.packed_params)
    g0, _g1 = pipe.unpack_stage_params(g_packed)
    onp.testing.assert_allclose(
        onp.asarray(g0["layer0.attn.qkv.weight"]),
        onp.asarray(g_ref["layer0.attn.qkv.weight"]), atol=1e-4)


def test_sharded_trainer_remat_under_dp8():
    # remat (jax.checkpoint) must be schedule-only under REAL shardings
    # too: dp=8 with and without recompute produce identical losses
    from mxnet_tpu.gluon import nn

    def build():
        net = nn.Dense(4, in_units=8)
        net.initialize(mx.init.Xavier())
        return net

    def loss_fn(out, label):
        diff = out - label
        return (diff * diff).mean()

    rng = onp.random.RandomState(9)
    data = rng.randn(16, 8).astype(onp.float32)
    label = rng.randn(16, 4).astype(onp.float32)

    losses = []
    for remat in (False, True):
        mx.random.seed(3)
        net = build()
        mesh = par.make_mesh({"dp": 8})
        tr = par.ShardedTrainer(net, loss_fn, mesh, optimizer="sgd",
                                optimizer_params={"lr": 0.1},
                                remat=remat)
        run = [float(tr.step(data, label)) for _ in range(3)]
        losses.append(run)
    onp.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
