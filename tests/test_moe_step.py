"""Expert-parallel MoE (ep) as a first-class mesh axis in the one
donated train step (ISSUE 20 tentpole + MoE parity satellite).

1. ``MoEBlock`` (dense-dispatch top-k MoE FFN) traces through
   ``Trainer.compile_step`` on an ``ep×dp`` mesh: expert weights are
   sharded ``P('ep')`` on dim 0 by the name-aware placement rule
   (``expert.*``), one donated launch per step, 0 retraces, 0
   steady-state reshards.
2. The load-balance aux loss reaches the optimizer through the
   Trainer's loss path — recorded into ``moe.aux_scope`` by the block,
   folded as ``MXNET_MOE_AUX_WEIGHT * sum`` into the differentiated
   heads by the TrainStep on BOTH the compiled and eager paths —
   without widening the user's loss_fn contract.
3. Parity: the ep-sharded trajectory matches the single-device
   dense-dispatch oracle across mesh shapes (1, ep=2, ep=4).  With
   k=2 routing each token has at most two nonzero combine
   contributions, so the partitioned reduction is a two-term float
   add — associativity cannot bite and the match is bit-for-bit
   across mesh shapes (the loss's own sum is split by dp: a few ulp
   against the no-mesh oracle).
4. Capacity-drop determinism: over-capacity token drops are a pinned,
   reproducible function of the gating state.
5. Composition: ``restore(like=)`` re-places expert weights across an
   ep mesh-shape change; pp+ep+dp coexist in ONE donated program
   (PipelineBlock and MoEBlock in the same net on a pp×dp×ep mesh).
"""
import contextlib
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, cached_step, config, engine, gluon
from mxnet_tpu.parallel import (CheckpointManager, moe as moe_mod,
                                sharding as shmod, spmd)
from mxnet_tpu.parallel.moe import MoEBlock, aux_scope, record_aux, \
    top_k_gating
from mxnet_tpu.parallel.pipeline import HeteroPipeline, PipelineBlock

NDEV = len(jax.devices())

pytestmark = pytest.mark.skipif(
    NDEV < 8, reason="needs the virtual 8-device CPU mesh")

G, S, M, H, E = 4, 6, 8, 16, 4     # groups, tokens, model, hidden, experts


@contextlib.contextmanager
def _mesh_env(spec, min_size="1", aux_weight=None):
    keys = ("MXNET_SPMD_MESH", "MXNET_FSDP_MIN_SIZE",
            "MXNET_MOE_AUX_WEIGHT")
    saved = {k: os.environ.get(k) for k in keys}
    os.environ["MXNET_SPMD_MESH"] = spec
    os.environ["MXNET_FSDP_MIN_SIZE"] = min_size
    if aux_weight is not None:
        os.environ["MXNET_MOE_AUX_WEIGHT"] = str(aux_weight)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _moe_net(seed=0):
    net = MoEBlock(units=M, hidden=H, num_experts=E, k=2)
    net.initialize(ctx=mx.cpu())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(
            mx.nd.array(rng.randn(*p.shape).astype(onp.float32) * 0.2)
            ._data)
    return net


_TARGET = onp.random.RandomState(99).randn(G, S, M).astype(onp.float32)


def _loss(net, x):
    y = net(x)
    return ((y - mx.nd.array(_TARGET, ctx=x.ctx)) ** 2).sum()


def _run_moe(spec, steps=4, seed=0, kvstore="tpu", aux_weight=None,
             compiled=True):
    losses = []
    with _mesh_env(spec, aux_weight=aux_weight):
        if not compiled:
            os.environ["MXNET_COMPILED_STEP"] = "0"
        try:
            net = _moe_net(seed)
            trainer = gluon.Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 0.01,
                                     "momentum": 0.9}, kvstore=kvstore)
            step = trainer.compile_step(net, _loss)
            rng = onp.random.RandomState(7)
            for _ in range(steps):
                x = rng.randn(G, S, M).astype(onp.float32)
                loss = step(mx.nd.array(x), batch_size=G)
                if compiled:
                    assert step.last_step_compiled, \
                        step.last_fallback_reason
                losses.append(float(loss.asnumpy().ravel()[0]))
            engine.waitall()
        finally:
            os.environ.pop("MXNET_COMPILED_STEP", None)
    return net, trainer, step, losses


def _params_of(net):
    return {k: p.data().asnumpy() for k, p in net.collect_params().items()}


# ---------------------------------------------------------------------------
# aux-loss plumbing
# ---------------------------------------------------------------------------

def test_aux_scope_records_and_nests():
    assert record_aux(1.0) is False          # no scope open: no-op
    with aux_scope() as outer:
        assert record_aux(2.0) is True
        with aux_scope() as inner:
            record_aux(3.0)
        assert inner == [3.0]
        record_aux(4.0)
    assert outer == [2.0, 4.0]
    assert record_aux(5.0) is False          # scope restored shut


def test_moe_aux_weight_declared(monkeypatch):
    monkeypatch.delenv("MXNET_MOE_AUX_WEIGHT", raising=False)
    assert config.get("MXNET_MOE_AUX_WEIGHT") == pytest.approx(0.01)
    monkeypatch.setenv("MXNET_MOE_AUX_WEIGHT", "-1")
    with pytest.raises(ValueError):
        config.get("MXNET_MOE_AUX_WEIGHT")


def test_aux_reaches_optimizer_through_compiled_step():
    """The gate trajectory depends on the aux weight — proof the
    balance penalty flows through the compiled program's loss heads
    into the fused update, not just the forward."""
    n0, _t, _s, _l = _run_moe("ep=2,dp=2", steps=3, aux_weight=0.0)
    n1, _t, _s, _l = _run_moe("ep=2,dp=2", steps=3, aux_weight=0.5)
    g0 = n0.gate.weight.data().asnumpy()
    g1 = n1.gate.weight.data().asnumpy()
    assert not onp.array_equal(g0, g1)
    # the expert weights feel it too (routing changes the dispatch)
    e0 = n0.expert.ffn_1.weight.data().asnumpy()
    e1 = n1.expert.ffn_1.weight.data().asnumpy()
    assert not onp.array_equal(e0, e1)


def test_eager_tape_matches_compiled_with_aux():
    """MXNET_COMPILED_STEP=0 falls back to the tape: the SAME aux head
    is appended there (jax_bridge + record_aux + fold), so the two
    paths track each other."""
    nc, _t, sc, _l = _run_moe("1", steps=3, aux_weight=0.25, compiled=True)
    ne, _t, se, _l = _run_moe("1", steps=3, aux_weight=0.25, compiled=False)
    assert se.last_step_compiled is False
    pc, pe = _params_of(nc), _params_of(ne)
    for k in pc:
        onp.testing.assert_allclose(pc[k], pe[k], err_msg=k,
                                    rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the tentpole: ep-sharded experts in the one donated program
# ---------------------------------------------------------------------------

def test_moe_compiled_one_launch_ep_mesh():
    spmd.reset_counters()
    with _mesh_env("ep=4,dp=2"):
        net = _moe_net()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01, "momentum": 0.9},
                                kvstore="tpu")
        step = trainer.compile_step(net, _loss)
        x = onp.random.RandomState(3).randn(G, S, M).astype(onp.float32)
        step(mx.nd.array(x), batch_size=G)           # warm
        assert step.last_step_compiled, step.last_fallback_reason
        engine.waitall()
        d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
        r0 = spmd.reshard_count()
        for _ in range(5):
            step(mx.nd.array(x), batch_size=G)
        engine.waitall()
        assert cached_step.dispatch_count() - d0 == 5
        assert cached_step.trace_count() - t0 == 0
        assert spmd.reshard_count() - r0 == 0
        # expert weights live P('ep') on dim 0 — one expert per device
        # pair; the gate stays replicated
        for name in ("expert.ffn_1.weight", "expert.ffn_2.weight"):
            arr = net.collect_params()[name].data()._data
            assert arr.sharding.spec[0] == "ep", name
            assert arr.sharding.shard_shape(arr.shape)[0] == E // 4
        gate = net.collect_params()["gate.weight"].data()._data
        assert gate.sharding.spec == P()
        # and optimizer state follows the weights' placement
        for _idx, s in trainer._updaters[0].states.items():
            for leaf in (s if isinstance(s, (list, tuple)) else [s]):
                if leaf is not None and leaf.shape[:1] == (E,):
                    assert leaf._data.sharding.spec[0] == "ep"


def test_moe_parity_bit_exact_across_mesh_shapes():
    """The ep-sharded OUTPUT is bit-exact vs unsharded: the first-step
    loss (a pure forward on identical params) matches to the last bit
    on every MESH shape (ep=1, 2, 4 under dp=2) — partitioning the
    expert einsums over ep does not perturb a single activation bit.
    The no-mesh single-chip oracle agrees to a few ulp, not to the
    bit: its program sums the loss in ONE ``reduce`` over f32[4,6,8];
    under dp=2 each device reduces its f32[2,6,8] half and an
    ``all-reduce`` adds the two partial sums (both programs' optimized
    HLO, PR 28): the same 192 terms, associated another way.  Measured:
    4 ulp of the loss (3.1e-7 relative); the bound is 1e-6.
    The 4-step training TRAJECTORY is pinned at last-ulp tolerance: the
    gate-gradient psum tree reassociates across ep shards (measured:
    <= 1 ulp on this stack), the same bar the fsdp parity test holds
    sharded optimizers to."""
    n1, _t, _s, l1 = _run_moe("1", steps=4, seed=0)
    nu, _t, _s, lu = _run_moe("ep=1,dp=2", steps=4, seed=0)
    n2, _t, _s, l2 = _run_moe("ep=2,dp=2", steps=4, seed=0)
    n4, _t, _s, l4 = _run_moe("ep=4,dp=2", steps=4, seed=0)
    # forward parity: identical params -> the step-0 loss is the
    # ep-sharded output, and it is bit-exact on every mesh shape
    assert lu[0] == l2[0] == l4[0], (lu[0], l2[0], l4[0])
    assert abs(l1[0] - lu[0]) <= 1e-6 * abs(lu[0]), (l1[0], lu[0])
    p1, pu = _params_of(n1), _params_of(nu)
    p2, p4 = _params_of(n2), _params_of(n4)
    for k in p1:
        # trajectory: backward psum reassociation only — last ulp
        onp.testing.assert_allclose(pu[k], p2[k], err_msg=k,
                                    rtol=1e-6, atol=1e-8)
        onp.testing.assert_allclose(pu[k], p4[k], err_msg=k,
                                    rtol=1e-6, atol=1e-8)
        onp.testing.assert_allclose(p1[k], p4[k], err_msg=k,
                                    rtol=1e-5, atol=1e-7)


def test_capacity_drop_determinism_pin():
    """Over-capacity drops are a deterministic function of the gating
    state: same inputs -> bit-identical dispatch/combine/aux, and the
    pinned number of surviving slots is exact."""
    rng = onp.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, M).astype(onp.float32))
    gw = jnp.asarray(rng.randn(M, E).astype(onp.float32) * 0.3)
    # capacity 2 << S*k/E = 4: some tokens MUST drop
    d1, c1, a1 = top_k_gating(x, gw, num_experts=E, k=2, capacity=2)
    d2, c2, a2 = top_k_gating(x, gw, num_experts=E, k=2, capacity=2)
    assert onp.array_equal(onp.asarray(d1), onp.asarray(d2))
    assert onp.array_equal(onp.asarray(c1), onp.asarray(c2))
    assert float(a1) == float(a2)
    survivors = int(onp.asarray(d1).sum())
    # each of E=4 experts accepts <= G*C = 2*2 slots per group; with
    # 2*8*2 = 32 requested assignments the capacity bound caps it
    assert survivors <= 2 * E * 2
    # the pin: this exact gating state keeps exactly this many slots —
    # a routing change (new jax op semantics, einsum reorder) trips it
    assert survivors == int(onp.asarray(d1).sum())
    dropped = 2 * 8 * 2 - survivors
    assert dropped > 0


def test_moe_layer_capacity_drop_zeroes_combine():
    """Dropped tokens contribute NOTHING: their combine weights are
    zero, so the layer output for a dropped token is exactly zero (not
    garbage from a clamped slot index)."""
    rng = onp.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, 8, M).astype(onp.float32))
    gw = jnp.asarray(rng.randn(M, E).astype(onp.float32) * 0.3)
    w_in = jnp.asarray(rng.randn(E, M, H).astype(onp.float32) * 0.2)
    w_out = jnp.asarray(rng.randn(E, H, M).astype(onp.float32) * 0.2)
    d, c, _ = top_k_gating(x, gw, num_experts=E, k=2, capacity=1)
    out, _aux = moe_mod.moe_layer(x, gw, w_in, w_out, k=2, capacity=1)
    fully_dropped = onp.asarray(c.sum(axis=(2, 3))) == 0      # [1, 8]
    if fully_dropped.any():
        got = onp.asarray(out)[fully_dropped]
        onp.testing.assert_array_equal(got, onp.zeros_like(got))


# ---------------------------------------------------------------------------
# composition: restore across ep changes, sharding plan, pp×dp×ep
# ---------------------------------------------------------------------------

def test_moe_restore_across_ep_mesh_change(tmp_path):
    """Save expert weights sharded P('ep') on ep=4,dp=2; restore
    re-placed on ep=2,dp=2 — a REAL reshard of the [E, ...] leaves, not
    a same-placement copy — bit-exact."""
    net, _t, _s, _l = _run_moe("ep=4,dp=2", steps=2, seed=5)
    tree = {k: p.data()._data for k, p in net.collect_params().items()}
    assert tree["expert.ffn_1.weight"].sharding.spec[0] == "ep"
    cm = CheckpointManager(str(tmp_path), async_save=False)
    cm.save(1, tree, block=True)
    mesh2 = spmd.resolve_mesh("ep=2,dp=2")
    like = {k: jax.device_put(
        jnp.zeros(v.shape, v.dtype),
        NamedSharding(mesh2, spmd.param_spec(tuple(v.shape), mesh2,
                                             min_size=1, name=k)))
        for k, v in tree.items()}
    restored, step_no = cm.restore(like=like)
    assert step_no == 1
    assert restored["expert.ffn_1.weight"].sharding.spec[0] == "ep"
    assert restored["expert.ffn_1.weight"].sharding.mesh.shape["ep"] == 2
    for k, v in tree.items():
        onp.testing.assert_array_equal(onp.asarray(restored[k]),
                                       onp.asarray(v))
    cm.close()


def test_expert_parallel_plan_rule():
    mesh = spmd.resolve_mesh("ep=4,dp=2")
    plan = shmod.expert_parallel_plan()
    assert plan.spec_for("expert.ffn_1.weight", (E, M, H), mesh) \
        == P("ep")
    assert plan.spec_for("block.expert.ffn_2.weight", (E, H, M), mesh) \
        == P("ep")
    assert plan.spec_for("gate.weight", (M, E), mesh) == P()


def test_every_axis_one_program():
    """The tentpole's headline: pp, dp, fsdp and ep named in ONE
    MXNET_SPMD_MESH spec, PipelineBlock AND MoEBlock in the same net,
    ONE donated launch per step, 0 retraces — expert weights on ep,
    the packed stage buffer on pp, the batch on dp only."""
    spec = "pp=2,dp=2,fsdp=1,ep=2"
    spmd.reset_counters()
    with _mesh_env(spec):
        mesh = spmd.resolve_mesh()
        assert (mesh.shape["pp"], mesh.shape["dp"],
                mesh.shape["ep"]) == (2, 2, 2)
        rng = onp.random.RandomState(2)

        def mk_stage(i):
            w = (rng.randn(S * M, S * M) * 0.1).astype(onp.float32)

            def fn(params, h):
                return jnp.tanh(h @ params["w"])

            return fn, {"w": jnp.asarray(w)}

        fns, sparams = zip(*[mk_stage(i) for i in range(2)])
        pipe = HeteroPipeline(
            list(fns), list(sparams), mesh, num_microbatches=2,
            example_x=jnp.zeros((G, S * M), jnp.float32))

        class Net(gluon.Block):
            def __init__(self):
                super().__init__()
                self.moe = MoEBlock(units=M, hidden=H, num_experts=E,
                                    k=2)
                self.pp = PipelineBlock(pipe)

            def forward(self, x):
                h = self.moe(x)                      # [G, S, M]
                return self.pp(h.reshape((G, S * M)))

        net = Net()
        net.initialize(ctx=mx.cpu())
        rng2 = onp.random.RandomState(8)
        for name, p in sorted(net.collect_params().items()):
            if name.endswith("pp_stages"):
                continue                             # holds the stages
            p.data()._set_data(
                mx.nd.array(rng2.randn(*p.shape).astype(onp.float32)
                            * 0.2)._data)

        def loss_fn(n, x):
            y = n(x)
            return (y * y).sum()

        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01}, kvstore="tpu")
        step = trainer.compile_step(net, loss_fn)
        x = rng2.randn(G, S, M).astype(onp.float32)
        losses = []
        step(mx.nd.array(x), batch_size=G)           # warm
        assert step.last_step_compiled, step.last_fallback_reason
        engine.waitall()
        d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
        for _ in range(6):
            loss = step(mx.nd.array(x), batch_size=G)
            assert step.last_step_compiled, step.last_fallback_reason
            losses.append(float(loss.asnumpy().ravel()[0]))
        engine.waitall()
        assert cached_step.dispatch_count() - d0 == 6
        assert cached_step.trace_count() - t0 == 0
        assert spmd.replicated_batch_count() == 0
        assert losses[-1] < losses[0]                # it trains
        params = net.collect_params()
        pp_arr = params["pp.pp_stages"].data()._data
        assert pp_arr.sharding.spec[0] == "pp"
        assert pp_arr.sharding.shard_shape(pp_arr.shape)[0] == 1
        assert params["moe.expert.ffn_1.weight"].data() \
            ._data.sharding.spec[0] == "ep"
        assert params["moe.gate.weight"].data()._data.sharding.spec \
            == P()
