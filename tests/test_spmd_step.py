"""Pod-scale SPMD training: kvstore='tpu' as mesh sharding inside the
donated compiled step (ISSUE 6 tentpole).

Covers the acceptance contract on the virtual 8-device CPU mesh
(conftest forces ``--xla_force_host_platform_device_count=8``):

1. ``Trainer(kvstore='tpu').compile_step`` runs the data-parallel step
   as ONE donated program across the mesh — params replicated over all
   mesh devices, batch sharded over 'dp', 1 compiled launch/step, 0
   steady-state reshards.
2. Parity vs the single-chip compiled step (SGD/Adam, fp32/AMP): the
   all-reduce changes only the floating-point REDUCTION ORDER, so the
   cross-topology compare is pinned at last-ulp tolerance while
   sharded-vs-sharded runs and the whole AMP scaler/deferred-gate
   decision chain (including an injected overflow across the lag
   window) are BIT-exact.
3. The blast radius: prefetcher staging with the batch NamedSharding,
   per-process sharded DataLoader sampling, COW checkpoints across a
   mesh-shape change, device metric accumulators on sharded values,
   the replicated ServingEngine, constraint legalization, and the
   ``spmd.put`` fault site.

The one-launch, no-retrace, no-steady-state-reshard contract of item 1
runs on meshes of 1, 2 and all devices.
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import amp, cached_step, engine, faults, gluon, metric
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
from mxnet_tpu.parallel import CheckpointManager, sharding as shmod, spmd
from mxnet_tpu.parallel.mesh import mesh_scope

NDEV = len(jax.devices())

pytestmark = pytest.mark.skipif(
    NDEV < 8, reason="needs the virtual 8-device CPU mesh")


def _mlp(seed=0):
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8, activation="relu")
            self.d2 = nn.Dense(4, in_units=16)

        def forward(self, x):
            return self.d2(self.d1(x))

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    net.hybridize()
    return net


def _loss_fn(net, x, y):
    return ((net(x) - y) ** 2).mean()


def _batches(n, rows=16, seed=3, overflow_at=()):
    rng = onp.random.RandomState(seed)
    out = []
    for i in range(n):
        x = rng.randn(rows, 8).astype(onp.float32)
        y = rng.randn(rows, 4).astype(onp.float32)
        if i in overflow_at:
            y = onp.full_like(y, 3e38)   # scaled grad -> inf, finite loss in
        out.append((x, y))               # fp32 squared error terms
    return out


def _run(kvstore, optimizer="sgd", opt_params=None, steps=4, scaler=None,
         seed=0, rows=16, overflow_at=()):
    net = _mlp(seed)
    trainer = gluon.Trainer(
        net.collect_params(), optimizer,
        dict(opt_params or {"learning_rate": 0.1, "momentum": 0.9}),
        kvstore=kvstore)
    if scaler is not None:
        trainer._amp_loss_scaler = amp.LossScaler(init_scale=scaler,
                                                  scale_window=3)
    step = trainer.compile_step(net, _loss_fn)
    for x, y in _batches(steps, rows=rows, overflow_at=overflow_at):
        step(mx.nd.array(x), mx.nd.array(y), batch_size=rows)
    assert step.last_step_compiled, step.last_fallback_reason
    engine.waitall()
    return net, trainer, step


def _params_of(net):
    return {k: p.data().asnumpy() for k, p in net.collect_params().items()}


def _states_of(trainer):
    out = {}
    for idx, s in trainer._updaters[0].states.items():
        leaves = s if isinstance(s, (list, tuple)) else [s]
        out[idx] = [x.asnumpy() for x in leaves if x is not None]
    return out


# ---------------------------------------------------------------------------
# mesh resolution
# ---------------------------------------------------------------------------

def test_mesh_resolution_knob(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "auto")
    m = spmd.resolve_mesh()
    assert m is not None and m.shape["dp"] == NDEV
    monkeypatch.setenv("MXNET_SPMD_MESH", "off")
    assert spmd.resolve_mesh() is None
    monkeypatch.setenv("MXNET_SPMD_MESH", "0")
    assert spmd.resolve_mesh() is None
    monkeypatch.setenv("MXNET_SPMD_MESH", "4")
    assert spmd.resolve_mesh().shape["dp"] == 4
    monkeypatch.setenv("MXNET_SPMD_MESH", "dp=2")
    assert spmd.resolve_mesh().shape["dp"] == 2
    monkeypatch.setenv("MXNET_SPMD_MESH", str(NDEV * 64))
    with pytest.raises(ValueError, match="devices"):
        spmd.resolve_mesh()
    monkeypatch.setenv("MXNET_SPMD_MESH", "tp=2")
    with pytest.raises(ValueError, match="dp"):
        spmd.resolve_mesh()
    # the store gate: only ICI-collective stores get a mesh
    monkeypatch.setenv("MXNET_SPMD_MESH", "auto")
    assert spmd.mesh_for_store("tpu") is not None
    assert spmd.mesh_for_store("device") is None
    assert spmd.mesh_for_store("dist_sync") is None
    assert spmd.mesh_for_store(None) is None


def test_kvstore_device_stays_single_chip():
    net, _tr, step = _run("device", steps=2)
    assert step.mesh is None and step.batch_sharding is None
    w = net.collect_params()["d1.weight"].data()._data
    assert len(getattr(w.sharding, "device_set", {0})) == 1


# ---------------------------------------------------------------------------
# the tentpole: kvstore='tpu' -> sharded donated step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev", [1, 2, NDEV], ids=["1", "2", "all"])
def test_kvstore_tpu_one_donated_program_across_mesh(n_dev, monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", str(n_dev))
    spmd.reset_counters()
    d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
    net, _tr, step = _run("tpu", steps=5)
    assert step.mesh is not None and step.mesh.shape["dp"] == n_dev
    # params + optimizer state replicated across every mesh device
    for _k, p in net.collect_params().items():
        assert len(p.data()._data.sharding.device_set) == n_dev
    # ONE compiled launch per step, ONE trace total, no silent
    # replication, and no steady-state resharding beyond first placement
    assert cached_step.dispatch_count() - d0 == 5
    assert cached_step.trace_count() - t0 == 1
    assert spmd.replicated_batch_count() == 0
    r_warm = spmd.reshard_count()
    x, y = _batches(1, seed=9)[0]
    step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
    assert spmd.reshard_count() == r_warm


def test_batch_sharding_property_exposed():
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu")
    step = trainer.compile_step(net, _loss_fn)
    sh = step.batch_sharding            # resolvable BEFORE the first step
    assert sh is not None and sh.spec == P("dp")
    assert sh.mesh.shape["dp"] == NDEV


@pytest.mark.parametrize("optimizer,opt_params,scaler", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}, None),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 8.0),
    ("adam", {"learning_rate": 0.05, "wd": 0.01}, None),
    ("adam", {"learning_rate": 0.05}, 8.0),
])
def test_parity_vs_single_chip(optimizer, opt_params, scaler):
    """Sharded step vs the single-chip compiled step: identical program
    up to the gradient reduction ORDER (partial sums + all-reduce vs one
    on-chip reduction), so params/optimizer state are pinned at last-ulp
    tolerance over 4 steps — and the scaler's decision chain (integral
    powers of two) must be BIT-exact."""
    n1, t1, _ = _run("device", optimizer, opt_params, scaler=scaler)
    n8, t8, step8 = _run("tpu", optimizer, opt_params, scaler=scaler)
    assert step8.mesh is not None
    # Adam's 1/(sqrt(v)+eps) normalization amplifies a last-ulp gradient
    # difference by ~1/sqrt(v); the bound below holds a few-ulp drift
    # over 4 steps without masking a real reduction bug (which lands
    # orders of magnitude outside it)
    tol = dict(rtol=1e-4, atol=5e-6)
    p1, p8 = _params_of(n1), _params_of(n8)
    for k in p1:
        onp.testing.assert_allclose(p1[k], p8[k], err_msg=k, **tol)
    s1, s8 = _states_of(t1), _states_of(t8)
    assert set(s1) == set(s8)
    for idx in s1:
        for a, b in zip(s1[idx], s8[idx]):
            onp.testing.assert_allclose(a, b, **tol)
    if scaler is not None:
        assert t1._amp_loss_scaler.loss_scale == t8._amp_loss_scaler.loss_scale
        assert t1._amp_loss_scaler._unskipped == t8._amp_loss_scaler._unskipped


def test_sharded_runs_bit_exact_deterministic():
    """Same mesh, same data: two sharded runs agree to the BIT (params
    and optimizer state) — the reduction order is fixed by the topology,
    not by luck."""
    na, ta, _ = _run("tpu", steps=4, seed=1)
    nb, tb, _ = _run("tpu", steps=4, seed=1)
    pa, pb = _params_of(na), _params_of(nb)
    for k in pa:
        assert onp.array_equal(pa[k], pb[k]), k
    sa, sb = _states_of(ta), _states_of(tb)
    for idx in sa:
        for a, b in zip(sa[idx], sb[idx]):
            assert onp.array_equal(a, b)


@pytest.mark.parametrize("overflow_at", [(5,), (0, 3)])
def test_amp_deferred_gate_sharded_overflow_bit_exact(monkeypatch,
                                                      overflow_at):
    """The deferred AMP gate survives sharding: lag=1 (flag read one
    step late, both scale candidates dispatched speculatively) ends
    bit-identical to the synchronous gate on the SAME mesh — params,
    optimizer state, and loss scale, across injected-overflow steps
    whose update must be skipped on-device."""
    monkeypatch.setenv("MXNET_AMP_LAG", "0")
    ns, ts, _ = _run("tpu", scaler=8.0, steps=6, overflow_at=overflow_at)
    monkeypatch.setenv("MXNET_AMP_LAG", "1")
    nd, td, _ = _run("tpu", scaler=8.0, steps=6, overflow_at=overflow_at)
    ps, pd = _params_of(ns), _params_of(nd)
    for k in ps:
        assert onp.array_equal(ps[k], pd[k]), k
    ss, sd = _states_of(ts), _states_of(td)
    for idx in ss:
        for a, b in zip(ss[idx], sd[idx]):
            assert onp.array_equal(a, b)
    assert ts._amp_loss_scaler.loss_scale == td._amp_loss_scaler.loss_scale
    assert ts._amp_loss_scaler._unskipped == td._amp_loss_scaler._unskipped
    # the overflow really flowed through the replicated device flag:
    # the skipped update changes the trajectory vs a clean run
    nc, _tc, _ = _run("tpu", scaler=8.0, steps=6)
    pc = _params_of(nc)
    assert any(not onp.array_equal(pc[k], pd[k]) for k in pc)


def test_indivisible_batch_replicates_loudly():
    """A batch the 'dp' axis cannot divide still runs compiled and
    correct — REPLICATED, with the warning + counter contract (never an
    error mid-step, never silent)."""
    b0 = spmd.replicated_batch_count()
    with pytest.warns(UserWarning, match="not divisible"):
        n8, _t8, step = _run("tpu", steps=2, rows=6)
    assert step.last_step_compiled
    assert spmd.replicated_batch_count() > b0
    n1, _t1, _ = _run("device", steps=2, rows=6)
    p1, p8 = _params_of(n1), _params_of(n8)
    for k in p1:
        onp.testing.assert_allclose(p1[k], p8[k], rtol=2e-6, atol=2e-7)


def test_dist_store_falls_back_naming_spmd():
    class _DistStore:
        type = "dist_sync"
        num_workers = 2
        rank = 0

        def is_capable(self, cap):
            return False

        def init(self, key, value):
            pass

        def pushpull(self, key, value, out=None, priority=0):
            pass

    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=_DistStore(),
                            update_on_kvstore=False)
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batches(1)[0]
    before = _params_of(net)
    step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
    assert not step.last_step_compiled
    assert "kvstore='tpu'" in step.last_fallback_reason
    after = _params_of(net)           # the eager tape still trained
    assert any(not onp.array_equal(before[k], after[k]) for k in before)


# ---------------------------------------------------------------------------
# prefetcher + DataLoader on sharded batches
# ---------------------------------------------------------------------------

def test_prefetcher_stages_sharded_batches_in_order():
    mesh = spmd.resolve_mesh(str(NDEV))
    sh = spmd.batch_sharding(mesh)
    batches = [(onp.full((16, 8), i, onp.float32),
                onp.full((16, 4), i, onp.float32)) for i in range(10)]
    pf = engine.DevicePrefetcher(iter(batches), depth=3,
                                 transfer=engine._sharded_transfer(sh))
    got = list(pf)
    assert len(got) == 10
    for i, (x, y) in enumerate(got):
        assert x._data.sharding.is_equivalent_to(sh, x._data.ndim)
        assert y._data.sharding.is_equivalent_to(sh, y._data.ndim)
        onp.testing.assert_array_equal(x.asnumpy(), batches[i][0])
        onp.testing.assert_array_equal(y.asnumpy(), batches[i][1])


def test_prefetched_sharded_batches_skip_resharding():
    """Batches the prefetcher staged with TrainStep.batch_sharding pass
    through the compiled step without ANY re-placement copy."""
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu")
    step = trainer.compile_step(net, _loss_fn)
    src = iter(_batches(4, seed=5))
    pf = engine.prefetch(src, depth=2, sharding=step.batch_sharding)
    first = True
    for x, y in pf:
        step(x, y, batch_size=16)
        if first:                      # params/state placed once at warm
            engine.waitall()
            r_warm = spmd.reshard_count()
            first = False
    assert step.last_step_compiled
    assert spmd.reshard_count() == r_warm


def test_dataloader_shard_slices_reassemble_global_batch():
    data = onp.arange(96, dtype=onp.float32).reshape(24, 4)
    ds = ArrayDataset(data)
    full = [b.asnumpy() for b in DataLoader(ds, batch_size=8)]
    shards = []
    for i in range(4):
        shards.append([b.asnumpy() for b in DataLoader(
            ds, batch_size=8, num_shards=4, shard_index=i)])
    for bi, ref in enumerate(full):
        glued = onp.concatenate([shards[i][bi] for i in range(4)], axis=0)
        onp.testing.assert_array_equal(glued, ref)
        assert shards[0][bi].shape[0] == 2        # 8 global / 4 shards


def test_dataloader_shard_composes_with_pad_and_prefetch():
    data = onp.arange(44, dtype=onp.float32).reshape(11, 4)
    ds = ArrayDataset(data)
    loaders = [DataLoader(ds, batch_size=8, last_batch="pad", num_shards=2,
                          shard_index=i, device_prefetch=True)
               for i in range(2)]
    outs, valids = [], []
    for ld in loaders:
        rows = []
        for b in ld:
            rows.append(b.asnumpy())
            valids.append(ld.last_batch_valid)
        outs.append(rows)
    ref = [b.asnumpy() for b in DataLoader(ds, batch_size=8,
                                           last_batch="pad")]
    for bi, r in enumerate(ref):
        glued = onp.concatenate([outs[0][bi], outs[1][bi]], axis=0)
        onp.testing.assert_array_equal(glued, r)
    assert valids[-1] == 3            # GLOBAL valid count of the tail


def test_dataloader_shard_validation():
    ds = ArrayDataset(onp.zeros((8, 2), onp.float32))
    with pytest.raises(ValueError, match="divide evenly"):
        DataLoader(ds, batch_size=6, num_shards=4)
    with pytest.raises(ValueError, match="out of range"):
        DataLoader(ds, batch_size=8, num_shards=2, shard_index=5)


def test_dataloader_sharding_stages_on_mesh():
    mesh = spmd.resolve_mesh(str(NDEV))
    sh = spmd.batch_sharding(mesh)
    data = onp.arange(64, dtype=onp.float32).reshape(16, 4)
    ds = ArrayDataset(data)
    for dp in (False, True):
        ld = DataLoader(ds, batch_size=8, sharding=sh, device_prefetch=dp)
        got = list(ld)
        assert len(got) == 2
        for b in got:
            assert b._data.sharding.is_equivalent_to(sh, b._data.ndim)
        onp.testing.assert_array_equal(got[0].asnumpy(), data[:8])


# ---------------------------------------------------------------------------
# checkpoints across mesh changes
# ---------------------------------------------------------------------------

def test_checkpoint_restore_across_mesh_change(tmp_path):
    """Save under dp=8, restore re-placed under dp=4 (gather-on-save /
    re-shard-on-restore policy): values bit-exact, placement follows the
    NEW mesh."""
    net, trainer, _step = _run("tpu", steps=3, seed=2)
    tree = {k: p.data()._data for k, p in net.collect_params().items()}
    cm = CheckpointManager(str(tmp_path), async_save=False)
    cm.save(1, tree, block=True)
    mesh4 = spmd.resolve_mesh("4")
    rep4 = spmd.replicated(mesh4)
    like = {k: jax.device_put(jnp.zeros(v.shape, v.dtype), rep4)
            for k, v in tree.items()}
    restored, step_no = cm.restore(like=like)
    assert step_no == 1
    for k, v in tree.items():
        assert len(restored[k].sharding.device_set) == 4
        onp.testing.assert_array_equal(onp.asarray(restored[k]),
                                       onp.asarray(v))
    cm.close()


def test_cow_checkpoint_async_on_sharded_params(tmp_path):
    """The COW snapshot works on mesh-sharded leaves: the on-device copy
    keeps the sharding, and overwriting the live (donated) buffers after
    save() cannot corrupt the snapshot."""
    net, _trainer, _step = _run("tpu", steps=2, seed=4)
    tree = {k: p.data()._data for k, p in net.collect_params().items()}
    want = {k: onp.asarray(v).copy() for k, v in tree.items()}
    cm = CheckpointManager(str(tmp_path), async_save=True)
    cm.save(7, tree)
    for _k, p in net.collect_params().items():       # overwrite live
        p.data()._set_data(jnp.zeros(p.shape, p.data()._data.dtype))
    engine.waitall()
    assert cm.snapshot_stats["async"] == 1
    restored, _ = cm.restore(like=tree)
    for k in want:
        onp.testing.assert_array_equal(onp.asarray(restored[k]), want[k])
    cm.close()


# ---------------------------------------------------------------------------
# metrics on sharded values
# ---------------------------------------------------------------------------

def test_metric_device_accumulator_on_sharded_values(monkeypatch):
    mesh = spmd.resolve_mesh(str(NDEV))
    sh = spmd.batch_sharding(mesh)
    rng = onp.random.RandomState(0)
    labels = (rng.rand(16) > 0.5).astype(onp.float32)
    preds = rng.rand(16, 2).astype(onp.float32)
    from mxnet_tpu.ndarray.ndarray import _wrap
    from mxnet_tpu.context import current_context

    l_nd = _wrap(jax.device_put(jnp.asarray(labels), sh), current_context())
    p_nd = _wrap(jax.device_put(jnp.asarray(preds), sh), current_context())
    m_dev = metric.Accuracy()
    assert m_dev._device_ok()
    m_dev.update([l_nd], [p_nd])
    assert m_dev._dev_pending == 1          # accumulated on device
    monkeypatch.setenv("MXNET_METRIC_DEVICE", "0")
    m_host = metric.Accuracy()
    m_host.update([mx.nd.array(labels)], [mx.nd.array(preds)])
    assert m_dev.get() == m_host.get()      # (sum, count) replicated scalars


# ---------------------------------------------------------------------------
# constraint: ambient mesh + loud legalization
# ---------------------------------------------------------------------------

def test_constraint_resolves_ambient_mesh_inside_jit():
    mesh = spmd.resolve_mesh(str(NDEV))
    x = jax.device_put(jnp.arange(float(NDEV * 2)).reshape(NDEV * 2, 1),
                       spmd.batch_sharding(mesh))

    def f(a):
        return shmod.constraint(a * 2, P("dp"))   # no mesh argument

    with mesh:                                    # bare jax mesh context
        out = jax.jit(f)(x)
    assert out.sharding.is_equivalent_to(spmd.batch_sharding(mesh), 2)
    with mesh_scope(mesh):                        # mesh_scope path
        out2 = jax.jit(f)(x)
    assert out2.sharding.is_equivalent_to(spmd.batch_sharding(mesh), 2)
    onp.testing.assert_array_equal(onp.asarray(out), onp.asarray(x) * 2)


def test_constraint_no_mesh_is_noop():
    x = jnp.arange(4.0)
    assert shmod.constraint(x, P("dp")) is x


def test_constraint_refuses_indivisible_loudly():
    mesh = spmd.resolve_mesh(str(NDEV))
    x = jnp.arange(float(NDEV + 1))               # not divisible by dp
    c0 = shmod.legalize_refusal_count()
    with pytest.warns(UserWarning, match="not divisible"):
        out = shmod.constraint(x, P("dp"), mesh=mesh)
    assert shmod.legalize_refusal_count() > c0
    onp.testing.assert_array_equal(onp.asarray(out), onp.asarray(x))


def test_constraint_unknown_axis_raises():
    mesh = spmd.resolve_mesh(str(NDEV))
    with pytest.raises(ValueError, match="typo"):
        shmod.constraint(jnp.zeros((8,)), P("modle"), mesh=mesh)


# ---------------------------------------------------------------------------
# fault site + serving
# ---------------------------------------------------------------------------

def test_spmd_put_fault_site_retries():
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu")
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batches(1)[0]
    with faults.active(faults.FaultPlan().fail("spmd.put", times=1)):
        step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
    assert step.last_step_compiled, step.last_fallback_reason
    assert any(e["action"] == "retry" for e in faults.events("spmd.put"))


def test_serving_engine_replicated_matches_eager():
    from mxnet_tpu import serving

    net = _mlp(seed=6)
    x = onp.random.RandomState(1).randn(16, 8).astype(onp.float32)
    ref = net(mx.nd.array(x)).asnumpy()
    mesh = spmd.resolve_mesh(str(NDEV))
    with serving.ServingEngine(net, mesh=mesh, max_delay_us=200) as eng:
        out = eng.infer(mx.nd.array(x))
        onp.testing.assert_array_equal(out.asnumpy(), ref)
        assert eng.stats()["mesh_devices"] == NDEV
    for _k, p in net.collect_params().items():
        assert len(p.data()._data.sharding.device_set) == NDEV

