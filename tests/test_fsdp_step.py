"""Beyond one chip's HBM: FSDP parameter sharding, tensor-parallel
constraints, and gradient accumulation inside the one donated train
step (ISSUE 18 tentpole).

Covers the acceptance contract on the virtual 8-device CPU mesh
(conftest forces ``--xla_force_host_platform_device_count=8``):

1. ``MXNET_SPMD_MESH='dp=A,fsdp=B'`` shards params AND optimizer state
   over the fsdp axis at warmup (largest evenly-divisible dim,
   ``MXNET_FSDP_MIN_SIZE`` floor, loud legalize-refusal fallback) while
   the step stays ONE donated launch, 0 retraces, 0 steady-state
   reshards — the partitioner schedules the all-gather/reduce-scatter
   inside the program, never the host.
2. Parity: the dp×fsdp trajectory matches the replicated-dp AND the
   single-chip compiled step at last-ulp tolerance (SGD/Adam,
   fp32/AMP) and is bit-deterministic run-to-run.
3. Gradient accumulation: ``compile_step(..., accum_steps=N)`` pays
   exactly N+1 dispatches per window (N microbatch grad programs + ONE
   fused update), matches the equivalent big-batch step for
   batch-size-linear (sum-convention) losses, advances
   ``optimizer.num_update`` once per WINDOW, and refuses the eager
   tape loudly.
4. Robustness composes: COW checkpoints on fsdp-sharded leaves,
   ``restore(like=)`` across a dp×fsdp → dp mesh change (4 → 2
   devices), sentinel digests mesh-shape-invariant, quarantine
   exclusion on multi-axis meshes.
5. The memory claim: ``spmd.param_bytes_per_device`` /
   ``spmd.opt_bytes_per_device`` gauges report ~1/fsdp of the global
   footprint, and a transformer-style LM with ≥4x one slice's param
   budget trains on dp=2,fsdp=4 at ≤ ~1/4 replicated bytes per device.
"""
import contextlib
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import amp, cached_step, engine, gluon, sentinel, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import CheckpointManager, sharding as shmod, spmd

NDEV = len(jax.devices())

pytestmark = pytest.mark.skipif(
    NDEV < 8, reason="needs the virtual 8-device CPU mesh")


@pytest.fixture(autouse=True)
def _pristine():
    yield
    sentinel.install_quarantine(None)


@contextlib.contextmanager
def _mesh_env(spec, min_size="1"):
    """Set the mesh + fsdp-floor knobs for one build, restoring after —
    the tiny test MLP is far below the production 1024-element floor."""
    saved = {k: os.environ.get(k)
             for k in ("MXNET_SPMD_MESH", "MXNET_FSDP_MIN_SIZE")}
    os.environ["MXNET_SPMD_MESH"] = spec
    os.environ["MXNET_FSDP_MIN_SIZE"] = min_size
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


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


def _loss_sum(net, x, y):
    # sum convention: batch-size-linear, so an accumulation window is
    # numerically ONE big batch (the documented parity contract)
    return ((net(x) - y) ** 2).sum()


def _data(rows=16, seed=3):
    rng = onp.random.RandomState(seed)
    return (rng.randn(rows, 8).astype(onp.float32),
            rng.randn(rows, 4).astype(onp.float32))


def _run_mesh(spec, optimizer="sgd", opt_params=None, steps=4, scaler=None,
              seed=0, rows=16, kvstore="tpu", accum=1):
    """Train `steps` windows under MXNET_SPMD_MESH=spec; with accum>1
    each window is `accum` microbatch calls over the SAME global rows."""
    with _mesh_env(spec):
        net = _mlp(seed)
        trainer = gluon.Trainer(
            net.collect_params(), optimizer,
            dict(opt_params or {"learning_rate": 0.1, "momentum": 0.9}),
            kvstore=kvstore)
        if scaler is not None:
            trainer._amp_loss_scaler = amp.LossScaler(init_scale=scaler,
                                                      scale_window=3)
        step = trainer.compile_step(net, _loss_sum, accum_steps=accum)
        micro = rows // accum
        rng = onp.random.RandomState(7)
        for _ in range(steps):
            x = rng.randn(rows, 8).astype(onp.float32)
            y = rng.randn(rows, 4).astype(onp.float32)
            for m in range(accum):
                sl = slice(m * micro, (m + 1) * micro)
                step(mx.nd.array(x[sl]), mx.nd.array(y[sl]),
                     batch_size=micro)
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
# mesh resolution + placement rules
# ---------------------------------------------------------------------------

def test_mesh_resolution_dp_fsdp(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "dp=2,fsdp=2")
    m = spmd.resolve_mesh()
    assert m.shape["dp"] == 2 and m.shape["fsdp"] == 2
    assert len(list(m.devices.flat)) == 4
    monkeypatch.setenv("MXNET_SPMD_MESH", "dp=2,fsdp=2,tp=2")
    m = spmd.resolve_mesh()
    assert (m.shape["dp"], m.shape["fsdp"], m.shape["tp"]) == (2, 2, 2)
    monkeypatch.setenv("MXNET_SPMD_MESH", f"dp=2,fsdp={NDEV * 64}")
    with pytest.raises(ValueError, match="devices"):
        spmd.resolve_mesh()
    # fsdp without dp is still rejected: the batch needs its axis
    monkeypatch.setenv("MXNET_SPMD_MESH", "fsdp=2")
    with pytest.raises(ValueError, match="dp"):
        spmd.resolve_mesh()


def test_param_spec_placement_rules(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD_MESH", "dp=2,fsdp=2")
    mesh = spmd.resolve_mesh()
    # largest evenly-divisible dim carries the fsdp axis
    assert spmd.param_spec((16, 8), mesh, min_size=1) == P("fsdp", None)
    assert spmd.param_spec((8, 16), mesh, min_size=1) == P(None, "fsdp")
    assert spmd.param_spec((16,), mesh, min_size=1) == P("fsdp")
    # scalars and sub-floor leaves stay replicated (no refusal noise)
    assert spmd.param_spec((), mesh, min_size=1) == P()
    assert spmd.param_spec((16, 8), mesh, min_size=1024) == P()
    # a leaf NO dim can divide falls through the loud legalize path:
    # replicated + counted
    shmod.reset_legalize_refusals()
    assert spmd.param_spec((15, 3), mesh, min_size=1) == P()
    assert shmod.legalize_refusal_count() == 1
    # dp-only mesh: fsdp axis is size-1, nothing to shard
    monkeypatch.setenv("MXNET_SPMD_MESH", "dp=4")
    mesh_dp = spmd.resolve_mesh()
    assert spmd.param_spec((16, 8), mesh_dp, min_size=1) == P()


# ---------------------------------------------------------------------------
# the tentpole: fsdp-sharded params/opt-state in the one donated program
# ---------------------------------------------------------------------------

def test_fsdp_shards_params_and_opt_state():
    spmd.reset_counters()
    net, trainer, step = _run_mesh("dp=2,fsdp=2", steps=3)
    assert step.mesh.shape["fsdp"] == 2
    # every weight leaf sharded over fsdp: shard shape != global shape
    for k, p in net.collect_params().items():
        arr = p.data()._data
        assert tuple(arr.sharding.shard_shape(arr.shape)) \
            != tuple(arr.shape), k
    # momentum state takes the weight's placement (same shape -> same
    # sharding), so optimizer state is sharded too
    upd = trainer._updaters[0]
    for _idx, s in upd.states.items():
        for leaf in (s if isinstance(s, (list, tuple)) else [s]):
            if leaf is None:
                continue
            arr = leaf._data
            if arr.size >= 2:
                assert tuple(arr.sharding.shard_shape(arr.shape)) \
                    != tuple(arr.shape)


def test_fsdp_memory_gauges_report_per_device_bytes():
    """The telemetry names of the memory-per-chip claim:
    spmd.param_bytes_per_device / spmd.opt_bytes_per_device are computed
    gauges — live in snapshot()/report(), ~1/fsdp of the global bytes."""
    net, trainer, _step = _run_mesh("dp=2,fsdp=2", steps=2)
    total = sum(p.data()._data.nbytes
                for p in net.collect_params().values())
    per_dev = spmd.param_bytes_per_device()
    assert per_dev == total // 2        # every leaf divides evenly here
    assert spmd.opt_bytes_per_device() > 0
    snap = telemetry.snapshot()
    assert snap["spmd.param_bytes_per_device"] == per_dev
    assert snap["spmd.opt_bytes_per_device"] \
        == spmd.opt_bytes_per_device()
    rep = telemetry.report(prefix="spmd")
    assert "spmd.param_bytes_per_device" in rep


def test_fsdp_one_launch_no_retrace_no_reshard():
    spmd.reset_counters()
    d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
    with _mesh_env("dp=2,fsdp=2"):
        net = _mlp()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                kvstore="tpu")
        step = trainer.compile_step(net, _loss_sum)
        x, y = _data()
        for _ in range(5):
            step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
            assert step.last_step_compiled, step.last_fallback_reason
        engine.waitall()
        assert cached_step.dispatch_count() - d0 == 5
        assert cached_step.trace_count() - t0 == 1
        assert spmd.replicated_batch_count() == 0
        r_warm = spmd.reshard_count()       # first placement only
        x, y = _data(seed=9)
        step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
        engine.waitall()
        assert spmd.reshard_count() == r_warm


def test_leaves_under_the_fsdp_floor_stay_placed_and_donate(monkeypatch):
    """Leaves below MXNET_FSDP_MIN_SIZE replicate while their neighbours
    shard.  Left to the partitioner their UPDATED values came back sharded
    over ``fsdp``: ``_prep`` re-placed them every step (a steady-state
    reshard) and their donated buffers could not alias.  The step pins its
    outputs to its inputs' shardings (``TrainStep._pinned`` for weights and
    optimizer state, ``_pin_mutations`` for BN running stats)."""
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8)
            self.bn = nn.BatchNorm(in_channels=16)
            self.d2 = nn.Dense(4, in_units=16)

        def forward(self, x):
            return self.d2(self.bn(self.d1(x)))

    orig = cached_step.TrainStep._prep

    def donating(self):     # the cpu backend keeps donation off otherwise
        prep = orig(self)
        prep.donate = True
        return prep

    monkeypatch.setattr(cached_step.TrainStep, "_prep", donating)
    spmd.reset_counters()
    with _mesh_env("dp=2,fsdp=2", min_size="64"):
        net = Net()
        net.initialize(mx.init.Xavier())
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9},
                                kvstore="tpu")
        step = trainer.compile_step(net, _loss_sum)
        x, y = _data()
        params = net.collect_params()

        def specs():
            return {n: p.data()._data.sharding.spec
                    for n, p in params.items()}

        step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
        assert step.last_step_compiled, step.last_fallback_reason
        engine.waitall()
        placed = specs()
        under = [n for n, p in params.items() if p.data().size < 64]
        assert len(under) == 6          # two biases, BN's four (16,) leaves
        assert all("fsdp" not in str(placed[n]) for n in under)
        assert "fsdp" in str(placed["d1.weight"])
        r_warm = spmd.reshard_count()   # first placement only
        for _ in range(3):
            old = [p.data()._data for p in params.values()
                   if p.grad_req != "null"]     # the donated operands
            step(mx.nd.array(x), mx.nd.array(y), batch_size=16)
            engine.waitall()
            assert specs() == placed
            assert len(old) == 6 and all(o.is_deleted() for o in old)
        assert spmd.reshard_count() == r_warm


@pytest.mark.parametrize("optimizer,opt_params,scaler", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}, None),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}, 8.0),
    ("adam", {"learning_rate": 0.02, "wd": 0.01}, None),
    ("adam", {"learning_rate": 0.02}, 8.0),
])
def test_parity_fsdp_vs_replicated_vs_single(optimizer, opt_params, scaler):
    """dp=2×fsdp=2 vs replicated dp=4 vs the single-chip step: the
    partitioner changes only the reduction/gather ORDER, so trajectories
    agree at last-ulp tolerance and the AMP scaler decision chain
    (integral powers of two) is exact."""
    n1, t1, _ = _run_mesh("1", optimizer, opt_params, scaler=scaler)
    n4, t4, _ = _run_mesh("dp=4", optimizer, opt_params, scaler=scaler)
    nf, tf, stepf = _run_mesh("dp=2,fsdp=2", optimizer, opt_params,
                              scaler=scaler)
    assert stepf.mesh.shape["fsdp"] == 2
    tol = dict(rtol=1e-4, atol=5e-6)
    p1, p4, pf = _params_of(n1), _params_of(n4), _params_of(nf)
    for k in p1:
        onp.testing.assert_allclose(p1[k], pf[k], err_msg=k, **tol)
        onp.testing.assert_allclose(p4[k], pf[k], err_msg=k, **tol)
    s1, sf = _states_of(t1), _states_of(tf)
    for idx in s1:
        for a, b in zip(s1[idx], sf[idx]):
            onp.testing.assert_allclose(a, b, **tol)
    if scaler is not None:
        assert t1._amp_loss_scaler.loss_scale \
            == tf._amp_loss_scaler.loss_scale
        assert t4._amp_loss_scaler.loss_scale \
            == tf._amp_loss_scaler.loss_scale


def test_fsdp_bit_exact_run_to_run():
    na, ta, _ = _run_mesh("dp=2,fsdp=2", steps=4, seed=1)
    nb, tb, _ = _run_mesh("dp=2,fsdp=2", steps=4, seed=1)
    pa, pb = _params_of(na), _params_of(nb)
    for k in pa:
        assert onp.array_equal(pa[k], pb[k]), k
    sa, sb = _states_of(ta), _states_of(tb)
    for idx in sa:
        for a, b in zip(sa[idx], sb[idx]):
            assert onp.array_equal(a, b)


def test_batch_shards_dp_only_on_2x2_mesh():
    """The put_batch regression (ISSUE-18 satellite): on a dp=2,fsdp=2
    mesh the batch divides over dp ONLY — 6 rows (divisible by dp=2,
    NOT by the 4-device product) must shard cleanly, never silently
    replicate."""
    spmd.reset_counters()
    with _mesh_env("dp=2,fsdp=2"):
        mesh = spmd.resolve_mesh()
        sh = spmd.batch_sharding(mesh)
        assert sh.spec == P("dp")
        placed = spmd.put_batch(jnp.arange(6 * 8, dtype=jnp.float32
                                           ).reshape(6, 8), mesh)
        assert placed.sharding.shard_shape(placed.shape) == (3, 8)
    assert spmd.replicated_batch_count() == 0
    # and through the full step: 6-row batches stay compiled + sharded
    _net, _tr, step = _run_mesh("dp=2,fsdp=2", steps=3, rows=6)
    assert spmd.replicated_batch_count() == 0
    assert step.last_step_compiled


# ---------------------------------------------------------------------------
# tensor parallelism: sharding.constraint through the compiled step
# ---------------------------------------------------------------------------

def _tp_mlp(seed=0):
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8, activation="relu")
            self.d2 = nn.Dense(4, in_units=16)

        def forward(self, x):
            h = self.d1(x)
            # Megatron column-parallel activation layout: batch over
            # dp, features over tp.  On meshes without tp this
            # legalizes away (size-1 axis), keeping the oracle valid.
            h = shmod.constraint(h, ("dp", "tp"))
            return self.d2(h)

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    net.hybridize()
    return net


def test_tp_constraint_composes_with_fsdp():
    """A constraint inside a hybridized forward reaches the XLA
    partitioner through the compiled step's trace on a dp×fsdp×tp mesh:
    still one launch/step, one trace, and last-ulp parity vs the
    single-chip oracle (where 'tp' legalizes away)."""
    def run(spec):
        with _mesh_env(spec):
            net = _tp_mlp(seed=5)
            trainer = gluon.Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 0.1, "momentum": 0.9},
                                    kvstore="tpu")
            step = trainer.compile_step(net, _loss_sum)
            rng = onp.random.RandomState(11)
            for _ in range(3):
                x = rng.randn(8, 8).astype(onp.float32)
                y = rng.randn(8, 4).astype(onp.float32)
                step(mx.nd.array(x), mx.nd.array(y), batch_size=8)
                assert step.last_step_compiled, step.last_fallback_reason
            engine.waitall()
        return net, step

    d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
    n_tp, step_tp = run("dp=2,fsdp=2,tp=2")
    assert cached_step.dispatch_count() - d0 == 3
    assert cached_step.trace_count() - t0 == 1
    assert step_tp.mesh.shape["tp"] == 2
    n_1, _ = run("1")
    p_tp, p_1 = _params_of(n_tp), _params_of(n_1)
    for k in p_1:
        onp.testing.assert_allclose(p_1[k], p_tp[k], err_msg=k,
                                    rtol=1e-4, atol=5e-6)


# ---------------------------------------------------------------------------
# gradient accumulation: N+1 dispatches, one fused update per window
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,optimizer,opt_params", [
    ("1", "sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("dp=2,fsdp=2", "sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("dp=2,fsdp=2", "adam", {"learning_rate": 0.01}),
])
def test_accum_window_matches_big_batch(spec, optimizer, opt_params):
    """An accum_steps=2 window over 2×8-row microbatches equals ONE
    16-row step for the sum-convention loss — the documented contract:
    the window divisor is batch_size × accum_steps."""
    n_big, t_big, _ = _run_mesh("1", optimizer, opt_params, steps=3,
                                rows=16, accum=1)
    n_acc, t_acc, _ = _run_mesh(spec, optimizer, opt_params, steps=3,
                                rows=16, accum=2)
    tol = dict(rtol=1e-4, atol=5e-6) if spec != "1" \
        else dict(rtol=1e-5, atol=1e-6)
    p_big, p_acc = _params_of(n_big), _params_of(n_acc)
    for k in p_big:
        onp.testing.assert_allclose(p_big[k], p_acc[k], err_msg=k, **tol)
    # lr/count semantics: one optimizer update per WINDOW, not per call
    assert t_big._optimizer.num_update == 3
    assert t_acc._optimizer.num_update == 3


def test_accum_exactly_n_plus_one_dispatches():
    with _mesh_env("dp=2,fsdp=2"):
        net = _mlp(seed=2)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9},
                                kvstore="tpu")
        step = trainer.compile_step(net, _loss_sum, accum_steps=3)
        x, y = _data(rows=8, seed=4)
        for _ in range(3):                          # warm window
            step(mx.nd.array(x), mx.nd.array(y), batch_size=8)
        engine.waitall()
        d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
        windows = 2
        for _ in range(3 * windows):
            step(mx.nd.array(x), mx.nd.array(y), batch_size=8)
        engine.waitall()
        # N+1 per window: 3 grad programs + 1 fused update, 0 retraces
        assert cached_step.dispatch_count() - d0 == (3 + 1) * windows
        assert cached_step.trace_count() - t0 == 0


def test_accum_amp_window_scale_consistent():
    """AMP composes with accumulation: the scale candidates are held
    fixed across a window, overflow is detected on the SUMMED grads,
    and the dp×fsdp trajectory matches the single-chip accum run."""
    n1, t1, _ = _run_mesh("1", scaler=8.0, steps=3, rows=16, accum=2)
    nf, tf, _ = _run_mesh("dp=2,fsdp=2", scaler=8.0, steps=3, rows=16,
                          accum=2)
    p1, pf = _params_of(n1), _params_of(nf)
    for k in p1:
        onp.testing.assert_allclose(p1[k], pf[k], err_msg=k,
                                    rtol=1e-4, atol=5e-6)
    assert t1._amp_loss_scaler.loss_scale == tf._amp_loss_scaler.loss_scale


def test_accum_refuses_eager_tape(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILED_STEP", "0")
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.compile_step(net, _loss_sum, accum_steps=2)
    x, y = _data(rows=8)
    with pytest.raises(MXNetError, match="accum_steps"):
        step(mx.nd.array(x), mx.nd.array(y), batch_size=8)


def test_accum_steps_validated():
    net = _mlp()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with pytest.raises(ValueError, match="accum_steps"):
        trainer.compile_step(net, _loss_sum, accum_steps=0)


# ---------------------------------------------------------------------------
# robustness composition: checkpoints, sentinel, quarantine
# ---------------------------------------------------------------------------

def test_checkpoint_restore_fsdp_to_dp(tmp_path):
    """Save under dp=2,fsdp=2 (4 devices, params fsdp-sharded), restore
    re-placed under a plain dp=2 mesh (2 devices, replicated): values
    bit-exact, placement follows the NEW mesh."""
    net, _tr, _step = _run_mesh("dp=2,fsdp=2", steps=3, seed=2)
    tree = {k: p.data()._data for k, p in net.collect_params().items()}
    cm = CheckpointManager(str(tmp_path), async_save=False)
    cm.save(1, tree, block=True)
    mesh2 = spmd.resolve_mesh("dp=2")
    rep2 = spmd.replicated(mesh2)
    like = {k: jax.device_put(jnp.zeros(v.shape, v.dtype), rep2)
            for k, v in tree.items()}
    restored, step_no = cm.restore(like=like)
    assert step_no == 1
    for k, v in tree.items():
        assert len(restored[k].sharding.device_set) == 2
        onp.testing.assert_array_equal(onp.asarray(restored[k]),
                                       onp.asarray(v))
    cm.close()


def test_cow_checkpoint_async_on_fsdp_leaves(tmp_path):
    """The COW snapshot holds on fsdp-SHARDED leaves: the on-device
    copy keeps the sharding, and overwriting the live (donated)
    buffers after save() cannot corrupt the snapshot."""
    net, _tr, _step = _run_mesh("dp=2,fsdp=2", steps=2, seed=4)
    tree = {k: p.data()._data for k, p in net.collect_params().items()}
    for v in tree.values():                  # really sharded going in
        assert tuple(v.sharding.shard_shape(v.shape)) != tuple(v.shape)
    want = {k: onp.asarray(v).copy() for k, v in tree.items()}
    cm = CheckpointManager(str(tmp_path), async_save=True)
    cm.save(7, tree)
    for _k, p in net.collect_params().items():
        p.data()._set_data(jnp.zeros(p.shape, p.data()._data.dtype))
    engine.waitall()
    assert cm.snapshot_stats["async"] == 1
    restored, _ = cm.restore(like=tree)
    for k in want:
        onp.testing.assert_array_equal(onp.asarray(restored[k]), want[k])
    cm.close()


def test_sentinel_digest_invariant_to_fsdp_sharding(monkeypatch):
    """The position-weighted uint32 fold is exact integer arithmetic:
    the SAME state digests to the SAME integer whether replicated,
    dp-sharded, or fsdp-sharded — a mesh-shape change (elastic restart,
    scale event) can never fake a corruption verdict."""
    rng = onp.random.RandomState(0)
    host = {"w": rng.randn(16, 8).astype(onp.float32),
            "b": rng.randn(16).astype(onp.float32)}
    base = sentinel.tree_digest(host)
    for spec in ("dp=4", "dp=2,fsdp=2", "dp=2,fsdp=4"):
        monkeypatch.setenv("MXNET_SPMD_MESH", spec)
        mesh = spmd.resolve_mesh()
        placed = {k: jax.device_put(
            v, spmd.param_sharding(v.shape, mesh))
            for k, v in host.items()}
        assert sentinel.tree_digest(placed) == base, spec


def test_quarantine_exclusion_on_multi_axis_mesh():
    """A quarantined suspect is excluded when resolving a MULTI-axis
    mesh too — dp=2,fsdp=2 draws its 4 devices from the filtered
    pool."""
    q = sentinel.install_quarantine(sentinel.Quarantine(None))
    victim = jax.devices()[1].id
    q.add_device(victim, "fsdp suspect")
    mesh = spmd.resolve_mesh("dp=2,fsdp=2")
    ids = [d.id for d in mesh.devices.flat]
    assert victim not in ids
    assert mesh.shape["dp"] == 2 and mesh.shape["fsdp"] == 2


# ---------------------------------------------------------------------------
# the acceptance bar: a model bigger than one slice's param budget
# ---------------------------------------------------------------------------

def test_transformer_lm_beyond_one_chip_budget():
    """Decoder-style LM (embedding → pre-norm FFN blocks → vocab
    projection) on dp=2,fsdp=4: global params are ≥4x what one
    fsdp slice holds — per-device param bytes ≤ ~1/4 the replicated
    footprint (biases stay replicated) — while the step stays one
    donated launch, zero retraces, and the loss goes down."""
    VOCAB, DIM, FFN, SEQ = 32, 64, 256, 8

    class Block(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.norm = nn.LayerNorm(in_channels=DIM)
            self.fc1 = nn.Dense(FFN, in_units=DIM, flatten=False,
                                activation="relu")
            self.fc2 = nn.Dense(DIM, in_units=FFN, flatten=False)

        def forward(self, x):
            return x + self.fc2(self.fc1(self.norm(x)))

    class LM(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(VOCAB, DIM)
            self.b1 = Block()
            self.b2 = Block()
            self.out = nn.Dense(VOCAB, in_units=DIM, flatten=False)

        def forward(self, tokens):
            return self.out(self.b2(self.b1(self.embed(tokens))))

    def lm_loss(net, tokens, onehot):
        logits = net(tokens)
        logp = (logits.softmax() + 1e-9).log()
        return -(onehot * logp).sum()

    with _mesh_env("dp=2,fsdp=4", min_size="1"):
        net = LM()
        net.initialize(mx.init.Xavier())
        rng = onp.random.RandomState(0)
        for _name, p in sorted(net.collect_params().items()):
            p.data()._set_data(
                mx.nd.array(rng.randn(*p.shape) * 0.05)._data)
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 3e-3}, kvstore="tpu")
        step = trainer.compile_step(net, lm_loss)
        toks = rng.randint(0, VOCAB, size=(8, SEQ)).astype(onp.int32)
        hot = onp.eye(VOCAB, dtype=onp.float32)[
            onp.roll(toks, -1, axis=1)]          # next-token targets
        losses = []
        d0 = cached_step.dispatch_count()
        t_warm = None
        for i in range(20):
            loss = step(mx.nd.array(toks), mx.nd.array(hot),
                        batch_size=8)
            assert step.last_step_compiled, step.last_fallback_reason
            if i == 0:
                t_warm = cached_step.trace_count()
            losses.append(float(loss.asnumpy().ravel()[0]))
        assert cached_step.dispatch_count() - d0 == 20
        assert cached_step.trace_count() == t_warm   # 0 retraces
        assert losses[-1] < losses[0] * 0.9          # it trains
        # the memory claim: ≥4x one slice's budget -> per-device bytes
        # at ~1/4 of the global footprint (small replicated biases and
        # norms leave a little slack)
        total = sum(p.data()._data.nbytes
                    for p in net.collect_params().values())
        per_dev = spmd.param_bytes_per_device()
        assert per_dev <= total * 0.30, (per_dev, total)
        assert spmd.opt_bytes_per_device() > 0
        # and really partitioned, not just claimed: the big matrices'
        # shards are a quarter of the leaf
        w = net.collect_params()["embed.weight"].data()._data
        assert tuple(w.sharding.shard_shape(w.shape)) in ((8, 64),
                                                          (32, 16))
