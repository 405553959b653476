"""Compiled whole-train-step (cached_step.TrainStep, PR 3 tentpole).

Covers the acceptance contract: (1) parity of params AND optimizer state
vs the eager tape over >= 3 steps, to the few float32 eps that one fused
multiply-add buys (SGD and Adam, fp32 and AMP loss-scaled), (2) exactly
ONE device dispatch per step (+1 host scalar read with AMP) counted via
ndarray.invoke_count /
cached_step.dispatch_count / fused.dispatch_count, (3) retrace count 1
across constant-shape steps with a new-shape retrace and a back-to-cached
hit, (4) transparent fallback (non-stageable forward, grad_req='add',
MXNET_COMPILED_STEP=0) that still trains, (5) the ``cached_step.step``
fault-injection site, and (6) the tools/check_dispatch_budget.py CI gate.
"""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, cached_step, faults, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import ndarray as _ndmod
from mxnet_tpu.optimizer import fused

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed, with_bn=False, hybridize=True):
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8, activation="relu")
            if with_bn:
                self.bn = nn.BatchNorm(in_channels=16)
            self.d2 = nn.Dense(4, in_units=16)

        def forward(self, x):
            h = self.d1(x)
            if with_bn:
                h = self.bn(h)
            return self.d2(h)

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    if hybridize:
        net.hybridize()
    return net


def _loss_fn(net, x, y):
    return ((net(x) - y) ** 2).mean()


def _batch(seed=42, n=6):
    rng = onp.random.RandomState(seed)
    return mx.nd.array(rng.randn(n, 8)), mx.nd.array(rng.randn(n, 4))


def _eps_of_scale(a, b):
    """max |a - b| in float32 eps of the tensor's largest magnitude."""
    an, bn = a.asnumpy(), b.asnumpy()
    scale = float(onp.abs(bn).max()) * onp.finfo(onp.float32).eps
    return float(onp.abs(an - bn).max()) / scale if scale else 0.0


def _state_leaves(s):
    if s is None:
        return []
    if isinstance(s, (list, tuple)):
        return [leaf for x in s for leaf in _state_leaves(x)]
    return [s]


def _run_compiled(optimizer, opt_params, steps=4, with_bn=False,
                  scaler=None, seed=0):
    net = _mlp(seed, with_bn)
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            dict(opt_params))
    if scaler is not None:
        trainer._amp_loss_scaler = amp.LossScaler(init_scale=scaler)
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    for _ in range(steps):
        step(x, y, batch_size=6)
    assert step.last_step_compiled, step.last_fallback_reason
    return net, trainer


def _run_eager(optimizer, opt_params, steps=4, with_bn=False, scaler=None,
               seed=0):
    net = _mlp(seed, with_bn)
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            dict(opt_params))
    sc = None
    if scaler is not None:
        sc = amp.LossScaler(init_scale=scaler)
        trainer._amp_loss_scaler = sc
    x, y = _batch()
    for _ in range(steps):
        with mx.autograd.record():
            loss = _loss_fn(net, x, y)
            if sc is not None and sc.loss_scale != 1.0:
                loss = loss * sc.loss_scale
        loss.backward()
        if sc is not None:
            base = getattr(trainer, "_amp_original_scale", trainer._scale)
            trainer._amp_original_scale = base
            trainer._scale = base / sc.loss_scale
        trainer.step(6)
    return net, trainer


@pytest.mark.parametrize("optimizer,opt_params,scaler", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}, None),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, 8.0),
    ("adam", {"learning_rate": 0.05, "wd": 0.01}, None),
    ("adam", {"learning_rate": 0.05}, 8.0),
])
def test_bit_exact_parity_vs_eager_tape(optimizer, opt_params, scaler):
    """Params AND optimizer state match the eager tape after >= 3 steps
    to the last few bits (loss scale 8.0 = power of two, so AMP scaling
    adds no rounding of its own).  Not to the last bit: in the compiled
    step the output bias gradient is ONE fusion,
    ``reduce(multiply(out - y, 2/24))``, whose multiply-add XLA's CPU
    backend contracts to an FMA (one rounding a term); the eager tape
    rounds the product to float32 in one program and sums it in the
    next (two).  Replaying both orders in numpy reproduces either
    gradient bit for bit (PR 28), so the first step already differs by
    2 ulp in that bias's momentum and every other tensor follows it.
    Measured over the four cases at 4 steps: at most 2.05 eps of a
    tensor's largest magnitude (Adam's second moment, which squares the
    gradient); the bound is twice that."""
    nc, tc = _run_compiled(optimizer, opt_params, scaler=scaler)
    ne, te = _run_eager(optimizer, opt_params, scaler=scaler)
    pc, pe = nc.collect_params(), ne.collect_params()
    for k in pc:
        assert _eps_of_scale(pc[k].data(), pe[k].data()) <= 4.0, k
    sc, se = tc._updaters[0].states, te._updaters[0].states
    assert set(sc) == set(se)
    for idx in sc:
        lc, le = _state_leaves(sc[idx]), _state_leaves(se[idx])
        assert len(lc) == len(le)
        for a, b in zip(lc, le):
            assert _eps_of_scale(a, b) <= 4.0, f"state {idx}"


def test_batchnorm_mutation_parity():
    """Running-stats mutation (the CachedOp aux-state analog) is written
    back from the compiled program.  XLA reassociates the BN backward
    when it fuses it with the forward, so gradients may differ in the
    last ulp — params/states must agree to float32 ulp tolerance, and
    the running statistics (pure forward texture) stay tight too."""
    nc, tc = _run_compiled("sgd", {"learning_rate": 0.1, "momentum": 0.9},
                           with_bn=True)
    ne, te = _run_eager("sgd", {"learning_rate": 0.1, "momentum": 0.9},
                        with_bn=True)
    pc, pe = nc.collect_params(), ne.collect_params()
    for k in pc:
        onp.testing.assert_allclose(
            pc[k].data().asnumpy(), pe[k].data().asnumpy(),
            rtol=1e-6, atol=1e-7, err_msg=k)
    sc, se = tc._updaters[0].states, te._updaters[0].states
    for idx in sc:
        for a, b in zip(_state_leaves(sc[idx]), _state_leaves(se[idx])):
            onp.testing.assert_allclose(a.asnumpy(), b.asnumpy(), rtol=0,
                                        atol=1e-8, err_msg=f"state {idx}")


def test_one_dispatch_per_step():
    """The acceptance counter bar: after the warm-up trace, each step is
    exactly 1 compiled launch — 0 eager op dispatches, 0 separate fused
    group programs, 0 re-traces."""
    net = _mlp(1)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    step(x, y, batch_size=6)                 # warm: trace + compile
    inv0, d0, f0, t0 = (_ndmod.invoke_count(), cached_step.dispatch_count(),
                        fused.dispatch_count(), cached_step.trace_count())
    for _ in range(3):
        step(x, y, batch_size=6)
    assert cached_step.dispatch_count() - d0 == 3
    assert _ndmod.invoke_count() - inv0 == 0
    assert fused.dispatch_count() - f0 == 0   # update rides INSIDE the step
    assert cached_step.trace_count() - t0 == 0


def test_retrace_one_across_steps_and_new_shape():
    net = _mlp(2)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.05})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch(n=6)
    t0 = cached_step.trace_count()
    step(x, y, batch_size=6)
    assert cached_step.trace_count() - t0 == 1   # exactly ONE trace
    for _ in range(4):
        step(x, y, batch_size=6)
    assert cached_step.trace_count() - t0 == 1
    # lr tick must ride as a traced argument, never re-trace
    trainer.set_learning_rate(0.01)
    step(x, y, batch_size=6)
    assert cached_step.trace_count() - t0 == 1
    # a NEW input shape is a new cache entry: one more trace...
    x2, y2 = _batch(n=3)
    h0 = cached_step.cache_stats()
    step(x2, y2, batch_size=3)
    assert cached_step.trace_count() - t0 == 2
    assert cached_step.cache_stats()["misses"] == h0["misses"] + 1
    # ...and the old shape is still cached (hit, no trace)
    step(x, y, batch_size=6)
    assert cached_step.trace_count() - t0 == 2
    assert cached_step.cache_stats()["hits"] == h0["hits"] + 1


def test_amp_overflow_skips_update_with_one_host_read():
    """A non-finite gradient skips the whole update ON DEVICE (the
    where(ok) gate inside the program) and halves the scale via the one
    host scalar read — still exactly one compiled dispatch."""
    net = _mlp(3)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    trainer._amp_loss_scaler = amp.LossScaler(init_scale=8.0)
    overflow_loss = lambda n, x, y: ((n(x) * 1e30) * 1e30).mean()
    step = trainer.compile_step(net, overflow_loss)
    x, y = _batch()
    step(x, y, batch_size=6)                 # warm (already overflows)
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    scale_before = trainer._amp_loss_scaler.loss_scale
    inv0, d0 = _ndmod.invoke_count(), cached_step.dispatch_count()
    step(x, y, batch_size=6)
    assert step.last_step_compiled
    assert cached_step.dispatch_count() - d0 == 1
    assert _ndmod.invoke_count() - inv0 == 0
    for k, p in net.collect_params().items():
        assert onp.array_equal(before[k], p.data().asnumpy()), k
    assert trainer._amp_loss_scaler.loss_scale == scale_before / 2


def test_fallback_non_stageable_forward_still_trains():
    """A forward the tracer cannot stage (host value read) falls back to
    the eager tape transparently — and the fallback is sticky, so later
    steps skip the failed trace.  The net must NOT be hybridized: an
    untraceable forward cannot run under hybridize either (same contract
    as the reference CachedOp)."""
    net = _mlp(4, hybridize=False)
    d1, d2 = net.d1, net.d2

    def bad_forward(x):
        m = float(x.mean().asnumpy())        # host read: untraceable
        return d2(d1(x)) * (1.0 + 0.0 * m)

    net.forward = bad_forward
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    w0 = net.collect_params()["d1.weight"].data().asnumpy().copy()
    d0 = cached_step.dispatch_count()
    loss = step(x, y, batch_size=6)
    assert step.fallback_reason is not None
    assert not step.last_step_compiled
    assert cached_step.dispatch_count() == d0    # no compiled launch
    assert onp.isfinite(float(loss.asnumpy()))
    assert not onp.array_equal(
        w0, net.collect_params()["d1.weight"].data().asnumpy())
    step(x, y, batch_size=6)                     # sticky: still eager
    assert not step.last_step_compiled


def test_fallback_matches_eager_numerics():
    """The fallback path IS the eager tape: forcing the knob off gives
    weights bit-identical to a hand-written record/backward/step loop."""
    os.environ["MXNET_COMPILED_STEP"] = "0"
    try:
        net = _mlp(5)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1, "momentum": 0.9})
        step = trainer.compile_step(net, _loss_fn)
        x, y = _batch()
        d0 = cached_step.dispatch_count()
        for _ in range(3):
            step(x, y, batch_size=6)
        assert cached_step.dispatch_count() == d0
        assert step.last_fallback_reason == "MXNET_COMPILED_STEP=0"
    finally:
        os.environ.pop("MXNET_COMPILED_STEP", None)
    ne, _te = _run_eager("sgd", {"learning_rate": 0.1, "momentum": 0.9},
                         steps=3, seed=5)
    for k, p in net.collect_params().items():
        assert onp.array_equal(p.data().asnumpy(),
                               ne.collect_params()[k].data().asnumpy()), k


def test_compiled_step_keeps_no_grad_buffers_and_no_store_copy():
    """Nothing reads a Parameter's grad buffer or the built-in store's
    init copy under the compiled step; each was a model's worth of memory
    on the first device (chip 0 of 4 held 2.1x its peers, chip run PR 21).
    The step releases the buffers; the eager tape re-creates them."""
    net = _mlp(8)
    params = net.collect_params()
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    step(x, y, batch_size=6)
    assert step.last_step_compiled, step.last_fallback_reason
    assert all(p._grad is None and p.data()._grad is None
               for p in params.values())
    assert trainer._kvstore._data == {}
    # a hand-written tape step on the same net: backward re-creates the
    # buffers, grad() and Trainer.step adopt them
    w = params["d1.weight"]
    w0 = w.data().asnumpy().copy()
    with mx.autograd.record():
        loss = _loss_fn(net, x, y)
    loss.backward()
    g = w.grad()
    assert g is w.data()._grad and onp.abs(g.asnumpy()).sum() > 0
    trainer.step(6)
    assert onp.allclose(w.data().asnumpy(), w0 - 0.1 * g.asnumpy() / 6,
                        rtol=1e-6, atol=1e-7)
    step(x, y, batch_size=6)                    # released again
    assert step.last_step_compiled and w._grad is None
    assert not w.grad().asnumpy().any()         # nothing written: zeros


def test_grad_req_add_falls_back():
    net = _mlp(6)
    net.collect_params()["d1.weight"].grad_req = "add"
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    step(x, y, batch_size=6)
    assert not step.last_step_compiled
    assert "grad_req='add'" in step.last_fallback_reason
    # non-sticky: an eligibility fallback is re-checked per call
    assert step.fallback_reason is None


def test_unfused_optimizer_falls_back_to_tape():
    net = _mlp(7)
    trainer = gluon.Trainer(net.collect_params(), "rmsprop",
                            {"learning_rate": 0.01})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    w0 = net.collect_params()["d1.weight"].data().asnumpy().copy()
    step(x, y, batch_size=6)
    assert not step.last_step_compiled
    assert "fused_update" in step.last_fallback_reason
    assert not onp.array_equal(
        w0, net.collect_params()["d1.weight"].data().asnumpy())


def test_compiled_step_inject_site():
    """The ``cached_step.step`` fault site is fail-fast (a train step is
    not idempotent); the spent plan trains normally afterwards."""
    net = _mlp(8)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.compile_step(net, _loss_fn)
    x, y = _batch()
    with faults.active(faults.FaultPlan().fail("cached_step.step",
                                               exc=faults.FatalFault)):
        with pytest.raises(faults.FatalFault):
            step(x, y, batch_size=6)
    w0 = net.collect_params()["d1.weight"].data().asnumpy().copy()
    step(x, y, batch_size=6)                    # plan spent: trains
    assert not onp.array_equal(
        w0, net.collect_params()["d1.weight"].data().asnumpy())


def _load_dispatch_gate():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_dispatch_budget",
        os.path.join(REPO, "tools", "check_dispatch_budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dispatch_budget_train_lane_smoke():
    """Tier-1 smoke for the dispatch-budget gate: the compiled TRAIN
    lane alone, measured through the gate's own `_measure` and held to
    its own BUDGET.  The full matrix (eager/AMP/infer/decode/router/
    sentinel/mesh/store subprocess lanes) rides the slow lane
    (ISSUE-17 wall slice 2)."""
    mod = _load_dispatch_gate()
    row = mod._measure(True)
    assert row["used_compiled"]
    for key, budget in mod.BUDGET.items():
        assert row[key] <= budget, (key, row[key], budget)


@pytest.mark.slow     # 20 s alone with a warm compile cache, 41 s cold (PR 28)
def test_dispatch_budget_gate():
    """The CI gate itself (tools/check_dispatch_budget.py, invoked like
    check_fault_sites): compiled-mode dispatches/step must not exceed
    the documented budget.  The whole lane matrix, so slow-marked;
    tier-1 keeps the train-lane smoke above."""
    assert _load_dispatch_gate().main() == 0
