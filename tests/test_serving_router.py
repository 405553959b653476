"""Fault-tolerant serving plane (ISSUE 14 tentpole,
``mxnet_tpu/serving_router.py``).

Pins: (1) the circuit-breaker state machine (closed → open →
half-open, probe re-admission) on an injectable clock, (2) the shared
deadline budget — ``faults.retry_call(deadline_us=)`` /
``faults.deadline_scope`` span NESTED retried sites with backoff
truncated to the remaining budget and ``DeadlineExceeded`` naming the
OUTERMOST site — and its propagation through router admission, engine
queue wait, and failover retries as typed ``ShedError(kind="deadline")``
sheds, (3) failover on replica death/wedge token-exact vs the
``eager_generate`` oracle under the ``router.dispatch`` fault site,
(4) hedged requests (first-wins + cancellation counters), (5) the
degraded modes (all-breakers-open → ``kind="unavailable"`` shed, the
``MXNET_ROUTER_EAGER_FALLBACK`` eager path, preemption-drain
``kind="draining"`` sheds), (6) telemetry-driven balancing and the
generalized in-memory HeartbeatMonitor, and (7) the availability gate
(``tools/check_availability_budget.py``) plus the dispatch-budget
``router`` zero-overhead lane (family ``serving.router`` counters),
run end-to-end.
"""
import functools
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx  # noqa: F401  (jax/backend init via conftest)
from mxnet_tpu import engine as _engine
from mxnet_tpu import faults, preemption, serving, telemetry
from mxnet_tpu import serving_decode as sd
from mxnet_tpu import serving_router as sr
from mxnet_tpu.parallel.elastic import HeartbeatMonitor
from mxnet_tpu.serving_router import (BREAKER_CLOSED, BREAKER_HALF_OPEN,
                                      BREAKER_OPEN, CircuitBreaker,
                                      ReplicaRouter)


@pytest.fixture(autouse=True)
def _pristine():
    yield
    preemption.reset()
    faults.uninstall()


def tiny(seed=0, **kw):
    """Module-shared model/params (ISSUE-17 wall slice 2): TinyCausalLM
    is stateless config and the param pytree is immutable jax arrays,
    so every test sharing a (seed, cfg) reuses ONE instance instead of
    re-initializing per test."""
    return _tiny_cached(seed, tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _tiny_cached(seed, kw_items):
    cfg = dict(vocab=31, d_model=16, n_layers=1, n_heads=2, max_seq=48)
    cfg.update(dict(kw_items))
    model = sd.TinyCausalLM(**cfg)
    return model, model.init_params(seed)


def mk_router(n=2, seed=0, max_rows=2, warm=8, **kw):
    model, params = tiny(seed)
    engines = []
    pools = []
    for i in range(n):
        pool = sd.PagePool(pages=32, page=4)
        eng = sd.GenerativeEngine(model, params=params, pool=pool,
                                  max_rows=max_rows, name=f"rep{i}")
        eng.warmup(max_len=warm)
        engines.append(eng)
        pools.append(pool)
    kw.setdefault("breaker_errs", 2)
    kw.setdefault("breaker_cooldown_s", 0.2)
    router = ReplicaRouter(engines, **kw)
    return router, engines, pools, model, params


# ---------------------------------------------------------------------------
# 1. circuit-breaker state machine (injectable clock, no waiting)
# ---------------------------------------------------------------------------
def test_breaker_state_machine():
    clock = [0.0]
    transitions = []
    br = CircuitBreaker(errs=2, window=4, cooldown_s=5.0,
                        clock=lambda: clock[0],
                        on_transition=lambda o, n, r: transitions.append(
                            (o, n)))
    assert br.state() == BREAKER_CLOSED and br.allow()
    br.record_failure("e1")
    assert br.state() == BREAKER_CLOSED          # 1 < errs
    br.record_failure("e2")
    assert br.state() == BREAKER_OPEN            # threshold
    assert not br.allow()
    clock[0] = 4.9
    assert br.state() == BREAKER_OPEN            # cooldown not elapsed
    clock[0] = 5.0
    assert br.state() == BREAKER_HALF_OPEN       # lazy transition
    assert br.allow()                            # THE probe
    assert not br.allow()                        # one probe at a time
    br.record_failure("probe died")
    assert br.state() == BREAKER_OPEN            # probe failure re-opens
    clock[0] = 10.0
    assert br.state() == BREAKER_HALF_OPEN
    assert br.allow()
    br.record_success()
    assert br.state() == BREAKER_CLOSED          # probe success closes
    # the window cleared on close: one stale failure cannot re-open
    br.record_failure("fresh")
    assert br.state() == BREAKER_CLOSED
    assert transitions == [
        (BREAKER_CLOSED, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_OPEN),
        (BREAKER_OPEN, BREAKER_HALF_OPEN),
        (BREAKER_HALF_OPEN, BREAKER_CLOSED)]


def test_breaker_trip_is_immediate():
    br = CircuitBreaker(errs=5, window=8, cooldown_s=5.0)
    br.trip("wedged")
    assert br.state() == BREAKER_OPEN            # no threshold needed


def test_breaker_rolling_window_forgets_old_failures():
    br = CircuitBreaker(errs=3, window=3, cooldown_s=1.0)
    br.record_failure("a")
    br.record_failure("b")
    for _ in range(3):
        br.record_success()                      # pushes failures out
    br.record_failure("c")
    br.record_failure("d")
    assert br.state() == BREAKER_CLOSED          # only 2 in the window


# ---------------------------------------------------------------------------
# 2. the shared deadline budget (faults.deadline_scope / deadline_us)
# ---------------------------------------------------------------------------
def test_deadline_budget_shared_across_nested_sites(monkeypatch):
    """Nested retried sites draw from ONE budget — no timeout
    multiplication — and exhaustion names the OUTERMOST site."""
    sleeps = []
    monkeypatch.setattr(faults, "_sleep",
                        lambda s: sleeps.append(s) or time.sleep(0.001))

    def inner():
        return faults.retry_call(
            boom, site="router.test_inner", retries=50, backoff=0.05)

    def boom():
        raise faults.TransientFault("inner failure")

    t0 = time.monotonic()
    with pytest.raises(faults.DeadlineExceeded) as ei:
        faults.retry_call(inner, site="router.test_outer", retries=50,
                          backoff=0.05, deadline_us=60_000)
    elapsed = time.monotonic() - t0
    # the outermost site owns the exception, the nested site is named
    assert "'router.test_outer'" in str(ei.value)
    assert "router.test_inner" in str(ei.value)
    # without the shared budget this loop would retry 50x50 times with
    # exponential backoff; the budget bounds it to ~60ms of wall clock
    assert elapsed < 2.0
    # backoff truncation: no sleep was allowed to overrun the budget
    assert all(s <= 0.06 + 0.05 for s in sleeps)


def test_deadline_scope_narrows_never_widens():
    with faults.deadline_scope(100_000, site="outer.site"):
        r_outer = faults.deadline_remaining_us()
        assert 0 < r_outer <= 100_000
        with faults.deadline_scope(10_000_000, site="inner.site"):
            # a looser nested budget cannot widen the outer one
            assert faults.deadline_remaining_us() <= r_outer
            assert faults.deadline_site() == "outer.site"
        with faults.deadline_scope(1_000, site="inner.site"):
            # a tighter nested budget narrows, attribution stays outer
            assert faults.deadline_remaining_us() <= 1_000
            assert faults.deadline_site() == "outer.site"
    assert faults.deadline_remaining_us() is None
    assert faults.deadline_site() is None


def test_deadline_budget_expired_never_attempts(monkeypatch):
    monkeypatch.setattr(faults, "_sleep", lambda s: None)
    calls = []
    with faults.deadline_scope(1, site="spent.site"):
        time.sleep(0.001)                        # budget now spent
        with pytest.raises(faults.DeadlineExceeded):
            faults.retry_call(lambda: calls.append(1),
                              site="spent.nested")
    assert calls == []                           # never ran the fn


# ---------------------------------------------------------------------------
# 3. failover: replica death is invisible to the client (token-exact)
# ---------------------------------------------------------------------------
def test_failover_token_exact_vs_oracle():
    router, engines, pools, model, params = mk_router()

    def boom(*a, **kw):
        raise RuntimeError("replica 0 died")

    engines[0].generate = boom
    prompts = [[1 + i, 2 + i, 3 + i] for i in range(6)]
    outs = [router.generate(p, max_new_tokens=5) for p in prompts]
    for p, o in zip(prompts, outs):
        assert o == sd.eager_generate(model, params, p, 5)
    st = router.stats()
    assert st["failovers"] >= 1
    assert st["breaker_opens"] >= 1
    assert router.breaker_state(0) in (BREAKER_OPEN, BREAKER_HALF_OPEN)
    # the fleet keeps serving through replica 1 with breaker 0 open
    assert router.breaker_state(1) == BREAKER_CLOSED
    # family 'serving.router' counters rode the registry
    snap = telemetry.snapshot()
    assert any(k.startswith("serving.router") and k.endswith(".failovers")
               and v for k, v in snap.items())
    _engine.waitall()
    assert all(p.in_use() == 0 for p in pools)


def test_router_dispatch_fault_site_injected_failover():
    """A planned fault at the ``router.dispatch`` site exercises the
    documented recovery: transparent re-dispatch, request delivered."""
    router, engines, pools, model, params = mk_router()
    with faults.active(faults.FaultPlan().fail("router.dispatch",
                                               times=2)):
        out = router.generate([3, 4, 5], max_new_tokens=4)
    assert out == sd.eager_generate(model, params, [3, 4, 5], 4)
    c = faults.counters("router.dispatch")
    assert c["injected"] == 2 and c["retries"] >= 2
    # injected dispatch-machinery faults blame no replica
    assert router.breaker_state(0) == BREAKER_CLOSED
    assert router.breaker_state(1) == BREAKER_CLOSED


def test_wedged_dispatch_evicted_and_failed_over():
    router, engines, pools, model, params = mk_router(
        wedge_s=0.4, breaker_cooldown_s=30.0)

    def wedge(*a, **kw):
        time.sleep(30.0)

    engines[0].generate = wedge
    t0 = time.monotonic()
    out = router.generate([7, 8], max_new_tokens=4)
    elapsed = time.monotonic() - t0
    assert out == sd.eager_generate(model, params, [7, 8], 4)
    st = router.stats()
    assert st["wedged"] == 1
    assert router.breaker_state(0) == BREAKER_OPEN
    assert 0.4 <= elapsed < 5.0                  # bounded by wedge_s
    _engine.waitall()                            # abandoned dispatch
    assert router.stats()["delivered"] == 1      # does not wedge drain


def test_breaker_flap_reopens_then_probe_readmits(monkeypatch):
    # affinity off: this test repeats ONE prompt, and prefix affinity
    # (ISSUE 16) would legitimately steer the repeats onto the healthy
    # warm replica after the first failover — starving the flaky
    # replica of the errors whose breaker mechanics are pinned here
    # (placement-vs-affinity behavior is covered in test_prefix_cache
    # and the router_prefix_storm drill)
    monkeypatch.setenv("MXNET_ROUTER_PREFIX_AFFINITY", "0")
    router, engines, pools, model, params = mk_router(
        breaker_cooldown_s=0.15)
    orig = engines[0].generate
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] <= 3:
            raise faults.TransientFault(f"flap {calls['n']}")
        return orig(*a, **kw)

    engines[0].generate = flaky
    for i in range(4):
        router.generate([1, 2], max_new_tokens=3)
    assert router.breaker_state(0) == BREAKER_OPEN
    time.sleep(0.2)                              # cooldown elapses
    deadline = time.monotonic() + 5.0
    while router.breaker_state(0) != BREAKER_CLOSED and \
            time.monotonic() < deadline:
        router.generate([1, 2], max_new_tokens=3)
    st = router.stats()
    assert router.breaker_state(0) == BREAKER_CLOSED
    assert st["breaker_opens"] >= 1 and st["breaker_closes"] >= 1
    assert st["probes"] >= 1


# ---------------------------------------------------------------------------
# 4. hedged requests: first-wins + cancellation
# ---------------------------------------------------------------------------
def test_hedge_first_wins_and_cancellation_counters():
    router, engines, pools, model, params = mk_router(hedge_pctl=50)
    for _ in range(20):                          # latency distribution
        router.generate([1, 2, 3], max_new_tokens=3)
    orig = engines[0].generate

    def slow(*a, **kw):
        time.sleep(1.5)
        return orig(*a, **kw)

    engines[0].generate = slow
    ref = sd.eager_generate(model, params, [1, 2, 3], 3)
    t0 = time.monotonic()
    outs = [router.generate([1, 2, 3], max_new_tokens=3)
            for _ in range(3)]
    elapsed = time.monotonic() - t0
    assert all(o == ref for o in outs)           # hedge winner is exact
    st = router.stats()
    assert st["hedges"] >= 1
    assert st["hedge_wins"] >= 1                 # the duplicate won
    assert st["hedge_cancelled"] >= 1            # the loser was dropped
    assert elapsed < 4.0                         # not 3 x 1.5s primaries
    _engine.waitall()


def test_hedge_off_by_default_and_below_min_samples():
    # warm=1: the threshold logic never dispatches, so the routers
    # don't need their program grids compiled (suite-time hygiene)
    router, engines, _pools, _m, _p = mk_router(warm=1)    # pctl 0
    assert router._hedge_threshold() is None
    router2, _e, _po, _m2, _p2 = mk_router(hedge_pctl=95, warm=1)
    assert router2._hedge_threshold() is None    # < 16 samples yet


# ---------------------------------------------------------------------------
# 5. degraded modes
# ---------------------------------------------------------------------------
def test_all_breakers_open_sheds_unavailable():
    router, engines, pools, model, params = mk_router()

    def boom(*a, **kw):
        raise RuntimeError("dead")

    engines[0].generate = boom
    engines[1].generate = boom
    for _ in range(6):
        with pytest.raises(faults.ShedError) as ei:
            router.generate([1], max_new_tokens=2)
        assert ei.value.kind == "unavailable"    # typed, never a hang
    st = router.stats()
    assert st["shed_unavailable"] == 6
    # both replicas ejected once their failure thresholds were crossed
    assert all(router.breaker_state(i) != BREAKER_CLOSED
               for i in range(2))


def test_eager_fallback_serves_when_all_replicas_down():
    router, engines, pools, model, params = mk_router(
        eager_fallback=True)

    def boom(*a, **kw):
        raise RuntimeError("dead")

    engines[0].generate = boom
    engines[1].generate = boom
    outs = [router.generate([2, 3], max_new_tokens=4) for _ in range(6)]
    ref = sd.eager_generate(model, params, [2, 3], 4)
    assert all(o == ref for o in outs)           # eager path, exact
    assert router.stats()["eager_fallbacks"] >= 1


def test_router_sheds_draining_on_preemption_notice():
    router, engines, pools, model, params = mk_router()
    router.generate([1, 2], max_new_tokens=2)
    preemption._DRAINING.set()
    try:
        with pytest.raises(faults.ShedError) as ei:
            router.generate([1, 2], max_new_tokens=2)
        assert ei.value.kind == "draining"
        assert router.stats()["shed_draining"] == 1
        _engine.waitall()                        # drains cleanly
    finally:
        preemption.reset()


# ---------------------------------------------------------------------------
# 6. per-request deadlines through the router
# ---------------------------------------------------------------------------
def test_expired_deadline_sheds_typed_never_hangs():
    router, engines, pools, model, params = mk_router()
    router.generate([1, 2], max_new_tokens=2)    # warm cost table
    t0 = time.monotonic()
    with pytest.raises(faults.ShedError) as ei:
        router.generate([1, 2], max_new_tokens=40, deadline_us=1_000)
    elapsed = time.monotonic() - t0
    assert ei.value.kind == "deadline"
    assert elapsed < 1.0                         # bounded, not a hang
    assert router.stats()["shed_deadline"] >= 1


def test_deadline_budget_covers_engine_admission_cost_table():
    """The engine's admission cost-table check draws from the SAME
    budget the router pinned: a request the table prices above the
    remaining budget sheds at admission, with zero decode compute."""
    model, params = tiny()
    pool = sd.PagePool(pages=32, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=2, name="ded")
    eng.warmup(max_len=8)
    eng.generate([1, 2, 3], max_new_tokens=6)    # warm the cost EMAs
    d0 = eng._stats["decode_steps"]
    with faults.deadline_scope(1_500, site="client.deadline"):
        with pytest.raises(faults.ShedError) as ei:
            eng.generate([1, 2, 3], max_new_tokens=40)
    assert ei.value.kind == "deadline"
    assert eng._stats["shed_deadline"] == 1
    assert eng._stats["decode_steps"] == d0      # shed BEFORE compute
    eng.close()


def test_generous_deadline_delivers_token_exact():
    router, engines, pools, model, params = mk_router()
    out = router.generate([4, 5, 6], max_new_tokens=5,
                          deadline_us=60_000_000)
    assert out == sd.eager_generate(model, params, [4, 5, 6], 5)
    assert router.stats()["shed_deadline"] == 0


# ---------------------------------------------------------------------------
# 7. balancing + heartbeat
# ---------------------------------------------------------------------------
def test_balancer_prefers_idle_replica():
    router, engines, pools, model, params = mk_router()
    # replica 0 reports heavy load; the next pick must be replica 1
    engines[0].load = lambda: {"queue_depth": 50.0, "in_flight": 1.0,
                               "pool_pressure": 0.9}
    picked = router._pick(exclude=set())
    assert picked.index == 1


def test_heartbeat_monitor_in_memory_generalization():
    hb = HeartbeatMonitor(timeout=0.2)           # no directory: in-memory
    hb.beat("replica0")
    hb.beat("replica1")
    assert hb.ranks() == ["replica0", "replica1"]
    assert hb.dead_ranks() == []
    assert hb.age("replica0") < 0.2
    time.sleep(0.25)
    hb.beat("replica1")
    assert hb.dead_ranks() == ["replica0"]       # stale beat
    assert hb.age("missing") is None


def test_router_validates_replicas():
    model, params = tiny()
    eng = sd.GenerativeEngine(model, params=params,
                              pool=sd.PagePool(pages=8, page=4),
                              max_rows=2)
    with pytest.raises(ValueError):
        ReplicaRouter([])
    with pytest.raises(TypeError):
        ReplicaRouter([object()])
    router = ReplicaRouter([eng])
    with pytest.raises(RuntimeError):
        router.infer(onp.zeros((1, 4), onp.float32))   # wrong API
    eng.close()


# ---------------------------------------------------------------------------
# 8. one-shot inference replicas (ServingEngine kind)
# ---------------------------------------------------------------------------
class _Net(mx.gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.d1 = mx.gluon.nn.Dense(8, in_units=4, activation="relu")
        self.d2 = mx.gluon.nn.Dense(3, in_units=8)

    def forward(self, x):
        return self.d2(self.d1(x))


def _infer_net(seed=0):
    net = _Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.2)._data)
    net.hybridize()
    return net


def test_infer_router_failover_matches_bare_forward():
    net = _infer_net()
    e1 = serving.ServingEngine(net, max_delay_us=0)
    e2 = serving.ServingEngine(net, max_delay_us=0)
    router = ReplicaRouter([e1, e2], breaker_errs=2)
    x = mx.nd.array(onp.random.RandomState(3).randn(4, 4)
                    .astype(onp.float32))
    want = net(x).asnumpy()
    got = router.infer(x).asnumpy()
    assert onp.array_equal(got, want)
    orig = e1.infer

    def boom(*a, **kw):
        raise RuntimeError("replica 0 died")

    e1.infer = boom
    for _ in range(4):
        out = router.infer(x)
        assert onp.array_equal(out.asnumpy(), want)
    assert router.stats()["failovers"] >= 1
    e1.infer = orig
    e1.close()
    e2.close()


def test_infer_router_generate_api_rejected():
    net = _infer_net()
    e1 = serving.ServingEngine(net, max_delay_us=0)
    router = ReplicaRouter([e1])
    with pytest.raises(RuntimeError):
        router.generate([1, 2])
    e1.close()


# ---------------------------------------------------------------------------
# 9. drain + gates
# ---------------------------------------------------------------------------
def test_waitall_drains_router_inflight():
    router, engines, pools, model, params = mk_router()
    outs = {}

    def fire(i):
        outs[i] = router.generate([1 + i, 2], max_new_tokens=6)

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    _engine.waitall()                            # must not wedge
    for t in threads:
        t.join(timeout=30.0)
    assert len(outs) == 4
    with router._lock:
        assert router._inflight == 0
    assert all(p.in_use() == 0 for p in pools)


def test_dispatch_budget_router_lane_in_process():
    import tools.check_dispatch_budget as cdb

    row = cdb._measure_router()
    assert row["extra_dispatches"] == 0
    assert row["extra_retraces"] == 0
    assert row["extra_host_syncs"] == 0
    assert row["outputs_equal"]
    assert row["leaked_pages"] == 0


def test_availability_gate_subprocess_scenarios():
    """The chaos-drill gate, end-to-end: a replica killed mid-decode
    (plus the preemption-notice drain) and the deadline storm, as real
    subprocesses under tools/check_availability_budget.py."""
    import tools.check_availability_budget as gate

    assert gate.main(["router_kill", "router_deadline_storm"]) == 0


# ---------------------------------------------------------------------------
# 11. elastic fleet membership (ISSUE 17)
# ---------------------------------------------------------------------------
def _mk_engine(model, params, max_rows=2, warm=None, name=None):
    pool = sd.PagePool(pages=32, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=max_rows, name=name)
    if warm:
        eng.warmup(max_len=warm)
    return eng, pool


def test_add_replica_serves_only_after_warmup():
    """A joiner is JOINING (invisible to _pick) for the whole warmup;
    the fleet keeps delivering through the incumbent, and the joiner
    flips to SERVING only once warm."""
    router, engines, pools, model, params = mk_router(n=1)
    joiner, jpool = _mk_engine(model, params)
    mid_warm = {}
    real_warmup = joiner.warmup

    def observed_warmup(**kw):
        rep = router._replicas[1]
        mid_warm["state"] = rep.state
        mid_warm["serving"] = router.serving_replicas()
        # traffic keeps flowing while the joiner warms
        mid_warm["out"] = router.generate([5, 6, 7], max_new_tokens=3)
        return real_warmup(**kw)

    joiner.warmup = observed_warmup
    idx = router.add_replica(joiner, warmup_kwargs={"max_len": 8})
    assert idx == 1
    assert mid_warm["state"] == sr.REPLICA_JOINING
    assert mid_warm["serving"] == 1
    assert mid_warm["out"] == sd.eager_generate(model, params,
                                                [5, 6, 7], 3)
    assert router._replicas[1].state == sr.REPLICA_SERVING
    assert router.serving_replicas() == 2
    fs = router.fleet_stats()
    assert fs["joins"] == 1 and fs["serving"] == 2
    # the fleet gauge rides the registry
    snap = telemetry.snapshot()
    assert any(k.endswith(".serving_replicas") and v == 2.0
               for k, v in snap.items())
    _engine.waitall()
    assert jpool.in_use() == 0 and pools[0].in_use() == 0


def test_drain_replica_idempotent_double_drain():
    router, engines, pools, model, params = mk_router()
    assert router.drain_replica(1) is True
    assert router.drain_replica(1) is True     # GONE fast-path
    fs = router.fleet_stats()
    assert fs["drains"] == 1 and fs["gone"] == 1 and fs["serving"] == 1
    # the survivor keeps serving token-exact
    out = router.generate([2, 3, 4], max_new_tokens=4)
    assert out == sd.eager_generate(model, params, [2, 3, 4], 4)
    states = [r["state"] for r in router.stats()["replicas"]]
    assert states == [sr.REPLICA_SERVING, sr.REPLICA_GONE]
    _engine.waitall()
    assert all(p.in_use() == 0 for p in pools)


def test_drain_while_hedge_outstanding():
    """Draining a replica with a hedged request still in flight on it:
    the drain waits the row out, the request is delivered exactly
    once, and the pool audits clean."""
    router, engines, pools, model, params = mk_router(hedge_pctl=50)
    for i in range(20):                       # arm the latency pctl
        router.generate([1 + i % 7, 2], max_new_tokens=2)
    real = engines[1].generate

    def slow(*a, **kw):
        time.sleep(0.8)
        return real(*a, **kw)

    engines[1].generate = slow
    prompts = [[3, 4, 5], [6, 7, 8], [9, 10, 11], [12, 13, 14]]
    outs = []
    threads = [threading.Thread(
        target=lambda p=p: outs.append(
            (str(p), router.generate(p, max_new_tokens=3))))
        for p in prompts]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5.0
    while (router._replicas[1].in_flight == 0
           and time.monotonic() < deadline):
        time.sleep(0.002)
    assert router._replicas[1].in_flight > 0   # a row is live there
    assert router.drain_replica(1, timeout=30.0) is True
    for t in threads:
        t.join(timeout=30.0)
    assert len(outs) == 4                      # each delivered once
    oracle = {str(p): sd.eager_generate(model, params, p, 3)
              for p in prompts}
    for key, out in outs:
        assert out == oracle[key]
    assert router.fleet_stats()["drains"] == 1
    _engine.waitall()
    assert all(p.in_use() == 0 for p in pools)


def test_supervisor_cooldown_and_bounds_injectable_clock():
    """The autoscaler state machine without waiting: up on saturation,
    capped at max, one action per cooldown, down on idle, floored at
    min — all on an injected clock and injected signals."""
    router, engines, pools, model, params = mk_router(n=1)
    clk = [0.0]
    retired = []

    def spawn():
        eng, _ = _mk_engine(model, params)
        return eng

    sup = sr.FleetSupervisor(
        router, spawn, retire=lambda eng, idx: retired.append(idx),
        enabled=True, min_replicas=1, max_replicas=2, cooldown_s=10.0,
        up_queue=1.0, down_queue=0.1, pool_high=0.9,
        warmup_kwargs={"max_len": 8}, clock=lambda: clk[0])
    sig = {"queue_per_replica": 5.0, "pool_pressure": 0.0, "p99_s": 0.0}
    sup.signals = lambda: dict(
        sig, serving=float(router.serving_replicas()))

    assert sup.tick() == "up"                  # saturated, under max
    assert router.serving_replicas() == 2
    assert sup.tick() is None                  # at max: no action
    sig["queue_per_replica"] = 0.0
    assert sup.tick() is None                  # idle but cooling down
    clk[0] = 11.0
    assert sup.tick() == "down"                # cooldown elapsed
    assert retired == [1]
    assert router.serving_replicas() == 1
    clk[0] = 22.0
    assert sup.tick() is None                  # min floor holds
    fs = router.fleet_stats()
    assert fs["scale_ups"] == 1 and fs["scale_downs"] == 1
    assert fs["ticks"] >= 5
    _engine.waitall()


def test_supervisor_disabled_is_inert():
    """Zero-overhead-off: a disabled supervisor starts no thread."""
    router, engines, pools, model, params = mk_router(n=1)
    sup = sr.FleetSupervisor(router, spawn=lambda: None,
                             enabled=False).start()
    assert sup.enabled is False
    assert sup._thread is None
    sup.stop()                                  # harmless no-op


def test_router_scale_fault_site_injected():
    """A planned fault at the ``router.scale`` site exercises the
    documented recovery: the membership change never happens — the
    fleet is exactly as it was — and a retry completes it."""
    router, engines, pools, model, params = mk_router(n=1)
    joiner, _ = _mk_engine(model, params, warm=8)
    with faults.active(faults.FaultPlan().fail("router.scale",
                                               times=1)):
        with pytest.raises(faults.TransientFault):
            router.add_replica(joiner, warmup_kwargs={"max_len": 8})
        assert router.serving_replicas() == 1        # untouched
        assert len(router._replicas) == 1
        assert router.fleet_stats()["joins"] == 0
        # retry joins
        assert router.add_replica(joiner,
                                  warmup_kwargs={"max_len": 8}) == 1
    assert faults.counters("router.scale")["injected"] == 1
    assert router.serving_replicas() == 2
    with faults.active(faults.FaultPlan().fail("router.scale",
                                               times=1)):
        with pytest.raises(faults.TransientFault):
            router.drain_replica(1)
        assert router._replicas[1].state == sr.REPLICA_SERVING
        assert router.drain_replica(1) is True       # retry drains
    assert router._replicas[1].state == sr.REPLICA_GONE
    _engine.waitall()


# ---------------------------------------------------------------------------
# 12. cross-host replicas (serving_remote, ISSUE 17)
# ---------------------------------------------------------------------------
def test_remote_replica_protocol_token_exact():
    from mxnet_tpu import serving_remote as srm

    model, params = tiny()
    eng, pool = _mk_engine(model, params, warm=8, name="wire0")
    srv = srm.ReplicaServer(eng).start()
    try:
        rr = srm.RemoteReplica("127.0.0.1", srv.port)
        out = rr.generate([4, 5, 6], max_new_tokens=5)
        assert out == sd.eager_generate(model, params, [4, 5, 6], 5)
        assert rr.ping() is True
        load = rr.load()
        for k in ("queue_depth", "in_flight", "pool_pressure"):
            assert k in load
        # a typed shed crosses the wire typed
        eng.begin_drain()
        with pytest.raises(faults.ShedError) as ei:
            rr.generate([4, 5, 6], max_new_tokens=2)
        assert ei.value.kind == "draining"
    finally:
        srv.close()
    _engine.waitall()
    assert pool.in_use() == 0


def test_router_remote_fault_site_injected_failover():
    """A planned fault at the ``router.remote`` site exercises the
    documented recovery: the unreachable remote prices out of _pick /
    the failed dispatch fails over — every request still delivered
    token-exact through the fleet."""
    from mxnet_tpu import serving_remote as srm

    router, engines, pools, model, params = mk_router(n=1)
    eng2, pool2 = _mk_engine(model, params, warm=8, name="wire1")
    srv = srm.ReplicaServer(eng2).start()
    try:
        rr = srm.RemoteReplica("127.0.0.1", srv.port)
        router.add_replica(rr)
        prompts = [[1 + i, 2 + i, 3 + i] for i in range(6)]
        with faults.active(faults.FaultPlan().fail("router.remote",
                                                   times=2)):
            outs = []
            threads = [threading.Thread(
                target=lambda p=p: outs.append(
                    (str(p), router.generate(p, max_new_tokens=4))))
                for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
        assert len(outs) == 6
        for key, out in outs:
            p = [int(x) for x in key.strip("[]").split(",")]
            assert out == sd.eager_generate(model, params, p, 4)
        assert faults.counters("router.remote")["injected"] >= 1
    finally:
        srv.close()
    _engine.waitall()
    assert pool2.in_use() == 0 and pools[0].in_use() == 0
