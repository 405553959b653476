"""ProgramStore: one keyed registry + persistent compilation cache +
AOT warmup (PR 7).

Covers: (1) ScopeCache LRU eviction order, per-namespace caps
(MXNET_PROGRAM_CACHE_CAPS + legacy-knob fallback), and the shared
counter surface; (2) all four legacy caches resolving through store
namespaces (train_step / serving / hybrid_forward / eager_jit); (3)
``Trainer.precompile`` from abstract shapes and
``ServingEngine.warmup`` over the declared bucket grid — steady state
must HIT the warmed programs; (4) the ``program_store.load`` fault
site: an injected/corrupted persistent entry degrades LOUDLY to a
recompile, never a crash; (5) the subprocess cold-start parity
contract: with MXNET_PROGRAM_CACHE_DIR set, a second process replaying
the same train-step + serving-bucket workload performs 0 fresh XLA
compiles (all disk/memory hits) with bit-exact outputs.
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import cached_step, faults, gluon, program_store, serving  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402


def _build_net(seed=0):
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
    for _n, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    return net


def _build_trainer(net):
    return gluon.Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})


def _loss_fn(n, x, y):
    return ((n(x) - y) ** 2).mean()


def _batch(seed=7, rows=6):
    rng = onp.random.RandomState(seed)
    return (mx.nd.array(rng.randn(rows, 8).astype(onp.float32)),
            mx.nd.array(rng.randn(rows, 4).astype(onp.float32)))


# ---------------------------------------------------------------------------
# ScopeCache / Namespace unit tests (eviction order, caps, counters)
# ---------------------------------------------------------------------------
def test_scope_cache_eviction_order_and_on_evict(monkeypatch):
    monkeypatch.setenv("MXNET_PROGRAM_CACHE_CAPS", "hybrid_forward=3")
    ns = program_store.namespace("hybrid_forward")
    h0, m0, e0 = ns.hits, ns.misses, ns.evictions
    evicted = []
    cache = program_store.scope(
        "hybrid_forward", on_evict=lambda k, v: evicted.append((k, v)))
    assert cache.lookup("a") is None              # miss
    for key in ("a", "b", "c"):
        cache.insert(key, f"prog-{key}")
    assert ns.misses - m0 == 1 and ns.evictions - e0 == 0
    assert cache.lookup("a") == "prog-a"          # hit refreshes recency
    assert ns.hits - h0 == 1
    cache.insert("d", "prog-d")                   # cap 3: evicts oldest
    cache.insert("e", "prog-e")
    # 'a' was refreshed, so eviction order is b, then c — strict LRU
    assert evicted == [("b", "prog-b"), ("c", "prog-c")]
    assert ns.evictions - e0 == 2
    assert list(cache) == ["a", "d", "e"]
    assert len(cache) == 3


def test_namespace_caps_spec_and_legacy_fallback(monkeypatch):
    ns = program_store.namespace("train_step")
    monkeypatch.delenv("MXNET_PROGRAM_CACHE_CAPS", raising=False)
    monkeypatch.setenv("MXNET_COMPILED_STEP_CACHE", "7")
    assert ns.cap() == 7                          # legacy knob fallback
    monkeypatch.setenv("MXNET_PROGRAM_CACHE_CAPS", "train_step=2,serving=9")
    assert ns.cap() == 2                          # caps spec wins
    assert program_store.namespace("serving").cap() == 9
    # unlisted namespace still falls back
    monkeypatch.setenv("MXNET_FORWARD_CACHE", "5")
    assert program_store.namespace("hybrid_forward").cap() == 5
    monkeypatch.setenv("MXNET_PROGRAM_CACHE_CAPS", "train_step=zero")
    with pytest.raises(ValueError):
        ns.cap()
    monkeypatch.setenv("MXNET_PROGRAM_CACHE_CAPS", "train_step=0")
    with pytest.raises(ValueError):
        ns.cap()


def test_stats_surface_covers_all_namespaces():
    st = program_store.stats()
    for name in ("train_step", "serving", "hybrid_forward", "eager_jit"):
        assert name in st
        for key in ("hits", "misses", "evictions", "traces", "dispatches",
                    "live", "cap", "aot_fallbacks", "load_degrades"):
            assert key in st[name]
    assert "persistent" in st and "enabled" in st["persistent"]
    assert program_store.stats("serving")["cap"] == \
        st["serving"]["cap"]
    ver = program_store.version_fingerprint()
    assert len(ver) == 3 and all(isinstance(v, str) for v in ver)


# ---------------------------------------------------------------------------
# the four legacy caches resolve through store namespaces
# ---------------------------------------------------------------------------
def test_train_step_resolves_through_store():
    net = _build_net()
    step = _build_trainer(net).compile_step(net, _loss_fn)
    x, y = _batch()
    ns = program_store.namespace("train_step")
    h0, m0, d0 = ns.hits, ns.misses, ns.dispatches
    step(x, y, batch_size=6)
    assert step.last_step_compiled
    assert (ns.misses - m0, ns.dispatches - d0) == (1, 1)
    step(x, y, batch_size=6)
    assert (ns.hits - h0, ns.dispatches - d0) == (1, 2)
    assert len(step._programs) == 1
    assert step._programs.namespace is ns
    # the module-level views ARE the namespace surface
    assert cached_step.cache_stats()["hits"] == ns.hits
    assert cached_step.dispatch_count() == ns.dispatches
    assert cached_step.trace_count() == ns.traces
    # the record owns an AOT executable (MXNET_PROGRAM_AOT default 1)
    rec = next(iter(step._programs.values()))
    assert isinstance(rec, program_store.Program)
    assert rec.executable is not None


def test_hybrid_forward_resolves_through_store():
    net = _build_net(seed=3)
    net.hybridize()
    ns = program_store.namespace("hybrid_forward")
    h0, m0 = ns.hits, ns.misses
    x, _ = _batch(rows=4)
    out1 = net(x)
    assert ns.misses - m0 == 1
    out2 = net(x)
    assert ns.hits - h0 == 1
    assert onp.array_equal(out1.asnumpy(), out2.asnumpy())
    assert len(net._cached) == 1
    net.hybridize()                                # clear=True default
    assert len(net._cached) == 0


def test_eager_jit_resolves_through_store(monkeypatch):
    from mxnet_tpu import config
    from mxnet_tpu.ndarray import ndarray as ndmod

    monkeypatch.setenv("MXNET_EAGER_JIT", "2")
    config.refresh("MXNET_EAGER_JIT")
    ns = program_store.namespace("eager_jit")
    assert ndmod._EAGER_JIT_CACHE.namespace is ns
    ndmod._EAGER_JIT_CACHE.clear()
    ndmod._EAGER_JIT_BAD.clear()
    ndmod._EAGER_JIT_KEYCOUNT.clear()
    try:
        m0, h0 = ns.misses, ns.hits
        a = mx.nd.array(onp.ones((4, 4), onp.float32))
        b = mx.nd.array(onp.ones((4, 4), onp.float32))
        _ = (a + b).asnumpy()
        assert ns.misses > m0                      # first (op, attrs) key
        _ = (a + b).asnumpy()
        assert ns.hits > h0                        # cached executable
    finally:
        config.refresh("MXNET_EAGER_JIT")


def test_serving_resolves_through_store(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "4,8")
    net = _build_net(seed=4)
    eng = serving.ServingEngine(net, max_delay_us=0)
    try:
        ns = program_store.namespace("serving")
        m0, d0 = ns.misses, ns.dispatches
        x = mx.nd.array(onp.random.RandomState(0)
                        .randn(3, 8).astype(onp.float32))
        eng.infer(x)
        assert ns.misses - m0 == 1 and ns.dispatches - d0 == 1
        assert eng._programs.namespace is ns
        eng.infer(x)
        assert ns.misses - m0 == 1                 # same bucket: hit
        assert serving.dispatch_count() == ns.dispatches
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# AOT warmup: Trainer.precompile + ServingEngine.warmup
# ---------------------------------------------------------------------------
def test_trainer_precompile_abstract_shapes_bit_exact():
    x, y = _batch(seed=11)
    # A: precompiled from (shape, dtype) specs — no data, no step
    net_a = _build_net(seed=5)
    trainer_a = _build_trainer(net_a)
    ns = program_store.namespace("train_step")
    d0 = ns.dispatches
    step_a = trainer_a.precompile(
        net_a, _loss_fn, [((6, 8), "float32"), ((6, 4), "float32")])
    m_warm = ns.misses
    assert ns.dispatches == d0                    # warmup never dispatches
    w_before = net_a.collect_params()["d1.weight"].data().asnumpy().copy()
    # precompile must not have touched parameter values
    assert onp.array_equal(
        w_before, _build_net(seed=5).collect_params()["d1.weight"]
        .data().asnumpy())
    loss_a = step_a(x, y, batch_size=6)
    assert step_a.last_step_compiled
    assert ns.misses == m_warm                    # first real step HITS
    # B: plain compile_step, same seed/batch — bit-exact parity
    net_b = _build_net(seed=5)
    step_b = _build_trainer(net_b).compile_step(net_b, _loss_fn)
    loss_b = step_b(x, y, batch_size=6)
    assert onp.array_equal(loss_a.asnumpy(), loss_b.asnumpy())
    for name in net_a.collect_params():
        assert onp.array_equal(
            net_a.collect_params()[name].data().asnumpy(),
            net_b.collect_params()[name].data().asnumpy()), name


def test_trainer_precompile_accepts_ndarray_specs():
    net = _build_net(seed=6)
    trainer = _build_trainer(net)
    x, y = _batch(seed=12)
    step = trainer.precompile(net, _loss_fn, [x, y])
    ns = program_store.namespace("train_step")
    m0 = ns.misses
    loss = step(x, y, batch_size=6)
    assert step.last_step_compiled
    assert ns.misses == m0
    assert onp.isfinite(float(loss.asnumpy()))


def test_trainer_precompile_raises_on_ineligible(monkeypatch):
    from mxnet_tpu import config
    from mxnet_tpu.base import MXNetError

    monkeypatch.setenv("MXNET_COMPILED_STEP", "0")
    config.refresh("MXNET_COMPILED_STEP")
    try:
        net = _build_net(seed=7)
        with pytest.raises(MXNetError, match="eager tape"):
            _build_trainer(net).precompile(
                net, _loss_fn, [((6, 8), "float32"), ((6, 4), "float32")])
    finally:
        config.refresh("MXNET_COMPILED_STEP")


def test_serving_warmup_compiles_grid_and_steady_state_hits(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "4,8,16")
    net = _build_net(seed=8)
    eng = serving.ServingEngine(net, max_delay_us=0)
    try:
        ns = program_store.namespace("serving")
        d0 = ns.dispatches
        n = eng.warmup(mx.nd.array(onp.zeros((1, 8), onp.float32)))
        assert n == 3                              # one program per bucket
        assert len(eng._programs) == 3
        assert ns.dispatches == d0                 # off the request path
        assert eng.stats()["warmup_programs"] == 3
        m_warm = ns.misses
        rng = onp.random.RandomState(1)
        for rows in (2, 4, 7, 8, 13):
            out = eng.infer(mx.nd.array(
                rng.randn(rows, 8).astype(onp.float32)))
            assert out.shape[0] == rows
        assert ns.misses == m_warm                 # every bucket was warm
        assert eng.bucket_refused is None
        # verify still ran on the first padded dispatch (warmup must not
        # weaken the refuse-on-mismatch contract)
        assert eng.stats()["verify_runs"] >= 1
        assert eng.warmup(mx.nd.array(
            onp.zeros((1, 8), onp.float32))) == 0  # idempotent
    finally:
        eng.close()


def test_serving_warmup_pow2_grid(monkeypatch):
    monkeypatch.setenv("MXNET_SHAPE_BUCKETS", "pow2")
    net = _build_net(seed=9)
    eng = serving.ServingEngine(net, max_delay_us=0)
    try:
        n = eng.warmup(mx.nd.array(onp.zeros((1, 8), onp.float32)),
                       max_rows=8)
        assert n == 4                              # 1, 2, 4, 8
    finally:
        eng.close()


def test_program_aot_disabled_keeps_jit_path(monkeypatch):
    from mxnet_tpu import config

    monkeypatch.setenv("MXNET_PROGRAM_AOT", "0")
    config.refresh("MXNET_PROGRAM_AOT")
    try:
        net = _build_net(seed=10)
        step = _build_trainer(net).compile_step(net, _loss_fn)
        x, y = _batch(seed=13)
        loss = step(x, y, batch_size=6)
        assert step.last_step_compiled
        rec = next(iter(step._programs.values()))
        assert rec.executable is None              # jit callable only
        assert onp.isfinite(float(loss.asnumpy()))
    finally:
        config.refresh("MXNET_PROGRAM_AOT")


# ---------------------------------------------------------------------------
# program_store.load fault site: loud degrade-to-recompile, never a crash
# ---------------------------------------------------------------------------
def test_program_store_load_fault_degrades_to_recompile(tmp_path):
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        ns = program_store.namespace("train_step")
        g0 = ns.load_degrades
        with faults.active(faults.FaultPlan().fail("program_store.load")):
            net = _build_net(seed=14)
            step = _build_trainer(net).compile_step(net, _loss_fn)
            x, y = _batch(seed=14)
            loss = step(x, y, batch_size=6)        # build hits the fault
        assert step.last_step_compiled             # ... and recovered
        assert onp.isfinite(float(loss.asnumpy()))
        assert ns.load_degrades - g0 == 1
        evs = faults.events("program_store.load")
        assert any(e["action"] == "degrade_to_recompile" for e in evs)
        # the cache config was restored after the bypassed recompile
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_program_store_load_fault_without_cache_falls_back_eager():
    """No persistent entry in play -> the failure is a real build error
    and the TrainStep's transparent eager fallback owns it (still never
    a crash, loss still computed)."""
    import jax

    # force "no cache in play" even when the harness enables the suite-wide
    # persistent compile cache (conftest.py)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        with faults.active(faults.FaultPlan().fail("program_store.load")):
            net = _build_net(seed=15)
            step = _build_trainer(net).compile_step(net, _loss_fn)
            x, y = _batch(seed=15)
            loss = step(x, y, batch_size=6)
        assert not step.last_step_compiled
        assert "injected fault" in step.fallback_reason
        assert onp.isfinite(float(loss.asnumpy()))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# subprocess cold-start parity (the acceptance contract)
# ---------------------------------------------------------------------------
_WORKER = r"""
import json, os, sys
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import faults, gluon, program_store, serving
from mxnet_tpu.gluon import nn

class Net(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.d1 = nn.Dense(16, in_units=8, activation="relu")
        self.d2 = nn.Dense(4, in_units=16)
    def forward(self, x):
        return self.d2(self.d1(x))

def build(seed):
    net = Net(); net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _n, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    return net

net = build(0)
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
loss_fn = lambda n, x, y: ((n(x) - y) ** 2).mean()
rng = onp.random.RandomState(42)
x = mx.nd.array(rng.randn(6, 8).astype(onp.float32))
y = mx.nd.array(rng.randn(6, 4).astype(onp.float32))
step = trainer.compile_step(net, loss_fn)
losses = []
for _ in range(3):
    losses.append(float(step(x, y, batch_size=6).asnumpy().ravel()[0]))
assert step.last_step_compiled, step.last_fallback_reason

snet = build(1)
eng = serving.ServingEngine(snet, max_delay_us=0)
eng.warmup(mx.nd.array(onp.zeros((1, 8), onp.float32)))
digest = [v.hex() for v in losses]
for rows in (3, 7):
    out = eng.infer(mx.nd.array(rng.randn(rows, 8).astype(onp.float32)))
    digest.extend(float(t).hex() for t in
                  onp.asarray(out.asnumpy(), onp.float64).ravel().tolist())
eng.close()
disk = program_store.disk_stats()
st = program_store.stats()
print(json.dumps({
    "fresh_compiles": disk["misses"],
    "disk_hits": disk["hits"],
    "enabled": disk["enabled"],
    "load_degrades": sum(st[n]["load_degrades"]
                         for n in ("train_step", "serving")),
    "degrade_events": sum(
        1 for e in faults.events("program_store.load")
        if e["action"] == "degrade_to_recompile"),
    "digest": digest}))
"""


def _run_worker(cache_dir):
    env = dict(os.environ)
    env["MXNET_PROGRAM_CACHE_DIR"] = str(cache_dir)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # our knob owns the dir
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_SHAPE_BUCKETS"] = "4,8"
    r = subprocess.run([sys.executable, "-c", _WORKER],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cold_start_parity_across_processes(tmp_path):
    """Process A warms N signatures with MXNET_PROGRAM_CACHE_DIR set;
    process B replays the same workload and must perform 0 fresh XLA
    compiles (disk hits >= N) with bit-exact outputs."""
    cache_dir = tmp_path / "program_cache"
    a = _run_worker(cache_dir)
    assert a["enabled"], "MXNET_PROGRAM_CACHE_DIR did not enable the cache"
    assert a["fresh_compiles"] > 0                # cold process compiled
    assert a["load_degrades"] == 0
    b = _run_worker(cache_dir)
    assert b["fresh_compiles"] == 0, \
        f"warm process performed {b['fresh_compiles']} fresh compiles"
    assert b["disk_hits"] >= a["fresh_compiles"]
    assert b["digest"] == a["digest"]             # bit-exact outputs
    # unset knob = prior behavior: no cache, no disk counters
    env = dict(os.environ)
    env.pop("MXNET_PROGRAM_CACHE_DIR", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_SHAPE_BUCKETS"] = "4,8"
    r = subprocess.run([sys.executable, "-c", _WORKER],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    c = json.loads(r.stdout.strip().splitlines()[-1])
    assert not c["enabled"]
    assert c["fresh_compiles"] == 0 and c["disk_hits"] == 0
    assert c["digest"] == a["digest"]


def test_corrupted_cache_entry_degrades_loudly(tmp_path):
    """Garbage in a persistent entry must degrade to a fresh recompile
    under program_store.load — recorded, bit-exact, never a crash."""
    cache_dir = tmp_path / "program_cache"
    a = _run_worker(cache_dir)
    entries = [p for p in os.listdir(cache_dir) if p.endswith("-cache")]
    assert entries
    for name in entries:                          # corrupt EVERY entry
        with open(os.path.join(cache_dir, name), "wb") as f:
            f.write(b"corrupt garbage, not an executable")
    c = _run_worker(cache_dir)
    assert c["digest"] == a["digest"]             # still correct
    assert c["load_degrades"] >= 1                # and LOUD about it
    assert c["degrade_events"] >= 1
