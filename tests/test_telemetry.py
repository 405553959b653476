"""Unified telemetry subsystem (ISSUE 10): the process-wide counter
registry, the structured event bus, the span layer, the exporters, and
the ``tools/check_telemetry.py`` CI gate.

Covers: (1) registry declaration/idempotence, deterministic snapshot
ordering, and cumulative-vs-gauge ``delta()`` semantics; (2) the
canonical counter map — every static counter and every dynamic family
this repo ships is named HERE (the gate's test-coverage check keys on
these literals); (3) thread-safety: the registry hammered from
prefetcher / checkpoint-writer / serving-dispatcher threads while
snapshots run concurrently — no torn reads, cumulatives monotonic,
final totals exact; (4) the event bus: step indices on fault events,
the ``MXNET_FAULT_EVENTS`` capacity knob (default + subprocess
override); (5) the ``profiler.dumps(reset=True)`` regression: a trace
reset clears events, never registry-backed ``profiler.Counter`` values;
(6) spans: context-manager + post-hoc records, StepTimeline phases,
``Trainer.step_spans()`` / engine ``spans()`` views, and the chrome
dump; (7) the legacy accessors as registry views; (8) the JSON-lines
flight recorder flushed by ``engine.waitall()``; (9) the gate itself.
"""
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as onp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import (cached_step, engine, faults, gluon, metric,  # noqa: E402
                       profiler, serving, serving_decode, telemetry)
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.parallel import sharding, spmd  # noqa: E402


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_declaration_and_idempotence():
    c1 = telemetry.counter("test.reg.alpha", "a test counter")
    c2 = telemetry.counter("test.reg.alpha", "redeclared")
    assert c1 is c2                       # idempotent by name
    c1.reset()
    c1.inc()
    c1.inc(4)
    assert c1.value == 5 and int(c1) == 5
    g = telemetry.gauge("test.reg.beta")
    g.set(17)
    assert g.kind == "gauge" and g.value == 17
    with pytest.raises(ValueError):
        telemetry.Counter("x", kind="bogus")
    with pytest.raises(KeyError):
        telemetry.get("test.reg.never_declared")
    meta = telemetry.registered()["test.reg.alpha"]
    assert meta["kind"] == "cumulative" and meta["doc"] == "a test counter"


def test_snapshot_deterministic_ordering_and_delta():
    a = telemetry.counter("test.delta.a")
    b = telemetry.counter("test.delta.b")
    g = telemetry.gauge("test.delta.g")
    a.reset(), b.reset()
    base = telemetry.snapshot()
    assert list(base) == sorted(base)     # deterministic ordering
    a.inc(3)
    g.set(42)
    d = telemetry.delta(base)
    assert d["test.delta.a"] == 3 and d["test.delta.b"] == 0
    assert d["test.delta.g"] == 42        # gauges report current value
    # a counter born after the base deltas from zero
    telemetry.counter("test.delta.late").inc(2)
    assert telemetry.delta(base)["test.delta.late"] == 2


def test_counter_group_is_a_mapping_view():
    grp = telemetry.CounterGroup(
        telemetry.instance_name("test.group"), ("x", "y"),
        family="test.group")
    assert dict(grp) == {"x": 0, "y": 0}
    grp.inc("x")
    grp["y"] = 7                          # absolute set
    grp["y"] += 1                         # get-then-set also works
    assert grp["x"] == 1 and grp["y"] == 8 and len(grp) == 2
    # the values live in the registry under the instance prefix
    assert telemetry.snapshot()[f"{grp.prefix}.y"] == 8
    # instance prefixes never collide
    assert telemetry.CounterGroup(
        telemetry.instance_name("test.group"), ("x",)).prefix != grp.prefix


def test_canonical_counters_registered():
    """The counter map: every STATIC registry counter ships declared
    (this list is also the gate's test-coverage anchor)."""
    static = [
        "cached_step.deferred_read",
        "metric.host_sync",
        "ndarray.invoke",
        "ndarray.host_sync",
        "spmd.reshard",
        "spmd.replicated_batch",
        "sharding.legalize_refusal",
        "transformer_lm.flash_fallback",
        "attention.fused",
        "attention.unfused",
        "loss.sparse_ce.fused",
        "amp.batch_norm.low_precision",
        "fused.trace",
        "fused.dispatch",
        "nn.pad_channels",
        "engine.drainables",
        "telemetry.events",
        "telemetry.spans",
    ]
    # the ops/nn + models + optimizer modules declare at import
    from mxnet_tpu import amp  # noqa: F401
    from mxnet_tpu.contrib import quantization  # noqa: F401
    from mxnet_tpu.models import transformer_lm  # noqa: F401
    from mxnet_tpu.ops import nn as _nn  # noqa: F401
    from mxnet_tpu.optimizer import fused as _fused  # noqa: F401

    reg = telemetry.registered()
    missing = [n for n in static if n not in reg]
    assert not missing, f"static counters not registered: {missing}"
    # program_store namespaces register the full field set
    for ns in ("train_step", "serving", "serving_decode",
               "hybrid_forward", "eager_jit"):
        for f in ("hits", "misses", "evictions", "traces", "dispatches",
                  "aot_fallbacks", "load_degrades", "compile_count",
                  "compile_seconds"):
            assert f"program_store.{ns}.{f}" in reg
    assert reg["program_store.train_step.hits"]["family"] \
        == "program_store.namespace"
    assert reg["program_store.train_step.compile_seconds"]["kind"] == "time"
    # dynamic families: instantiating an owner declares its group
    pool = serving_decode.PagePool(pages=4, page=8)
    assert reg_family(pool._counts.prefix + ".alloc") == "kv_pool"
    grp = faults._stats("telemetry.test_site")
    assert reg_family(grp.prefix + ".attempts") == "faults.site"
    # serving.engine / decode.engine / profiler.user families are pinned
    # by the engine + profiler tests below


def reg_family(name):
    return telemetry.registered()[name]["family"]


def test_engine_stats_are_registry_views():
    """ServingEngine.stats() / GenerativeEngine.stats() read through
    registry counter groups (families serving.engine / decode.engine)."""

    class Id(gluon.HybridBlock):
        def forward(self, x):
            return x * 2

    net = Id()
    net.initialize()
    eng = serving.ServingEngine(net)
    try:
        assert reg_family(eng._stats.prefix + ".requests") \
            == "serving.engine"
        out = eng.infer(mx.nd.ones((2, 3)))
        assert out.shape == (2, 3)
        st = eng.stats()
        assert st["requests"] == 1
        assert telemetry.snapshot()[eng._stats.prefix + ".requests"] == 1
    finally:
        eng.close()
    gen = serving_decode.GenerativeEngine(
        serving_decode.TinyCausalLM(),
        pool=serving_decode.PagePool(pages=32, page=8), max_rows=2)
    try:
        assert reg_family(gen._stats.prefix + ".requests") \
            == "decode.engine"
        toks = gen.generate(onp.asarray([3, 1]), max_new_tokens=2)
        assert len(toks) == 2
        assert gen.stats()["delivered"] == 1
        assert telemetry.snapshot()[gen._stats.prefix + ".delivered"] == 1
        # decode spans rode along (prefill + decode iterations)
        assert any(s["name"] == "decode.prefill" for s in gen.spans())
    finally:
        gen.close()


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------

def test_registry_thread_safety_under_hammer():
    """The satellite contract: hammer the registry from threads playing
    the prefetcher, checkpoint writer, and serving dispatcher while the
    main thread snapshots — snapshots are internally consistent (no torn
    reads), cumulatives are monotonic across snapshots, and the final
    totals are exact."""
    shared = telemetry.counter("test.hammer.shared")
    shared.reset()
    privates = {}
    N, ROLES = 2000, ("prefetcher", "checkpoint-writer",
                      "serving-dispatcher")
    for role in ROLES:
        privates[role] = telemetry.counter(f"test.hammer.{role}")
        privates[role].reset()
    stop = threading.Event()
    snaps = []

    def hammer(role):
        for _ in range(N):
            shared.inc()
            privates[role].inc()

    def snapper():
        while not stop.is_set():
            snaps.append(telemetry.snapshot())
        snaps.append(telemetry.snapshot())

    threads = [threading.Thread(target=hammer, args=(r,), name=r)
               for r in ROLES]
    sn = threading.Thread(target=snapper, name="snapper")
    sn.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    sn.join()
    # exact totals: no lost increment under contention
    assert shared.value == N * len(ROLES)
    for role in ROLES:
        assert privates[role].value == N
    # monotonic cumulatives + internal consistency across snapshots
    keys = ["test.hammer.shared"] + [f"test.hammer.{r}" for r in ROLES]
    for prev, cur in zip(snaps, snaps[1:]):
        for k in keys:
            assert cur[k] >= prev[k]
        # the shared counter can never lag the per-role counters it is
        # bumped in lockstep with (a torn read would break this)
        assert cur["test.hammer.shared"] >= max(
            cur[f"test.hammer.{r}"] for r in ROLES)


# ---------------------------------------------------------------------------
# event bus
# ---------------------------------------------------------------------------

def test_event_bus_step_indices_and_fault_routing():
    telemetry.clear_events()
    telemetry.set_step(41)
    telemetry.event("retrace", "test.bus")
    ev = telemetry.events(kind="retrace", name="test.bus")[-1]
    assert ev["step"] == 41 and ev["t_us"] > 0 and ev["seq"] > 0
    # fault events route through the bus and pick up the step index
    telemetry.set_step(42)
    faults.record_event("telemetry.test_site", "retry", ValueError("x"),
                        attempt=2)
    fev = telemetry.events(kind="fault", name="telemetry.test_site")[-1]
    assert fev["step"] == 42 and fev["action"] == "retry"
    assert fev["attempt"] == 2 and "ValueError" in fev["error"]
    # reserved-key collisions are prefixed, not dropped
    telemetry.event("fault", "test.bus", kind_override_check=1,
                    **{"kind": "TransientFault"})
    assert telemetry.events(name="test.bus")[-1]["x_kind"] \
        == "TransientFault"
    telemetry.set_step(None)


def test_fault_event_buffer_capacity_default():
    # the hard-coded deque(maxlen=1024) became the MXNET_FAULT_EVENTS
    # knob; default preserved
    from mxnet_tpu import config as _config

    assert _config.get("MXNET_FAULT_EVENTS") == 1024
    assert faults._EVENTS.maxlen == 1024
    assert telemetry._EVENTS.maxlen \
        == _config.get("MXNET_TELEMETRY_EVENTS") == 4096


def test_fault_event_buffer_capacity_knob_subprocess():
    """MXNET_FAULT_EVENTS bounds faults.events() (subprocess: the knob
    is read once at import)."""
    code = (
        "from mxnet_tpu import faults\n"
        "assert faults._EVENTS.maxlen == 7, faults._EVENTS.maxlen\n"
        "for i in range(20):\n"
        "    faults.record_event('cap.site', 'note', i=i)\n"
        "evs = faults.events('cap.site')\n"
        "assert len(evs) == 7 and evs[-1]['i'] == 19\n"
        "print('CAP_OK')\n")
    env = dict(os.environ, MXNET_FAULT_EVENTS="7", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CAP_OK" in r.stdout


# ---------------------------------------------------------------------------
# profiler interplay (satellite: dumps(reset=True) vs Counter)
# ---------------------------------------------------------------------------

def test_profiler_counter_survives_trace_reset():
    """Regression: ``profiler.dumps(reset=True)`` clears recorded trace
    events but must NOT clear declared counters — registry-backed
    ``profiler.Counter`` values persist across the reset and across
    re-instantiation."""
    profiler.set_state("run")
    try:
        c = profiler.Counter("survivor")
        c.set_value(5)
        c += 3
        assert c._value == 8
        profiler.dumps(reset=True)        # clears events...
        assert c._value == 8              # ...not the declared counter
        assert telemetry.snapshot()["profiler.survivor"] == 8
        # a re-created Counter of the same name resumes, not restarts
        c2 = profiler.Counter("survivor")
        c2.increment()
        assert c2._value == 9
        assert telemetry.registered()["profiler.survivor"]["family"] \
            == "profiler.user"
        # and the post-reset emission pipeline still works
        table = profiler.dumps(format="json")
        assert "survivor" in table
    finally:
        profiler.set_state("stop")


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _tiny_trainer():
    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d = nn.Dense(4, in_units=4)

        def forward(self, x):
            return self.d(x)

    net = Net()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01})
    step = tr.compile_step(net, lambda n, x, y: ((n(x) - y) ** 2).mean())
    x = mx.nd.ones((4, 4))
    y = mx.nd.zeros((4, 4))
    return tr, step, x, y


def test_spans_unify_train_step_and_step_timeline(tmp_path):
    telemetry.clear_spans()
    tr, step, x, y = _tiny_trainer()
    fn = str(tmp_path / "trace.json")
    profiler.set_config(filename=fn)
    profiler.set_state("run")
    try:
        tl = profiler.StepTimeline()
        with tl.phase("h2d"):
            pass
        with tl.phase("dispatch"):
            step(x, y, batch_size=4).asnumpy()
        tl.step()
        with telemetry.span("user.block", cat="user",
                            args={"k": 1}) as sp:
            sp.annotate(extra=2)
    finally:
        profiler.set_state("stop")
    # every layer landed in the ONE span buffer...
    cats = {s["cat"] for s in telemetry.spans()}
    assert {"train_step", "step_phase", "user"} <= cats
    rec = telemetry.spans(cat="user")[-1]
    assert rec["args"] == {"k": 1, "extra": 2} and rec["dur_us"] >= 1
    # ...and in the ONE chrome-trace pipe (profiler.dump)
    path = profiler.dump()
    with open(path) as f:
        trace = json.load(f)
    chrome_cats = {e["cat"] for e in trace["traceEvents"]
                   if e.get("ph") == "X"}
    assert {"train_step", "step_phase", "user"} <= chrome_cats
    # the per-step span record API: one record per TrainStep call,
    # carrying the step index and the compiled/eager path
    spans = tr.step_spans()
    assert spans and spans[-1]["name"] == "train_step.step"
    assert spans[-1]["args"]["path"] in ("compiled", "eager")
    assert isinstance(spans[-1]["args"]["step"], int)


def test_train_step_advances_step_index():
    _, step, x, y = _tiny_trainer()
    before = telemetry.current_step()
    step(x, y, batch_size=4)
    after = telemetry.current_step()
    assert after is not None and (before is None or after == before + 1)


def test_serving_engine_spans():
    class Id(gluon.HybridBlock):
        def forward(self, x):
            return x + 1

    net = Id()
    net.initialize()
    eng = serving.ServingEngine(net)
    try:
        eng.infer(mx.nd.ones((2, 2)))
    finally:
        eng.close()
    names = {s["name"] for s in eng.spans()}
    assert "serving.request" in names and "serving.dispatch" in names


# ---------------------------------------------------------------------------
# legacy accessors are views
# ---------------------------------------------------------------------------

def test_legacy_accessors_are_registry_views():
    # cached_step.deferred_read_count
    base = telemetry.snapshot()
    telemetry.get("cached_step.deferred_read").inc()
    assert cached_step.deferred_read_count() \
        == telemetry.snapshot()["cached_step.deferred_read"]
    telemetry.get("cached_step.deferred_read").inc(-1)  # restore
    # metric.host_sync_count (the loud host-path fallback counter)
    metric.reset_host_sync_count()
    metric._host(mx.nd.array([1.0, 2.0]))
    assert metric.host_sync_count() \
        == telemetry.snapshot()["metric.host_sync"] == 1
    # spmd / sharding counters
    assert spmd.reshard_count() == telemetry.snapshot()["spmd.reshard"]
    assert spmd.replicated_batch_count() \
        == telemetry.snapshot()["spmd.replicated_batch"]
    assert sharding.legalize_refusal_count() \
        == telemetry.snapshot()["sharding.legalize_refusal"]
    # engine drainables (computed gauge).  The registry is weak: settle
    # the collector first, or a collection between the two reads (earlier
    # tests leave TrainSteps in cycles) makes them differ
    gc.collect()
    assert telemetry.snapshot()["engine.drainables"] \
        == engine.drainable_count()
    # program_store-backed module views
    ns_traces = telemetry.snapshot()["program_store.train_step.traces"]
    assert cached_step.trace_count() == ns_traces
    # faults counters (family faults.site)
    faults.retry_call(lambda: 1, site="telemetry.test_site")
    assert faults.counters("telemetry.test_site")["attempts"] \
        == telemetry.snapshot()["faults.telemetry.test_site.attempts"]
    # reset functions reset the registry values too
    cached_step.reset_counters()
    assert telemetry.snapshot()["cached_step.deferred_read"] == 0


# ---------------------------------------------------------------------------
# flight recorder + report
# ---------------------------------------------------------------------------

def test_flight_recorder_flushed_by_waitall(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_DIR", str(tmp_path))
    telemetry.event("retrace", "test.recorder", detail="flush me")
    engine.waitall()                      # flushes the recorder
    path = telemetry.flight_recorder_path()
    assert path is not None and os.path.exists(path)
    lines = [json.loads(l) for l in open(path) if l.strip()]
    kinds = [l["kind"] for l in lines]
    assert "snapshot" in kinds            # the counter snapshot record
    assert any(l.get("name") == "test.recorder" for l in lines)
    snap = [l for l in lines if l["kind"] == "snapshot"][-1]
    assert "telemetry.events" in snap["counters"]
    # flush is incremental: a second flush does not duplicate events
    n0 = sum(1 for l in lines if l.get("name") == "test.recorder")
    telemetry.flush()
    lines2 = [json.loads(l) for l in open(path) if l.strip()]
    assert sum(1 for l in lines2
               if l.get("name") == "test.recorder") == n0


def test_flight_recorder_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY_DIR", raising=False)
    assert telemetry.flight_recorder_path() is None
    assert telemetry.flush() is None


def test_report_table():
    telemetry.counter("test.report.widget").inc(3)
    out = telemetry.report(prefix="test.report")
    assert "test.report.widget" in out and "cumulative" in out
    assert "declared counters" in out.splitlines()[-1]


# ---------------------------------------------------------------------------
# the CI gate
# ---------------------------------------------------------------------------

def _load_gate():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_telemetry", os.path.join(REPO, "tools",
                                        "check_telemetry.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_telemetry_gate_static_smoke():
    """Tier-1 smoke for the telemetry gate: the order-independent
    static + registry halves over the REAL tree — accessors found, zero
    raw (non-registry) counter state, and every counter registered so
    far named in a test.  The runtime lanes (deterministic TrainStep
    delta, chrome trace, 2-process merge) ride the slow lane (ISSUE-17
    wall slice 2)."""
    gate = _load_gate()
    pkg = os.path.join(REPO, "mxnet_tpu")
    accessors = gate.collect_accessors(pkg)
    assert accessors
    assert gate.collect_raw_state(pkg) == []
    assert gate.check_tested(telemetry.registered(),
                             os.path.join(REPO, "tests")) == []


def test_check_telemetry_gate_passes():
    """The CI gate itself: zero unregistered counters, every counter
    named in a test, deterministic steady-state TrainStep delta, chrome
    trace with >= 3 span categories.  10 s of compiled runtime lanes
    (PR 28)."""
    gate = _load_gate()
    assert gate.main(REPO) == 0


def test_check_telemetry_detects_rogue_counter(tmp_path):
    """A raw module-global counter (the pre-registry idiom) or an
    accessor with no registered counter fails the gate's static half."""
    gate = _load_gate()
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "_ROGUE_COUNT = 0\n\n"
        "def rogue_count():\n    return _ROGUE_COUNT\n")
    raw = gate.collect_raw_state(str(pkg))
    assert raw and "rogue" in raw[0]
    acc = gate.collect_accessors(str(pkg))
    assert "rogue" in acc
    assert gate.check_registered(acc, {"some.other.counter": {}}) \
        == [f"rogue_count (declared in "
            f"{os.path.join('mxnet_tpu', 'rogue.py')})"]
