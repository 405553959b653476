"""The host half of a train step as the program's own spans (ISSUE 35;
docs/OBSERVABILITY.md, "Host phases"): step phases, program builds and the
prefetcher's transfers, on ``time.time_ns``, each with the span that caused
it.  CPU only: what is recorded, in which order and under which parent;
never how long anything took.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, cached_step, gluon, profiler, telemetry
from mxnet_tpu import program_store as pstore

PHASES = ["train_step.prep", "train_step.operands", "train_step.launch",
          "train_step.writeback"]


def _mlp(seed=0):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
            gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    return net


def _loss_fn(net, x, y):
    return ((net(x) - y) ** 2).mean()


def _batch(seed=42, n=6):
    rng = onp.random.RandomState(seed)
    return mx.nd.array(rng.randn(n, 8)), mx.nd.array(rng.randn(n, 4))


def _step(accum_steps=1, scaler=None, seed=0):
    net = _mlp(seed)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    if scaler is not None:
        trainer._amp_loss_scaler = amp.LossScaler(init_scale=scaler)
    return trainer.compile_step(net, _loss_fn, accum_steps=accum_steps)


def _seq():
    sps = telemetry.spans()
    return sps[-1]["seq"] if sps else 0


def _since(base, cat=None):
    return [s for s in telemetry.spans(cat=cat) if s["seq"] > base]


def _children(spans, parent):
    return [s for s in spans if s["parent"] == parent["id"]]


# ---------------------------------------------------------------------------
# the clock and the record
# ---------------------------------------------------------------------------
def test_spans_and_events_are_on_time_ns_and_carry_id_and_parent():
    base = _seq()
    before = time.time_ns()
    with telemetry.span("test.host.outer", cat="user") as outer:
        telemetry.event("custom", "test.host.event")
        with telemetry.span("test.host.inner", cat="user"):
            pass
        telemetry.record_span("test.host.posthoc", "user",
                              time.time_ns() - 5_000, time.time_ns())
    after = time.time_ns()
    got = {s["name"]: s for s in _since(base, cat="user")}
    o, i, p = (got["test.host." + k] for k in ("outer", "inner", "posthoc"))
    for rec in (o, i, p):
        assert {"name", "cat", "step", "t0_ns", "t1_ns", "t0_us", "dur_us",
                "thread", "id", "parent", "seq"} <= set(rec)
        assert before <= rec["t0_ns"] <= rec["t1_ns"] <= after
        assert rec["t0_us"] == rec["t0_ns"] // 1000
        assert rec["thread"] == threading.get_ident()
    assert o["parent"] is None and o["id"] == outer._sid
    assert i["parent"] == o["id"] and p["parent"] == o["id"]
    assert o["t0_ns"] <= i["t0_ns"] and i["t1_ns"] <= o["t1_ns"]
    ev = telemetry.events(name="test.host.event")[-1]
    assert before // 1000 <= ev["t_us"] <= after // 1000
    assert "trace_id" not in ev            # no request scope, no trace fields


def test_a_chrome_trace_is_on_the_same_clock(tmp_path):
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.set_state("run")
    try:
        before = time.time_ns() // 1000
        with telemetry.span("test.host.chrome", cat="user"):
            with profiler.Task("test.host.task"):
                pass
        after = time.time_ns() // 1000
    finally:
        profiler.set_state("stop")
    import json

    evs = {e["name"]: e for e in
           json.loads(profiler.dumps(reset=True, format="json"))["traceEvents"]}
    for name in ("test.host.chrome", "test.host.task"):
        assert before <= evs[name]["ts"] <= after


def test_phases_are_consecutive_and_parent_what_they_cause():
    base = _seq()
    with telemetry.span("test.host.step", cat="user") as sp:
        ph = telemetry.phases("user")
        ph.to("test.host.a")
        ph.to("test.host.b", program="x")
        with telemetry.span("test.host.caused", cat="user"):
            pass
        ph.end()
        ph.to("test.host.dropped")
        ph.drop()
        assert telemetry._ambient_parent() == sp._sid
    got = {s["name"]: s for s in _since(base, cat="user")}
    assert "test.host.dropped" not in got
    a, b, c = (got["test.host." + k] for k in ("a", "b", "caused"))
    assert a["t1_ns"] == b["t0_ns"]            # one reading a boundary
    assert a["parent"] == b["parent"] == got["test.host.step"]["id"]
    assert c["parent"] == b["id"] and b["args"] == {"program": "x"}


def test_a_category_has_a_ring_of_its_own():
    telemetry.clear_spans()
    with telemetry.span("test.host.kept", cat="test_ring_a"):
        pass
    for _ in range(telemetry._RING + 10):
        telemetry.record_span("test.host.flood", "test_ring_b", 1, 2)
    assert len(telemetry.spans(cat="test_ring_b")) == telemetry._RING
    assert [s["name"] for s in telemetry.spans(cat="test_ring_a")] \
        == ["test.host.kept"]
    merged = telemetry.spans()
    assert merged[0]["name"] == "test.host.kept"
    assert [s["seq"] for s in merged] == sorted(s["seq"] for s in merged)
    assert telemetry.spans(name="test.host.kept", limit=5)[0]["cat"] \
        == "test_ring_a"


# ---------------------------------------------------------------------------
# the compiled step
# ---------------------------------------------------------------------------
def test_a_compiled_step_records_its_phases_in_order():
    step = _step()
    x, y = _batch()
    step(x, y, batch_size=6)
    base = _seq()
    before = time.time_ns()
    step(x, y, batch_size=6)
    after = time.time_ns()
    assert step.last_step_compiled, step.last_fallback_reason
    got = _since(base, cat="train_step")
    parent = got[-1]
    assert parent["name"] == "train_step.step" and parent["parent"] is None
    assert parent["args"] == {"path": "compiled", "step": parent["step"]}
    assert before <= parent["t0_ns"] <= parent["t1_ns"] <= after
    kids = _children(got, parent)
    assert [k["name"] for k in kids] == PHASES and len(got) == 5
    assert kids[2]["args"] == {"program": "step"}
    assert all(k["step"] == parent["step"] == telemetry.current_step()
               for k in kids)
    # nested in time, one after the other, nothing between them
    assert parent["t0_ns"] <= kids[0]["t0_ns"]
    assert kids[-1]["t1_ns"] <= parent["t1_ns"]
    for a, b in zip(kids, kids[1:]):
        assert a["t1_ns"] == b["t0_ns"]
    # a warm step builds nothing
    assert not [s for s in _since(base, cat="program")]
    # and Trainer.step_spans() still answers one record a call
    assert [s["name"] for s in step._trainer.step_spans(limit=2)] \
        == ["train_step.step"] * 2


def test_a_first_call_shows_the_build_under_launch():
    ns = pstore.namespace("train_step")
    step = _step(seed=1)
    x, y = _batch()
    c0, t0 = ns.compile_seconds, ns.trace_seconds
    base = _seq()
    step(x, y, batch_size=6)
    assert step.last_step_compiled, step.last_fallback_reason
    spans = _since(base)
    launch = [s for s in spans if s["name"] == "train_step.launch"][-1]
    build, = _children(spans, launch)
    assert build["name"] == "program.build" and build["cat"] == "program"
    assert build["args"] == {
        "namespace": "train_step", "label": "HybridSequential",
        "module": "jit_mx_train_step__HybridSequential"}
    trace, compile_ = _children(spans, build)
    assert (trace["name"], compile_["name"]) == ("program.trace",
                                                 "program.compile")
    assert compile_["args"]["cache"] in ("hit", "miss", "off")
    assert compile_["args"]["retrieval_s"] >= 0.0
    assert build["t0_ns"] <= trace["t0_ns"] <= trace["t1_ns"] \
        <= compile_["t0_ns"] <= compile_["t1_ns"] <= build["t1_ns"]
    trace_s = (trace["t1_ns"] - trace["t0_ns"]) / 1e9
    compile_s = (compile_["t1_ns"] - compile_["t0_ns"]) / 1e9
    assert ns.trace_seconds - t0 == pytest.approx(trace_s, abs=1e-3)
    assert ns.compile_seconds - c0 == pytest.approx(trace_s + compile_s,
                                                    abs=1e-3)
    assert pstore.stats("train_step")["trace_seconds"] >= trace_s - 1e-3
    assert telemetry.registered()[
        "program_store.train_step.trace_seconds"]["kind"] == "time"
    # the name the span gives is the name the device trace will show
    module, = step._programs.values()
    assert module.executable.runtime_executable().hlo_modules()[0].name \
        == build["args"]["module"]


def test_an_accumulation_window_has_four_grad_launches_and_one_update():
    step = _step(accum_steps=4)
    x, y = _batch()
    for _ in range(4):                                # the builds' window
        step(x, y, batch_size=6)
    base = _seq()
    for _ in range(4):
        step(x, y, batch_size=6)
    got = _since(base, cat="train_step")
    steps = [s for s in got if s["name"] == "train_step.step"]
    assert len(steps) == 4
    launches = [s["args"]["program"] for s in got
                if s["name"] == "train_step.launch"]
    assert launches == ["grad"] * 4 + ["update"]
    for s in steps[:3]:
        assert [k["name"] for k in _children(got, s)] == PHASES
    # the window-final call runs the same phases a second time
    assert [k["name"] for k in _children(got, steps[3])] == PHASES * 2
    assert [k["args"]["program"] for k in _children(got, steps[3])
            if k["name"] == "train_step.launch"] == ["grad", "update"]


@pytest.mark.parametrize("lag,where", [("1", "deferred"), ("0", "sync")])
def test_a_scaler_adds_the_gate_phase(monkeypatch, lag, where):
    monkeypatch.setenv("MXNET_AMP_LAG", lag)
    step = _step(scaler=8.0)
    x, y = _batch()
    step(x, y, batch_size=6)
    base = _seq()
    step(x, y, batch_size=6)
    got = _since(base, cat="train_step")
    kids = _children(got, got[-1])
    assert [k["name"] for k in kids] == PHASES + ["train_step.gate"]
    assert kids[-1]["args"] == {"where": where}
    if where == "deferred":
        base = _seq()
        step.drain()                       # the flag held back, read now
        gate, = _since(base, cat="train_step")
        assert gate["name"] == "train_step.gate"
        assert gate["args"] == {"where": "drain"}


def test_an_eager_fallback_step_records_no_phase(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILED_STEP", "0")
    step = _step()
    x, y = _batch()
    base = _seq()
    step(x, y, batch_size=6)
    assert not step.last_step_compiled
    got = _since(base, cat="train_step")
    assert [s["name"] for s in got] == ["train_step.step"]
    assert got[0]["args"]["path"] == "eager"


def test_steady_dispatches_do_not_retrace():
    step = _step()
    x, y = _batch()
    for _ in range(2):
        step(x, y, batch_size=6)
    traces, programs = cached_step.trace_count(), \
        pstore.namespace("train_step").compile_count
    base = _seq()
    for _ in range(10):
        step(x, y, batch_size=6)
    assert cached_step.trace_count() == traces
    assert pstore.namespace("train_step").compile_count == programs
    assert len(_since(base, cat="train_step")) == 50


def _step_program_text(monkeypatch, records):
    """The step program's lowered text, with the records taken or patched
    out of the step path."""
    if not records:
        monkeypatch.setattr(telemetry, "_record", lambda *a, **k: None)
        monkeypatch.setattr(telemetry.phases, "to",
                            lambda self, name, **args: None)
    texts = []
    build = pstore.build

    def catching(name, jitted, lower_args, **kw):
        texts.append(jitted.lower(*lower_args).as_text())
        return build(name, jitted, lower_args, **kw)

    monkeypatch.setattr(cached_step._pstore, "build", catching)
    step = _step(seed=3)
    x, y = _batch()
    step(x, y, batch_size=6)
    assert step.last_step_compiled, step.last_fallback_reason
    monkeypatch.undo()
    return texts


def test_the_step_program_is_the_same_with_the_records_patched_out(
        monkeypatch):
    base = _seq()
    without = _step_program_text(monkeypatch, records=False)
    assert not _since(base, cat="train_step")
    with_records = _step_program_text(monkeypatch, records=True)
    assert len(with_records) == 1 and with_records == without


# ---------------------------------------------------------------------------
# the prefetcher's thread
# ---------------------------------------------------------------------------
def test_a_prefetcher_records_its_transfers_and_slot_waits():
    n, depth = 6, 2
    batches = [(onp.full((4, 8), i, "float32"), onp.full((4,), i, "int32"))
               for i in range(n)]
    nbytes = 4 * 8 * 4 + 4 * 4
    base = _seq()
    pf = mx.engine.prefetch(iter(batches), depth=depth)
    try:
        deadline = time.time() + 30
        while pf._staged < depth + 1 and time.time() < deadline:
            time.sleep(0.01)          # the FIFO is full, the thread waits
        time.sleep(0.05)
        got = [(int(x.asnumpy()[0, 0]), int(y.asnumpy()[0]))
               for x, y in pf]
    finally:
        pf.close()
    assert got == [(i, i) for i in range(n)]
    spans = _since(base, cat="input")
    transfers = [s for s in spans if s["name"] == "input.transfer"]
    waits = [s for s in spans if s["name"] == "input.slot_wait"]
    assert len(transfers) == n == len(waits)
    assert all(s["args"] == {"bytes": nbytes} for s in transfers)
    thread = {s["thread"] for s in spans}
    assert len(thread) == 1 and thread != {threading.get_ident()}
    for t, w in zip(transfers, waits):
        assert t["t0_ns"] <= t["t1_ns"] <= w["t0_ns"] <= w["t1_ns"]
    # the third batch found both slots taken and waited for the consumer
    assert (waits[depth]["t1_ns"] - waits[depth]["t0_ns"]) / 1e9 > 0.04


def test_a_build_that_raises_counts_no_seconds():
    """``compile_seconds`` (the source of ``programs.compile_s``) and
    ``trace_seconds`` are sums over the programs that were built."""
    import jax

    def body(x):
        raise ValueError("cannot stage")

    ns = pstore.namespace("eager_jit")
    found = (ns.compile_count, ns.compile_seconds, ns.trace_seconds)
    base = _seq()
    with pytest.raises(ValueError, match="cannot stage"):
        pstore.build("eager_jit", jax.jit(body), (onp.zeros(3),))
    assert (ns.compile_count, ns.compile_seconds, ns.trace_seconds) == found
    # the spans still say what was tried
    assert [s["name"] for s in _since(base, cat="program")] \
        == ["program.trace", "program.build"]


def test_the_ahead_samples_are_bounded():
    from mxnet_tpu import engine

    n = engine._AHEAD_WINDOW + 50
    pf = mx.engine.prefetch(iter(range(n)), depth=2,
                            transfer=lambda item: item)
    try:
        assert list(pf) == list(range(n))
    finally:
        pf.close()
    stats = pf.stats()
    assert stats["consumed"] == n == stats["staged"]
    assert len(pf._ahead_samples) == engine._AHEAD_WINDOW
    assert 0 <= stats["steady_ahead"] <= stats["max_ahead"] <= 2
