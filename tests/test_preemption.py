"""Preemption survival: graceful-drain signal handling, recovery drills,
and the measured recovery-time budget (ISSUE 11 / ROADMAP 4c).

Covers, in-process wherever a fault plan suffices (the drill matrix's
real-signal end-to-end legs run as subprocesses inside
tools/check_recovery_budget.py, executed here as the suite gate):

1. SIGTERM drain under the async checkpoint writer + depth-k
   prefetcher: a REAL signal (os.kill to self) lands mid-step, the
   handler drains, force-saves the last completed step, and exits via
   the distinguished `Preempted`; the resumed loop is bit-exact vs an
   uninterrupted run.
2. Crash-between-saves via the `elastic.step` fault plan (the
   MXNET_FAULT_PLAN-drivable SIGKILL analog): replay counted in
   `elastic.steps_replayed`, restore timed in `elastic.recovery_s`,
   `restart` events on the bus.
3. Mesh 4→2 restore parity: checkpoint under a 4-device mesh, restore
   re-placed under a 2-device mesh — restored values bit-exact,
   recovery deterministic (two resumes bit-equal), trajectory tracking
   the 4-device run at float tolerance.
4. Corrupted-latest fallback: the sha256 content-digest sidecar catches
   a bit-flip that still unpickles; auto-selection degrades whole-step,
   explicit step= raises `DigestMismatch`, legacy sidecar-less files
   still load.
5. Serving drain shed-kind: both engines refuse new work with a typed
   `ShedError` kind `draining` while the flag is up — never a timeout.

Plus the new fault sites ("preemption.drain", "elastic.restore"), the
heartbeat auto-attach and no-materialize run_elastic satellites, and
the tools/check_recovery_budget.py gate itself.
"""
import importlib.util
import os
import signal
import time

import jax
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import drills, engine, faults, gluon, preemption, telemetry
from mxnet_tpu.parallel.elastic import (CheckpointManager, DigestMismatch,
                                        run_elastic)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NDEV = jax.device_count()


@pytest.fixture(autouse=True)
def _pristine_preemption():
    """A test that takes a preemption notice must not leave the whole
    process draining (every admission edge would shed for the rest of
    the suite)."""
    yield
    preemption.reset()
    preemption.uninstall()
    faults.uninstall()


def _mgr(tmp_path, **kw):
    return CheckpointManager(str(tmp_path / "ckpt"), **kw)


# ---------------------------------------------------------------------------
# 1. SIGTERM drain (real signal, in-process observable exit)
# ---------------------------------------------------------------------------

def test_sigterm_drain_under_async_writer_and_prefetcher(tmp_path):
    mgr = _mgr(tmp_path, keep=10, async_save=True)
    preemption.install()
    batches = [onp.float32(b) for b in range(1, 16)]
    kill_at = 7
    consumed = []

    def make_inputs():
        # the depth-k prefetcher stages the (host) batch stream; the
        # elastic loop indexes it positionally
        return list(range(len(batches)))

    pf = engine.prefetch(iter(batches), depth=2)

    def step(state, i):
        if int(state["i"]) == kill_at:
            os.kill(os.getpid(), signal.SIGTERM)   # handler runs HERE
        b = next(iter(pf))
        consumed.append(i)
        val = b.asnumpy() if hasattr(b, "asnumpy") else onp.asarray(b)
        return {"w": state["w"] + onp.float32(val),
                "i": state["i"] + 1}

    with pytest.raises(preemption.Preempted) as ei:
        run_elastic(step, {"w": onp.float32(0), "i": onp.int64(0)},
                    make_inputs(), mgr, save_every=5)
    assert ei.value.code == preemption.exit_code() == 83
    assert preemption.draining()
    # the drain force-saved the LAST COMPLETED step, blocking
    assert mgr.latest_step() == kill_at
    assert mgr._q.unfinished_tasks == 0          # writer queue flushed
    snap = telemetry.snapshot()
    assert snap["preemption.notices"] >= 1
    assert snap["preemption.drain_s"] > 0
    assert snap["preemption.draining"] == 1
    drains = telemetry.events(kind="drain")
    assert any(e["name"] == "preemption" and e.get("phase") == "notice"
               and e.get("sig") == signal.SIGTERM for e in drains)
    assert any(e["name"] == "preemption" and e.get("phase") == "complete"
               for e in drains)
    # draining stops the prefetcher from staging new batches
    time.sleep(0.05)
    with pytest.raises(StopIteration):
        for _ in range(len(batches)):
            next(iter(pf))
    # restart: resume from the drained checkpoint — 0 replay, final
    # state equals the uninterrupted run's
    preemption.reset()
    preemption.uninstall()
    pf2 = iter(batches[kill_at:])

    def step2(state, i):
        return {"w": state["w"] + onp.float32(next(pf2)),
                "i": state["i"] + 1}

    out, steps, restarts = run_elastic(
        step2, {"w": onp.float32(0), "i": onp.int64(0)}, make_inputs(),
        mgr, save_every=5)
    assert steps == len(batches) and restarts == 0
    assert float(out["w"]) == float(sum(batches))
    mgr.close()


def test_second_notice_exits_immediately():
    codes = []
    preemption.install(exit_fn=codes.append, grace_s=0)
    preemption.notice()
    assert codes == [83] and preemption.draining()
    preemption.notice()                       # supervisor escalated
    assert codes == [83, 83]


def test_preemption_drain_site_failure_degrades_exit_code():
    """An injected fault at the "preemption.drain" site (the drain's
    documented injection point): the exit code degrades to 1 — a
    supervisor must never trust the distinguished code after a failed
    drain."""
    codes = []
    preemption.install(exit_fn=codes.append)
    with faults.active(faults.FaultPlan().fail("preemption.drain")):
        preemption.notice()
    assert codes == [1]
    assert any(e["action"] == "drain_failed"
               for e in faults.events("preemption.drain"))


def test_grace_watchdog_force_exits_on_wedged_drain():
    codes = []
    preemption.install(exit_fn=codes.append, grace_s=0.05)
    preemption.on_drain(lambda: time.sleep(0.5))     # wedged hook
    preemption.notice()
    # the wedged drain eventually returns (exit 83 recorded last), but
    # the watchdog fired FIRST with the degraded code 84
    assert codes[0] == 84 and codes[-1] == 83


# ---------------------------------------------------------------------------
# 2. crash between saves via the fault plan (the SIGKILL analog a
#    MXNET_FAULT_PLAN="elastic.step@11:1" subprocess would run)
# ---------------------------------------------------------------------------

def test_crash_between_saves_replay_counted(tmp_path, monkeypatch):
    monkeypatch.setattr(faults, "_sleep", lambda s: None)
    telemetry.reset("elastic.")
    mgr = _mgr(tmp_path, async_save=True)
    batches = [onp.float32(b) for b in range(1, 13)]

    def step(state, b):
        return {"w": state["w"] + b, "i": state["i"] + 1}

    ref = {"w": onp.float32(0), "i": onp.int64(0)}
    for b in batches:
        ref = step(ref, b)

    with faults.active(faults.FaultPlan().fail("elastic.step", after=10)):
        out, steps, restarts = run_elastic(
            step, {"w": onp.float32(0), "i": onp.int64(0)}, batches,
            mgr, save_every=4, max_restarts=2)
    assert restarts == 1 and steps == 12
    assert float(out["w"]) == float(ref["w"])
    snap = telemetry.snapshot()
    # crashed at step 10 (after=10 -> 11th invocation), restored 8
    assert snap["elastic.steps_replayed"] == 2
    assert snap["elastic.restores"] == 1
    assert snap["elastic.recovery_s"] > 0
    evs = telemetry.events(kind="restart", name="elastic")
    assert any(e.get("replay") == 2 and e.get("step") == 8
               for e in evs)
    # no temp litter after recovery
    assert not [f for f in os.listdir(mgr.directory)
                if f.endswith(".tmp")]
    mgr.close()


def test_elastic_restore_site_retries_transient(tmp_path, monkeypatch):
    """The "elastic.restore" site: a transient restore failure (network
    FS flap) retries under the shared policy instead of burning a
    restart."""
    monkeypatch.setattr(faults, "_sleep", lambda s: None)
    mgr = _mgr(tmp_path, async_save=False)
    mgr.save(4, {"w": onp.arange(3.0)}, block=True)
    faults.reset()
    with faults.active(faults.FaultPlan().fail("elastic.restore", times=1)):
        out, steps, restarts = run_elastic(
            lambda s, b: {"w": s["w"] + b}, {"w": onp.zeros(3)},
            [onp.float32(1)] * 6, mgr, save_every=3)
    assert steps == 6 and restarts == 0
    assert faults.counters("elastic.restore")["retries"] == 1
    mgr.close()


def test_stale_tmp_files_cleaned_for_dead_writers(tmp_path):
    d = tmp_path / "ckpt"
    d.mkdir()
    # a dead pid's litter is removed; a live pid's (ours) is kept
    (d / "ckpt-4.pkl.999999.tmp").write_bytes(b"torn")
    (d / f"ckpt-8.pkl.{os.getpid()}.tmp").write_bytes(b"mine")
    mgr = CheckpointManager(str(d), async_save=False)
    files = set(os.listdir(str(d)))
    assert "ckpt-4.pkl.999999.tmp" not in files
    assert f"ckpt-8.pkl.{os.getpid()}.tmp" in files
    mgr.close()


# ---------------------------------------------------------------------------
# 3. mesh 4 -> 2 restore parity (in-process drill leg)
# ---------------------------------------------------------------------------

def _mesh_run(monkeypatch, mesh: str, first: int, last: int, tree=None,
              mgr=None):
    """Drill-composed leg: fresh net + Trainer(kvstore='tpu') under
    MXNET_SPMD_MESH=mesh, optionally restored from ``tree``, stepping
    [first, last) with the shared drill batches.  Returns (losses,
    capture, restored_params)."""
    monkeypatch.setenv("MXNET_SPMD_MESH", mesh)
    net = drills._drill_net(0)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="tpu")
    step = trainer.compile_step(net, drills._drill_loss)
    drills._warm_opt_states(trainer)
    restored_params = None
    if tree is not None:
        like = drills._capture(net, trainer)
        restored, s = mgr.restore(like=like)
        assert s == first
        drills._restore_into(net, trainer, restored)
        restored_params = {k: onp.asarray(v)
                           for k, v in restored["params"].items()}
    losses = {}
    for i in range(first, last):
        x, y = drills._host_batch(i)
        loss = step(mx.nd.array(x), mx.nd.array(y), batch_size=drills.ROWS)
        losses[i] = float(loss.asnumpy().ravel()[0]).hex()
    assert step.last_step_compiled, step.last_fallback_reason
    engine.waitall()
    return losses, drills._capture(net, trainer), restored_params


@pytest.mark.skipif(NDEV < 4, reason="needs the virtual multi-device mesh")
def test_mesh_4_to_2_restore_parity(tmp_path, monkeypatch):
    k, n = 5, 10
    # 4-device leg + checkpoint
    losses_a, cap_a, _ = _mesh_run(monkeypatch, "4", 0, k)
    mgr = _mgr(tmp_path, async_save=False)
    mgr.save(k, cap_a, block=True)
    want = {kk: onp.asarray(v) for kk, v in cap_a["params"].items()}
    # 2-device resume pair: restored values bit-exact, placement 2-dev,
    # resumed trajectory deterministic
    res = {}
    for leg in ("b1", "b2"):
        losses, cap, restored = _mesh_run(monkeypatch, "2", k, n,
                                          tree=True, mgr=mgr)
        res[leg] = (losses, cap)
        for kk in want:
            onp.testing.assert_array_equal(restored[kk], want[kk])
    assert res["b1"][0] == res["b2"][0]          # bit-exact recovery
    # cross-mesh: tracks the uninterrupted 4-device run within tolerance
    losses_f, _, _ = _mesh_run(monkeypatch, "4", 0, n)
    assert losses_a == {i: losses_f[i] for i in range(k)}  # prefix exact
    for i in range(k, n):
        a = float.fromhex(losses_f[i])
        b = float.fromhex(res["b1"][0][i])
        assert abs(a - b) <= drills.TOPO_RTOL * max(1.0, abs(a)), \
            (i, a, b)
    mgr.close()


# ---------------------------------------------------------------------------
# 4. corrupted-latest fallback (content digest sidecar)
# ---------------------------------------------------------------------------

def test_corrupted_latest_digest_fallback(tmp_path):
    telemetry.reset("checkpoint.")
    mgr = _mgr(tmp_path, keep=5, async_save=False)
    mgr.save(1, {"w": onp.arange(4.0)}, block=True)
    mgr.save(2, {"w": onp.arange(4.0) + 1}, block=True)
    path = mgr._path(2)
    assert os.path.exists(path + ".sha256")       # sidecar written
    # flip one payload byte: the pickle still loads — only the digest
    # catches it
    with open(path, "r+b") as f:
        f.seek(-7, os.SEEK_END)
        b = f.read(1)
        f.seek(-7, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    out, step = mgr.restore()                     # degrades whole-step
    assert step == 1
    onp.testing.assert_array_equal(out["w"], onp.arange(4.0))
    assert telemetry.snapshot()["checkpoint.digest_mismatches"] >= 1
    assert any(e["action"] == "digest_mismatch"
               for e in faults.events("checkpoint.restore"))
    # an EXPLICIT step never falls back
    with pytest.raises(DigestMismatch):
        mgr.restore(step=2)
    # legacy checkpoints without a sidecar still load unverified
    os.remove(mgr._path(1) + ".sha256")
    out, step = mgr.restore(step=1)
    onp.testing.assert_array_equal(out["w"], onp.arange(4.0))
    # GC removes sidecars with their steps
    for s in (3, 4, 5, 6, 7, 8):
        mgr.save(s, {"w": onp.arange(4.0)}, block=True)
    leftover = [f for f in os.listdir(mgr.directory)
                if f.endswith(".sha256")]
    assert sorted(leftover) == [f"ckpt-{s}.pkl.sha256"
                                for s in (4, 5, 6, 7, 8)]
    mgr.close()


def test_restore_like_structure_mismatch_is_loud(tmp_path):
    mgr = _mgr(tmp_path, async_save=False)
    mgr.save(3, {"a": onp.arange(2.0), "b": onp.arange(3.0)}, block=True)
    with pytest.raises(ValueError, match="leaves"):
        mgr._restore_step(3, like={"a": onp.zeros(2)})
    mgr.close()


# ---------------------------------------------------------------------------
# 5. serving drain shed-kind (typed ``draining``, never a timeout)
# ---------------------------------------------------------------------------

def test_generative_engine_sheds_draining():
    from mxnet_tpu.serving_decode import (GenerativeEngine, PagePool,
                                          TinyCausalLM)

    model = TinyCausalLM(vocab=16, d_model=8, n_layers=1, n_heads=2,
                         max_seq=32)
    eng = GenerativeEngine(model, pool=PagePool(pages=16, page=4),
                           max_rows=2, name="drainme")
    try:
        out = eng.generate([1, 2, 3], max_new_tokens=4)
        assert len(out) == 4
        preemption.install(exit_fn=lambda c: None)
        preemption.notice()
        assert preemption.draining()
        t0 = time.monotonic()
        with pytest.raises(faults.ShedError) as ei:
            eng.generate([1, 2, 3], max_new_tokens=4)
        assert time.monotonic() - t0 < 5.0        # immediate, no timeout
        assert ei.value.kind == "draining"
        assert eng.stats()["shed_draining"] == 1
        assert eng.stats()["pool"]["in_use"] == 0
        assert any(e.get("shed_kind") == "draining"
                   for e in telemetry.events(kind="shed", name="drainme"))
    finally:
        eng.close()


def test_serving_engine_infer_sheds_draining():
    from mxnet_tpu.serving import ServingEngine

    net = gluon.nn.Dense(3, in_units=4)
    net.initialize(mx.init.Xavier())
    eng = ServingEngine(net)
    try:
        eng.infer(mx.nd.ones((2, 4)))             # accepted while live
        preemption.install(exit_fn=lambda c: None)
        preemption.notice()
        with pytest.raises(faults.ShedError) as ei:
            eng.infer(mx.nd.ones((2, 4)))
        assert ei.value.kind == "draining"
        assert eng.stats()["shed_draining"] == 1
        assert any(e["action"] == "shed" and e.get("kind") == "draining"
                   for e in faults.events("serving.infer"))
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# run_elastic satellites
# ---------------------------------------------------------------------------

class _LenGetitemOnly:
    """Indexable inputs that must be consumed IN PLACE (materializing
    via iter() would double host RSS for an epoch of real batches)."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if not 0 <= i < self.n:
            raise IndexError(i)
        return onp.float32(i + 1)

    def __iter__(self):
        raise AssertionError("run_elastic materialized len+getitem "
                             "inputs via iter()")


def test_run_elastic_does_not_materialize_indexable_inputs(tmp_path):
    mgr = _mgr(tmp_path, async_save=False)
    out, steps, restarts = run_elastic(
        lambda s, b: {"w": s["w"] + b}, {"w": onp.float32(0)},
        _LenGetitemOnly(6), mgr, save_every=3)
    assert steps == 6 and float(out["w"]) == 21.0
    mgr.close()


class _FakeKV:
    type = "tpu"
    _heartbeat = None

    def attach_heartbeat(self, monitor):
        self._heartbeat = monitor


def test_heartbeat_auto_attach_with_barrier_deadline(tmp_path,
                                                     monkeypatch):
    mgr = _mgr(tmp_path, async_save=False)
    kv = _FakeKV()
    monkeypatch.setenv("MXNET_BARRIER_TIMEOUT", "5.0")
    run_elastic(lambda s, b: {"w": s["w"] + b}, {"w": onp.float32(0)},
                [onp.float32(1)] * 3, mgr, save_every=2, kvstore=kv)
    assert kv._heartbeat is not None             # attached automatically
    assert kv._heartbeat._thread is None         # and stopped at exit
    assert os.path.isdir(os.path.join(mgr.directory, "heartbeats"))
    # without a deadline configured, nothing is attached
    kv2 = _FakeKV()
    monkeypatch.setenv("MXNET_BARRIER_TIMEOUT", "0")
    run_elastic(lambda s, b: {"w": s["w"] + b}, {"w": onp.float32(0)},
                [onp.float32(1)] * 3, mgr, save_every=2, kvstore=kv2)
    assert kv2._heartbeat is None
    mgr.close()


# ---------------------------------------------------------------------------
# telemetry contracts
# ---------------------------------------------------------------------------

def test_recovery_counters_registered():
    reg = telemetry.registered()
    for name, kind in (("preemption.notices", "cumulative"),
                       ("preemption.drain_s", "time"),
                       ("elastic.recovery_s", "time"),
                       ("elastic.steps_replayed", "cumulative"),
                       ("elastic.restores", "cumulative"),
                       ("checkpoint.digest_mismatches", "cumulative")):
        assert name in reg and reg[name]["kind"] == kind, name
    assert "preemption.draining" in reg          # computed gauge


# ---------------------------------------------------------------------------
# the CI gate (full subprocess drill matrix)
# ---------------------------------------------------------------------------

def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "check_recovery_budget",
        os.path.join(REPO, "tools", "check_recovery_budget.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow     # 59 s alone, 63 s beside three other workers (PR 28)
def test_check_recovery_budget_gate():
    """The suite-run gate (tools/check_recovery_budget.py, loaded like
    check_fault_sites): every drill scenario green, warm recovery at 0
    fresh compiles, 0 leaked pages / temp files, recovery inside the
    wall-clock budget.  The FULL matrix is a minute of subprocess
    drills, so it runs slow-marked; tier-1 keeps the single-scenario
    smoke below."""
    gate = _load_gate()
    assert gate.main([]) == 0


def test_check_recovery_budget_gate_smoke():
    """Tier-1 smoke for the gate: ONE real subprocess drill through the
    same tools/check_recovery_budget.py path (scenario selection, budget
    lines, leak checks) — the full matrix rides the slow lane."""
    gate = _load_gate()
    assert gate.main(["corrupt_latest"]) == 0
