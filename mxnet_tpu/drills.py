"""Deterministic end-to-end preemption/recovery drills.

ROADMAP item 4(c), executed: the fault framework (PR 2), the compiled
SPMD train step (PRs 3/6), the async engine (PR 5), the persistent
compile cache (PR 7), the generative engine (PR 8), and the preemption
subsystem (`mxnet_tpu/preemption.py`) have individually-tested recovery
paths — this module KILLS real processes running all of them at once
and measures what recovery actually costs (arXiv:2008.01040's
"measure, don't guess", applied to failure instead of throughput).

Every scenario is a scripted subprocess drill, fully deterministic — no
parent-side signal races: children trigger their own SIGTERM/SIGKILL at
a scripted step (a real ``os.kill`` to themselves, delivered through
the real installed handler), batches derive from the step index, and
greedy decode is token-exact, so a drill either reproduces bit-for-bit
or fails loudly:

- ``sigterm_drain`` — SIGTERM mid-step under the compiled SPMD
  ``TrainStep`` (4-device mesh) with the depth-k prefetcher and the
  async checkpoint writer running: the child drains, force-saves the
  last completed step, and exits with the distinguished code; the
  restarted child resumes with **0 steps replayed** and a loss
  trajectory bit-exact vs the uninterrupted reference.
- ``sigkill_between_saves`` — SIGKILL (no grace, no drain) between
  periodic saves: recovery restores the newest complete checkpoint,
  replays the gap deterministically (replayed losses bit-equal the
  first run's), leaves 0 temp-file litter.
- ``topology_change`` — checkpoint under a 4-device mesh, restart under
  a 2-device mesh: ``restore(like=)`` re-places bit-exactly (params
  digest match), the resumed 2-device trajectory is deterministic (two
  resumes bit-equal — run twice, the second proving warm-cache
  recovery performs 0 fresh compiles) and tracks the 4-device reference
  within float tolerance (cross-mesh reduction order differs by ulps;
  same-mesh drills assert bit-exact).
- ``corrupt_latest`` — flip one payload byte in the newest checkpoint
  (its sha256 sidecar now disagrees): restore degrades whole-step to
  the previous complete one, counted in ``checkpoint.digest_mismatches``,
  and the longer replay still lands bit-exact.
- ``decode_drain`` — SIGTERM mid-stream under the continuous-batching
  ``GenerativeEngine``: in-flight rows decode to completion (token-exact
  vs the eager oracle), queued requests come back as typed ``draining``
  sheds, 0 KV pages leak, and a second process serves the shed
  requests token-exactly.
- ``router_kill`` / ``router_wedge`` / ``router_flap`` /
  ``router_deadline_storm`` / ``router_prefix_storm``
  (``ROUTER_SCENARIOS``, gated by
  ``tools/check_availability_budget.py``) — the SERVING chaos matrix
  over a 2-replica ``serving_router.ReplicaRouter``: a replica killed
  mid-decode (its compiled programs start raising; every in-flight and
  queued request fails over, token-exact, 0 pages leaked, and a
  preemption notice afterwards still drains the router to the
  distinguished exit code), a wedged dispatch (hangs forever; the
  heartbeat wedge timeout evicts the replica inside
  ``MXNET_ROUTER_WEDGE_S``), a breaker flap (transient error burst
  opens the breaker; the half-open probe re-admits within the probe
  budget), a deadline storm (tight ``deadline_us`` budgets shed
  typed ``deadline`` within bounded wall clock — never a hang — while
  feasible budgets deliver token-exact), and a shared-prefix storm
  (ISSUE 16: every request shares one system prompt, so prefix
  affinity converges the fleet on the replica holding the warm
  hash-keyed pages — which is exactly the replica the drill then
  kills; failover rebuilds the cache cold on the survivor,
  token-exact, with the page-pool refcount audit clean at drain: 0
  leaked, 0 double-freed, no index entry pointing at a dead page).
- ``router_scale_storm`` / ``router_host_loss`` — the ISSUE-17 elastic
  fleet cells.  The scale storm runs a ``FleetSupervisor`` over a
  1-replica router under bursty load: the autoscaler grows the fleet
  1 → 3 by spawning cross-host ``replica`` children (each joins
  JOINING → warm → SERVING off the shared program cache: 0 fresh
  compiles) and, when the burst subsides, shrinks back 3 → 1 where
  every scale-down IS a scheduled graceful preemption (drain →
  ``preempt`` op → SIGTERM → typed draining sheds → exit 83).  Host
  loss SIGKILLs a remote replica's process mid-storm: every open call
  fails at once, failover redelivers token-exactly on the survivor,
  the breaker opens, and ``kill_to_recovered_s`` stays inside the
  availability wall.
- ``bitflip_param`` — the ISSUE-13 silent-corruption drill: the child
  flips one bit of ONE device's replica of a parameter mid-run; the
  sentinel's cross-replica digest vote localizes the device within one
  cadence (named in a ``corruption`` event, persisted to the
  quarantine list), rollback restores the last digest-verified
  checkpoint, the resumed trajectory is bit-exact vs the uninterrupted
  reference, and a restarted child re-resolves the mesh WITHOUT the
  quarantined device.
- ``loss_spike`` — scripted poisoned batch (targets scaled 1e6): the
  sentinel's grad-norm z-score window trips BEFORE the tainted state
  is checkpointed, rollback replays exactly the save-interval gap, and
  the merged trajectory is bit-exact vs the reference (the poison is
  one-shot, so the replay is clean).

``run_drill(name, root)`` orchestrates one scenario (children share
``<root>/pcache`` — the ``MXNET_PROGRAM_CACHE_DIR`` disk cache — and
the memoized reference run) and returns a report with the measured
**recovery-time budget**: ``recovery_s`` (checkpoint restore),
``recovery_wall_s`` (process start -> first resumed step),
``steps_replayed``, ``drain_s``, and the restart's disk
``fresh_compiles`` (0 when the cache is warm — the PR-7 promise).
``tools/check_recovery_budget.py`` gates all of it in CI.

Child entry: ``python -m mxnet_tpu.drills train|decode ...`` (the
orchestrator builds the exact argv; children force ``JAX_PLATFORMS=cpu``
with an ``--xla_force_host_platform_device_count`` virtual mesh).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

__all__ = ["SCENARIOS", "ROUTER_SCENARIOS", "run_drill", "main"]

SCENARIOS = ("sigterm_drain", "sigkill_between_saves", "topology_change",
             "corrupt_latest", "decode_drain", "bitflip_param",
             "loss_spike")
# the serving-availability matrix (tools/check_availability_budget.py);
# kept OUT of SCENARIOS so the recovery gate's matrix is unchanged
ROUTER_SCENARIOS = ("router_kill", "router_wedge", "router_flap",
                    "router_deadline_storm", "router_prefix_storm",
                    "router_scale_storm", "router_host_loss",
                    "spec_draft_poison")

# the scripted workload every train drill shares
N_STEPS = 24
SAVE_EVERY = 4
ROWS = 16
HALF = N_STEPS // 2
# cross-mesh tolerance: 4-dev vs 2-dev all-reduce order differs by ulps
# per step (same-mesh comparisons are bit-exact; see test_spmd_step's
# sharded-vs-single-chip contract)
TOPO_RTOL = 1e-4

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared child workload pieces (import mxnet_tpu lazily — the parent
# orchestrator must stay import-light)
# ---------------------------------------------------------------------------

def _host_batch(i: int):
    import numpy as onp

    rng = onp.random.RandomState(10_000 + int(i))
    return (rng.randn(ROWS, 8).astype(onp.float32),
            rng.randn(ROWS, 4).astype(onp.float32))


def _drill_net(seed: int = 0):
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

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


def _drill_loss(net, x, y):
    return ((net(x) - y) ** 2).mean()


def _warm_opt_states(trainer) -> None:
    """Create every updater state slot up front so the state tree's
    STRUCTURE is constant from step 0 (restore(like=) degrades to an
    older step on a structural mismatch — an empty-states initial
    capture would make every later checkpoint look unrestorable)."""
    opt = trainer._optimizer
    upd = trainer._updaters[0]
    for p in trainer._params:
        if p.grad_req == "null":
            continue
        idx = trainer._param2idx[id(p)]
        if idx not in upd.states:
            upd.states[idx] = opt.create_state_multi_precision(
                idx, p.data())
            upd.states_synced[idx] = True
        opt._index_update_count.setdefault(idx, opt.begin_num_update)


def _capture(net, trainer):
    """Checkpointable pytree of everything the trajectory depends on:
    params, optimizer state (momentum buffers), and update counts."""
    import jax

    from mxnet_tpu.ndarray import NDArray

    def _leaf(x):
        return x._data if isinstance(x, NDArray) else x

    opt = trainer._optimizer
    states = {}
    for idx, s in trainer._updaters[0].states.items():
        states[int(idx)] = jax.tree_util.tree_map(_leaf, s)
    return {
        "params": {k: p.data()._data
                   for k, p in sorted(net.collect_params().items())},
        "opt": states,
        "counts": {int(i): int(c)
                   for i, c in opt._index_update_count.items()},
    }


def _restore_into(net, trainer, tree) -> None:
    """Push a restored :func:`_capture` tree back into the live net +
    trainer (the ``run_elastic(on_restore=)`` hookup): params keep
    their restored placement (``restore(like=)`` already re-placed them
    onto the CURRENT mesh), optimizer state re-wraps as NDArrays, and
    update counts catch up so schedules stay aligned."""
    import jax

    from mxnet_tpu.context import current_context
    from mxnet_tpu.ndarray.ndarray import _wrap

    for k, p in sorted(net.collect_params().items()):
        p.data()._set_data(tree["params"][k])
    upd = trainer._updaters[0]
    for idx, s in tree.get("opt", {}).items():
        upd.states[int(idx)] = jax.tree_util.tree_map(
            lambda x: _wrap(x, current_context()), s)
        upd.states_synced[int(idx)] = True
    opt = trainer._optimizer
    for i, c in tree.get("counts", {}).items():
        opt._index_update_count[int(i)] = int(c)
        opt.num_update = max(opt.num_update, int(c))


def _params_sha(net) -> str:
    import hashlib

    import numpy as onp

    h = hashlib.sha256()
    for k, p in sorted(net.collect_params().items()):
        h.update(k.encode())
        h.update(onp.ascontiguousarray(onp.asarray(p.data()._data)).tobytes())
    return h.hexdigest()


def _flip_param_bit(net, dev_index: int) -> int:
    """Silent-corruption injection: flip ONE mantissa bit of the first
    parameter's replica on mesh device position ``dev_index`` — the
    replicated array is rebuilt from per-device buffers with exactly
    one diverging, so only that physical replica carries the wrong
    bits (what a mis-executing chip or an HBM upset produces).
    Returns the id of the corrupted device."""
    import jax
    import numpy as onp

    _name, p = sorted(net.collect_params().items())[0]
    arr = p.data()._data
    shards = sorted(arr.addressable_shards, key=lambda s: s.device.id)
    bufs, victim = [], None
    for j, sh in enumerate(shards):
        host = onp.asarray(sh.data).copy()
        if j == dev_index % len(shards):
            victim = sh.device.id
            host.view(onp.uint32).ravel()[3] ^= onp.uint32(1 << 20)
        bufs.append(jax.device_put(host, sh.device))
    p.data()._set_data(jax.make_array_from_single_device_arrays(
        arr.shape, arr.sharding, bufs))
    return victim


# ---------------------------------------------------------------------------
# child: train drill
# ---------------------------------------------------------------------------

def _cmd_train(a) -> int:
    t_proc0 = time.monotonic()
    import mxnet_tpu as mx  # noqa: F401  (installs the runtime)
    from mxnet_tpu import engine, gluon, preemption, program_store, telemetry
    from mxnet_tpu.parallel.elastic import CheckpointManager, run_elastic

    net = _drill_net(seed=0)
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, kvstore="tpu")
    step = trainer.compile_step(net, _drill_loss)
    _warm_opt_states(trainer)
    ckpt = CheckpointManager(a.ckpt, keep=20, async_save=True)
    snt = None
    if a.sentinel_every:
        # constructed BEFORE the mesh resolves: a quarantine list
        # persisted by a prior incarnation excludes its suspects from
        # this process's mesh (the restart-time consumption contract)
        from mxnet_tpu import sentinel as _sentinel

        snt = _sentinel.Sentinel(step=step, directory=a.ckpt,
                                 every=a.sentinel_every)
    if a.preempt:
        preemption.install()
    losses_f = open(os.path.join(a.dir, f"losses-{a.label}.txt"), "a",
                    buffering=1)
    progress_f = open(os.path.join(a.dir, f"progress-{a.label}.txt"), "a",
                      buffering=1)

    # one-shot scripted events: after a rollback the replay regenerates
    # the SAME step indices, and a re-fired poison/flip would make the
    # drill diverge forever instead of proving bit-exact recovery
    fired = {"poison": False, "flip": False}

    def _drill_batch(j: int):
        x, y = _host_batch(j)
        if a.poison_at is not None and j == a.poison_at \
                and not fired["poison"]:
            fired["poison"] = True
            y = (y * 1e6).astype(y.dtype)
        return x, y

    # depth-k prefetcher staging batches onto the step's mesh sharding;
    # restarted from the restored index after every restore (the input
    # pipeline is part of what restore-and-replay rebuilds)
    pf = {"it": None, "next": -1}

    def _get_batch(i: int):
        if pf["it"] is None or pf["next"] != i:
            if hasattr(pf["it"], "close"):
                pf["it"].close()
            pf["it"] = engine.prefetch(
                (_drill_batch(j) for j in range(i, a.stop_at)),
                depth=2, sharding=step.batch_sharding)
            pf["next"] = i
        pf["next"] = i + 1
        return next(iter(pf["it"]))

    t_first = [None]
    restored_at = [None]
    restored_sha = [None]
    flipped_dev = [None]

    def step_fn(state, i):
        if a.sigkill_at is not None and i == a.sigkill_at:
            # let the queued async saves land first so the drill's
            # restore point is deterministic — the kill still falls
            # BETWEEN save boundaries (i % save_every != 0)
            ckpt.wait()
            os.kill(os.getpid(), signal.SIGKILL)      # no grace, no drain
        if a.sigterm_at is not None and i == a.sigterm_at \
                and restored_at[0] is None:
            # a real preemption notice, delivered mid-step through the
            # installed handler (the handler runs at the next bytecode)
            os.kill(os.getpid(), signal.SIGTERM)
        if a.bitflip_at is not None and i == a.bitflip_at \
                and not fired["flip"]:
            fired["flip"] = True
            flipped_dev[0] = _flip_param_bit(net, a.bitflip_dev)
        x, y = _get_batch(i)
        loss = step(x, y, batch_size=ROWS)
        lval = float(loss.asnumpy().ravel()[0])
        losses_f.write(f"{i} {lval.hex()}\n")
        progress_f.write(f"{i}\n")
        if t_first[0] is None:
            t_first[0] = time.monotonic()
        if a.delay:
            time.sleep(a.delay)
        return _capture(net, trainer)

    def on_restore(state, s):
        restored_at[0] = s
        _restore_into(net, trainer, state)
        restored_sha[0] = _params_sha(net)   # proves restore == saved
        pf["next"] = -1                 # restart the input pipeline
        return None

    preempted: Optional[int] = None
    steps_run = restarts = None
    try:
        _out, steps_run, restarts = run_elastic(
            step_fn, _capture(net, trainer), range(a.stop_at), ckpt,
            save_every=a.save_every, max_restarts=a.max_restarts,
            on_restore=on_restore, anomaly_fn=snt)
    except preemption.Preempted as e:
        preempted = int(e.code)
    engine.waitall()
    snap = telemetry.snapshot()
    telemetry.flush()       # shard == the snapshot this result records
    mesh = step.mesh
    res = {
        "label": a.label, "pid": os.getpid(),
        "preempted_code": preempted,
        "steps_run": steps_run, "restarts": restarts,
        "restored_at": restored_at[0],
        "restored_params_sha": restored_sha[0],
        "params_sha": _params_sha(net),
        "disk": program_store.disk_stats(),
        "recovery_s": snap.get("elastic.recovery_s"),
        "steps_replayed": snap.get("elastic.steps_replayed"),
        "drain_s": snap.get("preemption.drain_s"),
        "digest_mismatches": snap.get("checkpoint.digest_mismatches"),
        "wall_s": time.monotonic() - t_proc0,
        "first_step_s": (t_first[0] - t_proc0
                         if t_first[0] is not None else None),
        "mesh_devices": ([int(d.id) for d in mesh.devices.flat]
                         if mesh is not None else None),
        "flipped_device": flipped_dev[0],
        "sentinel_digests": snap.get("sentinel.digests"),
        "replica_divergence": snap.get("sentinel.replica_divergence"),
        "rollbacks": snap.get("sentinel.rollbacks"),
        "last_rollback": snt.last_rollback if snt is not None else None,
        "quarantine": (snt.quarantine.entries()
                       if snt is not None else None),
        "corruption_events": telemetry.events(kind="corruption"),
        "telemetry": snap,
    }
    with open(os.path.join(a.dir, f"result-{a.label}.json"), "w") as f:
        json.dump(res, f)
    return preempted or 0


# ---------------------------------------------------------------------------
# child: decode drill
# ---------------------------------------------------------------------------

def _decode_prompt(r: int) -> List[int]:
    return [1 + (r * 7 + j) % 49 for j in range(5 + r % 3)]


def _cmd_decode(a) -> int:
    import threading

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import engine, preemption, telemetry
    from mxnet_tpu.faults import ShedError
    from mxnet_tpu.serving_decode import (GenerativeEngine, PagePool,
                                          TinyCausalLM, eager_generate)

    model = TinyCausalLM(vocab=50, d_model=16, n_layers=1, n_heads=2,
                        max_seq=96)
    params = model.init_params(0)
    pool = PagePool(pages=64, page=8)
    eng = GenerativeEngine(model, params=params, pool=pool, max_rows=2,
                           name="drill")
    eng.warmup(max_len=8)
    if a.preempt:
        preemption.install()
    req_ids = [int(r) for r in a.requests.split(",") if r != ""]
    delivered: Dict[int, List[int]] = {}
    shed: Dict[int, Optional[str]] = {}
    trigger = {"fired": False}
    lock = threading.Lock()

    def worker(r: int):
        try:
            toks = eng.generate(_decode_prompt(r),
                                max_new_tokens=a.max_new)
            with lock:
                delivered[r] = [int(t) for t in toks]
        except ShedError as e:
            with lock:
                shed[r] = getattr(e, "kind", None)
        except BaseException as e:          # pragma: no cover - drill fail
            with lock:
                shed[r] = f"error:{e!r}"
        with lock:
            fire = (a.self_sigterm and not trigger["fired"]
                    and len(delivered) >= 1)
            trigger["fired"] = trigger["fired"] or fire
        if fire:
            # deterministic mid-stream preemption: the FIRST delivery
            # proves decode is rolling, other rows are live, the queue
            # is non-empty — notice now (delivered to the main thread)
            os.kill(os.getpid(), signal.SIGTERM)

    # graftlint: daemon-ok(drill request workers, joined in-scope below
    # before the drill writes its verdict)
    threads = [threading.Thread(target=worker, args=(r,)) for r in req_ids]
    for t in threads:
        t.start()
    preempted: Optional[int] = None
    try:
        for t in threads:
            while t.is_alive():
                t.join(timeout=0.05)     # interruptible by the handler
    except preemption.Preempted as e:
        preempted = int(e.code)
        for t in threads:
            t.join(timeout=30.0)        # drain already completed them
    engine.waitall()
    # token-exact vs the eager oracle on a deterministic subset (the
    # oracle re-runs a FULL eager forward per token — verifying every
    # delivery would dominate the drill's wall clock)
    verify = sorted(delivered)[:2]
    token_exact = all(
        delivered[r] == eager_generate(model, params, _decode_prompt(r),
                                       a.max_new)
        for r in verify)
    snap = telemetry.snapshot()
    telemetry.flush()       # shard == the snapshot this result records
    res = {
        "label": a.label, "preempted_code": preempted,
        "delivered": {str(r): t for r, t in delivered.items()},
        "shed": {str(r): k for r, k in shed.items()},
        "token_exact": token_exact,
        "pool_in_use": pool.in_use(),
        "drain_s": snap.get("preemption.drain_s"),
        "telemetry": snap,
    }
    with open(os.path.join(a.dir, f"result-{a.label}.json"), "w") as f:
        json.dump(res, f)
    return preempted or 0


# ---------------------------------------------------------------------------
# child: router chaos drill (the serving-availability matrix)
# ---------------------------------------------------------------------------

def _router_prompt(r: int) -> List[int]:
    return [1 + (r * 5 + j) % 47 for j in range(4 + r % 4)]


# the prefix-storm system prompt: 3 full page-blocks (page=8) every
# storm request shares, so the fleet's prefill work should scale with
# UNIQUE suffix bytes, not request count
_STORM_SYS = [2 + (j * 11) % 43 for j in range(24)]


def _storm_prompt(r: int) -> List[int]:
    # every 3rd request is byte-identical (full hit); the rest diverge
    # after the shared system prompt (partial hit + COW fork)
    if r % 3 == 0:
        return list(_STORM_SYS)
    return _STORM_SYS + [5 + (r * 7 + j) % 41 for j in range(2 + r % 3)]


def _cmd_router(a) -> int:
    if a.mode in ("scale_storm", "host_loss"):
        return _cmd_router_fleet(a)
    import threading

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import engine, faults, preemption, telemetry
    from mxnet_tpu.faults import ShedError
    from mxnet_tpu.serving_decode import (GenerativeEngine, PagePool,
                                          TinyCausalLM, eager_generate,
                                          high_agreement_pair)
    from mxnet_tpu.serving_router import ReplicaRouter

    spec_kw: Dict[str, Any] = {}
    if a.mode == "spec_draft_poison":
        # ISSUE 19: the speculative cell runs a HIGH-agreement pair so
        # the steady phase demonstrably engages speculation before the
        # draft is poisoned (the knob is uncached; child-local flip)
        os.environ["MXNET_SPEC_DECODE"] = "1"
        model, params, draft, dparams = high_agreement_pair(
            vocab=50, d_model=16, target_layers=2, draft_layers=1,
            n_heads=2, max_seq=96)
        spec_kw = dict(draft=draft, draft_params=dparams, spec_k=4)
    else:
        model = TinyCausalLM(vocab=50, d_model=16, n_layers=1,
                             n_heads=2, max_seq=96)
        params = model.init_params(0)
    pools = [PagePool(pages=64, page=8), PagePool(pages=64, page=8)]
    engines = [GenerativeEngine(model, params=params, pool=pools[i],
                                max_rows=2, name=f"rep{i}", **spec_kw)
               for i in range(2)]
    for e in engines:
        e.warmup(max_len=8)
    router = ReplicaRouter(
        engines, name="drill", breaker_errs=2, breaker_cooldown_s=0.5,
        wedge_s=(1.5 if a.mode == "wedge" else 30.0), hedge_pctl=0)
    if a.preempt:
        preemption.install()
    # the prefix storm routes every request through ONE shared system
    # prompt; the other modes keep their fully distinct prompts
    prompt_of = (_storm_prompt if a.mode == "prefix_storm"
                 else _router_prompt)

    records: Dict[int, Dict[str, Any]] = {}
    lock = threading.Lock()

    def fire(rid: int, deadline_us: Optional[int] = None) -> None:
        t0 = time.monotonic()
        rec: Dict[str, Any] = {
            "budget_s": deadline_us / 1e6 if deadline_us else None}
        try:
            toks = router.generate(prompt_of(rid),
                                   max_new_tokens=a.max_new,
                                   deadline_us=deadline_us)
            rec.update(status="delivered",
                       tokens=[int(t) for t in toks])
        except ShedError as e:
            rec.update(status="shed", kind=getattr(e, "kind", None))
        except BaseException as e:   # pragma: no cover - drill failure
            rec.update(status="error", error=repr(e))
        rec["elapsed_s"] = time.monotonic() - t0
        with lock:
            records[rid] = rec

    # -- phase A: steady state (sequential; also warms the cost table) --
    for rid in range(a.steady):
        fire(rid)
    steady_lat = sorted(records[r]["elapsed_s"] for r in range(a.steady)
                        if records[r]["status"] == "delivered")
    steady_p99_s = (steady_lat[min(len(steady_lat) - 1,
                                   int(len(steady_lat) * 0.99))]
                    if steady_lat else None)

    # -- chaos injection -------------------------------------------------
    orig_gen = engines[0].generate
    flap_calls = {"n": 0}

    class _Boom:
        """Stand-in for replica 0's compiled programs after the 'kill':
        the scheduler's next decode/prefill lookup raises — exactly what
        an engine whose process segment died mid-decode looks like from
        the host thread."""

        def __call__(self, *args, **kw):
            raise RuntimeError("replica 0 killed mid-decode")

    def apply_chaos() -> None:
        if a.mode in ("kill", "prefix_storm"):
            boom = _Boom()
            engines[0]._programs.insert(("decode",), boom)
            for b in (1, 2, 4, 8, 16, 32):
                engines[0]._programs.insert(("prefill", b), boom)
                engines[0]._programs.insert(("prefill_chunk", b), boom)
        elif a.mode == "wedge":
            def wedged(*args, **kw):
                time.sleep(120.0)
                raise RuntimeError("wedged dispatch finally released")
            engines[0].generate = wedged
        elif a.mode == "flap":
            def flaky(*args, **kw):
                flap_calls["n"] += 1
                if flap_calls["n"] <= 4:
                    raise faults.TransientFault(
                        f"flap {flap_calls['n']}")
                return orig_gen(*args, **kw)
            engines[0].generate = flaky
        elif a.mode == "spec_draft_poison":
            # wedge BOTH replicas' draft-round programs: every next
            # spec round raises, the engines must auto-disable via the
            # cost-table path and degrade to plain decode in-place —
            # no failover, no drop, token streams unchanged
            def poisoned(*args, **kw):
                raise RuntimeError("draft model poisoned mid-round")
            for e in engines:
                e._spec_programs.insert(("draft_round", 4), poisoned)

    # -- phase B: chaos under concurrent load ---------------------------
    base = a.steady
    chaos_ids = list(range(base, base + a.requests))
    if a.mode == "deadline_storm":
        # alternating infeasible (3 ms — the cost table prices a
        # max_new-token request far above it) and feasible budgets
        budgets = {rid: (3_000 if i % 2 == 0 else 30_000_000)
                   for i, rid in enumerate(chaos_ids)}
    else:
        budgets = {rid: None for rid in chaos_ids}
    # graftlint: daemon-ok(drill request workers, joined in-scope below
    # before the drill writes its verdict)
    threads = [threading.Thread(target=fire, args=(rid, budgets[rid]))
               for rid in chaos_ids]
    for t in threads:
        t.start()
    if a.mode in ("kill", "prefix_storm"):
        # strike while replica 0 is actively decoding chaos rows: wait
        # for its decode counter to move with live rows (bounded poll).
        # In the prefix storm replica 0 is ALSO the affinity target —
        # it took the first steady request, published the shared
        # prompt, and pulled the whole storm onto its warm pages — so
        # this kill lands on the cache itself.
        d0 = engines[0]._stats["decode_steps"]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if engines[0]._stats["decode_steps"] > d0 and \
                    len(engines[0]._live) > 0:
                break
            time.sleep(0.001)
        apply_chaos()
    elif a.mode in ("wedge", "flap", "spec_draft_poison"):
        apply_chaos()
    for t in threads:
        t.join(timeout=180.0)

    # -- flap: measure breaker re-admission (probe budget) --------------
    re_admit_s = None
    if a.mode == "flap":
        t0 = time.monotonic()
        deadline = t0 + 10.0
        while time.monotonic() < deadline:
            if router.breaker_state(0) == "closed":
                re_admit_s = time.monotonic() - t0
                break
            fire(10_000 + int((time.monotonic() - t0) * 1000))
            time.sleep(0.05)

    # -- kill: the PR-11 preemption leg — the router must still drain ---
    preempted: Optional[int] = None
    drain_ids: List[int] = []
    if a.preempt:
        drain_ids = list(range(20_000, 20_000 + 4))
        fired = {"sig": False}

        def drain_worker(rid: int) -> None:
            fire(rid)
            with lock:
                fire_now = not fired["sig"] and any(
                    records.get(r, {}).get("status") == "delivered"
                    for r in drain_ids if r in records)
                fired["sig"] = fired["sig"] or fire_now
            if fire_now:
                os.kill(os.getpid(), signal.SIGTERM)

        # graftlint: daemon-ok(drill request workers, joined in-scope
        # below before the drill writes its verdict)
        dthreads = [threading.Thread(target=drain_worker, args=(rid,))
                    for rid in drain_ids]
        for t in dthreads:
            t.start()
        try:
            for t in dthreads:
                while t.is_alive():
                    t.join(timeout=0.05)
        except preemption.Preempted as e:
            preempted = int(e.code)
            for t in dthreads:
                t.join(timeout=30.0)
    engine.waitall()

    # token-exactness of every delivered response vs the eager oracle
    # (the drill's model is tiny, so full verification is affordable)
    token_exact = True
    oracle_cache: Dict[int, List[int]] = {}
    for rid, rec in sorted(records.items()):
        if rec["status"] != "delivered":
            continue
        if rid not in oracle_cache:
            oracle_cache[rid] = eager_generate(
                model, params, prompt_of(rid), a.max_new)
        if rec["tokens"] != oracle_cache[rid]:
            token_exact = False
            rec["oracle"] = oracle_cache[rid]

    st = router.stats()
    # ISSUE-16 refcount audit at drain: every page accounted for
    # exactly once (free, cached, or referenced), no index entry
    # pointing at a dead page — 0 leaked AND 0 double-freed
    pool_audit = [m for p in pools for m in p.audit()]
    snap = telemetry.snapshot()
    hit_blocks = int(snap.get("prefix.hit_blocks", 0))
    miss_blocks = int(snap.get("prefix.miss_blocks", 0))
    telemetry.flush()       # shard == the snapshot this result records
    res = {
        "label": a.label, "mode": a.mode, "pid": os.getpid(),
        "preempted_code": preempted,
        "steady_ids": list(range(a.steady)),
        "chaos_ids": chaos_ids,
        "drain_ids": drain_ids,
        "records": {str(k): v for k, v in records.items()},
        "token_exact": token_exact,
        "steady_p99_s": steady_p99_s,
        "re_admit_s": re_admit_s,
        "leaked_pages": sum(p.in_use() for p in pools),
        "pool_audit": pool_audit,
        "prefix_hit_blocks": hit_blocks,
        "prefix_miss_blocks": miss_blocks,
        "prefix_cow_forks": int(snap.get("prefix.cow_forks", 0)),
        "prefix_hit_rate": hit_blocks / max(hit_blocks + miss_blocks, 1),
        "router": {k: v for k, v in st.items() if k != "replicas"},
        "breakers": [r["breaker"] for r in st["replicas"]],
        "spec": [{k: e.stats()[k]
                  for k in ("spec_rounds", "spec_proposed",
                            "spec_accepted", "spec_fallbacks",
                            "spec_disabled")}
                 for e in engines] if spec_kw else None,
        "drain_s": telemetry.snapshot().get("preemption.drain_s"),
        "telemetry": telemetry.snapshot(),
    }
    with open(os.path.join(a.dir, f"result-{a.label}.json"), "w") as f:
        json.dump(res, f)
    return preempted or 0


# ---------------------------------------------------------------------------
# child: one cross-host replica process (ISSUE 17 — the elastic fleet's
# unit of membership)
# ---------------------------------------------------------------------------

def _cmd_replica(a) -> int:
    """Warm a ``GenerativeEngine`` off the shared program cache, serve
    it over ``serving_remote.ReplicaServer``, and wait for retirement:
    a graceful preemption (the router's ``preempt`` op → SIGTERM →
    typed draining sheds → waitall → result JSON → exit 83) or a
    SIGKILL (the host-loss cell: no goodbye at all)."""
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import engine, preemption, program_store, telemetry
    from mxnet_tpu.serving_decode import (GenerativeEngine, PagePool,
                                          TinyCausalLM)
    from mxnet_tpu.serving_remote import ReplicaServer

    model = TinyCausalLM(vocab=50, d_model=16, n_layers=1, n_heads=2,
                         max_seq=96)
    params = model.init_params(0)
    pool = PagePool(pages=64, page=8)
    eng = GenerativeEngine(model, params=params, pool=pool, max_rows=2,
                           name=a.label)
    eng.warmup(max_len=8)       # off <root>/pcache: disk hits only
    preemption.install()
    srv = ReplicaServer(eng, name=a.label).start()
    # the port file is the join handshake, written AFTER warmup — the
    # supervisor's join clock prices the WHOLE boot tax
    tmp = os.path.join(a.dir, f"port-{a.label}.tmp")
    with open(tmp, "w") as f:
        f.write(f"{srv.port}\n")
    os.replace(tmp, os.path.join(a.dir, f"port-{a.label}.txt"))
    t0 = time.monotonic()
    preempted: Optional[int] = None
    try:
        while time.monotonic() - t0 < a.ttl:   # orphan guard
            time.sleep(0.1)
    except preemption.Preempted as e:
        preempted = int(e.code)
    engine.waitall()
    snap = telemetry.snapshot()
    telemetry.flush()       # shard == the snapshot this result records
    res = {
        "label": a.label, "pid": os.getpid(),
        "preempted_code": preempted,
        "disk": program_store.disk_stats(),
        "leaked_pages": pool.in_use(),
        "pool_audit": list(pool.audit()),
        "served": {k: v for k, v in eng.stats().items()
                   if isinstance(v, (int, float))},
        "drain_s": snap.get("preemption.drain_s"),
        "telemetry": snap,
    }
    with open(os.path.join(a.dir, f"result-{a.label}.json"), "w") as f:
        json.dump(res, f)
    return preempted or 0


def _spawn_replica(scen_dir: str, label: str, boot_timeout: float = 120.0
                   ) -> "tuple[subprocess.Popen, int]":
    """Launch a ``replica`` child and wait for its port handshake.
    Returns ``(popen, port)``; the caller owns the process handle.
    Environment is inherited — the fleet shares ``MXNET_PROGRAM_CACHE_DIR``
    (warm joins) and ``MXNET_TELEMETRY_DIR`` (rank-stamped shards) —
    except the platform: a replica is a CPU process (a chip belongs to
    one process, and the spawning drill may hold it)."""
    port_path = os.path.join(scen_dir, f"port-{label}.txt")
    if os.path.exists(port_path):
        os.remove(port_path)
    log = open(os.path.join(scen_dir, f"replica-{label}.log"), "w")
    popen = subprocess.Popen(
        [sys.executable, "-m", "mxnet_tpu.drills", "replica",
         "--dir", scen_dir, "--label", label],
        stdout=log, stderr=subprocess.STDOUT, cwd=_REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    deadline = time.monotonic() + boot_timeout
    while time.monotonic() < deadline:
        if os.path.exists(port_path):
            with open(port_path) as f:
                return popen, int(f.read().strip())
        if popen.poll() is not None:
            raise RuntimeError(f"replica {label} died during boot "
                               f"rc={popen.returncode}")
        time.sleep(0.05)
    popen.kill()
    raise RuntimeError(f"replica {label} never published its port")


def _cmd_router_fleet(a) -> int:
    """The ISSUE-17 elastic-fleet cells.

    ``scale_storm``: a ``FleetSupervisor`` over a 1-replica router under
    bursty load — the autoscaler grows 1 → 3 by spawning ``replica``
    children (JOINING → warm → SERVING, 0 fresh compiles off the shared
    cache), one remote is gracefully preempted WHILE serving (typed
    draining sheds hand queued rows back over the wire), and the
    subsiding burst shrinks the fleet back to 1 where every scale-down
    IS a scheduled graceful preemption (drain → SIGTERM → exit 83).

    ``host_loss``: a 2-replica router (local + remote) has the remote's
    process SIGKILLed mid-storm — every open call fails at once,
    failover redelivers token-exactly on the survivor, the breaker
    opens, and ``kill_to_recovered_s`` is measured for the gate."""
    import threading

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import engine, telemetry
    from mxnet_tpu.faults import ShedError
    from mxnet_tpu.serving_decode import (GenerativeEngine, PagePool,
                                          TinyCausalLM, eager_generate)
    from mxnet_tpu.serving_remote import RemoteReplica
    from mxnet_tpu.serving_router import (FleetSupervisor, ReplicaRouter,
                                          REPLICA_SERVING)

    model = TinyCausalLM(vocab=50, d_model=16, n_layers=1, n_heads=2,
                         max_seq=96)
    params = model.init_params(0)
    pool0 = PagePool(pages=64, page=8)
    local = GenerativeEngine(model, params=params, pool=pool0,
                             max_rows=2, name="rep0")
    # warms <root>/pcache BEFORE any replica spawns: joiners hit disk
    local.warmup(max_len=8)

    def prompt_of(rid: int) -> List[int]:
        # bounded distinct prompts: the eager oracle replays each
        # UNIQUE prompt, so the storm cycles 29 instead of minting
        # hundreds
        return _router_prompt(rid % 29)

    records: Dict[int, Dict[str, Any]] = {}
    lock = threading.Lock()

    def fire(rid: int) -> None:
        t0 = time.monotonic()
        rec: Dict[str, Any] = {}
        try:
            toks = router.generate(prompt_of(rid),
                                   max_new_tokens=a.max_new)
            rec.update(status="delivered",
                       tokens=[int(t) for t in toks])
        except ShedError as e:
            rec.update(status="shed", kind=getattr(e, "kind", None))
        except BaseException as e:   # pragma: no cover - drill failure
            rec.update(status="error", error=repr(e))
        rec["elapsed_s"] = time.monotonic() - t0
        rec["done_at"] = time.monotonic()
        with lock:
            records[rid] = rec

    extra: Dict[str, Any] = {}
    procs: List[Dict[str, Any]] = []

    if a.mode == "host_loss":
        popen, port = _spawn_replica(a.dir, "r1")
        procs.append({"label": "r1", "popen": popen})
        remote = RemoteReplica("127.0.0.1", port, name="r1")
        router = ReplicaRouter([local, remote], name="drill",
                               breaker_errs=2, breaker_cooldown_s=0.5,
                               hedge_pctl=0)
        for rid in range(a.steady):
            fire(rid)
        base = a.steady
        chaos_ids = list(range(base, base + max(a.requests, 10)))
        # graftlint: daemon-ok(drill request workers, joined in-scope
        # below before the drill writes its verdict)
        threads = [threading.Thread(target=fire, args=(rid,))
                   for rid in chaos_ids]
        for t in threads:
            t.start()
        # strike while the remote is actively serving: the router's own
        # in-flight ledger for replica 1, no wire round trip
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if router._replicas[1].in_flight > 0:
                break
            time.sleep(0.002)
        os.kill(popen.pid, signal.SIGKILL)
        t_kill = time.monotonic()
        popen.wait(timeout=30)
        for t in threads:
            t.join(timeout=180.0)
        # recovery = first delivery COMPLETED after the kill: the fleet
        # is answering again (failover absorbed the loss)
        with lock:
            done_after = sorted(
                v["done_at"] - t_kill for v in records.values()
                if v["status"] == "delivered" and v["done_at"] > t_kill)
        extra["kill_to_recovered_s"] = (done_after[0] if done_after
                                        else None)
        # open the corpse's breaker deterministically: concurrent fires
        # (a lone sequential request always picks the idle local
        # replica and the corpse would never be touched again)
        t0p = time.monotonic()
        rid = 30_000
        while (router.breaker_state(1) == "closed"
               and time.monotonic() - t0p < 15.0):
            # graftlint: daemon-ok(drill request workers, joined on the
            # next line)
            burst = [threading.Thread(target=fire, args=(rid + k,))
                     for k in range(4)]
            rid += 4
            for t in burst:
                t.start()
            for t in burst:
                t.join(timeout=60.0)
        chaos_ids = sorted(r for r in records if r >= base)
        remote.close()
        extra["remote_rc"] = popen.returncode

    else:   # scale_storm
        router = ReplicaRouter([local], name="drill", breaker_errs=2,
                               breaker_cooldown_s=0.5, hedge_pctl=0)
        plock = threading.Lock()

        def spawn():
            with plock:
                ent: Dict[str, Any] = {
                    "label": f"r{len(procs) + 1}",
                    "t_spawn": time.monotonic(),
                    "first_served_s": None, "exit_code": None}
                procs.append(ent)
            popen, port = _spawn_replica(a.dir, ent["label"])
            ent["popen"] = popen
            rr = RemoteReplica("127.0.0.1", port, name=ent["label"])
            ent["rr"] = rr
            return rr

        def retire(eng_, index: int) -> None:
            ent = next((e for e in procs if e.get("rr") is eng_), None)
            try:
                eng_.preempt()
            except BaseException:
                pass        # already dead (the preempt-under-load leg)
            if ent is not None and ent.get("popen") is not None:
                try:
                    ent["exit_code"] = ent["popen"].wait(timeout=60)
                except subprocess.TimeoutExpired:
                    ent["popen"].kill()
                    ent["exit_code"] = -9
            eng_.close()

        sup = FleetSupervisor(router, spawn, retire=retire, enabled=True,
                              min_replicas=1, max_replicas=3,
                              cooldown_s=0.3, interval_s=0.05,
                              up_queue=0.75, down_queue=0.05,
                              pool_high=0.95,
                              warmup_kwargs={"max_len": 8})
        sup.start()
        for rid in range(a.steady):
            fire(rid)
        base = a.steady
        # -- the burst: keep ~12 requests in flight until the fleet
        # reaches 3 SERVING replicas and each joiner took traffic ------
        threads: List[threading.Thread] = []
        next_rid = base
        storm_deadline = time.monotonic() + 240.0
        while time.monotonic() < storm_deadline:
            threads = [t for t in threads if t.is_alive()]
            while len(threads) < 12:
                # graftlint: daemon-ok(drill request workers, joined
                # in-scope below before the drill writes its verdict)
                t = threading.Thread(target=fire, args=(next_rid,))
                next_rid += 1
                t.start()
                threads.append(t)
            for r in list(router._replicas):
                if r.index == 0 or r.state != REPLICA_SERVING:
                    continue
                ent = next((e for e in procs
                            if e.get("rr") is r.engine), None)
                if (ent is not None and ent["first_served_s"] is None
                        and r.in_flight > 0):
                    ent["first_served_s"] = round(
                        time.monotonic() - ent["t_spawn"], 3)
            if (router.fleet_stats()["scale_ups"] >= 2
                    and all(e["first_served_s"] is not None
                            for e in procs if e.get("rr"))):
                break
            time.sleep(0.01)
        # -- graceful preemption UNDER LOAD: SIGTERM the youngest remote
        # while rows are queued on it — the queued rows come back as
        # typed draining sheds over the wire and fail over token-exact
        victims = [e for e in procs if e.get("rr") is not None]
        queued_at_preempt = 0
        if victims:
            ent = victims[-1]
            vr = next((r for r in list(router._replicas)
                       if r.engine is ent["rr"]), None)
            deadline = time.monotonic() + 10.0
            while (vr is not None and time.monotonic() < deadline
                   and vr.in_flight < 3):
                time.sleep(0.002)
            queued_at_preempt = vr.in_flight if vr is not None else 0
            try:
                ent["rr"].preempt()
                ent["exit_code"] = ent["popen"].wait(timeout=60)
            except BaseException as e:
                extra["preempt_error"] = repr(e)
        extra["queued_at_preempt"] = queued_at_preempt
        for t in threads:
            t.join(timeout=300.0)
        chaos_ids = list(range(base, next_rid))
        # -- the burst subsided: the supervisor shrinks back to 1, each
        # scale-down a drain → preempt → exit-83 retirement ------------
        down_deadline = time.monotonic() + 120.0
        while time.monotonic() < down_deadline:
            if (router.serving_replicas() == 1
                    and all(e.get("exit_code") is not None
                            for e in procs if e.get("popen"))):
                break
            time.sleep(0.05)
        sup.stop()
        for e in procs:
            e.pop("rr", None)
            e.pop("popen", None)
            e.pop("t_spawn", None)

    engine.waitall()

    # token-exactness of every delivered response vs the eager oracle
    token_exact = True
    oracle_cache: Dict[str, List[int]] = {}
    for rid, rec in sorted(records.items()):
        if rec["status"] != "delivered":
            continue
        key = str(prompt_of(rid))
        if key not in oracle_cache:
            oracle_cache[key] = eager_generate(
                model, params, prompt_of(rid), a.max_new)
        if rec["tokens"] != oracle_cache[key]:
            token_exact = False
            rec["oracle"] = oracle_cache[key]

    st = router.stats()
    remotes = []
    for e in procs:
        rres = _read_result(a.dir, e["label"]) or {}
        remotes.append({
            "label": e["label"],
            "exit_code": e.get("exit_code"),
            "first_served_s": e.get("first_served_s"),
            "preempted_code": rres.get("preempted_code"),
            "fresh_compiles": (rres.get("disk") or {}).get("misses"),
            "disk_hits": (rres.get("disk") or {}).get("hits"),
            "leaked_pages": rres.get("leaked_pages"),
            "pool_audit": rres.get("pool_audit"),
            "shed_draining": (rres.get("served") or {}).get(
                "shed_draining"),
        })
    telemetry.flush()       # shard == the snapshot this result records
    res = {
        "label": a.label, "mode": a.mode, "pid": os.getpid(),
        "preempted_code": None,
        "steady_ids": list(range(a.steady)),
        "chaos_ids": chaos_ids,
        "drain_ids": [],
        "records": {str(k): v for k, v in records.items()},
        "token_exact": token_exact,
        "steady_p99_s": None,
        "leaked_pages": pool0.in_use(),
        "pool_audit": [m for m in pool0.audit()],
        "router": {k: v for k, v in st.items() if k != "replicas"},
        "replica_states": [r["state"] for r in st["replicas"]],
        "breakers": [r["breaker"] for r in st["replicas"]],
        "remotes": remotes,
        "telemetry": telemetry.snapshot(),
        **extra,
    }
    with open(os.path.join(a.dir, f"result-{a.label}.json"), "w") as f:
        json.dump(res, f)
    return 0


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def _drill_telemetry_dir(root: str) -> str:
    """Where this drill's child processes flush their flight-recorder
    shards (ISSUE 15): an outer ``MXNET_TELEMETRY_DIR`` wins so a
    caller's fleet merge sees drill children too; otherwise a per-root
    directory the parent merges for its merged-vs-observed assertions."""
    from mxnet_tpu import config as _config

    return _config.get("MXNET_TELEMETRY_DIR") \
        or os.path.join(root, "telemetry")


def _child_env(root: str, devices: int) -> Dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["MXNET_SPMD_MESH"] = "auto"
    env["MXNET_PROGRAM_CACHE_DIR"] = os.path.join(root, "pcache")
    env["MXNET_PREEMPTION_GRACE_S"] = "60"
    env["MXNET_ENGINE_PREFETCH"] = "2"
    env["MXNET_RETRY_BACKOFF"] = "0.01"
    env["MXNET_ELASTIC_BACKOFF"] = "0"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    for k in ("MXNET_FAULT_PLAN", "MXNET_ENGINE_TYPE",
              "JAX_COMPILATION_CACHE_DIR"):
        env.pop(k, None)
    # children are fleet members: each flushes an atomic per-process
    # telemetry shard (on waitall and on the preemption drain) that the
    # parent folds back with telemetry.merge()
    env["MXNET_TELEMETRY_DIR"] = _drill_telemetry_dir(root)
    return env


def _run_child(argv: List[str], env: Dict[str, str],
               timeout: float = 300.0) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.drills"] + argv,
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_REPO)


def _train_child(root: str, scen_dir: str, label: str, devices: int,
                 stop_at: int = N_STEPS, sigterm_at: Optional[int] = None,
                 sigkill_at: Optional[int] = None, delay: float = 0.0,
                 preempt: bool = False, ckpt_name: str = "ckpt",
                 sentinel_every: int = 0,
                 bitflip_at: Optional[int] = None, bitflip_dev: int = 0,
                 poison_at: Optional[int] = None,
                 timeout: float = 300.0) -> subprocess.CompletedProcess:
    os.makedirs(scen_dir, exist_ok=True)
    argv = ["train", "--dir", scen_dir,
            "--ckpt", os.path.join(scen_dir, ckpt_name),
            "--label", label, "--stop-at", str(stop_at),
            "--save-every", str(SAVE_EVERY), "--delay", str(delay)]
    if sigterm_at is not None:
        argv += ["--sigterm-at", str(sigterm_at)]
    if sigkill_at is not None:
        argv += ["--sigkill-at", str(sigkill_at)]
    if preempt:
        argv += ["--preempt"]
    if sentinel_every:
        argv += ["--sentinel-every", str(sentinel_every)]
    if bitflip_at is not None:
        argv += ["--bitflip-at", str(bitflip_at),
                 "--bitflip-dev", str(bitflip_dev)]
    if poison_at is not None:
        argv += ["--poison-at", str(poison_at)]
    return _run_child(argv, _child_env(root, devices), timeout=timeout)


def _read_losses(scen_dir: str, label: str) -> Dict[int, str]:
    path = os.path.join(scen_dir, f"losses-{label}.txt")
    out: Dict[int, str] = {}
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) == 2:
                out[int(parts[0])] = parts[1]    # later replay wins
    return out


def _read_result(scen_dir: str, label: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(scen_dir, f"result-{label}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _tmp_litter(ckpt_dir: str) -> List[str]:
    if not os.path.isdir(ckpt_dir):
        return []
    return [f for f in os.listdir(ckpt_dir) if f.endswith(".tmp")]


def _ensure_reference(root: str, failures: List[str]) -> Dict[int, str]:
    """The memoized uninterrupted 4-device reference run (shared by
    every train scenario under ``root``; also warms the disk cache)."""
    scen_dir = os.path.join(root, "ref4")
    if _read_result(scen_dir, "ref") is None:
        r = _train_child(root, scen_dir, "ref", devices=4)
        if r.returncode != 0:
            failures.append(
                f"reference run failed rc={r.returncode}: "
                f"{r.stderr[-1500:]}")
            return {}
    losses = _read_losses(scen_dir, "ref")
    if len(losses) != N_STEPS:
        failures.append(
            f"reference run produced {len(losses)}/{N_STEPS} loss lines")
    return losses


def _check_resumed_trajectory(failures: List[str], ref: Dict[int, str],
                              first: Dict[int, str],
                              resumed: Dict[int, str],
                              restored_at: int, what: str) -> int:
    """Merged first-run + resumed losses must equal the reference
    bit-for-bit, and replayed overlap must equal the first run's —
    recovery neither loses, doubles, nor perturbs a step."""
    checked = 0
    for i in range(N_STEPS):
        want = ref.get(i)
        got = resumed.get(i) if i >= restored_at else first.get(i)
        if want is None or got is None:
            failures.append(f"{what}: step {i} missing a loss line")
            continue
        if want != got:
            failures.append(
                f"{what}: step {i} loss {got} != reference {want}")
        checked += 1
    for i, v in resumed.items():
        if i in first and first[i] != v:
            failures.append(
                f"{what}: replayed step {i} diverged from the first "
                f"run ({v} != {first[i]})")
    return checked


def run_drill(name: str, root: str, verbose: bool = False
              ) -> Dict[str, Any]:
    """Run one scenario under ``root`` (shared pcache + reference) and
    return its report: ``ok``, ``failures``, and the measured recovery
    budget (recovery_s / recovery_wall_s / steps_replayed / drain_s /
    fresh_compiles / disk hits)."""
    if name not in SCENARIOS and name not in ROUTER_SCENARIOS:
        raise ValueError(f"unknown drill {name!r} (one of "
                         f"{SCENARIOS + ROUTER_SCENARIOS})")
    os.makedirs(root, exist_ok=True)
    failures: List[str] = []
    report: Dict[str, Any] = {"scenario": name, "root": root}
    t0 = time.monotonic()
    if name in ROUTER_SCENARIOS:
        _drill_router(root, failures, report,
                      mode=(name[len("router_"):]
                            if name.startswith("router_") else name))
    elif name == "decode_drain":
        _drill_decode(root, failures, report)
    else:
        ref = _ensure_reference(root, failures)
        if not failures:
            {"sigterm_drain": _drill_sigterm,
             "sigkill_between_saves": _drill_sigkill,
             "topology_change": _drill_topology,
             "corrupt_latest": _drill_corrupt,
             "bitflip_param": _drill_bitflip,
             "loss_spike": _drill_loss_spike}[name](root, ref, failures,
                                                    report)
    report["ok"] = not failures
    report["failures"] = failures
    report["drill_wall_s"] = round(time.monotonic() - t0, 3)
    if verbose:
        print(json.dumps(report, indent=2, default=str))
    return report


def _resume_budget(report: Dict[str, Any], res: Dict[str, Any]) -> None:
    disk = res.get("disk") or {}
    report.update({
        "recovery_s": res.get("recovery_s"),
        "recovery_wall_s": res.get("first_step_s"),
        "steps_replayed": res.get("steps_replayed"),
        "restored_at": res.get("restored_at"),
        "fresh_compiles": disk.get("misses"),
        "disk_hits": disk.get("hits"),
        "resume_telemetry": res.get("telemetry"),
    })


def _drill_sigterm(root: str, ref: Dict[int, str], failures: List[str],
                   report: Dict[str, Any]) -> None:
    scen = os.path.join(root, "sigterm")
    kill_at = 9                       # mid-step, not on a save boundary
    c1 = _train_child(root, scen, "c1", devices=4, sigterm_at=kill_at,
                      preempt=True)
    res1 = _read_result(scen, "c1") or {}
    want_code = res1.get("preempted_code") or 83
    if c1.returncode != want_code:
        failures.append(
            f"sigterm child exited {c1.returncode}, wanted the "
            f"distinguished code {want_code}: {c1.stderr[-1500:]}")
    report["drain_s"] = res1.get("drain_s")
    report["exit_code_c1"] = c1.returncode
    if res1.get("drain_s") is None or res1.get("drain_s") <= 0:
        failures.append("sigterm drain recorded no preemption.drain_s")
    c2 = _train_child(root, scen, "c2", devices=4)
    if c2.returncode != 0:
        failures.append(f"sigterm resume failed rc={c2.returncode}: "
                        f"{c2.stderr[-1500:]}")
        return
    res2 = _read_result(scen, "c2") or {}
    _resume_budget(report, res2)
    first = _read_losses(scen, "c1")
    # graceful drain checkpointed the LAST COMPLETED step: 0 replay
    # (replay = steps the first process ran past the restore point)
    restored = res2.get("restored_at") or 0
    replay = max(0, (max(first) + 1 if first else 0) - restored)
    report["steps_replayed"] = replay
    if res2.get("restored_at") != kill_at:
        failures.append(
            f"sigterm resume restored step {res2.get('restored_at')}, "
            f"wanted the drained step {kill_at}")
    if replay != 0:
        failures.append(
            f"graceful drain must replay 0 steps, resume replayed "
            f"{replay}")
    if (res2.get("disk") or {}).get("misses") != 0:
        failures.append(
            f"sigterm warm resume performed "
            f"{(res2.get('disk') or {}).get('misses')} fresh compiles "
            "(wanted 0: disk hits only)")
    _check_resumed_trajectory(
        failures, ref, first, _read_losses(scen, "c2"), restored,
        "sigterm")
    report["leaked_tmp"] = _tmp_litter(os.path.join(scen, "ckpt"))
    if report["leaked_tmp"]:
        failures.append(f"sigterm left temp litter {report['leaked_tmp']}")


def _drill_sigkill(root: str, ref: Dict[int, str], failures: List[str],
                   report: Dict[str, Any]) -> None:
    scen = os.path.join(root, "sigkill")
    kill_at = 10                     # 2 past the last periodic save (8)
    c1 = _train_child(root, scen, "c1", devices=4, sigkill_at=kill_at)
    if c1.returncode != -signal.SIGKILL:
        failures.append(
            f"sigkill child exited {c1.returncode}, wanted "
            f"{-signal.SIGKILL}")
    report["exit_code_c1"] = c1.returncode
    c2 = _train_child(root, scen, "c2", devices=4)
    if c2.returncode != 0:
        failures.append(f"sigkill resume failed rc={c2.returncode}: "
                        f"{c2.stderr[-1500:]}")
        return
    res2 = _read_result(scen, "c2") or {}
    _resume_budget(report, res2)
    first = _read_losses(scen, "c1")
    restored = res2.get("restored_at") or 0
    replay = max(0, (max(first) + 1 if first else 0) - restored)
    report["steps_replayed"] = replay
    expect_restore = kill_at - (kill_at % SAVE_EVERY)
    if res2.get("restored_at") != expect_restore:
        failures.append(
            f"sigkill resume restored step {res2.get('restored_at')}, "
            f"wanted the last complete save {expect_restore}")
    if replay != kill_at - expect_restore:
        failures.append(
            f"sigkill resume replayed {replay} steps, wanted "
            f"{kill_at - expect_restore} (the save gap)")
    if (res2.get("disk") or {}).get("misses") != 0:
        failures.append(
            f"sigkill warm resume performed "
            f"{(res2.get('disk') or {}).get('misses')} fresh compiles "
            "(wanted 0: disk hits only)")
    _check_resumed_trajectory(
        failures, ref, first, _read_losses(scen, "c2"), restored,
        "sigkill")
    report["leaked_tmp"] = _tmp_litter(os.path.join(scen, "ckpt"))
    if report["leaked_tmp"]:
        failures.append(f"sigkill left temp litter {report['leaked_tmp']}")


def _drill_topology(root: str, ref: Dict[int, str], failures: List[str],
                    report: Dict[str, Any]) -> None:
    scen = os.path.join(root, "topology")
    c1 = _train_child(root, scen, "c1", devices=4, stop_at=HALF)
    if c1.returncode != 0:
        failures.append(f"topology 4-dev leg failed rc={c1.returncode}: "
                        f"{c1.stderr[-1500:]}")
        return
    res1 = _read_result(scen, "c1") or {}
    losses = {}
    import shutil

    for label in ("c2", "c2b"):       # the pair: determinism + warm cache
        # each resume gets its OWN copy of the 4-device checkpoint dir
        # (a shared dir would let c2's later saves turn c2b's restore
        # into a no-op)
        ckpt_name = f"ckpt-{label}"
        dst = os.path.join(scen, ckpt_name)
        if os.path.isdir(dst):
            shutil.rmtree(dst)
        shutil.copytree(os.path.join(scen, "ckpt"), dst)
        r = _train_child(root, scen, label, devices=2,
                         ckpt_name=ckpt_name)
        if r.returncode != 0:
            failures.append(
                f"topology 2-dev resume {label} failed "
                f"rc={r.returncode}: {r.stderr[-1500:]}")
            return
        losses[label] = _read_losses(scen, label)
    res2 = _read_result(scen, "c2") or {}
    res2b = _read_result(scen, "c2b") or {}
    _resume_budget(report, res2b)     # the WARM-cache recovery numbers
    if res2.get("restored_at") != HALF:
        failures.append(
            f"topology resume restored step {res2.get('restored_at')}, "
            f"wanted {HALF}")
    # bit-exact re-placement: the digest over the params RESTORED onto
    # the 2-device mesh must equal the 4-device saver's final params
    if res2.get("restored_params_sha") != res1.get("params_sha"):
        failures.append(
            "topology restore(like=) onto the 2-device mesh did not "
            "reproduce the 4-device params bit-exactly "
            f"({res2.get('restored_params_sha')} != "
            f"{res1.get('params_sha')})")
    if res2.get("params_sha") != res2b.get("params_sha"):
        failures.append("topology determinism pair diverged in final "
                        "params (recovery is not deterministic)")
    if losses["c2"] != losses["c2b"]:
        failures.append("topology determinism pair diverged in losses")
    # cross-mesh trajectory: tracks the 4-dev reference within tolerance
    for i in range(HALF, N_STEPS):
        w = ref.get(i)
        g = losses["c2"].get(i)
        if w is None or g is None:
            failures.append(f"topology: step {i} missing a loss line")
            continue
        wf, gf = float.fromhex(w), float.fromhex(g)
        if abs(wf - gf) > TOPO_RTOL * max(1.0, abs(wf)):
            failures.append(
                f"topology: step {i} loss {gf} drifted past rtol "
                f"{TOPO_RTOL} from the 4-dev reference {wf}")
    # warm persistent cache: the SECOND 2-dev resume recompiles nothing
    fresh = (res2b.get("disk") or {}).get("misses")
    if fresh != 0:
        failures.append(
            f"topology warm resume performed {fresh} fresh compiles "
            "(wanted 0 — every program from MXNET_PROGRAM_CACHE_DIR)")
    report["params_sha_c1"] = res1.get("params_sha")


def _drill_corrupt(root: str, ref: Dict[int, str], failures: List[str],
                   report: Dict[str, Any]) -> None:
    scen = os.path.join(root, "corrupt")
    c1 = _train_child(root, scen, "c1", devices=4, stop_at=HALF)
    if c1.returncode != 0:
        failures.append(f"corrupt setup leg failed rc={c1.returncode}: "
                        f"{c1.stderr[-1500:]}")
        return
    # flip one payload byte of the NEWEST checkpoint; its sha256 sidecar
    # now disagrees even though the pickle may still load
    ckpt_dir = os.path.join(scen, "ckpt")
    target = os.path.join(ckpt_dir, f"ckpt-{HALF}.pkl")
    with open(target, "r+b") as f:
        f.seek(-7, os.SEEK_END)
        b = f.read(1)
        f.seek(-7, os.SEEK_END)
        f.write(bytes([b[0] ^ 0xFF]))
    c2 = _train_child(root, scen, "c2", devices=4)
    if c2.returncode != 0:
        failures.append(f"corrupt resume failed rc={c2.returncode}: "
                        f"{c2.stderr[-1500:]}")
        return
    res2 = _read_result(scen, "c2") or {}
    _resume_budget(report, res2)
    first = _read_losses(scen, "c1")
    restored = res2.get("restored_at") or 0
    report["steps_replayed"] = max(
        0, (max(first) + 1 if first else 0) - restored)
    expect = HALF - SAVE_EVERY
    if res2.get("restored_at") != expect:
        failures.append(
            f"corrupt resume restored step {res2.get('restored_at')}, "
            f"wanted degradation to the previous complete step {expect}")
    if not res2.get("digest_mismatches"):
        failures.append("corrupt resume counted no "
                        "checkpoint.digest_mismatches")
    _check_resumed_trajectory(
        failures, ref, _read_losses(scen, "c1"), _read_losses(scen, "c2"),
        res2.get("restored_at") or 0, "corrupt")


def _merged_losses_vs_reference(failures: List[str], ref: Dict[int, str],
                                merged: Dict[int, str],
                                what: str) -> None:
    """An in-process rollback drill writes BOTH the tainted and the
    replayed loss lines to one file; last-line-wins merging must equal
    the uninterrupted reference bit-for-bit (rollback healed the run)."""
    for i in range(N_STEPS):
        want, got = ref.get(i), merged.get(i)
        if want is None or got is None:
            failures.append(f"{what}: step {i} missing a loss line")
        elif want != got:
            failures.append(
                f"{what}: post-rollback step {i} loss {got} != "
                f"reference {want}")


def _drill_bitflip(root: str, ref: Dict[int, str], failures: List[str],
                   report: Dict[str, Any]) -> None:
    """Silent corruption end-to-end: one flipped bit on one replica ->
    vote localizes the device -> rollback -> bit-exact resume ->
    restart excludes the quarantined device from the mesh."""
    scen = os.path.join(root, "bitflip")
    flip_at, flip_dev = 13, 2          # mid save-window, device pos 2
    c1 = _train_child(root, scen, "c1", devices=4,
                      sentinel_every=SAVE_EVERY,
                      bitflip_at=flip_at, bitflip_dev=flip_dev)
    if c1.returncode != 0:
        failures.append(f"bitflip child failed rc={c1.returncode}: "
                        f"{c1.stderr[-1500:]}")
        return
    res1 = _read_result(scen, "c1") or {}
    _resume_budget(report, res1)       # the in-process rollback budget
    report["steps_replayed"] = res1.get("steps_replayed")
    report["flipped_device"] = res1.get("flipped_device")
    report["quarantine"] = res1.get("quarantine")
    victim = res1.get("flipped_device")
    if res1.get("restarts") != 1:
        failures.append(
            f"bitflip run took {res1.get('restarts')} restarts, wanted "
            "exactly 1 (the sentinel rollback)")
    if not res1.get("replica_divergence"):
        failures.append("bitflip vote counted no "
                        "sentinel.replica_divergence")
    if not res1.get("rollbacks"):
        failures.append("bitflip counted no sentinel.rollbacks")
    named = {e.get("device") for e in res1.get("corruption_events") or []
             if e.get("name") == "sentinel"}
    if victim not in named:
        failures.append(
            f"bitflip corruption events named devices {sorted(named)}, "
            f"not the corrupted device {victim}")
    q = res1.get("quarantine") or []
    if victim not in [e["id"] for e in q if e["kind"] == "device"]:
        failures.append(
            f"bitflip quarantine {q} does not hold device {victim}")
    # detection within one sentinel cadence: the rollback's restore
    # point + replay gap locate the verdict step
    restored = res1.get("restored_at")
    detected = (restored or 0) + (res1.get("steps_replayed") or 0)
    if restored != flip_at - (flip_at % SAVE_EVERY):
        failures.append(
            f"bitflip restored step {restored}, wanted the last "
            f"verified save {flip_at - (flip_at % SAVE_EVERY)}")
    if not (0 < detected - flip_at <= SAVE_EVERY):
        failures.append(
            f"bitflip detected at step {detected}, flip at {flip_at} — "
            f"outside one sentinel cadence ({SAVE_EVERY})")
    # rollback healed the run: merged losses == the uninterrupted
    # reference bit-for-bit (the flip and the tainted steps left no
    # trace), at 0 fresh compiles (the ref leg warmed the disk cache;
    # rollback replays reuse the SAME program)
    _merged_losses_vs_reference(
        failures, ref, _read_losses(scen, "c1"), "bitflip")
    if (res1.get("disk") or {}).get("misses") != 0:
        failures.append(
            f"bitflip rollback performed "
            f"{(res1.get('disk') or {}).get('misses')} fresh compiles "
            "(wanted 0: same mesh, same program)")
    # restart: the persisted quarantine re-resolves the mesh WITHOUT
    # the suspect (the PR-11 topology machinery, triggered
    # automatically); run a few extra steps on the smaller mesh
    c2 = _train_child(root, scen, "c2", devices=4,
                      sentinel_every=SAVE_EVERY, stop_at=N_STEPS + 6)
    if c2.returncode != 0:
        failures.append(f"bitflip quarantined restart failed "
                        f"rc={c2.returncode}: {c2.stderr[-1500:]}")
        return
    res2 = _read_result(scen, "c2") or {}
    mesh2 = res2.get("mesh_devices")
    report["restart_mesh_devices"] = mesh2
    if mesh2 is None or len(mesh2) != 3 or victim in mesh2:
        failures.append(
            f"bitflip restart resolved mesh {mesh2}; wanted 3 devices "
            f"excluding the quarantined device {victim}")
    if res2.get("restored_at") != N_STEPS:
        failures.append(
            f"bitflip restart restored step {res2.get('restored_at')}, "
            f"wanted {N_STEPS} (resume onto the quarantined mesh)")
    if res2.get("steps_run") != N_STEPS + 6:
        failures.append(
            f"bitflip restart ran {res2.get('steps_run')} steps, "
            f"wanted {N_STEPS + 6}")


def _drill_loss_spike(root: str, ref: Dict[int, str],
                      failures: List[str],
                      report: Dict[str, Any]) -> None:
    """Scripted poisoned batch: the z-score window trips at the next
    checkpoint boundary (the tainted state is never saved), rollback
    replays exactly the save-interval gap, merged trajectory bit-exact."""
    scen = os.path.join(root, "spike")
    poison_at = 13
    c1 = _train_child(root, scen, "c1", devices=4,
                      sentinel_every=SAVE_EVERY, poison_at=poison_at)
    if c1.returncode != 0:
        failures.append(f"loss_spike child failed rc={c1.returncode}: "
                        f"{c1.stderr[-1500:]}")
        return
    res1 = _read_result(scen, "c1") or {}
    _resume_budget(report, res1)
    report["steps_replayed"] = res1.get("steps_replayed")
    report["last_rollback"] = res1.get("last_rollback")
    if res1.get("restarts") != 1:
        failures.append(
            f"loss_spike took {res1.get('restarts')} restarts, wanted "
            "exactly 1 (the windowed rollback)")
    if not res1.get("rollbacks"):
        failures.append("loss_spike counted no sentinel.rollbacks")
    if res1.get("replica_divergence"):
        failures.append(
            "loss_spike counted replica divergence — a poisoned batch "
            "perturbs every replica identically; the vote must stay "
            "unanimous")
    reason = (res1.get("last_rollback") or {}).get("reason")
    if reason not in ("grad_norm_anomaly", "loss_anomaly"):
        failures.append(
            f"loss_spike rollback reason {reason!r}, wanted the "
            "windowed z-score detector")
    expect_restore = poison_at - (poison_at % SAVE_EVERY)
    if res1.get("restored_at") != expect_restore:
        failures.append(
            f"loss_spike restored step {res1.get('restored_at')}, "
            f"wanted the last pre-poison save {expect_restore}")
    if res1.get("steps_replayed") != SAVE_EVERY:
        failures.append(
            f"loss_spike replayed {res1.get('steps_replayed')} steps, "
            f"wanted exactly the save-window gap {SAVE_EVERY}")
    _merged_losses_vs_reference(
        failures, ref, _read_losses(scen, "c1"), "loss_spike")
    if (res1.get("disk") or {}).get("misses") != 0:
        failures.append(
            f"loss_spike rollback performed "
            f"{(res1.get('disk') or {}).get('misses')} fresh compiles "
            "(wanted 0)")


def _drill_decode(root: str, failures: List[str],
                  report: Dict[str, Any]) -> None:
    scen = os.path.join(root, "decode")
    os.makedirs(scen, exist_ok=True)
    req_ids = list(range(8))
    argv = ["decode", "--dir", scen, "--label", "c1", "--preempt",
            "--self-sigterm", "--max-new", "12",
            "--requests", ",".join(map(str, req_ids))]
    c1 = _run_child(argv, _child_env(root, 1))
    res1 = _read_result(scen, "c1") or {}
    code = res1.get("preempted_code") or 83
    report["exit_code_c1"] = c1.returncode
    report["drain_s"] = res1.get("drain_s")
    if c1.returncode != code:
        failures.append(
            f"decode child exited {c1.returncode}, wanted the "
            f"distinguished code {code}: {c1.stderr[-1500:]}")
        return
    delivered = {int(k): v for k, v in (res1.get("delivered") or {}).items()}
    shed = {int(k): v for k, v in (res1.get("shed") or {}).items()}
    if set(delivered) | set(shed) != set(req_ids):
        failures.append(
            f"decode drain lost requests: delivered {sorted(delivered)} "
            f"+ shed {sorted(shed)} != {req_ids}")
    if not delivered:
        failures.append("decode drain delivered nothing before the "
                        "notice (self-trigger broken)")
    if not shed:
        failures.append("decode drain shed nothing — the queue was "
                        "empty at the notice (drill not mid-stream)")
    bad_kinds = {r: k for r, k in shed.items() if k != "draining"}
    if bad_kinds:
        failures.append(f"decode sheds were not typed 'draining': "
                        f"{bad_kinds}")
    if not res1.get("token_exact"):
        failures.append("decode in-flight completions were not "
                        "token-exact vs the eager oracle")
    if res1.get("pool_in_use") != 0:
        failures.append(
            f"decode drain leaked {res1.get('pool_in_use')} KV pages")
    report["leaked_pages"] = res1.get("pool_in_use")
    # restart: the shed requests re-queue on a fresh process, token-exact
    if shed:
        argv = ["decode", "--dir", scen, "--label", "c2",
                "--max-new", "12",
                "--requests", ",".join(str(r) for r in sorted(shed))]
        c2 = _run_child(argv, _child_env(root, 1))
        res2 = _read_result(scen, "c2") or {}
        if c2.returncode != 0:
            failures.append(f"decode re-queue leg failed "
                            f"rc={c2.returncode}: {c2.stderr[-1500:]}")
            return
        redone = {int(k) for k in (res2.get("delivered") or {})}
        if redone != set(shed):
            failures.append(
                f"decode re-queue delivered {sorted(redone)} != shed "
                f"{sorted(shed)}")
        if not res2.get("token_exact"):
            failures.append("decode re-queued requests were not "
                            "token-exact")
        if res2.get("pool_in_use") != 0:
            failures.append("decode re-queue leg leaked pages")


def _check_child_shard(root: str, failures: List[str],
                       report: Dict[str, Any], res: Dict[str, Any],
                       what: str, counters: Dict[str, Any]) -> None:
    """Fold the drill's telemetry shards (``telemetry.merge``) and pin
    the named counters of the child's OWN shard against the totals the
    child reported in its result JSON — the cross-process aggregation
    path proven against ground truth the parent already holds."""
    from mxnet_tpu import telemetry as _tel

    tel_dir = _drill_telemetry_dir(root)
    if not os.path.isdir(tel_dir):
        failures.append(f"{what}: no telemetry shard dir at {tel_dir}")
        return
    merged = _tel.merge(tel_dir)
    report["telemetry_shards"] = len(merged["shards"])
    pid = res.get("pid")
    proc = next((p for p in merged["processes"] if p["pid"] == pid), None)
    if proc is None:
        failures.append(
            f"{what}: no telemetry shard for child pid {pid} "
            f"(shards: {merged['shards']})")
        return
    shard = _tel._read_shard(os.path.join(tel_dir, proc["shard"]))
    snap = (shard["snapshot"] or {}).get("counters", {})
    for name, want in counters.items():
        got = snap.get(name)
        if want is not None and got != want:
            failures.append(
                f"{what}: merged shard counter {name}={got} != "
                f"child-observed {want}")
    # and the FLEET fold can only ever hold at least the child's total
    for name, want in counters.items():
        fleet = merged["counters"].get(name)
        if want is not None and fleet is not None and fleet < want:
            failures.append(
                f"{what}: fleet-merged {name}={fleet} < child's {want}")


def _drill_router(root: str, failures: List[str],
                  report: Dict[str, Any], mode: str) -> None:
    """One cell of the serving chaos matrix: a 2-replica router child
    under {kill | wedge | flap | deadline_storm | prefix_storm}.  The
    availability contract every cell shares: 0 dropped requests (every
    submission ends delivered or typed-shed), every delivery
    token-exact vs the eager oracle, 0 leaked KV pages, and a clean
    page-pool refcount audit at drain (ISSUE 16: no page leaked,
    double-freed, or indexed while dead)."""
    scen = os.path.join(root, f"router-{mode}")
    os.makedirs(scen, exist_ok=True)
    argv = ["router", "--dir", scen, "--label", "c1", "--mode", mode,
            "--steady", "12", "--requests", "8", "--max-new", "10"]
    if mode in ("kill", "prefix_storm"):
        argv += ["--preempt"]
    # the fleet cells spawn replica subprocesses (a JAX boot each):
    # give the child a longer leash than the in-process cells
    timeout = 600.0 if mode in ("scale_storm", "host_loss") else 300.0
    c1 = _run_child(argv, _child_env(root, 1), timeout=timeout)
    res = _read_result(scen, "c1") or {}
    report["exit_code_c1"] = c1.returncode
    want_code = ((res.get("preempted_code") or 83)
                 if mode in ("kill", "prefix_storm") else 0)
    if c1.returncode != want_code:
        failures.append(
            f"router[{mode}] child exited {c1.returncode}, wanted "
            f"{want_code}: {c1.stderr[-1500:]}")
        return
    records = {int(k): v for k, v in (res.get("records") or {}).items()}
    submitted = (len(res.get("steady_ids") or [])
                 + len(res.get("chaos_ids") or [])
                 + len(res.get("drain_ids") or []))
    # 0 dropped: every request the child submitted has a typed outcome
    errors = {r: v for r, v in records.items() if v["status"] == "error"}
    if errors:
        failures.append(
            f"router[{mode}] requests errored instead of "
            f"delivering/shedding: {errors}")
    known = sum(1 for v in records.values()
                if v["status"] in ("delivered", "shed"))
    if len(records) < submitted:
        failures.append(
            f"router[{mode}] dropped requests: {len(records)} outcomes "
            f"for {submitted} submissions")
    report["dropped"] = max(0, submitted - known)
    if not res.get("token_exact"):
        failures.append(
            f"router[{mode}] delivered responses were not token-exact "
            "vs the eager oracle (failover/hedge broke greedy "
            "idempotence)")
    if res.get("leaked_pages"):
        failures.append(
            f"router[{mode}] leaked {res['leaked_pages']} KV pages")
    report["leaked_pages"] = res.get("leaked_pages")
    if res.get("pool_audit"):
        failures.append(
            f"router[{mode}] page-pool refcount audit failed at drain: "
            f"{res['pool_audit']}")
    rt = res.get("router") or {}
    # ISSUE-15 fleet aggregation: the child flushed an atomic telemetry
    # shard; merging it back must reproduce the failover/shed/delivered
    # totals the parent observed in the child's own result record —
    # cross-process counters survive the round trip exactly
    _check_child_shard(root, failures, report, res, what=f"router[{mode}]",
                       counters={
                           "serving.router0.failovers": rt.get("failovers"),
                           "serving.router0.sheds": rt.get("sheds"),
                           "serving.router0.delivered": rt.get("delivered"),
                       })
    chaos = [records[r] for r in (res.get("chaos_ids") or [])
             if r in records]
    chaos_lat = sorted(v["elapsed_s"] for v in chaos
                       if v["status"] == "delivered")
    report["steady_p99_s"] = res.get("steady_p99_s")
    report["chaos_p99_s"] = (
        chaos_lat[min(len(chaos_lat) - 1, int(len(chaos_lat) * 0.99))]
        if chaos_lat else None)
    report["failovers"] = rt.get("failovers")
    report["hedges"] = rt.get("hedges")
    report["breaker_opens"] = rt.get("breaker_opens")
    report["breaker_closes"] = rt.get("breaker_closes")
    report["re_admit_s"] = res.get("re_admit_s")
    report["drain_s"] = res.get("drain_s")

    if mode == "kill":
        if not rt.get("failovers"):
            failures.append("router[kill] counted no failovers — the "
                            "dead replica's requests were not re-routed")
        if not rt.get("breaker_opens"):
            failures.append("router[kill] never opened the dead "
                            "replica's breaker")
        drain_recs = [records[r] for r in (res.get("drain_ids") or [])
                      if r in records]
        bad = [v for v in drain_recs
               if v["status"] == "shed" and v.get("kind") != "draining"]
        if bad:
            failures.append(
                f"router[kill] drain-phase sheds were not typed "
                f"'draining': {bad}")
        if res.get("drain_s") is None:
            failures.append("router[kill] preemption drain recorded no "
                            "preemption.drain_s — waitall did not drain "
                            "the router")
    elif mode == "wedge":
        if not rt.get("wedged"):
            failures.append("router[wedge] never declared the wedged "
                            "dispatch (heartbeat eviction broken)")
        if not rt.get("failovers"):
            failures.append("router[wedge] counted no failovers")
    elif mode == "flap":
        if not rt.get("breaker_opens"):
            failures.append("router[flap] flap burst never opened the "
                            "breaker")
        if not rt.get("breaker_closes"):
            failures.append("router[flap] breaker never closed again "
                            "(half-open probe re-admission broken)")
        if res.get("re_admit_s") is None:
            failures.append("router[flap] re-admission never observed")
    elif mode == "prefix_storm":
        # ISSUE 16: shared-prefix storm + replica kill.  The affinity
        # weight converged the storm onto replica 0's warm cache, so the
        # kill lands on exactly the replica holding the shared pages —
        # failover must rebuild the prefix cold on replica 1 with zero
        # refcount damage.
        if not rt.get("failovers"):
            failures.append("router[prefix_storm] counted no failovers — "
                            "the warm replica's requests were not "
                            "re-routed after the kill")
        if not rt.get("breaker_opens"):
            failures.append("router[prefix_storm] never opened the dead "
                            "replica's breaker")
        if not res.get("prefix_hit_blocks"):
            failures.append(
                "router[prefix_storm] counted 0 prefix.hit_blocks — the "
                "shared system prompt never hit the content-addressed "
                "cache (affinity or publish broken)")
        drain_recs = [records[r] for r in (res.get("drain_ids") or [])
                      if r in records]
        bad = [v for v in drain_recs
               if v["status"] == "shed" and v.get("kind") != "draining"]
        if bad:
            failures.append(
                f"router[prefix_storm] drain-phase sheds were not typed "
                f"'draining': {bad}")
        report["prefix_hit_blocks"] = res.get("prefix_hit_blocks")
        report["prefix_miss_blocks"] = res.get("prefix_miss_blocks")
        report["prefix_hit_rate"] = res.get("prefix_hit_rate")
        report["prefix_cow_forks"] = res.get("prefix_cow_forks")
    elif mode == "scale_storm":
        fleet = (rt.get("fleet") or {})
        remotes = res.get("remotes") or []
        report["fleet"] = fleet
        report["remotes"] = remotes
        report["join_to_first_served_s"] = max(
            (r["first_served_s"] for r in remotes
             if r.get("first_served_s") is not None), default=None)
        if fleet.get("scale_ups", 0) < 2:
            failures.append(
                f"router[scale_storm] autoscaler counted "
                f"{fleet.get('scale_ups')} scale_ups, wanted >=2 "
                "(the fleet never reached 3 replicas)")
        if fleet.get("scale_downs", 0) < 2:
            failures.append(
                f"router[scale_storm] autoscaler counted "
                f"{fleet.get('scale_downs')} scale_downs, wanted >=2 "
                "(the fleet never shrank back)")
        if fleet.get("drains", 0) < 2:
            failures.append(
                "router[scale_storm] scale-down skipped the graceful "
                f"drain ({fleet.get('drains')} drains for "
                f"{fleet.get('scale_downs')} scale_downs)")
        states = res.get("replica_states") or []
        if sum(1 for s in states if s == "serving") != 1:
            failures.append(
                f"router[scale_storm] fleet did not settle back to 1 "
                f"SERVING replica: {states}")
        for r in remotes:
            if r.get("exit_code") != 83:
                failures.append(
                    f"router[scale_storm] remote {r.get('label')} "
                    f"exited {r.get('exit_code')}, wanted the "
                    "distinguished preemption code 83")
            if r.get("fresh_compiles"):
                failures.append(
                    f"router[scale_storm] remote {r.get('label')} "
                    f"performed {r['fresh_compiles']} fresh compiles "
                    "(wanted 0: warm join off the shared program cache)")
            if r.get("leaked_pages"):
                failures.append(
                    f"router[scale_storm] remote {r.get('label')} "
                    f"leaked {r['leaked_pages']} KV pages")
            if r.get("pool_audit"):
                failures.append(
                    f"router[scale_storm] remote {r.get('label')} "
                    f"pool audit failed: {r['pool_audit']}")
            if r.get("first_served_s") is None:
                failures.append(
                    f"router[scale_storm] remote {r.get('label')} "
                    "joined but never served a request")
        if res.get("queued_at_preempt", 0) > 2:
            sheds = sum(int(r.get("shed_draining") or 0)
                        for r in remotes)
            if not sheds:
                failures.append(
                    "router[scale_storm] preempt-under-load had "
                    f"{res['queued_at_preempt']} rows queued on the "
                    "victim but no typed draining shed came back over "
                    "the wire (the handback path never ran)")
    elif mode == "host_loss":
        report["kill_to_recovered_s"] = res.get("kill_to_recovered_s")
        if not rt.get("failovers"):
            failures.append(
                "router[host_loss] counted no failovers — the killed "
                "host's requests were not re-routed")
        if res.get("kill_to_recovered_s") is None:
            failures.append(
                "router[host_loss] never delivered a request after the "
                "SIGKILL (the fleet did not recover)")
        if not rt.get("breaker_opens"):
            failures.append(
                "router[host_loss] never opened the dead host's "
                "breaker")
    elif mode == "spec_draft_poison":
        # ISSUE 19: a poisoned draft must cost ZERO availability — the
        # engines auto-disable speculation via the cost-table path and
        # degrade to plain decode in-place; the shared contract above
        # (0 dropped, token-exact, clean audit) already holds, so the
        # cell-specific checks are about the disable machinery itself
        spec = res.get("spec") or []
        report["spec"] = spec
        report["spec_autodisabled"] = int(
            (res.get("telemetry") or {}).get("spec.autodisabled", 0))
        if not any(s.get("spec_rounds") for s in spec):
            failures.append(
                "router[spec_draft_poison] steady phase never engaged "
                "speculation (0 spec rounds before the poison — the "
                "cell exercised nothing)")
        if not all(s.get("spec_disabled") for s in spec):
            failures.append(
                "router[spec_draft_poison] a poisoned replica did not "
                f"auto-disable speculation: {spec}")
        if report["spec_autodisabled"] < 1:
            failures.append(
                "router[spec_draft_poison] no spec.autodisabled event "
                "was counted despite the poisoned draft")
    elif mode == "deadline_storm":
        for r, v in sorted(records.items()):
            b = v.get("budget_s")
            if b is None:
                continue
            if b < 0.01:                      # the infeasible budgets
                if v["status"] != "shed" or v.get("kind") != "deadline":
                    failures.append(
                        f"router[deadline_storm] request {r} with a "
                        f"{b * 1e6:.0f}us budget ended "
                        f"{v['status']}:{v.get('kind')} (wanted a "
                        "typed 'deadline' shed)")
                if v["elapsed_s"] > b + 1.0:
                    failures.append(
                        f"router[deadline_storm] request {r} consumed "
                        f"{v['elapsed_s']:.3f}s against a "
                        f"{b:.3f}s budget (+1s slack) — the deadline "
                        "did not bound the wait")
            elif v["status"] != "delivered":
                failures.append(
                    f"router[deadline_storm] feasible request {r} "
                    f"ended {v['status']}:{v.get('kind')}")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="mxnet_tpu.drills",
                                description=__doc__.splitlines()[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="train-drill child")
    t.add_argument("--dir", required=True)
    t.add_argument("--ckpt", required=True)
    t.add_argument("--label", default="c1")
    t.add_argument("--stop-at", type=int, default=N_STEPS,
                   dest="stop_at")
    t.add_argument("--save-every", type=int, default=SAVE_EVERY,
                   dest="save_every")
    t.add_argument("--max-restarts", type=int, default=3,
                   dest="max_restarts")
    t.add_argument("--delay", type=float, default=0.0)
    t.add_argument("--sigterm-at", type=int, default=None,
                   dest="sigterm_at")
    t.add_argument("--sigkill-at", type=int, default=None,
                   dest="sigkill_at")
    t.add_argument("--preempt", action="store_true")
    t.add_argument("--sentinel-every", type=int, default=0,
                   dest="sentinel_every")
    t.add_argument("--bitflip-at", type=int, default=None,
                   dest="bitflip_at")
    t.add_argument("--bitflip-dev", type=int, default=0,
                   dest="bitflip_dev")
    t.add_argument("--poison-at", type=int, default=None,
                   dest="poison_at")

    d = sub.add_parser("decode", help="decode-drill child")
    d.add_argument("--dir", required=True)
    d.add_argument("--label", default="c1")
    d.add_argument("--requests", default="0,1,2,3")
    d.add_argument("--max-new", type=int, default=32, dest="max_new")
    d.add_argument("--preempt", action="store_true")
    d.add_argument("--self-sigterm", action="store_true",
                   dest="self_sigterm")

    ro = sub.add_parser("router", help="router-chaos-drill child")
    ro.add_argument("--dir", required=True)
    ro.add_argument("--label", default="c1")
    ro.add_argument("--mode", default="kill",
                    choices=("kill", "wedge", "flap", "deadline_storm",
                             "prefix_storm", "scale_storm", "host_loss",
                             "spec_draft_poison"))
    ro.add_argument("--steady", type=int, default=12)
    ro.add_argument("--requests", type=int, default=8)
    ro.add_argument("--max-new", type=int, default=10, dest="max_new")
    ro.add_argument("--preempt", action="store_true")

    rp = sub.add_parser("replica", help="cross-host replica child "
                                        "(ISSUE 17)")
    rp.add_argument("--dir", required=True)
    rp.add_argument("--label", default="r1")
    rp.add_argument("--ttl", type=float, default=600.0)

    r = sub.add_parser("run", help="orchestrate scenarios")
    r.add_argument("scenarios", nargs="*", default=list(SCENARIOS))
    r.add_argument("--root", default=None)
    r.add_argument("--json", action="store_true")

    a = p.parse_args(argv)
    if a.cmd == "train":
        return _cmd_train(a)
    if a.cmd == "decode":
        return _cmd_decode(a)
    if a.cmd == "router":
        return _cmd_router(a)
    if a.cmd == "replica":
        return _cmd_replica(a)
    import tempfile

    root = a.root or tempfile.mkdtemp(prefix="mxnet-drills-")
    reports = [run_drill(s, root, verbose=not a.json)
               for s in (a.scenarios or SCENARIOS)]
    if a.json:
        print(json.dumps(reports, default=str))
    return 0 if all(r["ok"] for r in reports) else 1


if __name__ == "__main__":
    sys.exit(main())
