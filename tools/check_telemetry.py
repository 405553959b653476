#!/usr/bin/env python
"""Telemetry CI gate (the observability analog of check_fault_sites):

1. **No unregistered counters.**  Every public ``*_count``-style
   accessor in ``mxnet_tpu/`` must be a view over a declared telemetry
   registry counter — the accessor's base name must match the final
   segment of a registered counter name (``deferred_read_count`` →
   ``cached_step.deferred_read``, ``trace_count`` →
   ``program_store.*.traces``).  Raw module-global counter state
   (``_X_COUNT = 0``) is forbidden outright.

2. **No untested counters.**  Every registered counter's name — or, for
   dynamic per-site/per-instance counters, its declared ``family`` —
   must appear as a literal in at least one file under ``tests/``.

3. **Deterministic steady-state snapshot.**  Two identical 3-step
   windows of a warmed compiled TrainStep must produce byte-identical
   ``telemetry.delta()`` results over the deterministic (cumulative)
   counters — a nondeterministic counter in the steady state is a
   measurement you can't regress against.

4. **Chrome-trace export.**  One compiled train step + one decode batch
   recorded under the profiler must dump valid chrome-trace JSON
   carrying >= 3 distinct span categories (train_step / decode /
   serving / step_phase) — the unified-timeline acceptance bar.

5. **Routed requests are traced** (ISSUE 15).  A 2-replica
   ``ReplicaRouter`` driven through a failover, a hedge, and a
   deadline shed must stamp a NON-EMPTY ``trace_id`` on every ``shed``
   / ``failover`` / ``hedge`` event it emits — an unstitchable
   lifecycle record is a regression.

6. **Merge correctness** (ISSUE 15).  Two subprocesses each run the
   identical steady-state TrainStep window and flush one flight-
   recorder shard; ``telemetry.merge`` over the pair must equal
   exactly 2x either process's cumulative window delta on the
   deterministic counters — cross-process aggregation is arithmetic,
   not approximation.

Exit code 0 = all gates green.  Usage:
``python tools/check_telemetry.py [repo_root]`` (run by the suite via
tests/test_telemetry.py; ``--merge-worker`` is gate 6's child entry).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import Dict, List, Set, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools.lint import walk_package  # noqa: E402
from tools.lint import rules as _lint_rules  # noqa: E402


def _py_files(root: str):
    for dirpath, _dirs, files in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def _walk(pkg_dir: str):
    pkg_dir = os.path.abspath(pkg_dir)
    return walk_package(os.path.dirname(pkg_dir),
                        os.path.basename(pkg_dir))


def collect_accessors(pkg_dir: str) -> Dict[str, Set[str]]:
    """Accessor base name (minus ``_count``) -> files declaring it.
    Since graftlint: the shared AST walk's collection (real FunctionDef
    nodes, public non-``reset_*`` names) instead of a regex."""
    return _lint_rules.collect_accessors(_walk(pkg_dir))


def collect_raw_state(pkg_dir: str) -> List[str]:
    """Forbidden pre-registry counter state still in the tree — the
    graftlint ``counter-discipline`` rule's collection (module-global
    ``_X_COUNT = <n>`` and public ``self.x_count = <n>``)."""
    return sorted(f"{src.rel}: {what}" for src, _node, what
                  in _lint_rules.collect_raw_state(_walk(pkg_dir)))


def _base_matches_segment(base: str, seg: str) -> bool:
    return seg in (base, base + "s", base + "es")


def check_registered(accessors: Dict[str, Set[str]],
                     registry: Dict[str, dict]) -> List[str]:
    """Accessor bases with NO matching registered counter.  A base
    ``<scope>_<what>`` (``spec_trace_count``) also matches a counter
    ``...<scope>.<what>s`` (``program_store.serving_spec.traces``)."""
    segs = {n.rsplit(".", 1)[-1] for n in registry}
    missing = []
    for base, files in sorted(accessors.items()):
        scope, _, what = base.rpartition("_")
        scoped = scope and any(
            _base_matches_segment(what, n.rsplit(".", 1)[-1])
            and scope in n.rsplit(".", 1)[0] for n in registry)
        if not scoped and not any(_base_matches_segment(base, s)
                                  for s in segs):
            missing.append(f"{base}_count (declared in "
                           f"{', '.join(sorted(files))})")
    return missing


def check_tested(registry: Dict[str, dict], tests_dir: str) -> List[str]:
    """Registered counters whose name/family appears in NO test file.
    Counters under ``test.`` are fixtures the suite itself registered
    while this gate runs in-process — skipped."""
    needles: Dict[str, str] = {}
    for name, meta in registry.items():
        if name.startswith("test."):
            continue
        needles[name] = meta.get("family") or name
    blob = []
    for path in _py_files(tests_dir):
        with open(path, encoding="utf-8") as f:
            blob.append(f.read())
    blob = "\n".join(blob)
    missing = sorted({f"{n} (family {needle!r})" if needle != n else n
                      for n, needle in needles.items()
                      if needle not in blob})
    return missing


# ---------------------------------------------------------------------------
# runtime checks (CPU, tiny shapes)
# ---------------------------------------------------------------------------
# counter namespaces a steady-state compiled train step may touch; the
# reproducibility gate compares EXACTLY these so a background thread
# from an unrelated co-resident test cannot flake the check
_DETERMINISTIC_PREFIXES = ("program_store.train_step.", "cached_step.",
                           "spmd.", "sharding.", "metric.", "fused.",
                           "ndarray.", "faults.", "telemetry.",
                           "prefix.")


def _train_fixture():
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8, activation="relu")
            self.out = nn.Dense(4, in_units=16)

        def forward(self, x):
            return self.out(self.d1(x))

    net = Net()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    step = trainer.compile_step(net, lambda n, x, y: ((n(x) - y) ** 2)
                                .mean())
    rng = onp.random.RandomState(0)
    x = mx.nd.array(rng.randn(8, 8).astype(onp.float32))
    y = mx.nd.array(rng.randn(8, 4).astype(onp.float32))
    return step, x, y


def _steady_delta(telemetry, step, x, y, n=3) -> Dict[str, object]:
    base = telemetry.snapshot()
    for _ in range(n):
        loss = step(x, y, batch_size=8)
    loss.asnumpy()
    kinds = telemetry.registered()
    return {k: v for k, v in telemetry.delta(base).items()
            if k.startswith(_DETERMINISTIC_PREFIXES)
            and kinds.get(k, {}).get("kind") == "cumulative"}


def check_deterministic_snapshot() -> List[str]:
    from mxnet_tpu import telemetry

    step, x, y = _train_fixture()
    for _ in range(2):                    # warm: trace + compile + AOT
        loss = step(x, y, batch_size=8)
    loss.asnumpy()
    if step.last_fallback_reason is not None:
        return [f"TrainStep fell back eager: {step.last_fallback_reason}"]
    d1 = _steady_delta(telemetry, step, x, y)
    d2 = _steady_delta(telemetry, step, x, y)
    if d1 != d2:
        diff = {k: (d1.get(k), d2.get(k))
                for k in set(d1) | set(d2) if d1.get(k) != d2.get(k)}
        return [f"steady-state TrainStep delta not reproducible: {diff}"]
    if d1.get("program_store.train_step.dispatches") != 3:
        return ["steady-state window did not dispatch 3 compiled steps: "
                f"{d1}"]
    return []


def check_chrome_trace() -> List[str]:
    """One compiled train step + one decode batch under the profiler ->
    the dump must be valid JSON with >= 3 span categories."""
    import numpy as onp

    from mxnet_tpu import profiler, serving_decode, telemetry

    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        profiler.set_config(filename=path)
        profiler.set_state("run")
        step, x, y = _train_fixture()
        tl = profiler.StepTimeline()
        with tl.phase("dispatch"):
            step(x, y, batch_size=8).asnumpy()
        tl.step()
        eng = serving_decode.GenerativeEngine(
            serving_decode.TinyCausalLM(),
            pool=serving_decode.PagePool(pages=64, page=8), max_rows=2)
        try:
            eng.generate(onp.asarray([3, 1, 4]), max_new_tokens=2)
        finally:
            eng.close()
        profiler.set_state("stop")
        out = profiler.dump()
        with open(out) as f:
            trace = json.load(f)          # must be valid JSON
        span_cats = {e["cat"] for e in trace["traceEvents"]
                     if e.get("ph") == "X"}
        want = {"train_step", "decode", "serving", "step_phase"}
        got = span_cats & want
        if len(got) < 3:
            return [f"chrome trace carries {len(got)} span categories "
                    f"{sorted(got)} (need >= 3 of {sorted(want)}); all "
                    f"cats: {sorted(span_cats)}"]
        n_spans = len(telemetry.spans())
        if n_spans < 3:
            return [f"telemetry span buffer has only {n_spans} records"]
    finally:
        os.unlink(path)
    return []


def check_routed_trace_ids() -> List[str]:
    """ISSUE-15 gate: drive a 2-replica router through a failover, a
    hedged dispatch, and a deadline shed — every ``shed`` / ``failover``
    / ``hedge`` event emitted on those routed requests must carry a
    non-empty ``trace_id``."""
    import time as _time
    from collections import deque as _deque

    from mxnet_tpu import faults, telemetry
    from mxnet_tpu import serving_decode as sd
    from mxnet_tpu.serving_router import ReplicaRouter

    model = sd.TinyCausalLM(vocab=31, d_model=16, n_layers=1, n_heads=2,
                            max_seq=48)
    params = model.init_params(0)
    engines, pools = [], []
    for i in range(2):
        pool = sd.PagePool(pages=32, page=4)
        eng = sd.GenerativeEngine(model, params=params, pool=pool,
                                  max_rows=2, name=f"trace_gate{i}")
        eng.warmup(max_len=8)
        engines.append(eng)
        pools.append(pool)
    router = ReplicaRouter(engines, breaker_errs=4,
                           breaker_cooldown_s=0.2, hedge_pctl=50)
    evs0 = telemetry.events()
    base_seq = evs0[-1]["seq"] if evs0 else 0
    failures: List[str] = []
    orig = engines[0].generate
    try:
        # failover: replica 0 fails its first dispatch
        calls = [0]

        def flaky(*a, **kw):
            calls[0] += 1
            if calls[0] == 1:
                raise faults.TransientFault("trace-gate failover")
            return orig(*a, **kw)

        engines[0].generate = flaky
        router.generate([1, 2, 3], max_new_tokens=3)
        engines[0].generate = orig
        # deadline shed: a 1us budget can never admit
        try:
            router.generate([1, 2, 3], max_new_tokens=3, deadline_us=1)
            failures.append("trace gate: 1us-budget request was not shed")
        except faults.ShedError:
            pass
        # hedge: prime the latency distribution, slow replica-side
        # dispatch past p50, fire once
        router._lat_dispatch = _deque((0.001,) * 16, maxlen=4096)

        def slow(*a, **kw):
            _time.sleep(0.25)
            return orig(*a, **kw)

        engines[0].generate = engines[1].generate = slow
        router.generate([1, 2, 3], max_new_tokens=2)
    finally:
        engines[0].generate = orig
        engines[1].generate = orig
        for eng in engines:
            eng.close()
        router.close()
    new = [e for e in telemetry.events() if e["seq"] > base_seq]
    for want in ("failover", "shed", "hedge"):
        of_kind = [e for e in new if e["kind"] == want]
        if not of_kind:
            failures.append(
                f"trace gate emitted no {want!r} event — the scenario "
                "drill broke, the stamping contract is unverified")
        bad = [e for e in of_kind if not e.get("trace_id")]
        if bad:
            failures.append(
                f"{len(bad)} routed {want!r} event(s) carry no "
                f"trace_id: {bad[:2]}")
    leaked = sum(p.in_use() for p in pools)
    if leaked:
        failures.append(f"trace gate leaked {leaked} KV pages")
    return failures


_MERGE_WORKER_FLAG = "--merge-worker"


def _merge_worker() -> int:
    """Gate-6 child: run the identical steady-state window and flush
    ONE shard whose snapshot is exactly the window's delta (counters
    reset after warmup, so cumulative == since-reset).  The window
    includes a shared-prefix decode hit so the ``prefix.*`` counters
    (ISSUE 16) prove they shard and merge like everything else."""
    from mxnet_tpu import engine, telemetry
    from mxnet_tpu import serving_decode as sd

    step, x, y = _train_fixture()
    for _ in range(2):                    # warm: trace + compile + AOT
        loss = step(x, y, batch_size=8)
    loss.asnumpy()
    # prefix-cache fixture: prime (compile + publish) BEFORE the reset
    # so the measured window sees a pure deterministic full hit
    model = sd.TinyCausalLM(vocab=29, d_model=16, n_layers=1,
                            n_heads=2, max_seq=48)
    eng = sd.GenerativeEngine(model, params=model.init_params(4),
                              pool=sd.PagePool(pages=32, page=4),
                              max_rows=2, name="merge_gate")
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    eng.generate(shared, max_new_tokens=2)
    telemetry.reset()
    for _ in range(3):
        loss = step(x, y, batch_size=8)
    loss.asnumpy()
    eng.generate(shared, max_new_tokens=2)    # full hit, zero prefill
    eng.close()
    engine.waitall()                      # flushes the flight recorder
    return 0


def check_prefix_zero_when_off() -> List[str]:
    """ISSUE-16 disabled-mode contract: with ``MXNET_PREFIX_CACHE=0`` a
    shared-prompt workload leaves every ``prefix.*`` counter untouched
    and parks nothing in the pool's resident cache — no hashing, no
    index, the pre-cache pool byte-for-byte (the knob is uncached, so
    the env flip takes effect immediately)."""
    from mxnet_tpu import serving_decode as sd
    from mxnet_tpu import telemetry

    prev = os.environ.get("MXNET_PREFIX_CACHE")
    os.environ["MXNET_PREFIX_CACHE"] = "0"
    try:
        model = sd.TinyCausalLM(vocab=29, d_model=16, n_layers=1,
                                n_heads=2, max_seq=48)
        pool = sd.PagePool(pages=32, page=4)
        eng = sd.GenerativeEngine(model, params=model.init_params(2),
                                  pool=pool, max_rows=2,
                                  name="prefix_off_gate")
        base = telemetry.snapshot()
        try:
            for _ in range(2):            # the same prompt twice: the
                eng.generate([5, 4, 3, 2, 1, 6, 7, 8],  # on-path would
                             max_new_tokens=3)          # full-hit here
        finally:
            eng.close()
        moved = {k: v for k, v in telemetry.delta(base).items()
                 if k.startswith("prefix.") and v}
        out: List[str] = []
        if moved:
            out.append("MXNET_PREFIX_CACHE=0 still moved prefix "
                       f"counters: {moved}")
        st = pool.stats()
        if st["cached"] != 0 or st["in_use"] != 0:
            out.append(f"off-path pool holds residue: {st}")
        return out
    finally:
        if prev is None:
            os.environ.pop("MXNET_PREFIX_CACHE", None)
        else:
            os.environ["MXNET_PREFIX_CACHE"] = prev


def check_merge_correctness() -> List[str]:
    """Two processes, identical windows: the shard snapshots must be
    byte-identical on the deterministic counters and the merge must
    equal exactly 2x one of them."""
    import subprocess

    from mxnet_tpu import telemetry

    d = tempfile.mkdtemp(prefix="check-telemetry-merge-")
    env = dict(os.environ)
    env["MXNET_TELEMETRY_DIR"] = d
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("MXNET_FAULT_PLAN", None)
    # the two processes are independent by construction — run them
    # concurrently so the gate pays one worker's wall clock, not two
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), _MERGE_WORKER_FLAG],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for _ in range(2)]
    for i, p in enumerate(procs):
        try:
            _out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            return [f"merge worker {i} timed out"]
        if p.returncode != 0:
            return [f"merge worker {i} failed rc={p.returncode}: "
                    f"{err[-1000:]}"]
    merged = telemetry.merge(d)
    if len(merged["shards"]) != 2:
        return [f"expected 2 shards, merged {merged['shards']}"]
    windows = []
    for proc in merged["processes"]:
        sh = telemetry._read_shard(os.path.join(d, proc["shard"]))
        kinds = (sh["meta"] or {}).get("counter_kinds", {})
        snap = (sh["snapshot"] or {}).get("counters", {})
        windows.append({
            n: v for n, v in snap.items()
            if n.startswith(_DETERMINISTIC_PREFIXES)
            and kinds.get(n) == "cumulative"})
    if windows[0] != windows[1]:
        diff = {k: (windows[0].get(k), windows[1].get(k))
                for k in set(windows[0]) | set(windows[1])
                if windows[0].get(k) != windows[1].get(k)}
        return [f"identical windows produced different shard "
                f"snapshots: {diff}"]
    doubled = {n: 2 * v for n, v in windows[0].items()}
    got = {n: merged["counters"].get(n, 0) for n in doubled}
    if got != doubled:
        diff = {k: (doubled[k], got[k]) for k in doubled
                if doubled[k] != got.get(k)}
        return [f"2-process merge != 2x the single-process window "
                f"delta: {diff}"]
    if windows[0].get("program_store.train_step.dispatches") != 3:
        return ["merge worker window did not dispatch 3 compiled "
                f"steps: {windows[0]}"]
    return []


def main(root: str = None) -> int:
    root = root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    pkg = os.path.join(root, "mxnet_tpu")
    tests = os.path.join(root, "tests")
    failures: List[Tuple[str, List[str]]] = []

    accessors = collect_accessors(pkg)
    if not accessors:
        print("check_telemetry: no *_count accessors found under "
              f"{pkg} — regex or layout broke", file=sys.stderr)
        return 1

    raw = collect_raw_state(pkg)
    if raw:
        failures.append(("raw (non-registry) counter state", raw))

    # import every counter-declaring surface, then read the registry
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu import (cached_step, engine, metric,  # noqa: F401
                           profiler, program_store, serving,
                           serving_decode, telemetry)
    from mxnet_tpu.contrib import quantization  # noqa: F401
    from mxnet_tpu.models import transformer_lm  # noqa: F401
    from mxnet_tpu.ops import nn as _ops_nn  # noqa: F401
    from mxnet_tpu.optimizer import fused  # noqa: F401
    from mxnet_tpu.parallel import sharding, spmd  # noqa: F401

    # the runtime checks run FIRST: they instantiate the per-instance
    # counter families (kv_pool, decode.engine, serving.router) the
    # registry checks then see
    failures.extend(("deterministic steady-state snapshot", [m])
                    for m in check_deterministic_snapshot())
    failures.extend(("chrome-trace export", [m])
                    for m in check_chrome_trace())
    failures.extend(("routed-request trace stamping", [m])
                    for m in check_routed_trace_ids())
    failures.extend(("prefix counters zero with the knob off", [m])
                    for m in check_prefix_zero_when_off())
    failures.extend(("two-process merge correctness", [m])
                    for m in check_merge_correctness())

    registry = telemetry.registered()
    unregistered = check_registered(accessors, registry)
    if unregistered:
        failures.append(("accessors with no registered counter",
                         unregistered))
    untested = check_tested(registry, tests)
    if untested:
        failures.append(("registered counters never named in a test",
                         untested))

    if failures:
        print("check_telemetry: FAILED", file=sys.stderr)
        for what, items in failures:
            print(f"  [{what}]", file=sys.stderr)
            for it in items:
                print(f"    {it}", file=sys.stderr)
        return 1
    print(f"check_telemetry: {len(accessors)} accessors, "
          f"{len(registry)} registered counters, deterministic "
          "steady-state delta, chrome trace >= 3 span categories, "
          "routed events trace-stamped, prefix counters 0 with the "
          "knob off, 2-process merge == 2x window")
    return 0


if __name__ == "__main__":
    if _MERGE_WORKER_FLAG in sys.argv:
        sys.exit(_merge_worker())
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
