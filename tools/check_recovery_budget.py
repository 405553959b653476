#!/usr/bin/env python
"""CI gate: preemption survival has a MEASURED recovery budget.

The recovery analog of check_dispatch_budget/check_fault_sites: runs
the full `mxnet_tpu.drills` scenario matrix — real subprocesses, real
SIGTERM/SIGKILL, a 4→2 device mesh change, a corrupted checkpoint, a
mid-stream decode kill — and FAILS (exit 1) unless:

- **every drill scenario is green** (bit-exact resumed loss
  trajectories, token-exact decode completions/re-queues, typed
  ``draining`` sheds, the distinguished preemption exit code);
- **graceful drain replays 0 steps** (the SIGTERM checkpoint is the
  exact pre-signal state) and a SIGKILL replays exactly the
  save-interval gap;
- **warm recovery performs 0 fresh compiles**: every restart resumes
  from ``MXNET_PROGRAM_CACHE_DIR`` disk hits only (the PR-7 promise,
  now enforced under failure, including after the topology change);
- **nothing leaks**: 0 KV pages after the decode drain's
  ``waitall()``, 0 temp checkpoint files after a kill;
- **recovery fits the wall-clock budget**: checkpoint restore under
  ``RECOVERY_S_MAX`` and process-start→first-resumed-step under
  ``RECOVERY_WALL_S_MAX`` (generous CI bounds — the point is a loud
  regression, not a race).

Invoked by the test suite (tests/test_preemption.py) exactly like the
other gates, and runnable standalone:
``python tools/check_recovery_budget.py [scenario ...]``.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the budget docs/ROBUSTNESS.md promises.  The seconds bounds are CI-
# generous (a loaded runner must not flake) — the drill REPORTS the
# real measured numbers.
BUDGET = {
    "graceful_steps_replayed": 0,
    "warm_recovery_fresh_compiles": 0,
    "leaked_kv_pages": 0,
    "leaked_tmp_files": 0,
    "recovery_s_max": 60.0,
    "recovery_wall_s_max": 120.0,
}


def main(argv=None) -> int:
    from mxnet_tpu.drills import SCENARIOS, run_drill

    names = [a for a in (argv or []) if not a.startswith("-")] or SCENARIOS
    root = tempfile.mkdtemp(prefix="mxnet-recovery-gate-")
    failures = []
    reports = []
    for name in names:
        rep = run_drill(name, root)
        reports.append(rep)
        for f in rep["failures"]:
            failures.append(f"{name}: {f}")
        # the cross-scenario budget lines (scenario-internal contracts —
        # restore points, bit-exactness, typed sheds — already fail
        # through rep['failures'])
        if rep.get("fresh_compiles") is not None and \
                rep["fresh_compiles"] != BUDGET["warm_recovery_fresh_compiles"]:
            failures.append(
                f"{name}: warm recovery performed {rep['fresh_compiles']} "
                "fresh compiles (budget: 0 — disk hits only)")
        if rep.get("leaked_pages") not in (None, BUDGET["leaked_kv_pages"]):
            failures.append(
                f"{name}: {rep['leaked_pages']} KV pages leaked "
                "(budget: 0)")
        if rep.get("leaked_tmp"):
            failures.append(
                f"{name}: temp checkpoint litter {rep['leaked_tmp']} "
                "(budget: 0 files)")
        if rep.get("recovery_s") is not None and \
                rep["recovery_s"] > BUDGET["recovery_s_max"]:
            failures.append(
                f"{name}: checkpoint restore took {rep['recovery_s']:.2f}s "
                f"(budget {BUDGET['recovery_s_max']}s)")
        if rep.get("recovery_wall_s") is not None and \
                rep["recovery_wall_s"] > BUDGET["recovery_wall_s_max"]:
            failures.append(
                f"{name}: restart->first-step took "
                f"{rep['recovery_wall_s']:.2f}s "
                f"(budget {BUDGET['recovery_wall_s_max']}s)")
        line = {k: rep.get(k) for k in
                ("scenario", "ok", "recovery_s", "recovery_wall_s",
                 "steps_replayed", "drain_s", "fresh_compiles",
                 "disk_hits", "restored_at", "drill_wall_s")}
        print(f"check_recovery_budget: {json.dumps(line, default=str)}")
    if failures:
        print("check_recovery_budget: FAIL", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"check_recovery_budget: {len(names)} scenario(s) green, "
          "0 fresh compiles on warm recovery, 0 leaks, inside the "
          "recovery budget")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
