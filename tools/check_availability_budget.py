#!/usr/bin/env python
"""CI gate: the fault-tolerant serving plane has a MEASURED
availability budget.

The serving analog of check_recovery_budget: runs the
``mxnet_tpu.drills`` ROUTER scenario matrix — a replica killed
mid-decode (plus a preemption notice through the still-routing
process), a wedged-dispatch hang, a circuit-breaker flap, and a
deadline storm — against a 2-replica ``serving_router.ReplicaRouter``
and FAILS (exit 1) unless:

- **every scenario is green**: 0 dropped requests (every submission
  ends delivered or typed-shed — ``draining`` during the drain,
  ``deadline`` past its budget, never a hang or a bare error), every
  delivered response token-exact vs the uninterrupted
  ``eager_generate`` oracle;
- **failover is bounded**: chaos-phase p99 ≤
  ``failover_p99_mult`` × steady-state p99 + ``failover_p99_slack_s``
  (the slack absorbs the wedge timeout and breaker cooldown, which are
  deliberate, documented waits — the point is a loud regression, not a
  race);
- **nothing leaks**: 0 KV pages in use across every replica pool after
  ``engine.waitall()``, including after the mid-decode kill;
- **the breaker re-admits within the probe budget**
  (``breaker_readmit_s``): after a flap burst ends, the half-open
  probe must close the breaker again — ejection is supposed to be
  temporary;
- **deadlines are honest**: a request with an infeasible
  ``deadline_us`` sheds ``ShedError(kind="deadline")`` without
  consuming more than budget + ``deadline_overrun_s``;
- **the elastic fleet heals itself** (ISSUE 17): in the scale storm
  the autoscaler grows 1 → 3 with every joiner serving its first
  request inside ``join_first_serve_s`` of its spawn (0 fresh compiles
  off the shared program cache) and shrinks back 3 → 1 where every
  scale-down is a graceful preemption (drain → typed draining sheds →
  exit 83); in the host-loss cell a SIGKILL'd remote replica costs at
  most ``kill_recover_s`` before the fleet delivers again, with every
  admitted request still delivered token-exact;
- **a poisoned draft costs zero availability** (ISSUE 19): in the
  ``spec_draft_poison`` cell a wedged draft model must auto-disable
  speculation via the cost table (``spec.autodisabled``) and degrade
  to plain decode in-place — 0 dropped requests, token streams
  unchanged, clean page-pool audit across both KV geometries.

Invoked by the test suite (tests/test_serving_router.py) exactly like
the other gates, and runnable standalone:
``python tools/check_availability_budget.py [scenario ...]``.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the budget docs/ROBUSTNESS.md promises.  Seconds bounds are
# CI-generous (a loaded runner must not flake); the drill REPORTS the
# real measured numbers.
BUDGET = {
    "dropped": 0,
    "leaked_kv_pages": 0,
    "failover_p99_mult": 10.0,
    "failover_p99_slack_s": 5.0,
    "breaker_readmit_s": 8.0,
    "deadline_overrun_s": 1.0,     # enforced inside the drill itself
    # the ISSUE-17 elastic-fleet walls: spawn → warm join → first
    # request served (a whole JAX boot rides inside this), and
    # SIGKILL'd host → next delivered request
    "join_first_serve_s": 90.0,
    "kill_recover_s": 10.0,
}


def main(argv=None) -> int:
    from mxnet_tpu.drills import ROUTER_SCENARIOS, run_drill

    names = [a for a in (argv or []) if not a.startswith("-")] \
        or ROUTER_SCENARIOS
    root = tempfile.mkdtemp(prefix="mxnet-availability-gate-")
    failures = []
    for name in names:
        rep = run_drill(name, root)
        for f in rep["failures"]:
            failures.append(f"{name}: {f}")
        if rep.get("dropped"):
            failures.append(
                f"{name}: {rep['dropped']} request(s) dropped "
                "(budget: 0 — every request delivered or typed-shed)")
        if rep.get("leaked_pages") not in (None,
                                           BUDGET["leaked_kv_pages"]):
            failures.append(
                f"{name}: {rep['leaked_pages']} KV pages leaked "
                "(budget: 0)")
        steady, chaos = rep.get("steady_p99_s"), rep.get("chaos_p99_s")
        if steady and chaos is not None:
            cap = (steady * BUDGET["failover_p99_mult"]
                   + BUDGET["failover_p99_slack_s"])
            if chaos > cap:
                failures.append(
                    f"{name}: chaos p99 {chaos:.3f}s exceeds "
                    f"{BUDGET['failover_p99_mult']}x steady p99 "
                    f"({steady:.3f}s) + "
                    f"{BUDGET['failover_p99_slack_s']}s slack")
        if name == "router_flap":
            ra = rep.get("re_admit_s")
            if ra is not None and ra > BUDGET["breaker_readmit_s"]:
                failures.append(
                    f"{name}: breaker re-admitted after {ra:.2f}s "
                    f"(probe budget {BUDGET['breaker_readmit_s']}s)")
        if name == "router_scale_storm":
            js = rep.get("join_to_first_served_s")
            if js is not None and js > BUDGET["join_first_serve_s"]:
                failures.append(
                    f"{name}: slowest join served its first request "
                    f"after {js:.2f}s (wall "
                    f"{BUDGET['join_first_serve_s']}s)")
        if name == "spec_draft_poison":
            # ISSUE 19: the wedged draft costs ZERO availability — the
            # drill's own cell checks pin auto-disable + degrade; here
            # we pin that speculation actually ran before the poison
            # (a cell that never speculated proves nothing)
            spec = rep.get("spec") or []
            if spec and not any(s.get("spec_rounds") for s in spec):
                failures.append(
                    f"{name}: no spec rounds before the poison — the "
                    "drill exercised plain decode only")
        if name == "router_host_loss":
            kr = rep.get("kill_to_recovered_s")
            if kr is not None and kr > BUDGET["kill_recover_s"]:
                failures.append(
                    f"{name}: first delivery {kr:.2f}s after the "
                    f"SIGKILL (wall {BUDGET['kill_recover_s']}s)")
        line = {k: rep.get(k) for k in
                ("scenario", "ok", "dropped", "leaked_pages",
                 "steady_p99_s", "chaos_p99_s", "failovers",
                 "breaker_opens", "breaker_closes", "re_admit_s",
                 "drain_s", "join_to_first_served_s",
                 "kill_to_recovered_s", "spec_autodisabled",
                 "drill_wall_s")}
        print(f"check_availability_budget: {json.dumps(line, default=str)}")
    if failures:
        print("check_availability_budget: FAIL", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(f"check_availability_budget: {len(names)} scenario(s) green — "
          "0 dropped, 0 leaked pages, failover p99 inside budget, "
          "breaker re-admitted, deadlines honest")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
