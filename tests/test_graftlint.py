"""graftlint: the AST invariant linter + runtime lock-order detector.

Covers: (1) every static rule demonstrates a true-positive, a clean
pass, and a pragma suppression against its checked-in fixture trio
(tests/fixtures/graftlint/); (2) pragma parsing (reasons required for
daemon-ok, multi-line reasons, statement-span application); (3) the
baseline mechanism; (4) the runtime lock-order recorder: a synthetic
A→B / B→A cycle MUST be caught, a consistent order must not, and
instrumented locks keep full Lock/Condition semantics; (5) the real
tree: an in-process static run reports ZERO non-baseline findings, and
the full `python -m tools.lint --all` gate (static + fresh-process
lock-order scenario over one compiled train step + one decode batch +
one preemption drain) exits 0 and lands its JSON report in
benchmark/artifacts/ — the suite-level wiring of docs/STATIC_ANALYSIS.md.
"""
import json
import os
import shutil
import subprocess
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import RULES, load_baseline, run_static  # noqa: E402
from tools.lint import runtime as lint_runtime  # noqa: E402
from tools.lint.core import Finding  # noqa: E402

FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")

# rule -> (fixture stem, filename the fixture must land under in the
# tmp package — host-sync only watches the declared hot-path modules)
RULE_FIXTURES = {
    "env-discipline": ("env", "fixture_mod.py"),
    "thread-discipline": ("thread", "fixture_mod.py"),
    "host-sync": ("hostsync", "cached_step.py"),
    "fault-site": ("faultsite", "fixture_mod.py"),
    "counter-discipline": ("counter", "fixture_mod.py"),
    "donation": ("donation", "fixture_mod.py"),
}


def _mini_tree(tmp_path, rule, variant):
    """tmp repo: mxnet_tpu/<target> from the fixture + docs/tests stubs
    (the fault-site rule cross-checks both)."""
    stem, target = RULE_FIXTURES[rule]
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir(exist_ok=True)
    shutil.copy(os.path.join(FIXTURES, f"{stem}_{variant}.py"),
                str(pkg / target))
    docs = tmp_path / "docs"
    docs.mkdir(exist_ok=True)
    (docs / "ROBUSTNESS.md").write_text(
        "| Site | Where | Recovery |\n|---|---|---|\n"
        "| `fixture.documented` | fixture | retried |\n")
    tests = tmp_path / "tests"
    tests.mkdir(exist_ok=True)
    (tests / "test_fixture.py").write_text(
        'PLAN = "fixture.documented"\n')
    return str(tmp_path)


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_true_positive(rule, tmp_path):
    root = _mini_tree(tmp_path, rule, "violation")
    findings, _ = run_static(root, only={rule})
    assert findings, f"{rule}: violation fixture produced no finding"
    assert all(f.rule == rule for f in findings)
    expected = {"env-discipline": 3, "host-sync": 4, "fault-site": 2,
                "counter-discipline": 3, "donation": 2,
                "thread-discipline": 1}[rule]
    assert len(findings) == expected, [str(f) for f in findings]


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_clean(rule, tmp_path):
    root = _mini_tree(tmp_path, rule, "clean")
    findings, _ = run_static(root, only={rule})
    assert findings == [], [str(f) for f in findings]


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_pragma_suppressed(rule, tmp_path):
    root = _mini_tree(tmp_path, rule, "pragma")
    findings, ctx = run_static(root, only={rule})
    assert findings == [], [str(f) for f in findings]
    assert ctx.suppressed >= 1, \
        f"{rule}: pragma suppression was not counted"


def test_daemon_ok_requires_reason(tmp_path):
    """An empty daemon-ok() justifies nothing — the finding stands."""
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "import threading\n\n"
        "def go():\n"
        "    # graftlint: daemon-ok()\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n")
    findings, _ = run_static(str(tmp_path), only={"thread-discipline"})
    assert len(findings) == 1


def test_parse_error_is_a_finding(tmp_path):
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "broken.py").write_text("def oops(:\n")
    findings, _ = run_static(str(tmp_path), only={"env-discipline"})
    assert any(f.rule == "parse-error" for f in findings)


def test_baseline_filters_known_findings(tmp_path):
    root = _mini_tree(tmp_path, "env-discipline", "violation")
    findings, _ = run_static(root, only={"env-discipline"})
    baseline = {f.key for f in findings}
    live = [f for f in findings if f.key not in baseline]
    assert live == []
    # the key is line-free: a Finding at another line matches the same
    # baseline entry
    f = findings[0]
    moved = Finding(f.rule, f.path, f.line + 40, 0, f.message)
    assert moved.key in baseline


def test_list_rules_names_all_six():
    assert set(RULE_FIXTURES) <= set(RULES)
    for r in RULES.values():
        assert r.doc, f"rule {r.name} has no doc"


# ---------------------------------------------------------------------------
# runtime lock-order recorder
# ---------------------------------------------------------------------------

def test_lock_cycle_synthetic():
    """The canonical inversion: thread 1 takes A then B, thread 2 takes
    B then A.  No deadlock ever happens (the threads run sequentially)
    — the ORDER graph still carries the cycle, which is the point:
    deterministic detection without the unlucky interleaving."""
    rec = lint_runtime.enable()
    try:
        a = threading.Lock()
        b = threading.Lock()

        def t1():
            with a:
                with b:
                    pass

        def t2():
            with b:
                with a:
                    pass

        for fn in (t1, t2):
            th = threading.Thread(target=fn)
            th.start()
            th.join()
    finally:
        lint_runtime.disable()
    cycles = rec.cycles()
    assert len(cycles) == 1, rec.report()
    assert len(cycles[0]) == 2
    assert all("test_graftlint.py" in site for site in cycles[0])


def test_lock_consistent_order_no_cycle():
    rec = lint_runtime.enable()
    try:
        a = threading.Lock()
        b = threading.Lock()

        def t(n):
            for _ in range(n):
                with a:
                    with b:
                        pass

        for _ in range(2):
            th = threading.Thread(target=t, args=(3,))
            th.start()
            th.join()
    finally:
        lint_runtime.disable()
    assert rec.cycles() == []
    assert rec.acquisitions >= 12


def test_instrumented_locks_keep_semantics():
    """Wrapped locks must behave as locks: context manager, Condition
    protocol (incl. RLock delegation), locked(), and survival after
    disable()."""
    rec = lint_runtime.enable()
    try:
        lock = threading.Lock()
        with lock:
            assert lock.locked()
        assert not lock.locked()
        cv = threading.Condition(threading.RLock())
        hit = []

        def waiter():
            with cv:
                cv.wait(timeout=5)
                hit.append(1)

        th = threading.Thread(target=waiter)
        th.start()
        import time

        time.sleep(0.05)
        with cv:
            cv.notify_all()
        th.join(timeout=5)
        assert hit == [1]
    finally:
        lint_runtime.disable()
    # post-disable: the same wrapper objects still function
    with lock:
        assert lock.locked()
    assert rec.acquisitions > 0 and not rec.active


def test_instance_level_edges_no_false_cycle():
    """Two lock INSTANCES from one creation site, nested both ways
    across threads, are NOT a cycle (per-instance ordered locks are a
    legal pattern); the graph is instance-keyed exactly for this."""
    rec = lint_runtime.enable()
    try:
        locks = [threading.Lock() for _ in range(2)]   # one site

        def t(first, second):
            with locks[first]:
                with locks[second]:
                    pass

        th = threading.Thread(target=t, args=(0, 1))
        th.start()
        th.join()
        # same ordered pair again — never the reverse
        th = threading.Thread(target=t, args=(0, 1))
        th.start()
        th.join()
    finally:
        lint_runtime.disable()
    assert rec.cycles() == []


# ---------------------------------------------------------------------------
# the real tree
# ---------------------------------------------------------------------------

def test_real_tree_static_zero_findings():
    """mxnet_tpu/ lints clean with an EMPTY baseline — every
    grandfathered finding was fixed or pragma'd with a reason."""
    findings, ctx = run_static(REPO)
    baseline = load_baseline()
    assert baseline == set(), \
        "baseline must stay empty (docs/STATIC_ANALYSIS.md policy)"
    live = [str(f) for f in findings]
    assert live == [], "\n".join(live)
    assert len(ctx.sources) > 100          # the walk actually walked
    assert ctx.suppressed > 0              # pragmas are in play


def test_full_gate_subprocess_and_artifact():
    """`python -m tools.lint --all`: static rules + the fresh-process
    lock-order scenario (compiled train step + decode batch + preemption
    drain) exit 0, the acquisition graph is acyclic, and the JSON report
    lands in benchmark/artifacts/ for bench rounds to diff."""
    artifact = os.path.join(REPO, "benchmark", "artifacts",
                            "graftlint.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--all", "--json", artifact],
        capture_output=True, text=True, timeout=540, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    with open(artifact) as f:
        report = json.load(f)
    assert report["static"]["findings"] == []
    rt = report["runtime"]
    assert not rt.get("error"), rt
    assert rt["cycles"] == []
    assert rt["locks"] > 10 and rt["acquisitions"] > 50
    # the scenario really ran its three legs
    assert rt["scenario"]["train_steps"] == 3
    assert rt["scenario"]["drain_exit_code"] == 83
    # framework locks are in the observed graph, not just jax internals
    sites = {e["held"] for e in rt["edges"]} \
        | {e["acquired"] for e in rt["edges"]}
    assert any(s.startswith("mxnet_tpu/") for s in sites), sites
