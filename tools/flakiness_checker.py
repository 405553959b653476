"""Run a test many times with fresh seeds to expose flakiness (reference
tools/flakiness_checker.py).

The suite's conftest derives per-test seeds from ``MXNET_TEST_SEED``; this
driver re-runs the chosen test N times with different seeds and reports
every failing seed, so a flaky test becomes reproducible with
``MXNET_TEST_SEED=<seed> pytest <test>``.

    python tools/flakiness_checker.py tests/test_operator.py::test_dot -n 20
"""
import argparse
import os
import random
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_once(test: str, seed: int, timeout: float) -> bool:
    env = dict(os.environ)
    env["MXNET_TEST_SEED"] = str(seed)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", test, "-x", "-q"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout)
    return r.returncode == 0


def main():
    p = argparse.ArgumentParser(description="flakiness checker")
    p.add_argument("test", help="pytest node id, e.g. tests/t.py::test_x")
    p.add_argument("-n", "--trials", type=int, default=10)
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--seed", type=int, default=None,
                   help="seed the seed sequence itself (reproducible runs)")
    args = p.parse_args()

    rng = random.Random(args.seed)
    failed = []
    for i in range(args.trials):
        seed = rng.randrange(2 ** 31)
        ok = run_once(args.test, seed, args.timeout)
        print(f"trial {i + 1}/{args.trials} seed={seed}: "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            failed.append(seed)

    print()
    if failed:
        print(f"FLAKY: {len(failed)}/{args.trials} failures; reproduce "
              f"with e.g. MXNET_TEST_SEED={failed[0]} python -m pytest "
              f"{args.test}")
        sys.exit(1)
    print(f"stable across {args.trials} seeded trials")


if __name__ == "__main__":
    main()
