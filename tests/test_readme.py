"""README code snippets stay executable (a doc snippet already shipped
broken once — this is the guard; the reference's analog is its doctest
suite, tests/python/doctest)."""
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python_blocks():
    text = open(os.path.join(REPO, "README.md")).read()
    return re.findall(r"```python\n(.*?)```", text, re.DOTALL)


def test_readme_python_snippets_execute():
    blocks = _python_blocks()
    assert len(blocks) >= 2, "README lost its quick-start snippets"
    # snippets build on each other: run them as one program, in order
    program = "\n\n".join(blocks) + "\nprint('README_OK')\n"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, "-c",
         f"import sys; sys.path.insert(0, {REPO!r})\n" + program],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (
        f"README snippet failed:\nstdout:{r.stdout[-1500:]}\n"
        f"stderr:{r.stderr[-1500:]}")
    assert "README_OK" in r.stdout
