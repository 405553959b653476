"""chip_smoke.py's contract on a machine WITHOUT a chip, and the three
device-plumbing rules it rests on: one compile-cache resolver, Pallas
interpret mode on the CPU only, and tpu() never resolving to the host
silently."""
import os
import subprocess
import sys

import jax
import pytest

from mxnet_tpu import context, program_store
from mxnet_tpu.ops import pallas_kernels

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_to_start_without_a_tpu():
    r = _run(timeout=120)
    assert r.returncode not in (0, None)
    assert "'cpu'" in r.stderr and "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""            # no result of any kind


@pytest.mark.slow     # 54 s alone, 85 s beside three other workers (PR 28)
def test_smoke_rehearsal_passes_on_the_cpu_and_never_prints_the_pass_line():
    """Every phase, the mesh phase included (4 virtual devices)."""
    r = _run("--rehearse", "--chips", "4", timeout=900, devices=4)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines and all(l.startswith("REHEARSAL") for l in lines)
    assert '"ok"' not in r.stdout
    for phase in ("train/resnet50", "train/bert_base", "kernel/flash",
                  "serve/decode", "mesh/resnet50", "mesh/bert_base"):
        assert f"== {phase}" in r.stdout
    assert "[FAIL]" not in r.stdout


def test_cache_resolver_yields_to_the_environment(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_PROGRAM_CACHE_DIR", "/somewhere/else")
    assert program_store.cache_dir() == str(tmp_path)
    assert program_store.enable_persistent_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in [k for k, _ in calls]


def test_cache_resolver_default_is_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("MXNET_PROGRAM_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert program_store.cache_dir() == want
    assert program_store.enable_persistent_cache() == want
    assert ("jax_compilation_cache_dir", want) in calls
    monkeypatch.setenv("MXNET_PROGRAM_CACHE_DIR", "/tmp/knob")
    assert program_store.cache_dir() == "/tmp/knob"


def test_no_other_code_names_a_cache_directory():
    """The resolver is the ONLY place that points jax at a directory
    (tests/conftest.py's setdefault of the env var aside), and no code
    spells out the sandbox's checkout path."""
    offenders = []
    sandbox_path = "/" + "/".join(("root", "repo"))   # no literal here
    for root, _dirs, files in os.walk(REPO):
        if any(part.startswith(".") for part in
               os.path.relpath(root, REPO).split(os.sep) if part != "."):
            continue
        for f in files:
            if not f.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, f)
            rel = os.path.relpath(path, REPO)
            if rel.startswith("tests" + os.sep) or \
                    rel == os.path.join("mxnet_tpu", "program_store.py"):
                continue
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            if '"jax_compilation_cache_dir"' in src or sandbox_path in src:
                offenders.append(rel)
    assert offenders == []


@pytest.mark.parametrize("platform,want", [("tpu", False), ("cpu", True)])
def test_interpret_follows_the_platform(monkeypatch, platform, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert pallas_kernels._interpret() is want


def test_interpret_raises_off_tpu_and_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        pallas_kernels._interpret()


def test_tpu_context_raises_unless_cpu_was_asked_for():
    """JAX_PLATFORMS=cpu (this suite): tpu() resolves to the host.  Any
    other setting with no accelerator present: it raises."""
    assert context.tpu(0).jax_device.platform == "cpu"
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="no accelerator"):
            context.tpu(0).jax_device
    finally:
        jax.config.update("jax_platforms", "cpu")
