"""Diagnose the environment for bug reports (reference tools/diagnose.py).

Prints platform, Python, dependency versions, framework feature flags,
native-library status, and device availability.  The device probe runs in
a SUBPROCESS with a timeout: a hung accelerator backend (or a chip held
by another process) must never hang the diagnosis itself.

    python tools/diagnose.py [--probe-timeout 60]
"""
import argparse
import os
import platform
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("machine      :", platform.machine())


def check_deps():
    print("----------Dependency Versions----------")
    for mod in ("numpy", "jax", "jaxlib", "flax", "optax"):
        try:
            m = __import__(mod)
            print(f"{mod:<13}: {getattr(m, '__version__', 'unknown')}")
        except ImportError:
            print(f"{mod:<13}: not installed")


def check_framework():
    print("----------Framework----------")
    import mxnet_tpu as mx

    print("mxnet_tpu    :", mx.__version__)
    print("location     :", os.path.dirname(mx.__file__))
    try:
        paths = mx.libinfo.find_lib_path()
        print("native libs  :", ", ".join(os.path.basename(p)
                                          for p in paths))
    except RuntimeError as e:
        print("native libs  : none (", e, ")")
    from mxnet_tpu import runtime

    feats = [f.name for f in runtime.feature_list() if f.enabled]
    print("features     :", ", ".join(feats) if feats else "(none)")
    envs = {k: v for k, v in os.environ.items() if k.startswith("MXNET_")}
    print("MXNET_* env  :", envs or "(none)")


def check_devices(timeout: float):
    print("----------Devices----------")
    code = ("import jax;"
            "print('backend:', jax.default_backend());"
            "print('devices:', jax.devices())")
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=timeout)
        out = (r.stdout + r.stderr).strip()
        print(out if out else f"probe exited rc={r.returncode}")
    except subprocess.TimeoutExpired:
        print(f"device probe TIMED OUT after {timeout:.0f}s — the "
              f"accelerator backend hangs (is another process holding "
              f"the chip?). CPU-only work still runs with "
              f"JAX_PLATFORMS=cpu.")


def main():
    p = argparse.ArgumentParser(description="diagnose the environment")
    p.add_argument("--probe-timeout", type=float, default=60.0)
    args = p.parse_args()
    check_os()
    check_python()
    check_deps()
    check_framework()
    check_devices(args.probe_timeout)
    print("diagnose: done")


if __name__ == "__main__":
    main()
