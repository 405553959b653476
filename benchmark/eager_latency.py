"""Eager per-op dispatch latency: plain dispatch vs the per-op jit cache
(MXNET_EAGER_JIT), plus the Trainer-step lane comparing the fused
multi-tensor optimizer path (MXNET_FUSED_OPTIMIZER, optimizer/fused.py)
against the per-parameter scalar loop.  Run on the chip to fill
docs/PERF.md's eager table (round-5 VERDICT Weak #4); CPU runs are still
meaningful A/Bs of python dispatch overhead.

Method per op: warm (compile + cache) with host-value reads, then time N
invocations fenced by a host read — dispatch is asynchronous, so
unfenced loops measure enqueue rate, not latency.

The trainer lane reports ``dispatches_per_step`` = eager op dispatches
(ndarray.invoke_count) + compiled group-program launches
(fused.dispatch_count) per ``trainer.step()``: the fused path must stay
at <= 1 + (number of distinct parameter groups) while the loop path pays
>= 1 per parameter (the acceptance bar for PR 1).

The train_step_compiled lane rides next to it (PR 3): a hybridized MLP
trained through ``Trainer.compile_step`` (cached_step.TrainStep), whose
whole step — forward+backward+update — must land at 1 dispatch/step with
retrace count 0 after warm-up; it also reports program-cache hits/misses.
``--train-step-only`` emits just that lane (bench.py's lanes[] entry).

Usage: python benchmark/eager_latency.py [--ops N] [--json]
                                         [--trainer-params P] [--no-trainer]
                                         [--train-step-only]
Each mode runs in a SUBPROCESS so the jit cache and config are clean.
"""
import json
import os
import subprocess
import sys
import time

_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import nd

N = int(os.environ.get("EAGER_N", "100"))
rng = onp.random.RandomState(0)
x = nd.array(rng.randn(128, 256).astype(onp.float32))
w = nd.array(rng.randn(256, 256).astype(onp.float32))
b = nd.array(rng.randn(256).astype(onp.float32))
img = nd.array(rng.randn(8, 32, 32, 64).astype(onp.float32))
k = nd.array(rng.randn(64, 3, 3, 64).astype(onp.float32))
gamma = nd.ones((64,)); beta = nd.zeros((64,))
rm = nd.zeros((64,)); rv = nd.ones((64,))

OPS = {
    "elemwise_add": lambda: x + x,
    "FullyConnected": lambda: nd.FullyConnected(x, w, b, num_hidden=256),
    "softmax": lambda: nd.softmax(x, axis=-1),
    "Convolution3x3": lambda: nd.Convolution(
        img, k, kernel=(3, 3), pad=(1, 1), num_filter=64, no_bias=True,
        layout="NHWC"),
    "BatchNorm(infer)": lambda: nd.BatchNorm(
        img, gamma, beta, rm, rv, eps=1e-5, momentum=0.9, fix_gamma=False,
        use_global_stats=True, axis=3),
    "mean_axis": lambda: x.mean(axis=1),
}

rows = {}
def _first(o):
    return o[0] if isinstance(o, (list, tuple)) else o

for name, fn in OPS.items():
    for _ in range(5):                       # warm: compile + caches
        out = fn()
    _ = float(_first(out).asnumpy().ravel()[0])  # drain the dispatch queue
    t0 = time.perf_counter()
    for _ in range(N):
        out = fn()
    _ = float(_first(out).asnumpy().ravel()[0])  # fence
    dt = time.perf_counter() - t0
    rows[name] = dt / N * 1e6                # us/op incl. device time

import jax
print(json.dumps({"platform": jax.default_backend(),
                  "eager_jit": os.environ.get("MXNET_EAGER_JIT", "default"),
                  "us_per_op": rows}))
"""


# Trainer-step lane: a flat >=50-parameter "model" (grads pre-filled so
# the measurement is pure step() cost), stepped with the fused
# multi-tensor path on/off.  Dispatch counts come from the in-tree
# counters, not wall clock, so the lane is meaningful on any backend.
_TRAINER_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.ndarray import ndarray as _ndmod
from mxnet_tpu.optimizer import fused as _fused

NPARAM = int(os.environ.get("TRAINER_PARAMS", "56"))
STEPS = int(os.environ.get("TRAINER_STEPS", "20"))
OPT = os.environ.get("TRAINER_OPT", "sgd")
rng = onp.random.RandomState(0)
params = {}
for i in range(NPARAM):
    p = gluon.Parameter(f"w{i}", shape=(32, 32))
    p.initialize(init=mx.init.Xavier())
    params[f"w{i}"] = p
opt_kw = {"learning_rate": 0.01}
if OPT == "sgd":
    opt_kw["momentum"] = 0.9
trainer = gluon.Trainer(params, OPT, opt_kw)

def fill_grads():
    for p in params.values():
        g = p.list_grad()[0]
        g._set_data(mx.nd.array(
            rng.randn(*g.shape).astype("float32") * 0.01)._data)

fill_grads()
trainer.step(1)                          # warm: state create + compile
for p in params.values():                # drain
    _ = p.data().asnumpy()

inv0, fus0 = _ndmod.invoke_count(), _fused.dispatch_count()
t0 = time.perf_counter()
for _ in range(STEPS):
    trainer.step(1)
_ = next(iter(params.values())).data().asnumpy()   # fence
dt = time.perf_counter() - t0
inv = _ndmod.invoke_count() - inv0
fus = _fused.dispatch_count() - fus0

import jax
print(json.dumps({
    "platform": jax.default_backend(),
    "fused": bool(_fused.enabled(trainer._optimizer)),
    "n_params": NPARAM,
    "n_groups": 1,
    "steps": STEPS,
    "dispatches_per_step": (inv + fus) / STEPS,
    "compiled_group_dispatches_per_step": fus / STEPS,
    "us_per_step": dt / STEPS * 1e6,
}))
"""


# Compiled whole-train-step lane (cached_step.TrainStep): a small
# hybridized MLP trained via trainer.compile_step — forward+backward+
# update as ONE donated program.  Reports dispatches/step (the bar: 1,
# +1 host read under AMP), program-cache hits/misses, and the retrace
# count across constant-shape steps (the bar: 0 after warm).  Counter-
# based, so the lane is meaningful on any backend; us/step is a device
# number only on a chip run.
_TRAIN_STEP_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, os.getcwd())      # the parent runs us from the checkout root
from mxnet_tpu import program_store as _ps
_ps.enable_persistent_cache(min_compile_secs=1)
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import cached_step, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import ndarray as _ndmod
from mxnet_tpu.optimizer import fused as _fused

WIDTH = int(os.environ.get("TRAIN_STEP_WIDTH", "64"))
DEPTH = int(os.environ.get("TRAIN_STEP_DEPTH", "4"))
STEPS = int(os.environ.get("TRAIN_STEP_STEPS", "20"))
OPT = os.environ.get("TRAINER_OPT", "sgd")

class Net(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        for i in range(DEPTH):
            setattr(self, f"d{i}", nn.Dense(
                WIDTH, in_units=WIDTH, activation="relu"))
        self.out = nn.Dense(WIDTH, in_units=WIDTH)
    def forward(self, x):
        for i in range(DEPTH):
            x = getattr(self, f"d{i}")(x)
        return self.out(x)

net = Net()
net.initialize(mx.init.Xavier())
net.hybridize()
rng = onp.random.RandomState(0)
opt_kw = {"learning_rate": 0.01}
if OPT == "sgd":
    opt_kw["momentum"] = 0.9
trainer = gluon.Trainer(net.collect_params(), OPT, opt_kw)
loss_fn = lambda n, x, y: ((n(x) - y) ** 2).mean()
step = trainer.compile_step(net, loss_fn)
x = mx.nd.array(rng.randn(128, WIDTH).astype(onp.float32))
y = mx.nd.array(rng.randn(128, WIDTH).astype(onp.float32))

t_c = time.perf_counter()
loss = step(x, y, batch_size=128)          # warm: trace + compile
_ = float(loss.asnumpy().ravel()[0])       # drain
compile_s = time.perf_counter() - t_c
inv0, d0, f0, t0 = (_ndmod.invoke_count(), cached_step.dispatch_count(),
                    _fused.dispatch_count(), cached_step.trace_count())
c0 = dict(cached_step.cache_stats())
from mxnet_tpu import telemetry
_tel0 = telemetry.snapshot()               # steady-state baseline
t_start = time.perf_counter()
for _ in range(STEPS):
    loss = step(x, y, batch_size=128)
_ = float(loss.asnumpy().ravel()[0])       # fence
dt = time.perf_counter() - t_start
c1 = cached_step.cache_stats()
# the full namespaced steady-state counter delta (every registry
# counter); the hand-picked keys below stay as aliases so BENCH_*
# rounds remain comparable
_tel = {k: v for k, v in telemetry.delta(_tel0).items() if v}

import jax
from mxnet_tpu import program_store
_disk = program_store.disk_stats()
print(json.dumps({
    "platform": jax.default_backend(),
    "compiled": step.last_fallback_reason is None,
    "n_params": len(trainer._params),
    "steps": STEPS,
    "dispatches_per_step":
        (_ndmod.invoke_count() - inv0 + cached_step.dispatch_count() - d0
         + _fused.dispatch_count() - f0) / STEPS,
    "compiled_launches_per_step":
        (cached_step.dispatch_count() - d0) / STEPS,
    "retrace_count": cached_step.trace_count() - t0,
    "program_cache_hits": c1["hits"] - c0["hits"],
    "program_cache_misses": c1["misses"] - c0["misses"],
    "compile_s": round(compile_s, 3),
    "cache_hits": _disk["hits"],
    "cache_misses": _disk["misses"],
    "us_per_step": dt / STEPS * 1e6,
    "telemetry": _tel,
}))
"""


def run(mode: str, n: int) -> dict:
    env = dict(os.environ)
    env["MXNET_EAGER_JIT"] = mode
    env["EAGER_N"] = str(n)
    r = subprocess.run([sys.executable, "-u", "-c", _WORKER],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(f"mode {mode} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_trainer(fused: bool, n_params: int, steps: int = 20,
                opt: str = "sgd") -> dict:
    env = dict(os.environ)
    env["MXNET_FUSED_OPTIMIZER"] = "1" if fused else "0"
    env["TRAINER_PARAMS"] = str(n_params)
    env["TRAINER_STEPS"] = str(steps)
    env["TRAINER_OPT"] = opt
    r = subprocess.run([sys.executable, "-u", "-c", _TRAINER_WORKER],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(
            f"trainer lane (fused={fused}) failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run_train_step(steps: int = 20, opt: str = "sgd") -> dict:
    env = dict(os.environ)
    env["TRAIN_STEP_STEPS"] = str(steps)
    env["TRAINER_OPT"] = opt
    r = subprocess.run([sys.executable, "-u", "-c", _TRAIN_STEP_WORKER],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))) or ".")
    if r.returncode != 0:
        raise RuntimeError(
            f"train_step_compiled lane failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main() -> None:
    n = 100
    as_json = "--json" in sys.argv
    if "--train-step-only" in sys.argv:
        # bench.py's lanes[] entry point: just the compiled-step lane
        lane = run_train_step()
        print(json.dumps({"train_step_compiled": lane}) if as_json
              else lane)
        return
    if "--ops" in sys.argv:
        n = int(sys.argv[sys.argv.index("--ops") + 1])
    trainer_params = 56
    if "--trainer-params" in sys.argv:
        trainer_params = int(
            sys.argv[sys.argv.index("--trainer-params") + 1])
    off = run("0", n)
    on = run("2", n)
    result = {"platform": off["platform"], "n": n,
              "plain_us": off["us_per_op"], "jit_us": on["us_per_op"],
              "speedup": {k: round(off["us_per_op"][k] / on["us_per_op"][k], 2)
                          for k in off["us_per_op"]}}
    if "--no-trainer" not in sys.argv:
        t_fused = run_trainer(True, trainer_params)
        t_loop = run_trainer(False, trainer_params)
        result["trainer_step"] = {
            "n_params": trainer_params,
            "fused": t_fused, "loop": t_loop,
            "dispatch_reduction": round(
                t_loop["dispatches_per_step"]
                / max(t_fused["dispatches_per_step"], 1e-9), 1)}
        # the compiled whole-train-step lane rides next to the trainer
        # lane: same counters, but forward+backward fold in too
        result["train_step_compiled"] = run_train_step()
    if as_json:
        print(json.dumps(result))
        return
    print(f"eager dispatch latency ({off['platform']}, {n} calls/op, "
          "us/op incl. device time)")
    print(f"{'op':<20} {'plain':>10} {'per-op jit':>12} {'speedup':>9}")
    for k in off["us_per_op"]:
        print(f"{k:<20} {off['us_per_op'][k]:>10.1f} "
              f"{on['us_per_op'][k]:>12.1f} {result['speedup'][k]:>8.2f}x")
    if "trainer_step" in result:
        ts = result["trainer_step"]
        print(f"\ntrainer step ({ts['n_params']} params, sgd+momentum, "
              "dispatches per step())")
        print(f"{'path':<8} {'dispatches':>11} {'group-progs':>12} "
              f"{'us/step':>10}")
        for name, lane in (("fused", ts["fused"]), ("loop", ts["loop"])):
            print(f"{name:<8} {lane['dispatches_per_step']:>11.1f} "
                  f"{lane['compiled_group_dispatches_per_step']:>12.1f} "
                  f"{lane['us_per_step']:>10.1f}")
        print(f"dispatch reduction: {ts['dispatch_reduction']}x")
    if "train_step_compiled" in result:
        c = result["train_step_compiled"]
        print(f"\ncompiled train step ({c['n_params']} params, "
              f"{'compiled' if c['compiled'] else 'FELL BACK'}, "
              f"{c['steps']} steps)")
        print(f"dispatches/step {c['dispatches_per_step']:.1f} "
              f"(compiled launches {c['compiled_launches_per_step']:.1f}), "
              f"retraces {c['retrace_count']}, program cache "
              f"{c['program_cache_hits']}h/{c['program_cache_misses']}m, "
              f"compile {c['compile_s']:.1f}s (disk "
              f"{c['cache_hits']}h/{c['cache_misses']}m), "
              f"{c['us_per_step']:.1f} us/step")


if __name__ == "__main__":
    main()
