"""Pipelined-train-loop A/B: the async pipeline engine (PR 5) vs the
synchronous loop, SAME model / batches / optimizer.

Synchronous lane (the pre-pipeline loop): per batch — a blocking
device_put (`mx.nd.array`), one compiled train-step dispatch, and a
host-side metric update (`MXNET_METRIC_DEVICE=0`, the silent per-batch
``float()`` sync).  Pipelined lane: `engine.prefetch` stages batch N+1
into HBM on the transfer thread while step N runs, and the Loss metric
accumulates ON DEVICE (host read only at the final ``.get()``).

Both lanes run under a ``profiler.StepTimeline``; the headline metric is
``device_idle_gap_us`` — mean per-step host time OUTSIDE the dispatch
phase (the window in which the one-program-per-step device can run dry).
The lane also reports the steady-state dispatch-ahead depth (how many
batches were already staged each time the loop took one — the PR-5
acceptance bar is >= 2) and host syncs per step (budget: 0 in the
pipelined steady state).

Counter-based + wall-clock: equally meaningful on the CPU backend,
honest about platform either way.

Usage: python benchmark/pipeline_latency.py [--pipeline-only] [--json]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = int(os.environ.get("PIPELINE_STEPS", "30"))
BATCH = 32
FEAT = 64
DEPTH = 3


def _build():
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(128, in_units=FEAT, activation="relu")
            self.d2 = nn.Dense(16, in_units=128)

        def forward(self, x):
            return self.d2(self.d1(x))

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(0)
    for _n, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = lambda n, x, y: ((n(x) - y) ** 2).mean()
    return net, trainer, loss_fn


def _host_batches(seed=7, n=STEPS):
    import numpy as onp

    rng = onp.random.RandomState(seed)
    return [(rng.randn(BATCH, FEAT).astype(onp.float32),
             rng.randn(BATCH, 16).astype(onp.float32)) for _ in range(n)]


def _run_loop(pipelined: bool) -> dict:
    import mxnet_tpu as mx
    from mxnet_tpu import engine, metric, profiler
    from mxnet_tpu.ndarray import ndarray as _ndmod

    os.environ["MXNET_METRIC_DEVICE"] = "1" if pipelined else "0"
    try:
        net, trainer, loss_fn = _build()
        step = trainer.compile_step(net, loss_fn)
        batches = _host_batches()
        # warm: trace + compile outside the timed region
        wx, wy = batches[0]
        t_c = time.perf_counter()
        loss = step(mx.nd.array(wx), mx.nd.array(wy), batch_size=BATCH)
        float(loss.asnumpy().ravel()[0])
        compile_s = time.perf_counter() - t_c
        engine.waitall()

        loss_metric = metric.Loss()
        # warm the metric path too (the device kernel's first update
        # traces/compiles) — trace cost must not book as steady-state
        loss_metric.update(0, loss)
        loss_metric.get()
        loss_metric.reset()
        tl = profiler.StepTimeline("pipeline" if pipelined else "sync")
        pf = None
        if pipelined:
            pf = engine.DevicePrefetcher(iter(batches), depth=DEPTH)
            time.sleep(0.05)         # let the transfer thread fill HBM
            it = pf
        else:
            it = iter(batches)
        h0 = _ndmod.host_sync_count()
        ms0 = metric.host_sync_count()
        t_wall0 = time.perf_counter_ns()
        last = None
        for _ in range(len(batches)):
            with tl.phase("h2d"):
                if pipelined:
                    x, y = next(it)
                else:
                    hx, hy = next(it)
                    x, y = mx.nd.array(hx), mx.nd.array(hy)
            with tl.phase("dispatch"):
                last = step(x, y, batch_size=BATCH)
            with tl.phase("read"):
                loss_metric.update(0, last)
            tl.step()
        last.wait_to_read()          # device fence FIRST: the final fold
        # must not book the last step's in-flight compute as host time
        with tl.phase("read"):
            name, value = loss_metric.get()     # the ONE pipelined read
        wall_us = (time.perf_counter_ns() - t_wall0) / 1000.0
        out = tl.summary()
        out.update({
            "mode": "pipelined" if pipelined else "sync",
            "loss_metric": round(float(value), 6),
            "host_syncs_per_step":
                round((_ndmod.host_sync_count() - h0) / len(batches), 2),
            "metric_host_syncs":
                metric.host_sync_count() - ms0,
            "wall_us": round(wall_us, 1),
            "compiled": step.last_step_compiled,
            "compile_s": round(compile_s, 3),
        })
        if pf is not None:
            s = pf.stats()
            out["steady_ahead_depth"] = s["steady_ahead"]
            out["max_ahead_depth"] = s["max_ahead"]
            pf.close()
        return out
    finally:
        os.environ.pop("MXNET_METRIC_DEVICE", None)


def run() -> dict:
    import jax

    from mxnet_tpu import program_store

    from mxnet_tpu import telemetry

    tel0 = telemetry.snapshot()
    sync = _run_loop(False)
    pipe = _run_loop(True)
    gap_s, gap_p = sync["device_idle_gap_us"], pipe["device_idle_gap_us"]
    disk = program_store.disk_stats()
    return {
        "platform": jax.default_backend(),
        # full namespaced counter delta across both loops; the
        # hand-picked keys below stay as aliases for BENCH_* continuity
        "telemetry": {k: v for k, v in telemetry.delta(tel0).items()
                      if v},
        "steps": STEPS,
        "depth": DEPTH,
        "compile_s": round(sync["compile_s"] + pipe["compile_s"], 3),
        "cache_hits": disk["hits"],
        "cache_misses": disk["misses"],
        "sync": sync,
        "pipelined": pipe,
        "steady_ahead_depth": pipe.get("steady_ahead_depth", 0),
        "device_idle_gap_us": gap_p,
        "device_idle_gap_us_sync": gap_s,
        "idle_gap_reduction": round(gap_s / max(gap_p, 0.1), 2),
        "wall_speedup": round(sync["wall_us"] / max(pipe["wall_us"], 1), 3),
    }


def main():
    from mxnet_tpu import program_store

    program_store.enable_persistent_cache(min_compile_secs=1)
    res = {"pipeline": run()}
    if "--json" in sys.argv:
        print(json.dumps(res), flush=True)
    else:
        p = res["pipeline"]
        print(f"platform {p['platform']}, {p['steps']} steps, "
              f"depth {p['depth']}")
        for mode in ("sync", "pipelined"):
            r = p[mode]
            print(f"  {mode:<10} idle-gap {r['device_idle_gap_us']:>8.1f} "
                  f"us/step  wall {r['wall_us_per_step']:>8.1f} us/step  "
                  f"host-syncs/step {r['host_syncs_per_step']}")
        print(f"  dispatch-ahead depth (steady) {p['steady_ahead_depth']}, "
              f"idle-gap reduction {p['idle_gap_reduction']}x, "
              f"wall speedup {p['wall_speedup']}x")


if __name__ == "__main__":
    main()
