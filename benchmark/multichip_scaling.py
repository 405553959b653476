"""1→N device scaling of the SPMD compiled train step (kvstore='tpu').

The headline distributed claim (SNIPPETS.md / PAPER.md): Gluon Trainer
push/pull as an ICI-collective all-reduce INSIDE the one donated XLA
program, scaling ResNet-class training across a pod.  This lane measures
the claim directly: the SAME model and per-chip batch run on meshes of
1, 2, 4, ... N devices (subset meshes over the visible device world, the
``MXNET_SPMD_MESH=<n>`` knob), weak scaling — the global batch grows
with the mesh, so perfect scaling holds img/s/chip FLAT.

Per mesh size the lane reports:

- ``img_s_per_chip`` — samples/sec divided by mesh size (the headline;
  the ISSUE-1 bar is the 1→8 curve staying near-flat on ICI)
- ``step_ms_p50`` / ``step_ms_std`` — per-step wall time and its
  variance (collective jitter shows up here first)
- ``efficiency`` — img/s/chip relative to the 1-device lane
- ``param_bytes_per_device`` / ``opt_bytes_per_device`` — the
  memory-per-chip column (ISSUE-18), stamped from the ``spmd.*``
  computed gauges: flat across the data-parallel curve (replicated
  params) and ~1/N on the model-parallel sub-lane

Counter-based sanity rides along: every lane asserts ONE compiled launch
per step (no host-driven fan-out) and zero steady-state reshards.

The MODEL-PARALLEL sub-lane (ISSUE-18, docs/PERF.md "Sharded
training") holds the GLOBAL parameter count fixed while the fsdp axis
grows (``MXNET_SPMD_MESH=dp=1,fsdp=N`` for N = 1, 2, ... n): the
memory-per-chip claim is ``param_bytes_per_device`` and
``opt_bytes_per_device`` dropping ~1/N while the step stays one launch
with zero steady-state reshards.

On CPU the virtual 8-device world
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, set below for
standalone runs) exercises the identical partitioned-program path; the
numbers are honest about ``platform`` either way.

Usage: python benchmark/multichip_scaling.py [--json] [--out FILE]
       [--per-chip N] [--steps N]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", "") \
        and os.environ.get("JAX_PLATFORMS", "") == "cpu":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

PER_CHIP = int(os.environ.get("MULTICHIP_PER_CHIP", "32"))
STEPS = int(os.environ.get("MULTICHIP_STEPS", "20"))
WARMUP = 3
FEAT = 64


def _build(rows):
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(256, in_units=FEAT, activation="relu")
            self.d2 = nn.Dense(64, in_units=256, activation="relu")
            self.d3 = nn.Dense(16, in_units=64)

        def forward(self, x):
            return self.d3(self.d2(self.d1(x)))

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(0)
    for _n, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore="tpu")
    x = mx.nd.array(rng.randn(rows, FEAT))
    y = mx.nd.array(rng.randn(rows, 16))
    loss_fn = lambda n, a, b: ((n(a) - b) ** 2).mean()
    return net, trainer, loss_fn, x, y


def _lane(n_dev: int, per_chip: int, steps: int) -> dict:
    import jax

    from mxnet_tpu import cached_step
    from mxnet_tpu.parallel import spmd

    prev = os.environ.get("MXNET_SPMD_MESH")
    os.environ["MXNET_SPMD_MESH"] = str(n_dev)
    try:
        rows = per_chip * n_dev
        net, trainer, loss_fn, x, y = _build(rows)
        step = trainer.compile_step(net, loss_fn)
        for _ in range(WARMUP):
            loss = step(x, y, batch_size=rows)
        jax.block_until_ready(loss._data)
        d0 = cached_step.dispatch_count()
        r0 = spmd.reshard_count()
        times = []
        t_all = time.perf_counter()
        for _ in range(steps):
            t0 = time.perf_counter()
            loss = step(x, y, batch_size=rows)
            jax.block_until_ready(loss._data)   # per-step fence: the
            times.append(time.perf_counter() - t0)  # variance is the point
        elapsed = time.perf_counter() - t_all
        assert step.last_step_compiled, step.last_fallback_reason
        launches = (cached_step.dispatch_count() - d0) / steps
        times_ms = sorted(t * 1e3 for t in times)
        mean = sum(times_ms) / len(times_ms)
        std = (sum((t - mean) ** 2 for t in times_ms) / len(times_ms)) ** 0.5
        return {
            "devices": n_dev,
            "global_batch": rows,
            "img_s": rows * steps / elapsed,
            "img_s_per_chip": rows * steps / elapsed / n_dev,
            "step_ms_p50": times_ms[len(times_ms) // 2],
            "step_ms_mean": mean,
            "step_ms_std": std,
            "launches_per_step": launches,
            "reshards_after_warm": spmd.reshard_count() - r0,
            "mesh_devices": len(
                net.collect_params()["d1.weight"].data()
                ._data.sharding.device_set),
            # memory-per-chip column: replicated params hold this flat
            # across the data-parallel curve
            "param_bytes_per_device": spmd.param_bytes_per_device(),
            "opt_bytes_per_device": spmd.opt_bytes_per_device(),
        }
    finally:
        if prev is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev


def _model_lane(n_fsdp: int, per_chip: int, steps: int) -> dict:
    """Model-parallel sub-lane: GLOBAL params fixed, fsdp axis grows —
    the claim is memory per chip dropping ~1/N, not throughput."""
    import jax

    from mxnet_tpu import cached_step
    from mxnet_tpu.parallel import spmd

    prev = os.environ.get("MXNET_SPMD_MESH")
    prev_min = os.environ.get("MXNET_FSDP_MIN_SIZE")
    os.environ["MXNET_SPMD_MESH"] = f"dp=1,fsdp={n_fsdp}"
    os.environ["MXNET_FSDP_MIN_SIZE"] = "1"     # the bench MLP is small
    try:
        rows = per_chip                          # fixed global batch too
        net, trainer, loss_fn, x, y = _build(rows)
        step = trainer.compile_step(net, loss_fn)
        for _ in range(WARMUP):
            loss = step(x, y, batch_size=rows)
        jax.block_until_ready(loss._data)
        d0 = cached_step.dispatch_count()
        r0 = spmd.reshard_count()
        t_all = time.perf_counter()
        for _ in range(steps):
            loss = step(x, y, batch_size=rows)
            jax.block_until_ready(loss._data)
        elapsed = time.perf_counter() - t_all
        assert step.last_step_compiled, step.last_fallback_reason
        total = sum(p.data()._data.nbytes
                    for _n, p in sorted(net.collect_params().items()))
        return {
            "fsdp": n_fsdp,
            "global_batch": rows,
            "img_s": rows * steps / elapsed,
            "step_ms_mean": elapsed * 1e3 / steps,
            "launches_per_step":
                (cached_step.dispatch_count() - d0) / steps,
            "reshards_after_warm": spmd.reshard_count() - r0,
            "param_bytes_global": total,
            "param_bytes_per_device": spmd.param_bytes_per_device(),
            "opt_bytes_per_device": spmd.opt_bytes_per_device(),
        }
    finally:
        if prev is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev
        if prev_min is None:
            os.environ.pop("MXNET_FSDP_MIN_SIZE", None)
        else:
            os.environ["MXNET_FSDP_MIN_SIZE"] = prev_min


def _moe_lane(steps: int) -> dict:
    """Expert-parallel MoE sub-lane (ISSUE 20, docs/PERF.md "Every-axis
    mesh"): an MoEBlock under MXNET_SPMD_MESH='ep=4,dp=2' — the value is
    routed tokens/s/chip through the ONE donated step (gating, dispatch/
    combine, ep-sharded expert einsums, folded aux head, fused update).
    Capacity-drop counters ride along (host recomputation of the same
    deterministic gating state), stamped as ``moe.*`` gauges so
    check_perf_delta defends both the throughput and the drop rate."""
    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import cached_step, gluon, telemetry
    from mxnet_tpu.parallel import moe as moe_mod, spmd

    n_dev = len(jax.devices())
    if n_dev < 8:
        return {"skipped": f"only {n_dev} device(s)"}
    G, S, M, H, E = 8, 16, 32, 64, 4
    prev = os.environ.get("MXNET_SPMD_MESH")
    prev_min = os.environ.get("MXNET_FSDP_MIN_SIZE")
    os.environ["MXNET_SPMD_MESH"] = "ep=4,dp=2"
    os.environ["MXNET_FSDP_MIN_SIZE"] = "1"
    try:
        net = moe_mod.MoEBlock(units=M, hidden=H, num_experts=E, k=2)
        net.initialize(mx.init.Xavier())
        rng = onp.random.RandomState(0)
        for _n, p in sorted(net.collect_params().items()):
            p.data()._set_data(
                mx.nd.array(rng.randn(*p.shape).astype(onp.float32)
                            * 0.1)._data)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01, "momentum": 0.9},
                                kvstore="tpu")
        loss_fn = lambda n, a: ((n(a)) ** 2).mean()
        x_host = rng.randn(G, S, M).astype(onp.float32)
        x = mx.nd.array(x_host)
        step = trainer.compile_step(net, loss_fn)
        for _ in range(WARMUP):
            loss = step(x, batch_size=G)
        jax.block_until_ready(loss._data)
        assert step.last_step_compiled, step.last_fallback_reason
        d0, r0 = cached_step.dispatch_count(), spmd.reshard_count()
        t_all = time.perf_counter()
        for _ in range(steps):
            loss = step(x, batch_size=G)
            jax.block_until_ready(loss._data)
        elapsed = time.perf_counter() - t_all
        tokens_s = G * S * steps / elapsed
        # drop counters: recompute the deterministic gating state on the
        # host with the trained gate — survivors vs G*S*k routed slots
        import jax.numpy as jnp

        gate_w = net.collect_params()["gate.weight"].data()._data
        disp, _comb, _aux = moe_mod.top_k_gating(
            jnp.asarray(x_host), gate_w, num_experts=E, k=2)
        routed = G * S * 2
        survivors = int(onp.asarray(disp).sum())
        ew = net.collect_params()["expert.ffn_1.weight"].data()._data
        lane = {
            "skipped": None,
            "devices": n_dev,
            "tokens_per_step": G * S,
            "tokens_s": tokens_s,
            "tokens_s_per_chip": tokens_s / n_dev,
            "step_ms_mean": elapsed * 1e3 / steps,
            "launches_per_step":
                (cached_step.dispatch_count() - d0) / steps,
            "reshards_after_warm": spmd.reshard_count() - r0,
            "expert_sharded": bool(ew.sharding.spec
                                   and ew.sharding.spec[0] == "ep"),
            "routed_slots": routed,
            "dropped_slots": routed - survivors,
            "drop_rate": (routed - survivors) / routed,
        }
        telemetry.gauge(
            "moe.tokens_per_s_per_chip",
            "MoE bench lane: routed tokens/s/chip through the one "
            "donated ep-sharded step").set(lane["tokens_s_per_chip"])
        telemetry.gauge(
            "moe.dropped_slots",
            "MoE bench lane: over-capacity slots dropped by the "
            "deterministic top-k gating on the bench batch").set(
            lane["dropped_slots"])
        return lane
    finally:
        if prev is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev
        if prev_min is None:
            os.environ.pop("MXNET_FSDP_MIN_SIZE", None)
        else:
            os.environ["MXNET_FSDP_MIN_SIZE"] = prev_min


def _pp_lane(steps: int) -> dict:
    """Pipeline-parallel sub-lane (ISSUE 20): a 2-stage PipelineBlock
    under MXNET_SPMD_MESH='pp=2,dp=2,fsdp=2', stepped at two microbatch
    counts (M=2, M=4).  The per-microbatch ramp cost falls out of the
    step-time slope over 1/M — T(M) = A + B/M with B the fill/drain
    (bubble) term — giving a MEASURED bubble fraction next to the
    GPipe closed form (S-1)/(M+S-1).  Stamped as ``pp.*`` gauges so
    check_perf_delta catches a bubble regression even when wall-clock
    noise hides it."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import cached_step, gluon, telemetry
    from mxnet_tpu.parallel import pipeline as pipe_mod, spmd

    n_dev = len(jax.devices())
    if n_dev < 8:
        return {"skipped": f"only {n_dev} device(s)"}
    S_STAGES, DIM, BATCH = 2, 64, 8
    prev = os.environ.get("MXNET_SPMD_MESH")
    prev_min = os.environ.get("MXNET_FSDP_MIN_SIZE")
    os.environ["MXNET_SPMD_MESH"] = "pp=2,dp=2,fsdp=2"
    os.environ["MXNET_FSDP_MIN_SIZE"] = "1"
    try:
        def measure(num_micro: int) -> dict:
            mesh = spmd.resolve_mesh()
            rng = onp.random.RandomState(1)
            ws = [jnp.asarray((rng.randn(DIM, DIM) * 0.2)
                              .astype(onp.float32))
                  for _ in range(S_STAGES)]

            def stage(params, xx):
                return jnp.tanh(xx @ params["w"])

            pipe = pipe_mod.HeteroPipeline(
                [stage] * S_STAGES, [{"w": w} for w in ws], mesh,
                num_microbatches=num_micro,
                example_x=jnp.zeros((BATCH, DIM), jnp.float32))
            blk = pipe_mod.PipelineBlock(pipe)
            trainer = gluon.Trainer(blk.collect_params(), "sgd",
                                    {"learning_rate": 0.05,
                                     "momentum": 0.9}, kvstore="tpu")
            loss_fn = lambda n, a: ((n(a)) ** 2).sum()
            x = mx.nd.array(rng.randn(BATCH, DIM).astype(onp.float32))
            step = trainer.compile_step(blk, loss_fn)
            for _ in range(WARMUP):
                loss = step(x, batch_size=BATCH)
            jax.block_until_ready(loss._data)
            assert step.last_step_compiled, step.last_fallback_reason
            d0, r0 = cached_step.dispatch_count(), spmd.reshard_count()
            t_all = time.perf_counter()
            for _ in range(steps):
                loss = step(x, batch_size=BATCH)
                jax.block_until_ready(loss._data)
            elapsed = time.perf_counter() - t_all
            return {
                "num_microbatches": num_micro,
                "step_ms_mean": elapsed * 1e3 / steps,
                "launches_per_step":
                    (cached_step.dispatch_count() - d0) / steps,
                "reshards_after_warm": spmd.reshard_count() - r0,
                "bubble_fraction_theoretical":
                    pipe_mod.bubble_fraction(S_STAGES, num_micro),
            }

        m2 = measure(2)
        m4 = measure(4)
        # T(M) = A + B/M: B/M is the fill/drain ramp's share of the step
        b_term = (m2["step_ms_mean"] - m4["step_ms_mean"]) / (0.5 - 0.25)
        measured = (max(0.0, b_term) / 4) / m4["step_ms_mean"] \
            if m4["step_ms_mean"] else 0.0
        lane = {
            "skipped": None,
            "devices": n_dev,
            "stages": S_STAGES,
            "step_ms_mean": m4["step_ms_mean"],
            "launches_per_step": m4["launches_per_step"],
            "reshards_after_warm": (m2["reshards_after_warm"]
                                    + m4["reshards_after_warm"]),
            "bubble_fraction_measured": measured,
            "bubble_fraction_theoretical":
                m4["bubble_fraction_theoretical"],
            "points": [m2, m4],
        }
        telemetry.gauge(
            "pp.bubble_fraction_measured",
            "pp bench lane: fill/drain share of step time from the "
            "T(M) = A + B/M slope fit at M=4").set(measured)
        telemetry.gauge(
            "pp.step_ms_mean",
            "pp bench lane: mean step wall-time (ms) at M=4 on the "
            "pp=2,dp=2,fsdp=2 mesh").set(lane["step_ms_mean"])
        return lane
    finally:
        if prev is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev
        if prev_min is None:
            os.environ.pop("MXNET_FSDP_MIN_SIZE", None)
        else:
            os.environ["MXNET_FSDP_MIN_SIZE"] = prev_min


def run_moe(steps: int = STEPS) -> dict:
    import jax

    from mxnet_tpu import program_store, telemetry

    t_c0 = program_store.compile_seconds()
    lane = _moe_lane(steps)
    disk = program_store.disk_stats()
    telemetry.flush()
    out = {
        "metric": "moe_tokens_per_s_per_chip",
        "value": lane.get("tokens_s_per_chip", 0.0),
        "unit": "tokens/s/chip",
        "n_devices": len(jax.devices()),
        "steps": steps,
        "platform": jax.default_backend(),
        "compile_s": round(program_store.compile_seconds() - t_c0, 3),
        "cache_hits": disk["hits"],
        "cache_misses": disk["misses"],
        "telemetry": telemetry.snapshot(),
    }
    out.update({k: v for k, v in lane.items() if k != "telemetry"})
    return out


def run_pp(steps: int = STEPS) -> dict:
    import jax

    from mxnet_tpu import program_store, telemetry

    t_c0 = program_store.compile_seconds()
    lane = _pp_lane(steps)
    disk = program_store.disk_stats()
    telemetry.flush()
    out = {
        "metric": "pp_bubble_fraction",
        "value": lane.get("bubble_fraction_measured", 0.0),
        "unit": "fraction",
        "n_devices": len(jax.devices()),
        "steps": steps,
        "platform": jax.default_backend(),
        "compile_s": round(program_store.compile_seconds() - t_c0, 3),
        "cache_hits": disk["hits"],
        "cache_misses": disk["misses"],
        "telemetry": telemetry.snapshot(),
    }
    out.update({k: v for k, v in lane.items() if k != "telemetry"})
    return out


def run(per_chip: int = PER_CHIP, steps: int = STEPS,
        sizes=None) -> dict:
    import jax

    n = len(jax.devices())
    if sizes is None:
        sizes = [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= n]
        if n not in sizes:
            sizes.append(n)
    from mxnet_tpu import program_store

    t_c0 = program_store.compile_seconds()
    curve = [_lane(s, per_chip, steps) for s in sizes]
    base = curve[0]["img_s_per_chip"]
    for lane in curve:
        lane["efficiency"] = lane["img_s_per_chip"] / base if base else 0.0
    # model-parallel sub-lane: fixed global params, growing fsdp axis
    model_curve = [_model_lane(s, per_chip, steps) for s in sizes]
    mp_base = model_curve[0]["param_bytes_per_device"]
    for lane in model_curve:
        lane["param_bytes_frac"] = (
            lane["param_bytes_per_device"] / mp_base if mp_base else 1.0)
    head = curve[-1]
    disk = program_store.disk_stats()
    from mxnet_tpu import telemetry

    telemetry.flush()   # flight-recorder shard for the lane's fleet merge
    return {
        "metric": "multichip_img_s_per_chip",
        "value": head["img_s_per_chip"],
        "unit": "img/s/chip",
        "n_devices": n,
        "per_chip_batch": per_chip,
        "steps": steps,
        "platform": jax.default_backend(),
        "scaling_efficiency": head["efficiency"],
        "step_ms_std_max": max(l["step_ms_std"] for l in curve),
        # one program per mesh size: the cold-start tax this lane pays
        "compile_s": round(program_store.compile_seconds() - t_c0, 3),
        "cache_hits": disk["hits"],
        "cache_misses": disk["misses"],
        # memory-per-chip headline: per-device param bytes on the
        # largest fsdp mesh as a fraction of the 1-device footprint
        "model_parallel_param_bytes_frac":
            model_curve[-1]["param_bytes_frac"],
        "curve": curve,
        "model_parallel_curve": model_curve,
    }


def main():
    from mxnet_tpu import program_store

    program_store.enable_persistent_cache(min_compile_secs=1)
    argv = sys.argv[1:]

    def _val(flag, default):
        if flag in argv:
            return int(argv[argv.index(flag) + 1])
        return default

    if "--moe" in argv:
        result = run_moe(steps=_val("--steps", STEPS))
        if "--json" in argv:
            print(json.dumps(result))
        elif result.get("skipped"):
            print(f"moe lane SKIPPED ({result['skipped']})")
        else:
            print(f"moe (ep=4,dp=2, {result['platform']}): "
                  f"{result['value']:.0f} tokens/s/chip, "
                  f"{result['step_ms_mean']:.2f} ms/step, "
                  f"{result['launches_per_step']:.1f} launches/step, "
                  f"{result['dropped_slots']}/{result['routed_slots']} "
                  f"slots dropped")
        return 0
    if "--pp" in argv:
        result = run_pp(steps=_val("--steps", STEPS))
        if "--json" in argv:
            print(json.dumps(result))
        elif result.get("skipped"):
            print(f"pp lane SKIPPED ({result['skipped']})")
        else:
            print(f"pp (pp=2,dp=2,fsdp=2, {result['platform']}): "
                  f"bubble {result['value']:.2f} measured / "
                  f"{result['bubble_fraction_theoretical']:.2f} "
                  f"theoretical, {result['step_ms_mean']:.2f} ms/step, "
                  f"{result['launches_per_step']:.1f} launches/step")
        return 0
    result = run(per_chip=_val("--per-chip", PER_CHIP),
                 steps=_val("--steps", STEPS))
    if "--out" in argv:
        path = argv[argv.index("--out") + 1]
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    if "--json" in argv:
        print(json.dumps(result))
    else:
        print(f"multichip scaling ({result['platform']}, "
              f"{result['n_devices']} devices, weak scaling, "
              f"{result['per_chip_batch']}/chip):")
        for lane in result["curve"]:
            print(f"  {lane['devices']:>3} dev  "
                  f"{lane['img_s_per_chip']:>10.0f} img/s/chip  "
                  f"p50 {lane['step_ms_p50']:.2f} ms  "
                  f"std {lane['step_ms_std']:.2f} ms  "
                  f"eff {lane['efficiency']:.2f}  "
                  f"launches/step {lane['launches_per_step']:.1f}  "
                  f"{lane['param_bytes_per_device'] / 1024:.1f} "
                  f"KiB params/chip")
        print("model parallel (fixed global params, dp=1,fsdp=N):")
        for lane in result["model_parallel_curve"]:
            print(f"  fsdp={lane['fsdp']:<3} "
                  f"{lane['param_bytes_per_device'] / 1024:>8.1f} KiB "
                  f"params/chip ({lane['param_bytes_frac']:.2f}x)  "
                  f"{lane['opt_bytes_per_device'] / 1024:>8.1f} KiB "
                  f"opt/chip  "
                  f"launches/step {lane['launches_per_step']:.1f}  "
                  f"reshards {lane['reshards_after_warm']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
