#!/usr/bin/env python
"""CI gate: the compiled train step AND the serving path must stay
inside their dispatch budgets.

Runs a tiny MLP under both step modes and FAILS (exit 1) if the compiled
mode exceeds the documented budget — guarding against silent de-fusion
regressions (an eager op sneaking back into the hot loop, a per-step
re-trace, a group program splitting off the whole-step program):

- compiled mode: exactly ``1`` compiled launch per step
  (``cached_step.dispatch_count``), ``0`` eager op dispatches
  (``ndarray.invoke_count``), ``0`` separate fused group-program launches
  (``fused.dispatch_count`` — the update must ride INSIDE the step
  program), and ``0`` re-traces across constant-shape steps;
- eager mode (comparison lane, printed, not gated): the tape path's
  dispatches/step.

The INFERENCE gate (PR 4, docs/PERF.md "Serving") drives a
``serving.ServingEngine`` over a randomized variable-length request
stream after warming every bucket: exactly ``1`` compiled launch per
dispatched batch, ``0`` re-traces, and the compiled-program count
bounded by the bucket grid.

The DECODE gate (PR 8, docs/PERF.md "Continuous batching + paged
KV-cache") drives a ``serving_decode.GenerativeEngine`` through a
concurrent join/retire storm: live programs == prefill buckets + 1
decode, ``0`` re-traces after warm-up, exactly ``1`` dispatch per
decode iteration (plus one per prefill, nothing else), and ``0``
leaked KV pages after ``engine.waitall()``.

Invoked by the test suite (tests/test_cached_step.py /
tests/test_serving.py) exactly like tools/check_fault_sites.py, and
runnable standalone:
``JAX_PLATFORMS=cpu python tools/check_dispatch_budget.py``.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# standalone runs need the virtual multi-device CPU world BEFORE jax
# initializes (the suite's conftest already provides it in-process)
if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

# the budget the docs promise (docs/PERF.md "Compiled whole-train-step" +
# "Pipelined train loop"): a steady-state non-AMP compiled step performs
# ZERO blocking host syncs; with AMP at most ONE read per step, and it
# must be the DEFERRED read (step N-1's flag, never a stall on step N)
BUDGET = {"compiled_launches_per_step": 1, "eager_invokes_per_step": 0,
          "group_launches_per_step": 0, "retraces_after_warm": 0,
          "host_syncs_per_step": 0}
AMP_BUDGET = {"host_syncs_per_step": 1, "deferred_reads_per_step": 1}
# the serving budget (docs/PERF.md "Serving: shape buckets + dynamic
# batching"): steady state over a variable-length stream
INFER_BUDGET = {"launches_per_batch": 1, "retraces_after_warm": 0,
                "programs_over_buckets": 0}
# the DECODE budget (docs/PERF.md "Continuous batching + paged
# KV-cache"): across a join/retire storm the generative engine holds
# exactly prefill-buckets + 1 decode program, re-traces nothing after
# warm-up, performs exactly ONE dispatch per decode iteration (and one
# per prefill), and leaks zero KV pages once drained
DECODE_BUDGET = {"retraces_after_warm": 0, "programs_over_grid": 0,
                 "extra_dispatches": 0, "leaked_pages": 0}
# the PROGRAM-STORE budget (docs/PERF.md "ProgramStore"): steady state
# keeps the live-program count at the declared grid (train: 1 signature
# -> 1 program; serving: <= buckets, covered by programs_over_buckets),
# performs ZERO evictions, and — with MXNET_PROGRAM_CACHE_DIR set — a
# WARM SECOND PROCESS replaying the same train+serving workload
# performs ZERO fresh XLA compiles (all disk/memory hits, bit-exact
# outputs)
STORE_BUDGET = {"evictions_after_warm": 0, "live_train_programs_over": 0,
                "second_process_compiles": 0}
# the SENTINEL budget (docs/ROBUSTNESS.md "Training-integrity
# sentinel"): with a Sentinel attached at cadence E the step STAYS one
# compiled launch with zero retraces — the digest rides an in-program
# lax.cond selected by a traced flag — and the only added host syncs
# are the deferred digest reads (exactly one per cadence window, never
# one per step)
SENTINEL_BUDGET = {"compiled_launches_per_step": 1,
                   "eager_invokes_per_step": 0,
                   "retraces_after_warm": 0,
                   "replica_divergence": 0}
# the ROUTER budget (docs/ROBUSTNESS.md "Partial serving failure"):
# zero-overhead-off — a ReplicaRouter wrapping ONE healthy replica with
# hedging off and the breaker closed adds NOTHING to the engine's
# per-request costs: dispatch count, retrace count, and host syncs for
# an identical request stream must equal the bare engine's, and the
# token streams must be identical
ROUTER_BUDGET = {"extra_dispatches": 0, "extra_retraces": 0,
                 "extra_host_syncs": 0}
# the SPEC budget (ISSUE 19, docs/PERF.md "Speculative decoding +
# sampled decode"): with MXNET_SPEC_DECODE=1 and a high-agreement
# draft, a mixed greedy/sampled join/retire storm holds the BOUNDED
# program set (target grid + draft prefill buckets + 1 draft round + 1
# verify per k — all warmup-compiled), re-traces NOTHING, pays
# strictly LESS than one target-model dispatch per committed token
# (the k-for-1 win), and leaks zero pages across both geometries;
# with MXNET_SPEC_DECODE=0 a draft-attached engine's greedy stream is
# byte-identical in dispatch budget (and tokens) to a draft-free one
SPEC_BUDGET = {"retraces_after_warm": 0, "programs_over_grid": 0,
               "leaked_pages": 0, "greedy_off_extra_dispatches": 0,
               "greedy_off_extra_retraces": 0}
# the MESH budget (docs/PERF.md "Pod-scale SPMD train step"): under
# kvstore='tpu' the data-parallel step stays ONE compiled launch — the
# SPMD partitioner fans out over the mesh, never the host (no per-chip
# dispatch fan-out) — with ZERO steady-state host-side cross-device
# copies (params/state placed once; prefetched/sharded batches pass
# through; spmd.reshard_count stays flat) and every batch truly sharded
# (spmd.replicated_batch_count flat: an indivisible batch would silently
# run replicated = un-scaled)
MESH_BUDGET = {"compiled_launches_per_step": 1, "eager_invokes_per_step": 0,
               "group_launches_per_step": 0, "retraces_after_warm": 0,
               "host_syncs_per_step": 0, "reshards_after_warm": 0,
               "replicated_batches": 0}
# the FSDP budget (docs/PERF.md "Sharded training"): with
# MXNET_SPMD_MESH='dp=2,fsdp=2' params AND optimizer state shard over
# the fsdp axis, yet the step STAYS one compiled launch with zero
# retraces and zero steady-state reshards — the partitioner schedules
# the all-gather/reduce-scatter INSIDE the one donated program, never
# the host.  Accumulation sub-lane: compile_step(accum_steps=N) pays
# exactly N+1 dispatches per window (N microbatch grad programs + ONE
# fused update), zero retraces once both programs are warm —
# accum_extra_dispatches is measured-per-window minus (N+1)
FSDP_BUDGET = {"compiled_launches_per_step": 1, "eager_invokes_per_step": 0,
               "group_launches_per_step": 0, "retraces_after_warm": 0,
               "host_syncs_per_step": 0, "reshards_after_warm": 0,
               "replicated_batches": 0, "accum_extra_dispatches": 0,
               "accum_retraces_after_warm": 0}
# the PP budget (ISSUE 20, docs/PERF.md "Every-axis mesh"): with
# MXNET_SPMD_MESH='pp=2,dp=2,fsdp=2' a PipelineBlock-backed step stays
# ONE compiled launch — the GPipe microbatch schedule is scan-INTERNAL,
# never a per-stage or per-microbatch host dispatch — with 0 retraces,
# 0 steady-state reshards (the packed stage buffer is placed P('pp')
# once), batches sharded over dp only, and PR-18 accumulation still at
# exactly N+1 dispatches per window on the pp mesh
PP_BUDGET = {"compiled_launches_per_step": 1, "eager_invokes_per_step": 0,
             "group_launches_per_step": 0, "retraces_after_warm": 0,
             "reshards_after_warm": 0, "replicated_batches": 0,
             "accum_extra_dispatches": 0, "accum_retraces_after_warm": 0}
# the MOE budget (ISSUE 20, docs/PERF.md "Every-axis mesh"): with
# MXNET_SPMD_MESH='ep=4,dp=2' an MoEBlock step — dispatch/combine,
# expert einsums, the load-balance aux head folded into the loss, and
# the fused update over ep-sharded expert weights — stays ONE compiled
# launch with 0 retraces and 0 steady-state reshards
MOE_BUDGET = {"compiled_launches_per_step": 1, "eager_invokes_per_step": 0,
              "group_launches_per_step": 0, "retraces_after_warm": 0,
              "reshards_after_warm": 0, "replicated_batches": 0}
STEPS = 5
INFER_REQUESTS = 24
INFER_MAXLEN = 16


def _build(seed: int = 0, rows: int = 6, kvstore: str = "device"):
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(16, in_units=8, activation="relu")
            self.d2 = nn.Dense(4, in_units=16)

        def forward(self, x):
            return self.d2(self.d1(x))

    net = Net()
    net.initialize(mx.init.Xavier())
    rng = onp.random.RandomState(seed)
    for _name, p in sorted(net.collect_params().items()):
        p.data()._set_data(mx.nd.array(rng.randn(*p.shape) * 0.1)._data)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore=kvstore)
    data = mx.nd.array(rng.randn(rows, 8))
    label = mx.nd.array(rng.randn(rows, 4))
    loss_fn = lambda n, x, y: ((n(x) - y) ** 2).mean()
    return net, trainer, loss_fn, data, label


def _measure(compiled: bool, with_amp: bool = False) -> dict:
    import mxnet_tpu as mx
    from mxnet_tpu import amp, cached_step
    from mxnet_tpu.ndarray import ndarray as _ndmod
    from mxnet_tpu.optimizer import fused

    net, trainer, loss_fn, data, label = _build()
    if with_amp:
        trainer._amp_loss_scaler = amp.LossScaler(init_scale=8.0)
    if compiled:
        step = trainer.compile_step(net, loss_fn)

        def one_step():
            return step(data, label, batch_size=6)
    else:
        def one_step():
            with mx.autograd.record():
                loss = loss_fn(net, data, label)
            loss.backward()
            trainer.step(6)
            return loss

    loss = one_step()                    # warm: trace + state create
    float(loss.asnumpy().ravel()[0])     # drain
    inv0, d0, f0, t0 = (_ndmod.invoke_count(), cached_step.dispatch_count(),
                        fused.dispatch_count(), cached_step.trace_count())
    h0, dr0 = _ndmod.host_sync_count(), cached_step.deferred_read_count()
    for _ in range(STEPS):
        loss = one_step()
    h1, dr1 = _ndmod.host_sync_count(), cached_step.deferred_read_count()
    float(loss.asnumpy().ravel()[0])     # fence (after the sync window)
    out = {
        "mode": ("compiled" if compiled else "eager")
                + ("+amp" if with_amp else ""),
        "used_compiled": compiled and step.last_step_compiled,
        "eager_invokes_per_step":
            (_ndmod.invoke_count() - inv0) / STEPS,
        "compiled_launches_per_step":
            (cached_step.dispatch_count() - d0) / STEPS,
        "group_launches_per_step": (fused.dispatch_count() - f0) / STEPS,
        "retraces_after_warm": cached_step.trace_count() - t0,
        "host_syncs_per_step": (h1 - h0) / STEPS,
        "deferred_reads_per_step": (dr1 - dr0) / STEPS,
    }
    out["dispatches_per_step"] = (out["eager_invokes_per_step"]
                                  + out["compiled_launches_per_step"]
                                  + out["group_launches_per_step"])
    # program-store lane input: one constant-shape signature must hold
    # exactly ONE live program in this step's keyspace
    out["live_programs"] = len(step._programs) if compiled else 0
    return out


def _measure_sentinel() -> dict:
    """Training-integrity sentinel lane: a Sentinel at cadence 2 rides
    the compiled step for 6 steps — still 1 launch/step, 0 retraces,
    digest reads == cadence windows (each a deferred read, counted as a
    host sync), fingerprints bit-stable across two identical windows,
    and the in-program fold equals a host recomputation of the same
    state."""
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import cached_step, sentinel, telemetry
    from mxnet_tpu.ndarray import ndarray as _ndmod

    net, trainer, loss_fn, data, label = _build(seed=7)
    step = trainer.compile_step(net, loss_fn)
    snt = sentinel.Sentinel(step=step, every=2)
    loss = step(data, label, batch_size=6)          # warm (call 1)
    float(loss.asnumpy().ravel()[0])
    d0, t0 = cached_step.dispatch_count(), cached_step.trace_count()
    i0, h0 = _ndmod.invoke_count(), _ndmod.host_sync_count()
    base = telemetry.snapshot()
    STEPS_S = 5                       # calls 2..6: last call is a
    for _ in range(STEPS_S):          # sentinel step, so the flushed
        loss = step(data, label, batch_size=6)    # fold matches the
    assert step.last_step_compiled, step.last_fallback_reason  # live state
    snt.flush()
    snap = telemetry.snapshot()
    reads = snap["sentinel.digests"] - base["sentinel.digests"]
    # host recomputation of the fold over exactly what the program
    # digests: post-update trainable params + optimizer state
    upd = trainer._updaters[0]
    leaves = [p.data()._data for p in trainer._params
              if p.grad_req != "null"]
    import jax

    states = [upd.states[trainer._param2idx[id(p)]]
              for p in trainer._params if p.grad_req != "null"]
    state_leaves = [getattr(l, "_data", l)
                    for l in jax.tree_util.tree_leaves(states)]
    host_fold = sentinel.tree_digest(leaves + state_leaves)
    out = {
        "mode": "sentinel",
        "compiled_launches_per_step":
            (cached_step.dispatch_count() - d0) / STEPS_S,
        "eager_invokes_per_step":
            (_ndmod.invoke_count() - i0) / STEPS_S,
        "retraces_after_warm": cached_step.trace_count() - t0,
        "digest_reads": reads,
        "host_syncs": _ndmod.host_sync_count() - h0,
        "replica_divergence": snap["sentinel.replica_divergence"]
        - base["sentinel.replica_divergence"],
        "fold": snt.last_fold,
        "host_fold": host_fold,
        "fold_matches_host": snt.last_fold == host_fold,
    }
    return out


def _measure_mesh() -> dict:
    """kvstore='tpu' under the 8-device mesh: the data-parallel step must
    stay ONE compiled launch (the partitioner fans out, not the host),
    re-trace 0, and perform zero steady-state host-side cross-device
    copies or silently-replicated batches."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import cached_step
    from mxnet_tpu.ndarray import ndarray as _ndmod
    from mxnet_tpu.optimizer import fused
    from mxnet_tpu.parallel import spmd

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"mode": "mesh", "skipped": f"only {n_dev} device(s)"}
    # 2 rows per device: divisible batch, truly sharded
    net, trainer, loss_fn, data, label = _build(
        seed=2, rows=2 * n_dev, kvstore="tpu")
    step = trainer.compile_step(net, loss_fn)

    loss = step(data, label, batch_size=2 * n_dev)      # warm
    float(loss.asnumpy().ravel()[0])
    inv0, d0, f0, t0 = (_ndmod.invoke_count(), cached_step.dispatch_count(),
                        fused.dispatch_count(), cached_step.trace_count())
    h0 = _ndmod.host_sync_count()
    r0, b0 = spmd.reshard_count(), spmd.replicated_batch_count()
    for _ in range(STEPS):
        loss = step(data, label, batch_size=2 * n_dev)
    h1 = _ndmod.host_sync_count()
    r1, b1 = spmd.reshard_count(), spmd.replicated_batch_count()
    float(loss.asnumpy().ravel()[0])
    weight = net.collect_params()["d1.weight"].data()._data
    out = {
        "mode": "mesh",
        "skipped": None,
        "used_compiled": step.last_step_compiled,
        "mesh_active": step.mesh is not None,
        "mesh_devices": len(weight.sharding.device_set),
        "n_devices": n_dev,
        "eager_invokes_per_step": (_ndmod.invoke_count() - inv0) / STEPS,
        "compiled_launches_per_step":
            (cached_step.dispatch_count() - d0) / STEPS,
        "group_launches_per_step": (fused.dispatch_count() - f0) / STEPS,
        "retraces_after_warm": cached_step.trace_count() - t0,
        "host_syncs_per_step": (h1 - h0) / STEPS,
        "reshards_after_warm": r1 - r0,
        "replicated_batches": b1 - b0,
    }
    return out


def _measure_fsdp() -> dict:
    """dp×fsdp lane: params + optimizer state sharded over the fsdp
    axis, batch over dp only — still ONE launch/step, zero retraces,
    zero steady-state reshards, and param bytes per device at 1/fsdp of
    the replicated footprint.  Then the accumulation sub-lane on the
    same mesh: accum_steps=2 must pay exactly 3 dispatches per window
    (2 grad + 1 fused update), zero retraces after the first window."""
    import jax

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import cached_step
    from mxnet_tpu.ndarray import ndarray as _ndmod
    from mxnet_tpu.optimizer import fused
    from mxnet_tpu.parallel import spmd

    n_dev = len(jax.devices())
    if n_dev < 4:
        return {"mode": "fsdp", "skipped": f"only {n_dev} device(s)"}
    prev_mesh = os.environ.get("MXNET_SPMD_MESH")
    prev_min = os.environ.get("MXNET_FSDP_MIN_SIZE")
    os.environ["MXNET_SPMD_MESH"] = "dp=2,fsdp=2"
    os.environ["MXNET_FSDP_MIN_SIZE"] = "1"     # the gate MLP is tiny
    try:
        net, trainer, loss_fn, data, label = _build(
            seed=3, rows=8, kvstore="tpu")
        step = trainer.compile_step(net, loss_fn)
        loss = step(data, label, batch_size=8)          # warm
        float(loss.asnumpy().ravel()[0])
        weight = net.collect_params()["d1.weight"].data()._data
        shard = weight.sharding.shard_shape(weight.shape)
        total = sum(p.data()._data.nbytes
                    for _n, p in sorted(net.collect_params().items()))
        per_dev = spmd.param_bytes_per_device()
        inv0, d0, f0, t0 = (_ndmod.invoke_count(),
                            cached_step.dispatch_count(),
                            fused.dispatch_count(),
                            cached_step.trace_count())
        h0 = _ndmod.host_sync_count()
        r0, b0 = spmd.reshard_count(), spmd.replicated_batch_count()
        for _ in range(STEPS):
            loss = step(data, label, batch_size=8)
        h1 = _ndmod.host_sync_count()
        r1, b1 = spmd.reshard_count(), spmd.replicated_batch_count()
        float(loss.asnumpy().ravel()[0])
        out = {
            "mode": "fsdp",
            "skipped": None,
            "used_compiled": step.last_step_compiled,
            "mesh_active": step.mesh is not None,
            "param_sharded": tuple(shard) != tuple(weight.shape),
            "param_bytes_per_device": per_dev,
            "param_bytes_frac": per_dev / total if total else 1.0,
            "eager_invokes_per_step":
                (_ndmod.invoke_count() - inv0) / STEPS,
            "compiled_launches_per_step":
                (cached_step.dispatch_count() - d0) / STEPS,
            "group_launches_per_step":
                (fused.dispatch_count() - f0) / STEPS,
            "retraces_after_warm": cached_step.trace_count() - t0,
            "host_syncs_per_step": (h1 - h0) / STEPS,
            "reshards_after_warm": r1 - r0,
            "replicated_batches": b1 - b0,
        }
        # accumulation sub-lane: same dp×fsdp mesh, accum_steps=2 —
        # exactly N+1 = 3 dispatches per window, zero retraces after
        # the first full window (grad + update programs both warm)
        net2, tr2, loss2, d2, l2 = _build(seed=4, rows=8, kvstore="tpu")
        astep = tr2.compile_step(net2, loss2, accum_steps=2)
        for _ in range(2):                              # warm one window
            loss = astep(d2, l2, batch_size=8)
        float(loss.asnumpy().ravel()[0])
        ad0, at0 = cached_step.dispatch_count(), cached_step.trace_count()
        windows = 3
        for _ in range(2 * windows):
            loss = astep(d2, l2, batch_size=8)
        float(loss.asnumpy().ravel()[0])
        per_window = (cached_step.dispatch_count() - ad0) / windows
        out["accum_used_compiled"] = astep.last_step_compiled
        out["accum_dispatches_per_window"] = per_window
        out["accum_extra_dispatches"] = per_window - 3.0
        out["accum_retraces_after_warm"] = cached_step.trace_count() - at0
        return out
    finally:
        if prev_mesh is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev_mesh
        if prev_min is None:
            os.environ.pop("MXNET_FSDP_MIN_SIZE", None)
        else:
            os.environ["MXNET_FSDP_MIN_SIZE"] = prev_min


def _measure_pp() -> dict:
    """pp×dp×fsdp lane: a 2-stage PipelineBlock under
    MXNET_SPMD_MESH='pp=2,dp=2,fsdp=2' — the scan-internal GPipe
    schedule keeps the step at ONE donated launch with zero retraces
    and zero steady-state reshards, the packed stage buffer sharded
    one-stage-per-pp-group.  Accum sub-lane: accum_steps=2 on the same
    mesh pays exactly 3 dispatches per window."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import cached_step, gluon
    from mxnet_tpu.ndarray import ndarray as _ndmod
    from mxnet_tpu.optimizer import fused
    from mxnet_tpu.parallel import pipeline as pipe_mod, spmd

    n_dev = len(jax.devices())
    if n_dev < 8:
        return {"mode": "pp", "skipped": f"only {n_dev} device(s)"}
    prev_mesh = os.environ.get("MXNET_SPMD_MESH")
    prev_min = os.environ.get("MXNET_FSDP_MIN_SIZE")
    os.environ["MXNET_SPMD_MESH"] = "pp=2,dp=2,fsdp=2"
    os.environ["MXNET_FSDP_MIN_SIZE"] = "1"
    try:
        def build(seed):
            mesh = spmd.resolve_mesh()
            rng = onp.random.RandomState(seed)
            ws = [jnp.asarray((rng.randn(8, 8) * 0.3)
                              .astype(onp.float32)) for _ in range(2)]

            def stage(params, x):
                return jnp.tanh(x @ params["w"])

            pipe = pipe_mod.HeteroPipeline(
                [stage, stage], [{"w": w} for w in ws], mesh,
                num_microbatches=2,
                example_x=jnp.zeros((4, 8), jnp.float32))
            blk = pipe_mod.PipelineBlock(pipe)
            trainer = gluon.Trainer(blk.collect_params(), "sgd",
                                    {"learning_rate": 0.05,
                                     "momentum": 0.9}, kvstore="tpu")
            loss_fn = lambda n, x: ((n(x)) ** 2).sum()
            data = mx.nd.array(rng.randn(4, 8).astype(onp.float32))
            return blk, trainer, loss_fn, data

        blk, trainer, loss_fn, data = build(seed=11)
        step = trainer.compile_step(blk, loss_fn)
        loss = step(data, batch_size=4)                 # warm
        float(loss.asnumpy().ravel()[0])
        packed = blk.pp_stages.data()._data
        shard = packed.sharding.shard_shape(packed.shape)
        inv0, d0, f0, t0 = (_ndmod.invoke_count(),
                            cached_step.dispatch_count(),
                            fused.dispatch_count(),
                            cached_step.trace_count())
        r0, b0 = spmd.reshard_count(), spmd.replicated_batch_count()
        for _ in range(STEPS):
            loss = step(data, batch_size=4)
        r1, b1 = spmd.reshard_count(), spmd.replicated_batch_count()
        float(loss.asnumpy().ravel()[0])
        out = {
            "mode": "pp",
            "skipped": None,
            "used_compiled": step.last_step_compiled,
            "mesh_active": step.mesh is not None,
            "stage_sharded": packed.sharding.spec
            and packed.sharding.spec[0] == "pp" and shard[0] == 1,
            "bubble_fraction": pipe_mod.bubble_fraction(2, 2),
            "eager_invokes_per_step":
                (_ndmod.invoke_count() - inv0) / STEPS,
            "compiled_launches_per_step":
                (cached_step.dispatch_count() - d0) / STEPS,
            "group_launches_per_step":
                (fused.dispatch_count() - f0) / STEPS,
            "retraces_after_warm": cached_step.trace_count() - t0,
            "reshards_after_warm": r1 - r0,
            "replicated_batches": b1 - b0,
        }
        # accum sub-lane: N+1 dispatches per window on the pp mesh
        blk2, tr2, loss2, d2 = build(seed=12)
        astep = tr2.compile_step(blk2, loss2, accum_steps=2)
        for _ in range(2):                              # warm one window
            loss = astep(d2, batch_size=4)
        float(loss.asnumpy().ravel()[0])
        ad0, at0 = cached_step.dispatch_count(), cached_step.trace_count()
        windows = 3
        for _ in range(2 * windows):
            loss = astep(d2, batch_size=4)
        float(loss.asnumpy().ravel()[0])
        per_window = (cached_step.dispatch_count() - ad0) / windows
        out["accum_used_compiled"] = astep.last_step_compiled
        out["accum_dispatches_per_window"] = per_window
        out["accum_extra_dispatches"] = per_window - 3.0
        out["accum_retraces_after_warm"] = cached_step.trace_count() - at0
        return out
    finally:
        if prev_mesh is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev_mesh
        if prev_min is None:
            os.environ.pop("MXNET_FSDP_MIN_SIZE", None)
        else:
            os.environ["MXNET_FSDP_MIN_SIZE"] = prev_min


def _measure_moe() -> dict:
    """ep×dp lane: an MoEBlock (4 experts, top-2 routing) under
    MXNET_SPMD_MESH='ep=4,dp=2' — gating, dispatch/combine, the
    ep-sharded expert einsums, the folded aux head, and the fused
    update all inside ONE donated launch per step."""
    import jax
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import cached_step, gluon
    from mxnet_tpu.ndarray import ndarray as _ndmod
    from mxnet_tpu.optimizer import fused
    from mxnet_tpu.parallel import moe as moe_mod, spmd

    n_dev = len(jax.devices())
    if n_dev < 8:
        return {"mode": "moe", "skipped": f"only {n_dev} device(s)"}
    prev_mesh = os.environ.get("MXNET_SPMD_MESH")
    prev_min = os.environ.get("MXNET_FSDP_MIN_SIZE")
    os.environ["MXNET_SPMD_MESH"] = "ep=4,dp=2"
    os.environ["MXNET_FSDP_MIN_SIZE"] = "1"
    try:
        net = moe_mod.MoEBlock(units=8, hidden=16, num_experts=4, k=2)
        net.initialize(mx.init.Xavier())
        rng = onp.random.RandomState(13)
        for _name, p in sorted(net.collect_params().items()):
            p.data()._set_data(
                mx.nd.array(rng.randn(*p.shape).astype(onp.float32)
                            * 0.2)._data)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01, "momentum": 0.9},
                                kvstore="tpu")
        loss_fn = lambda n, x: ((n(x)) ** 2).sum()
        data = mx.nd.array(rng.randn(4, 6, 8).astype(onp.float32))
        step = trainer.compile_step(net, loss_fn)
        loss = step(data, batch_size=4)                 # warm
        float(loss.asnumpy().ravel()[0])
        ew = net.collect_params()["expert.ffn_1.weight"].data()._data
        inv0, d0, f0, t0 = (_ndmod.invoke_count(),
                            cached_step.dispatch_count(),
                            fused.dispatch_count(),
                            cached_step.trace_count())
        r0, b0 = spmd.reshard_count(), spmd.replicated_batch_count()
        for _ in range(STEPS):
            loss = step(data, batch_size=4)
        r1, b1 = spmd.reshard_count(), spmd.replicated_batch_count()
        float(loss.asnumpy().ravel()[0])
        return {
            "mode": "moe",
            "skipped": None,
            "used_compiled": step.last_step_compiled,
            "mesh_active": step.mesh is not None,
            "expert_sharded": ew.sharding.spec
            and ew.sharding.spec[0] == "ep"
            and ew.sharding.shard_shape(ew.shape)[0] == 1,
            "eager_invokes_per_step":
                (_ndmod.invoke_count() - inv0) / STEPS,
            "compiled_launches_per_step":
                (cached_step.dispatch_count() - d0) / STEPS,
            "group_launches_per_step":
                (fused.dispatch_count() - f0) / STEPS,
            "retraces_after_warm": cached_step.trace_count() - t0,
            "reshards_after_warm": r1 - r0,
            "replicated_batches": b1 - b0,
        }
    finally:
        if prev_mesh is None:
            os.environ.pop("MXNET_SPMD_MESH", None)
        else:
            os.environ["MXNET_SPMD_MESH"] = prev_mesh
        if prev_min is None:
            os.environ.pop("MXNET_FSDP_MIN_SIZE", None)
        else:
            os.environ["MXNET_FSDP_MIN_SIZE"] = prev_min


def _measure_infer() -> dict:
    """Variable-length request stream through the serving engine: warm
    every bucket the stream can hit, then count launches/retraces over a
    randomized stream (the steady-state contract)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import serving

    net, _trainer, _loss_fn, _d, _l = _build(seed=1)
    policy = serving.BucketPolicy()
    eng = serving.ServingEngine(net, max_delay_us=200, policy=policy)
    buckets = set()
    n = 1
    while n <= INFER_MAXLEN:
        b = policy.bucket(n)
        if b is not None and b not in buckets:
            buckets.add(b)
            eng.infer(mx.nd.array(onp.zeros((b, 8), onp.float32)))
        n += 1
    rng = onp.random.RandomState(7)
    t0, d0 = serving.trace_count(), serving.dispatch_count()
    lengths = rng.randint(1, INFER_MAXLEN + 1, size=INFER_REQUESTS)
    for ln in lengths:
        out = eng.infer(mx.nd.array(rng.randn(int(ln), 8)))
        assert out.shape[0] == int(ln)
    batches = eng.stats()["batches"] - len(buckets)
    out = {
        "mode": "serving",
        "bucket_refused": eng.bucket_refused,
        "requests": INFER_REQUESTS,
        "launches_per_batch":
            (serving.dispatch_count() - d0) / max(batches, 1),
        "retraces_after_warm": serving.trace_count() - t0,
        "programs_over_buckets": max(0, len(eng._programs) - len(buckets)),
        "programs": len(eng._programs),
        "buckets": len(buckets),
    }
    eng.close()
    return out


def _measure_decode() -> dict:
    """Join/retire storm through the continuous batcher: concurrent
    variable-length requests with staggered lengths and budgets so
    sequences join mid-stream and retire early, then count programs,
    retraces, dispatches-per-iteration, and leaked pages."""
    import threading

    import numpy as onp

    from mxnet_tpu import engine as _engine
    from mxnet_tpu import serving_decode as sd

    model = sd.TinyCausalLM(vocab=37, d_model=16, n_layers=2, n_heads=2,
                            max_seq=32)
    params = model.init_params(3)
    pool = sd.PagePool(pages=48, page=4)
    eng = sd.GenerativeEngine(model, params=params, pool=pool,
                              max_rows=4, name="budget")
    grid = eng.warmup(max_len=16)        # pow2 buckets 1..16 + decode
    t0, d0 = sd.trace_count(), sd.dispatch_count()
    rng = onp.random.RandomState(11)
    prompts = [rng.randint(0, 37, size=rng.randint(1, 13)).tolist()
               for _ in range(8)]
    budgets = [3, 9, 5, 2, 7, 4, 8, 6]   # early retires + long tails
    errs = []

    def fire(i):
        try:
            out = eng.generate(prompts[i], max_new_tokens=budgets[i])
            assert len(out) == budgets[i]
        except BaseException as e:        # pragma: no cover
            errs.append(repr(e))

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    _engine.waitall()                    # drains the engine's queue
    st = eng.stats()
    out = {
        "mode": "decode",
        "errors": errs,
        "warmup_programs": grid,
        "programs": st["programs"],
        "programs_over_grid": max(0, st["programs"] - grid),
        "retraces_after_warm": sd.trace_count() - t0,
        # 1 dispatch per decode iteration + 1 per prefill, nothing else
        "dispatches": sd.dispatch_count() - d0,
        "decode_steps": st["decode_steps"],
        "prefills": st["prefills"],
        "extra_dispatches": (sd.dispatch_count() - d0)
        - st["decode_steps"] - st["prefills"],
        "rows_per_decode": round(st.get("rows_per_decode", 0.0), 2),
        "leaked_pages": pool.in_use(),
        "shed": st["shed"],
    }
    eng.close()
    return out


def _measure_router() -> dict:
    """Zero-overhead-off lane: the SAME sequential request stream
    through a bare GenerativeEngine and through a ReplicaRouter
    wrapping one replica (hedging off, breaker closed) — the router
    must add zero dispatches, zero retraces, zero host syncs, and the
    token streams must match bit-for-bit."""
    from mxnet_tpu import serving_decode as sd
    from mxnet_tpu.ndarray import ndarray as _ndmod
    from mxnet_tpu.serving_router import ReplicaRouter

    model = sd.TinyCausalLM(vocab=31, d_model=16, n_layers=1, n_heads=2,
                            max_seq=32)
    params = model.init_params(5)
    prompts = [[1 + (i * 3 + j) % 29 for j in range(3 + i % 3)]
               for i in range(6)]

    def run(route: bool) -> dict:
        from mxnet_tpu import telemetry as _tel

        pool = sd.PagePool(pages=32, page=4)
        eng = sd.GenerativeEngine(model, params=params, pool=pool,
                                  max_rows=2, name="lane")
        eng.warmup(max_len=8)
        front = (ReplicaRouter([eng], hedge_pctl=0) if route else eng)
        t0, d0 = sd.trace_count(), sd.dispatch_count()
        h0 = _ndmod.host_sync_count()
        evs = _tel.events()
        e0 = evs[-1]["seq"] if evs else 0
        sps = _tel.spans()          # built when read: told apart by seq
        sp0 = sps[-1]["seq"] if sps else 0
        outs = [front.generate(p, max_new_tokens=5) for p in prompts]
        new_evs = [e for e in _tel.events() if e["seq"] > e0]
        new_sps = [s for s in _tel.spans() if s["seq"] > sp0]
        row = {"outs": outs,
               "dispatches": sd.dispatch_count() - d0,
               "retraces": sd.trace_count() - t0,
               "host_syncs": _ndmod.host_sync_count() - h0,
               "trace_fields": sum(1 for e in new_evs
                                   if "trace_id" in e)
               + sum(1 for s in new_sps if "trace_id" in s),
               "leaked_pages": pool.in_use()}
        eng.close()
        return row

    bare = run(False)
    routed = run(True)
    # ISSUE-15 disabled-mode contract: with MXNET_TELEMETRY_TRACE=0 the
    # routed lane is BYTE-IDENTICAL to PR 14 — same token streams, same
    # dispatch/retrace/host-sync counts, and zero trace fields on any
    # event or span (the knob is uncached, so the env flip takes
    # effect immediately)
    prev = os.environ.get("MXNET_TELEMETRY_TRACE")
    os.environ["MXNET_TELEMETRY_TRACE"] = "0"
    try:
        routed_off = run(True)
    finally:
        if prev is None:
            os.environ.pop("MXNET_TELEMETRY_TRACE", None)
        else:
            os.environ["MXNET_TELEMETRY_TRACE"] = prev
    return {
        "mode": "router",
        "requests": len(prompts),
        "bare_dispatches": bare["dispatches"],
        "routed_dispatches": routed["dispatches"],
        "extra_dispatches": routed["dispatches"] - bare["dispatches"],
        "extra_retraces": routed["retraces"] - bare["retraces"],
        "extra_host_syncs": routed["host_syncs"] - bare["host_syncs"],
        "outputs_equal": bare["outs"] == routed["outs"],
        "leaked_pages": (bare["leaked_pages"] + routed["leaked_pages"]
                         + routed_off["leaked_pages"]),
        "traced_off_outputs_equal": routed_off["outs"] == bare["outs"],
        "traced_off_extra_dispatches":
            routed_off["dispatches"] - bare["dispatches"],
        "traced_off_extra_retraces":
            routed_off["retraces"] - bare["retraces"],
        "traced_off_extra_host_syncs":
            routed_off["host_syncs"] - bare["host_syncs"],
        "traced_off_trace_fields": routed_off["trace_fields"],
    }


def _measure_spec() -> dict:
    """Speculative-decoding lane: a high-agreement draft under
    MXNET_SPEC_DECODE=1 drives a mixed greedy/sampled join/retire
    storm — bounded programs (== the warmup grid across BOTH
    ProgramStore namespaces), 0 retraces, < 1 target dispatch per
    committed token, greedy rows token-exact vs the eager oracle, 0
    leaked pages.  Then the off leg: the SAME greedy stream through a
    draft-attached engine with MXNET_SPEC_DECODE=0 must match a
    draft-free engine's dispatch/retrace budget and tokens exactly."""
    import threading

    import numpy as onp

    from mxnet_tpu import engine as _engine
    from mxnet_tpu import serving_decode as sd

    target, tp, draft, dp = sd.high_agreement_pair(
        vocab=41, d_model=16, target_layers=2, draft_layers=1,
        n_heads=2, max_seq=64, seed=5)
    rng = onp.random.RandomState(23)
    prompts = [rng.randint(0, 41, size=rng.randint(1, 10)).tolist()
               for _ in range(8)]
    budgets = [6, 9, 4, 8, 5, 7, 10, 6]
    # even rows greedy (token-exactness leg), odd rows sampled (the
    # heterogeneous-config leg: same programs, zero retraces)
    samps = [None if i % 2 == 0
             else sd.SamplingSpec(temperature=0.9, top_k=7, top_p=0.95,
                                  seed=100 + i)
             for i in range(8)]
    prev = os.environ.get("MXNET_SPEC_DECODE")
    os.environ["MXNET_SPEC_DECODE"] = "1"
    try:
        pool = sd.PagePool(pages=96, page=4)
        eng = sd.GenerativeEngine(target, params=tp, pool=pool,
                                  max_rows=4, name="spec_lane",
                                  draft=draft, draft_params=dp,
                                  spec_k=4)
        grid = eng.warmup(max_len=16)
        t0 = sd.trace_count() + sd.spec_trace_count()
        d0 = sd.dispatch_count() + sd.spec_dispatch_count()
        outs: list = [None] * 8
        errs: list = []

        def fire(i):
            try:
                outs[i] = eng.generate(prompts[i],
                                       max_new_tokens=budgets[i],
                                       sampling=samps[i])
            except BaseException as e:    # pragma: no cover
                errs.append(repr(e))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _engine.waitall()
        st = eng.stats()
        greedy_exact = all(
            outs[i] == sd.eager_generate(target, tp, prompts[i],
                                         budgets[i])
            for i in range(0, 8, 2) if outs[i] is not None)
        tokens = sum(len(o) for o in outs if o is not None)
        # target-equivalent dispatches: each plain decode step AND each
        # verify round costs one target-model launch; the draft's
        # launches ride the cheap geometry and are priced by the cost
        # table, not this ratio
        target_dispatches = st["decode_steps"] + st["spec_rounds"]
        row = {
            "mode": "spec",
            "errors": errs,
            "warmup_programs": grid,
            "programs": st["programs"] + st["spec_programs"],
            "programs_over_grid":
                max(0, st["programs"] + st["spec_programs"] - grid),
            "retraces_after_warm":
                (sd.trace_count() + sd.spec_trace_count()) - t0,
            "dispatches":
                (sd.dispatch_count() + sd.spec_dispatch_count()) - d0,
            "spec_rounds": st["spec_rounds"],
            "spec_proposed": st["spec_proposed"],
            "spec_accepted": st["spec_accepted"],
            "acceptance": (st["spec_accepted"]
                           / max(st["spec_proposed"], 1)),
            "spec_disabled": st["spec_disabled"],
            "tokens": tokens,
            "target_dispatches_per_token":
                target_dispatches / max(tokens, 1),
            "greedy_token_exact": greedy_exact,
            "leaked_pages": pool.in_use(),
        }
        eng.close()
    finally:
        if prev is None:
            os.environ.pop("MXNET_SPEC_DECODE", None)
        else:
            os.environ["MXNET_SPEC_DECODE"] = prev
    # the OFF leg: greedy path byte-identical dispatch budget with the
    # knob off, draft attached or not (MXNET_SPEC_DECODE=0 is ambient
    # here — the knob is uncached)

    def run_off(with_draft: bool) -> dict:
        pool2 = sd.PagePool(pages=64, page=4)
        kw = (dict(draft=draft, draft_params=dp, spec_k=4)
              if with_draft else {})
        e2 = sd.GenerativeEngine(target, params=tp, pool=pool2,
                                 max_rows=2, name="spec_off", **kw)
        e2.warmup(max_len=16)
        t1 = sd.trace_count() + sd.spec_trace_count()
        d1 = sd.dispatch_count() + sd.spec_dispatch_count()
        toks = [e2.generate(p, max_new_tokens=5) for p in prompts[:4]]
        got = {
            "outs": toks,
            "dispatches":
                (sd.dispatch_count() + sd.spec_dispatch_count()) - d1,
            "retraces": (sd.trace_count() + sd.spec_trace_count()) - t1,
            "leaked_pages": pool2.in_use(),
        }
        e2.close()
        return got

    bare = run_off(False)
    offd = run_off(True)
    row["greedy_off_extra_dispatches"] = (offd["dispatches"]
                                          - bare["dispatches"])
    row["greedy_off_extra_retraces"] = offd["retraces"] - bare["retraces"]
    row["greedy_off_outputs_equal"] = offd["outs"] == bare["outs"]
    row["leaked_pages"] += bare["leaked_pages"] + offd["leaked_pages"]
    return row


def _store_worker() -> None:
    """``--store-worker`` mode: run the tiny train-step + serving-bucket
    workload in THIS process and print its program-store verdict as one
    JSON line.  The parent runs it twice against one
    MXNET_PROGRAM_CACHE_DIR; the second run must report 0 fresh XLA
    compiles and a bit-exact output digest."""
    import json
    import time

    t0 = time.perf_counter()
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import program_store, serving

    net, trainer, loss_fn, data, label = _build()
    step = trainer.compile_step(net, loss_fn)
    losses = []
    first_result_s = None
    for _ in range(3):
        loss = step(data, label, batch_size=6)
        losses.append(float(loss.asnumpy().ravel()[0]))
        if first_result_s is None:
            first_result_s = time.perf_counter() - t0
    assert step.last_step_compiled, step.last_fallback_reason
    net2, _tr, _lf, _d, _l = _build(seed=1)
    eng = serving.ServingEngine(net2, max_delay_us=0)
    out = eng.infer(mx.nd.array(onp.ones((3, 8), onp.float32)))
    digest = ([l.hex() for l in losses]
              + [float(v).hex() for v in
                 onp.asarray(out.asnumpy(), onp.float64).ravel().tolist()])
    eng.close()
    ds = program_store.disk_stats()
    print(json.dumps({
        "fresh_compiles": ds["misses"], "disk_hits": ds["hits"],
        "persistent_enabled": ds["enabled"],
        "first_result_s": round(first_result_s, 3),
        "digest": digest}), flush=True)


def _measure_store_cold_start() -> dict:
    """Warm second-process lane: two subprocesses replay the same
    workload against one persistent program cache — process B must
    compile nothing and reproduce process A's outputs bit-exactly."""
    import json
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="mxnet_program_store_gate_")
    env = dict(os.environ)
    env["MXNET_PROGRAM_CACHE_DIR"] = cache_dir
    # the knob under test must own the cache dir (never piggyback on an
    # externally configured jax cache)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # persistence semantics are platform-independent, and this process
    # may already hold the chip: the workers are CPU processes
    env["JAX_PLATFORMS"] = "cpu"
    runs = []
    for i in ("A", "B"):
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--store-worker"],
            env=env, capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            return {"mode": "store", "error":
                    f"store worker {i} rc={r.returncode}: "
                    + r.stderr.strip()[-500:]}
        runs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    a, b = runs
    return {
        "mode": "store",
        "error": None,
        "cache_dir": cache_dir,
        "persistent_enabled": a["persistent_enabled"],
        "first_process_compiles": a["fresh_compiles"],
        "second_process_compiles": b["fresh_compiles"],
        "second_process_disk_hits": b["disk_hits"],
        "first_result_s": (a["first_result_s"], b["first_result_s"]),
        "outputs_bit_exact": a["digest"] == b["digest"],
    }


def main() -> int:
    from mxnet_tpu import program_store as _ps

    ev0 = sum(_ps.stats(n)["evictions"]
              for n in ("train_step", "serving", "hybrid_forward"))
    compiled = _measure(True)
    eager = _measure(False)
    amp_row = _measure(True, with_amp=True)
    print(f"{'mode':<13} {'dispatches':>11} {'compiled':>9} "
          f"{'eager-ops':>10} {'group':>6} {'retrace':>8} {'syncs':>6}")
    for row in (compiled, amp_row, eager):
        print(f"{row['mode']:<13} {row['dispatches_per_step']:>11.1f} "
              f"{row['compiled_launches_per_step']:>9.1f} "
              f"{row['eager_invokes_per_step']:>10.1f} "
              f"{row['group_launches_per_step']:>6.1f} "
              f"{row['retraces_after_warm']:>8d} "
              f"{row['host_syncs_per_step']:>6.1f}")
    infer = _measure_infer()
    print(f"{'serving':<10} requests {infer['requests']} -> "
          f"{infer['launches_per_batch']:.1f} launches/batch, "
          f"{infer['retraces_after_warm']} retraces, "
          f"{infer['programs']} programs over {infer['buckets']} buckets")
    decode = _measure_decode()
    print(f"{'decode':<10} storm -> {decode['programs']} programs "
          f"(grid {decode['warmup_programs']}), "
          f"{decode['retraces_after_warm']} retraces, "
          f"{decode['dispatches']} dispatches = "
          f"{decode['decode_steps']} decode + "
          f"{decode['prefills']} prefill "
          f"({decode['rows_per_decode']} rows/step), "
          f"{decode['leaked_pages']} leaked pages")
    spec = _measure_spec()
    print(f"{'spec':<10} mixed storm -> {spec['programs']} programs "
          f"(grid {spec['warmup_programs']}), "
          f"{spec['retraces_after_warm']} retraces, "
          f"{spec['spec_rounds']} rounds "
          f"{spec['spec_accepted']}/{spec['spec_proposed']} accepted "
          f"({spec['acceptance']:.2f}), "
          f"{spec['target_dispatches_per_token']:.2f} target "
          f"dispatches/token over {spec['tokens']} tokens; off leg "
          f"{spec['greedy_off_extra_dispatches']} extra dispatches")
    router = _measure_router()
    print(f"{'router':<10} 1 replica, hedge off -> "
          f"{router['routed_dispatches']} dispatches "
          f"(bare {router['bare_dispatches']}), "
          f"{router['extra_retraces']} extra retraces, "
          f"{router['extra_host_syncs']} extra host syncs, outputs "
          f"{'==' if router['outputs_equal'] else '!='} bare")
    snt = _measure_sentinel()
    print(f"{'sentinel':<10} cadence 2 -> "
          f"{snt['compiled_launches_per_step']:.1f} launch/step, "
          f"{snt['retraces_after_warm']} retraces, "
          f"{snt['digest_reads']} digest reads "
          f"({snt['host_syncs']} syncs), fold "
          f"{'==' if snt['fold_matches_host'] else '!='} host recompute")
    mesh = _measure_mesh()
    if mesh["skipped"]:
        print(f"mesh       SKIPPED ({mesh['skipped']})")
    else:
        print(f"{'mesh':<10} {mesh['mesh_devices']} devices -> "
              f"{mesh['compiled_launches_per_step']:.1f} launch/step, "
              f"{mesh['retraces_after_warm']} retraces, "
              f"{mesh['reshards_after_warm']} reshards, "
              f"{mesh['replicated_batches']} replicated batches")
    fsdp = _measure_fsdp()
    if fsdp["skipped"]:
        print(f"fsdp       SKIPPED ({fsdp['skipped']})")
    else:
        print(f"{'fsdp':<10} dp=2,fsdp=2 -> "
              f"{fsdp['compiled_launches_per_step']:.1f} launch/step, "
              f"{fsdp['retraces_after_warm']} retraces, "
              f"{fsdp['reshards_after_warm']} reshards, "
              f"{fsdp['param_bytes_frac']:.2f}x param bytes/device; "
              f"accum 2 -> {fsdp['accum_dispatches_per_window']:.1f} "
              f"dispatches/window, "
              f"{fsdp['accum_retraces_after_warm']} retraces")
    pp = _measure_pp()
    if pp["skipped"]:
        print(f"pp         SKIPPED ({pp['skipped']})")
    else:
        print(f"{'pp':<10} pp=2,dp=2,fsdp=2 -> "
              f"{pp['compiled_launches_per_step']:.1f} launch/step, "
              f"{pp['retraces_after_warm']} retraces, "
              f"{pp['reshards_after_warm']} reshards, theoretical "
              f"bubble {pp['bubble_fraction']:.2f}; accum 2 -> "
              f"{pp['accum_dispatches_per_window']:.1f} "
              f"dispatches/window, "
              f"{pp['accum_retraces_after_warm']} retraces")
    moe = _measure_moe()
    if moe["skipped"]:
        print(f"moe        SKIPPED ({moe['skipped']})")
    else:
        print(f"{'moe':<10} ep=4,dp=2 -> "
              f"{moe['compiled_launches_per_step']:.1f} launch/step, "
              f"{moe['retraces_after_warm']} retraces, "
              f"{moe['reshards_after_warm']} reshards, experts "
              f"{'sharded' if moe['expert_sharded'] else 'REPLICATED'}")
    # program-store lane: all the steady-state runs above went through
    # the store — they must not have evicted anything
    ev_after_warm = sum(
        _ps.stats(n)["evictions"]
        for n in ("train_step", "serving", "hybrid_forward")) - ev0
    store = _measure_store_cold_start()
    if store["error"]:
        print(f"store      FAILED ({store['error']})")
    else:
        print(f"{'store':<10} warm 2nd process: "
              f"{store['second_process_compiles']} fresh compiles, "
              f"{store['second_process_disk_hits']} disk hits "
              f"(1st process compiled {store['first_process_compiles']}), "
              f"first result {store['first_result_s'][0]:.2f}s -> "
              f"{store['first_result_s'][1]:.2f}s, "
              f"{ev_after_warm} evictions in-process")
    failures = []
    if not compiled["used_compiled"]:
        failures.append("compiled mode fell back to the eager tape")
    for key, budget in BUDGET.items():
        if compiled[key] > budget:
            failures.append(
                f"{key} = {compiled[key]} exceeds budget {budget}")
    if not amp_row["used_compiled"]:
        failures.append("compiled AMP mode fell back to the eager tape")
    for key, budget in AMP_BUDGET.items():
        if amp_row[key] > budget:
            failures.append(
                f"AMP {key} = {amp_row[key]} exceeds budget {budget}")
    if amp_row["host_syncs_per_step"] > amp_row["deferred_reads_per_step"]:
        failures.append(
            "AMP step performs a blocking host sync beyond the deferred "
            f"flag read ({amp_row['host_syncs_per_step']} syncs vs "
            f"{amp_row['deferred_reads_per_step']} deferred reads)")
    if infer["bucket_refused"] is not None:
        failures.append(
            f"serving refused bucketing: {infer['bucket_refused']}")
    for key, budget in INFER_BUDGET.items():
        if infer[key] > budget:
            failures.append(
                f"serving {key} = {infer[key]} exceeds budget {budget}")
    if decode["errors"]:
        failures.append(f"decode storm errors: {decode['errors']}")
    if decode["shed"]:
        failures.append(
            f"decode storm shed {decode['shed']} request(s) — the gate "
            "pool is sized to absorb the whole storm")
    for key, budget in DECODE_BUDGET.items():
        if decode[key] > budget:
            failures.append(
                f"decode {key} = {decode[key]} exceeds budget {budget}")
    if spec["errors"]:
        failures.append(f"spec storm errors: {spec['errors']}")
    for key, budget in SPEC_BUDGET.items():
        if spec[key] > budget:
            failures.append(
                f"spec {key} = {spec[key]} exceeds budget {budget}")
    if spec["spec_rounds"] == 0 or spec["spec_disabled"]:
        failures.append(
            "spec lane never engaged speculation (0 rounds or "
            "auto-disabled) on the high-agreement fixture")
    if spec["acceptance"] < 0.7:
        failures.append(
            f"spec acceptance {spec['acceptance']:.2f} < 0.7 on the "
            "high-agreement draft (rejection sampling broken?)")
    if spec["target_dispatches_per_token"] >= 1.0:
        failures.append(
            f"spec pays {spec['target_dispatches_per_token']:.2f} "
            "target dispatches per committed token (must be < 1: the "
            "k-for-1 verify win is gone)")
    if not spec["greedy_token_exact"]:
        failures.append(
            "spec greedy rows diverge from the eager oracle "
            "(token-exactness invariant broken under speculation)")
    if not spec["greedy_off_outputs_equal"]:
        failures.append(
            "MXNET_SPEC_DECODE=0 draft-attached token streams differ "
            "from the draft-free engine's")
    for key, budget in ROUTER_BUDGET.items():
        if router[key] > budget:
            failures.append(
                f"router {key} = {router[key]} exceeds budget {budget} "
                "(zero-overhead-off broken)")
    if not router["outputs_equal"]:
        failures.append(
            "router-wrapped token streams differ from the bare engine's")
    if router["leaked_pages"]:
        failures.append(
            f"router lane leaked {router['leaked_pages']} KV pages")
    # ISSUE-15: tracing disabled must be byte-identical to PR 14
    if not router["traced_off_outputs_equal"]:
        failures.append(
            "router token streams under MXNET_TELEMETRY_TRACE=0 differ "
            "from the bare engine's")
    for key in ("traced_off_extra_dispatches", "traced_off_extra_retraces",
                "traced_off_extra_host_syncs", "traced_off_trace_fields"):
        if router[key] != 0:
            failures.append(
                f"router {key} = {router[key]} with tracing disabled "
                "(must be 0: zero overhead when off)")
    for key, budget in SENTINEL_BUDGET.items():
        if snt[key] > budget:
            failures.append(
                f"sentinel {key} = {snt[key]} exceeds budget {budget}")
    if snt["digest_reads"] != 3:
        failures.append(
            f"sentinel read {snt['digest_reads']} digests over 5 steps "
            "at cadence 2 (expected 3: one per cadence window)")
    if snt["host_syncs"] > snt["digest_reads"]:
        failures.append(
            "sentinel step performs host syncs beyond the deferred "
            f"digest reads ({snt['host_syncs']} syncs vs "
            f"{snt['digest_reads']} reads)")
    if not snt["fold_matches_host"]:
        failures.append(
            f"in-program digest {snt['fold']} != host recomputation "
            f"{snt['host_fold']} — the fingerprint does not attest the "
            "state it claims to")
    if not mesh["skipped"]:
        if not mesh["used_compiled"]:
            failures.append("mesh mode fell back to the eager tape")
        if not mesh["mesh_active"]:
            failures.append(
                "kvstore='tpu' did not resolve an SPMD mesh")
        if mesh["mesh_devices"] != mesh["n_devices"]:
            failures.append(
                f"params replicated over {mesh['mesh_devices']} devices, "
                f"expected {mesh['n_devices']}")
        for key, budget in MESH_BUDGET.items():
            if mesh[key] > budget:
                failures.append(
                    f"mesh {key} = {mesh[key]} exceeds budget {budget}")
    if not fsdp["skipped"]:
        if not fsdp["used_compiled"]:
            failures.append("fsdp mode fell back to the eager tape")
        if not fsdp["accum_used_compiled"]:
            failures.append(
                "fsdp accumulation mode fell back to the eager tape")
        if not fsdp["mesh_active"]:
            failures.append(
                "fsdp lane: kvstore='tpu' did not resolve a dp=2,fsdp=2 "
                "mesh")
        if not fsdp["param_sharded"]:
            failures.append(
                "fsdp lane: d1.weight is fully replicated — the fsdp "
                "axis did not shard the parameters")
        if fsdp["param_bytes_frac"] > 0.75:
            failures.append(
                f"fsdp lane: param bytes per device is "
                f"{fsdp['param_bytes_frac']:.2f}x the global footprint "
                "(expected ~1/fsdp = 0.5x on a 2-way fsdp axis)")
        for key, budget in FSDP_BUDGET.items():
            if fsdp[key] > budget:
                failures.append(
                    f"fsdp {key} = {fsdp[key]} exceeds budget {budget}")
    if not pp["skipped"]:
        if not pp["used_compiled"]:
            failures.append("pp mode fell back to the eager tape")
        if not pp["accum_used_compiled"]:
            failures.append(
                "pp accumulation mode fell back to the eager tape")
        if not pp["mesh_active"]:
            failures.append(
                "pp lane: kvstore='tpu' did not resolve a "
                "pp=2,dp=2,fsdp=2 mesh")
        if not pp["stage_sharded"]:
            failures.append(
                "pp lane: packed stage buffer is not one-stage-per-pp-"
                "group (expected P('pp') with shard dim 0 == 1)")
        for key, budget in PP_BUDGET.items():
            if pp[key] > budget:
                failures.append(
                    f"pp {key} = {pp[key]} exceeds budget {budget}")
    if not moe["skipped"]:
        if not moe["used_compiled"]:
            failures.append("moe mode fell back to the eager tape")
        if not moe["mesh_active"]:
            failures.append(
                "moe lane: kvstore='tpu' did not resolve an ep=4,dp=2 "
                "mesh")
        if not moe["expert_sharded"]:
            failures.append(
                "moe lane: expert weights are replicated — the ep axis "
                "did not shard dim 0 (expected 1 expert per ep group)")
        for key, budget in MOE_BUDGET.items():
            if moe[key] > budget:
                failures.append(
                    f"moe {key} = {moe[key]} exceeds budget {budget}")
    if ev_after_warm > STORE_BUDGET["evictions_after_warm"]:
        failures.append(
            f"program store evicted {ev_after_warm} programs during "
            "steady-state runs (caps must cover the declared grid)")
    if compiled["live_programs"] - 1 > \
            STORE_BUDGET["live_train_programs_over"]:
        failures.append(
            f"train step holds {compiled['live_programs']} live programs "
            "for one constant-shape signature (expected 1)")
    if store["error"]:
        failures.append(f"program-store cold-start lane: {store['error']}")
    else:
        if not store["persistent_enabled"]:
            failures.append(
                "MXNET_PROGRAM_CACHE_DIR did not enable the persistent "
                "compilation cache in the worker")
        if store["second_process_compiles"] > \
                STORE_BUDGET["second_process_compiles"]:
            failures.append(
                f"warm second process performed "
                f"{store['second_process_compiles']} fresh XLA compiles "
                "(expected 0: every program must be a disk/memory hit)")
        if not store["outputs_bit_exact"]:
            failures.append(
                "warm second process outputs differ from the first "
                "process (disk-cached executables must be bit-exact)")
    if failures:
        print("check_dispatch_budget: FAILED —", "; ".join(failures),
              file=sys.stderr)
        return 1
    print(f"check_dispatch_budget: compiled step within budget "
          f"({compiled['dispatches_per_step']:.0f} dispatch/step, "
          f"{compiled['host_syncs_per_step']:.0f} host syncs over "
          f"{STEPS} steps; AMP pays {amp_row['host_syncs_per_step']:.0f} "
          f"sync = {amp_row['deferred_reads_per_step']:.0f} deferred "
          f"read; eager tape pays "
          f"{eager['dispatches_per_step']:.0f}); serving within budget "
          f"({infer['launches_per_batch']:.0f} launch/batch, "
          f"{infer['retraces_after_warm']} retraces, "
          f"{infer['programs']} programs <= {infer['buckets']} buckets)"
          f"; decode within budget ({decode['programs']} programs == "
          f"grid {decode['warmup_programs']}, "
          f"{decode['retraces_after_warm']} retraces, "
          f"{decode['extra_dispatches']} extra dispatches, "
          f"{decode['leaked_pages']} leaked pages)"
          f"; spec within budget ({spec['programs']} programs == grid, "
          f"{spec['target_dispatches_per_token']:.2f} target "
          f"dispatches/token at {spec['acceptance']:.2f} acceptance, "
          f"off leg {spec['greedy_off_extra_dispatches']} extra)"
          f"; router within budget ({router['extra_dispatches']} extra "
          f"dispatches over {router['requests']} routed requests)"
          f"; sentinel within budget "
          f"({snt['compiled_launches_per_step']:.0f} launch/step, "
          f"{snt['digest_reads']} digest reads, fold == host)"
          + ("" if mesh["skipped"] else
             f"; mesh within budget ({mesh['mesh_devices']}-device SPMD, "
             f"{mesh['compiled_launches_per_step']:.0f} launch/step, "
             f"{mesh['reshards_after_warm']} steady-state reshards)")
          + ("" if fsdp["skipped"] else
             f"; fsdp within budget "
             f"({fsdp['compiled_launches_per_step']:.0f} launch/step at "
             f"{fsdp['param_bytes_frac']:.2f}x param bytes/device, accum "
             f"{fsdp['accum_dispatches_per_window']:.0f} "
             f"dispatches/window)")
          + ("" if pp["skipped"] else
             f"; pp within budget "
             f"({pp['compiled_launches_per_step']:.0f} launch/step "
             f"scan-internal schedule, accum "
             f"{pp['accum_dispatches_per_window']:.0f} "
             f"dispatches/window)")
          + ("" if moe["skipped"] else
             f"; moe within budget "
             f"({moe['compiled_launches_per_step']:.0f} launch/step, "
             f"{moe['reshards_after_warm']} reshards, ep-sharded "
             f"experts)")
          + f"; program store within budget ({ev_after_warm} evictions, "
            f"warm 2nd process {store['second_process_compiles']} "
            f"compiles / {store['second_process_disk_hits']} disk hits)")
    return 0


if __name__ == "__main__":
    if "--store-worker" in sys.argv:
        _store_worker()
        sys.exit(0)
    sys.exit(main())
