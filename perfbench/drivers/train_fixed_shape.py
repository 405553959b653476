"""Driver ``train_fixed_shape``: one fixed batch shape through
``gluon.Trainer(kvstore='tpu').compile_step``, fed as a user's loop feeds it.

    for x, y in engine.prefetch(batches, sharding=step.batch_sharding):
        loss = step(x, y, batch_size=b)

After dispatching step n the loop reads the loss of step n-2: at most three
steps are in flight, every loss is checked, and the window ends in a host
read of the last loss.  No other host read, no ``waitall``, no metric object
inside the window.

A mix of this kind states ``batch_per_chip``, ``pool_batches``,
``warmup_steps``, ``traced_steps``, ``min_steps`` and ``env``
(``MXNET_SPMD_MESH``, always explicit), plus whatever its configuration's
``make_pool`` reads (``seq_len``).  The configuration's module gives
``build``, ``ops_per_sample``, ``make_pool``, ``check_batch`` and
``reference``; its JSON gives ``amp_dtype``, ``check`` (mode and tolerances)
and ``loss_fall_margin``.
"""
from __future__ import annotations

import glob
import itertools
import math
import os
import shutil
import time
from collections import deque
from types import SimpleNamespace

from perfbench import trace_reduce
from perfbench.spans import Spans

IN_FLIGHT = 2          # losses left unread behind the newest dispatch


def run(cell, opts, devices, peak, say):
    import mxnet_tpu as mx

    mx.program_store.enable_persistent_cache()
    mx.amp.init(cell.sizes["amp_dtype"])
    try:
        return _run(mx, cell, opts, devices, peak, say)
    finally:
        mx.amp.uninit()


# ---------------------------------------------------------------------------
def _run(mx, cell, opts, devices, peak, say):
    import jax

    cfg, sizes, mix = cell.config_module, cell.sizes, cell.mix
    batch = mix["batch_per_chip"] * cell.chips
    setup, lap = {}, _laps()
    base = _counters(mx)

    mx.random.seed(opts.seed)
    built = cfg.build(mx, sizes)
    net, head_loss = built["net"], built["head_loss"]
    setup["build"] = lap()

    check = _reference_check(mx, cfg, net, head_loss, sizes, mix, opts.seed)
    setup["reference_check"] = lap()
    say(f"reference check: logits {check['logits_err']:.2e} of scale "
        f"(allowed {check['logits_tol']:.0e}), loss {check['loss']:.5f} vs "
        f"{check['reference_loss']:.5f}, {check['loss_err']:.2e} relative "
        f"(allowed {check['loss_tol']:.0e})")

    trainer = mx.gluon.Trainer(net.collect_params(), built["optimizer"],
                               built["optimizer_params"], kvstore="tpu")
    step = trainer.compile_step(
        net, lambda n, x, y: head_loss(n(x), y))
    pool = cfg.make_pool(opts.seed, sizes, mix, batch, mix["pool_batches"])
    setup["make_pool"] = lap()
    prefetcher = mx.engine.prefetch(itertools.cycle(pool),
                                    sharding=step.batch_sharding)
    try:
        warm = _steps(step, prefetcher, batch, Spans(),
                      lambda n, now: n >= mix["warmup_steps"])
        setup["warmup_steps"] = lap()
        after_setup = _counters(mx)
        say(f"warm-up: losses {' '.join(f'{l:.4f}' for l in warm.losses)}; "
            f"set-up by phase {({k: round(v, 2) for k, v in setup.items()})}")

        trace, traced = None, None
        if opts.trace:
            traced, trace = _traced(step, prefetcher, batch,
                                    mix["traced_steps"], opts.out_dir)
            setup["traced_steps"] = lap()

        spans = Spans()
        before = _counters(mx)
        setup_s = time.perf_counter() - opts.t_start
        deadline = time.perf_counter() + opts.seconds
        win = _steps(step, prefetcher, batch, spans,
                     lambda n, now: now >= deadline and n >= mix["min_steps"])
        after = _counters(mx)
    finally:
        prefetcher.close()

    memory = _memory(jax, devices, opts.rehearse)
    platforms = sorted({d.platform for a in jax.live_arrays()
                        for d in a.devices()})
    losses = win.losses + (traced.losses if traced else [])
    tail = win.losses[-max(1, len(win.losses) // 10):]
    last = sum(tail) / len(tail)
    fell = warm.losses[0] - last
    failed = sum(not math.isfinite(l) for l in losses)
    checks = {
        "reference": check["ok"],
        "losses_finite": failed == 0 and all(map(math.isfinite, warm.losses)),
        "loss_fell": fell >= sizes["loss_fall_margin"],
        "no_retrace_in_window": after["traces"] == before["traces"],
        "no_fallback": after["fallback_seq"] == base["fallback_seq"]
        and step.last_step_compiled,
        "operands_on_device": platforms == [devices[0].platform],
    }
    say(f"window: {win.steps} steps of {batch} in {win.wall_s:.3f} s; loss "
        f"{warm.losses[0]:.4f} -> {last:.4f} (fell "
        f"{fell:.4f}, margin {sizes['loss_fall_margin']}); checks {checks}")

    ops_per_sample = cfg.ops_per_sample(sizes, mix)
    samples_per_s = win.steps * batch / win.wall_s
    peak_bytes = max(m["peak_bytes"] for m in memory)
    return {
        "correct": all(checks.values()),
        "checks": {**checks, "reference_check": check, "loss_fell_by": fell,
                   "live_array_platforms": platforms},
        "attempted": len(losses),
        "failed": failed,
        "end_to_end": {
            "samples_per_s": samples_per_s,
            "mfu": 100.0 * samples_per_s * ops_per_sample
            / (cell.chips * peak["bf16_flops_per_s"]),
            "peak_hbm_gib": peak_bytes / 2 ** 30,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": peak_bytes,
        "setup": setup,
        # what the per-layer readers see (README.md, "A layer metric")
        "obs": {
            "steps": win.steps, "window_s": win.wall_s, "batch": batch,
            "chips": cell.chips, "ops_per_step": ops_per_sample * batch,
            "peak": peak,
            "spans": {k: spans.seconds(k) for k in spans.records},
            "counters": {k: after[k] - before[k] for k in after},
            "programs": {k: after_setup[k] - base[k] for k in base},
            "memory": memory, "trace": trace,
        },
    }


def _laps():
    last = [time.perf_counter()]

    def lap():
        now = time.perf_counter()
        out, last[0] = now - last[0], now
        return out

    return lap


def _steps(step, prefetcher, batch, spans, stop):
    """The user's loop until ``stop(steps, now)``; every loss is read,
    ``IN_FLIGHT`` steps behind the dispatch, and the last read is the fence
    that ends the wall time."""
    losses, pending, n = [], deque(), 0
    t0 = time.perf_counter()
    while True:
        with spans("input_wait"):
            x, y = next(prefetcher)
        with spans("dispatch"):
            pending.append(step(x, y, batch_size=batch))
        n += 1
        if len(pending) > IN_FLIGHT:
            with spans("loss_read"):
                losses.append(float(pending.popleft().asnumpy()))
        if stop(n, time.perf_counter()):
            break
    while pending:
        with spans("loss_read"):
            losses.append(float(pending.popleft().asnumpy()))
    return SimpleNamespace(steps=n, losses=losses,
                           wall_s=time.perf_counter() - t0)


def _traced(step, prefetcher, batch, k, out_dir):
    """``k`` steady steps under the profiler, reduced in this process; the
    raw trace stays in the run's output directory."""
    import jax

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    # the host loop is part of what is measured, so the profiler traces the
    # device only; the benchmark's spans are laid over it by wall time
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False     # 0.1 GB a step program, unread here
    spans = Spans()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        traced = _steps(step, prefetcher, batch, spans, lambda n, now: n >= k)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    events, start_ns = trace_reduce.load_xplane(
        max(paths, key=os.path.getmtime))
    return traced, trace_reduce.reduce(events, steps=k,
                                       spans=spans.since(start_ns))


def _reference_check(mx, cfg, net, head_loss, sizes, mix, seed):
    """The program's forward pass on one seeded batch, at the configuration's
    own widths and precision, against the plain float32 reference.

    The check has weights of its own (:func:`_check_weights`); the net gets
    its initial ones back afterwards.  The net's first call, which resolves
    the deferred shapes inside one compiled program, is made here too."""
    import jax
    import jax.numpy as jnp

    spec = sizes["check"]
    x, y = cfg.check_batch(seed, sizes, mix)
    x_nd, y_nd = mx.nd.array(x), mx.nd.array(y)
    mode = mx.autograd.train_mode if spec["mode"] == "train" \
        else mx.autograd.predict_mode
    with mode():
        net(x_nd)
    initial = _check_weights(net, spec, seed)
    try:
        with mode():
            logits = net(x_nd)
            loss = float(head_loss(logits, y_nd).asnumpy())
        params = {n: p.data()._data for n, p in net.collect_params().items()}
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_logits = jax.jit(
                lambda p, x, y: cfg.reference(p, x, y, sizes))(params, x, y)
        scale = float(jnp.max(jnp.abs(ref_logits)))
        logits_err = float(jnp.max(jnp.abs(
            logits._data.astype(jnp.float32) - ref_logits))) / scale
    finally:
        for p, value in initial:
            p.set_data(value)
    ref_loss = float(ref_loss)
    loss_err = abs(loss - ref_loss) / max(abs(ref_loss), 1e-6)
    return {"ok": logits_err <= spec["logits_tol"]
            and loss_err <= spec["loss_tol"],
            "logits_err": logits_err, "logits_tol": spec["logits_tol"],
            "loss": loss, "reference_loss": ref_loss,
            "loss_err": loss_err, "loss_tol": spec["loss_tol"]}


def _check_weights(net, spec, seed):
    """Weights for the reference check, from the seed: every trainable
    vector (norm scale and shift, bias) is scaled by its entry of
    ``spec['scale']`` (matched by the end of its name) and gets noise of
    ``spec['vector_noise']``.  An initializer leaves biases and shifts at
    zero and scales at one, where a term left out of the mathematics would
    not show; and ResNet-50 at its initial weights, with batch statistics,
    turns one bf16 rounding of the input into a fifth of the logit scale
    (PERF.md, Findings), so its residual branches are damped here.
    Returns ``(parameter, initial value)`` pairs to restore."""
    import numpy as np

    rng = np.random.default_rng([seed, 2])
    initial = []
    for name, p in net.collect_params().items():
        if p.grad_req == "null" or len(p.shape) != 1:
            continue
        value = p.data().asnumpy()
        gain = next((g for end, g in spec["scale"].items()
                     if name.endswith(end)), 1.0)
        initial.append((p, value))
        p.set_data((value * gain + spec["vector_noise"]
                    * rng.standard_normal(value.shape)).astype(value.dtype))
    return initial


def _counters(mx):
    """The program's own counts (process-wide, so read as differences)."""
    cs, store = mx.cached_step, mx.program_store
    disk = store.disk_stats()
    return {
        "dispatches": cs.dispatch_count(),
        "traces": cs.trace_count(),
        "deferred_reads": cs.deferred_read_count(),
        "reshards": mx.parallel.spmd.reshard_count(),
        "fallback_seq": max((e["seq"] for e in
                             mx.telemetry.events("fallback")), default=0),
        "compile_s": store.compile_seconds(),
        "programs": sum(ns.compile_count
                        for ns in store.NAMESPACES.values()),
        "aot_fallbacks": sum(ns.aot_fallbacks
                             for ns in store.NAMESPACES.values()),
        "cache_hits": disk["hits"],
        "cache_misses": disk["misses"],
    }


def _memory(jax, devices, rehearse):
    """Per device, what the runtime counts.  On the TPU ``bytes_in_use`` is
    the live buffers and ``bytes_reserved`` the loaded programs' scratch,
    which ``peak_bytes_in_use`` leaves out: ResNet-50's step at batch 128
    holds 0.5 GB of buffers and reserves 4.3 GB (my chip run, PR 22).  The
    peak is their sum; the two peaks coincide while a training loop runs."""
    keys = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
            "peak_bytes_reserved")
    stats = [d.memory_stats() or {} for d in devices]
    if all(k in s for s in stats for k in keys):
        return [{**{k: s[k] for k in keys},
                 "peak_bytes": s["peak_bytes_in_use"]
                 + s["peak_bytes_reserved"]} for s in stats]
    if not rehearse:
        raise RuntimeError(
            f"this backend's memory_stats() lacks one of {keys}: "
            "peak_hbm_gib cannot be measured, and a zero would be a lie")
    # the CPU backend of a rehearsal: live-array bytes stand in
    used = dict.fromkeys(devices, 0)
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            if shard.device in used:
                used[shard.device] += shard.data.nbytes
    return [{"bytes_in_use": u, "bytes_reserved": 0, "peak_bytes": u}
            for u in used.values()]
