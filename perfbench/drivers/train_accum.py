"""Driver ``train_accum``: one fixed microbatch shape through
``gluon.Trainer(kvstore='tpu').compile_step(net, loss_fn,
accum_steps=N)``, fed as a user's accumulation loop feeds it.

    for x, y in engine.prefetch(batches, sharding=step.batch_sharding):
        loss = step(x, y, batch_size=b)      # every Nth call also updates

A WINDOW is ``accum_steps`` microbatch dispatches and the one update the
last of them brings: ``accum_steps + 1`` device dispatches and one
optimizer step.  The loop reads ONE loss a window, the last microbatch's,
two windows behind the dispatch; the measured time ends in a host read of
the last window's loss.  ``samples_per_s`` counts sequences:
windows x ``accum_steps`` x microbatch over the wall time.  ``attempted``
and ``failed`` count windows.

Everything but the loop is driver ``train_fixed_shape`` itself, run from a
private copy of that module: its set-up, reference check, counters, memory
reading, traced window and end-to-end arithmetic.  This file hands it the
loop below in place of its own and has ``compile_step`` called with
``accum_steps``; what that driver counted a step is here a window of
``accum_steps`` microbatches, so the sequences a step, and with them
``samples_per_s``, ``mfu`` and the operations a step, are multiplied by
``accum_steps`` afterwards.  A run whose dispatches are not
``accum_steps + 1`` a window did not run this loop, and is refused.

A mix of this kind states what a ``train_fixed_shape`` mix states, with
``batch_per_chip`` the MICROBATCH a chip, plus ``accum_steps``;
``warmup_steps``, ``traced_steps`` and ``min_steps`` count windows.
"""
from __future__ import annotations

import time
from collections import deque
from types import SimpleNamespace

from perfbench import manifest

IN_FLIGHT = 2          # windows' losses left unread behind the newest


def run(cell, opts, devices, peak, say):
    import mxnet_tpu as mx

    base = manifest.load_module("drivers", "train_fixed_shape")  # private copy
    accum = int(cell.mix["accum_steps"])
    base._steps = lambda step, prefetcher, micro, spans, stop: _windows(
        step, prefetcher, micro, accum, spans, stop)
    compile_step = mx.gluon.Trainer.compile_step
    mx.gluon.Trainer.compile_step = lambda trainer, net, loss_fn: \
        compile_step(trainer, net, loss_fn, accum_steps=accum)
    try:
        result = base.run(cell, opts, devices, peak, say)
    finally:
        mx.gluon.Trainer.compile_step = compile_step

    obs, rates = result["obs"], result["end_to_end"]
    if obs["counters"]["dispatches"] != (accum + 1) * obs["steps"]:
        raise RuntimeError(
            f"{obs['counters']['dispatches']} dispatches in {obs['steps']} "
            f"windows of {accum} microbatches: train_fixed_shape did not run "
            "this driver's loop (has its _steps or its compile_step call "
            "been renamed?)")
    for key in ("samples_per_s", "mfu"):
        rates[key] *= accum
    obs.update(batch=obs["batch"] * accum, accum_steps=accum,
               ops_per_step=obs["ops_per_step"] * accum)
    say(f"a step above is a window of {accum} microbatches: {obs['steps']} "
        f"updates of {obs['batch']} sequences, "
        f"{rates['samples_per_s']:.2f} samples/s")
    return result


def _windows(step, prefetcher, micro, accum, spans, stop):
    """The user's accumulation loop until ``stop(windows, now)``: one loss a
    window is read, ``IN_FLIGHT`` windows behind the dispatch, and the last
    read is the fence that ends the wall time."""
    losses, pending, n = [], deque(), 0
    t0 = time.perf_counter()
    while True:
        for _ in range(accum):
            with spans("input_wait"):
                x, y = next(prefetcher)
            with spans("dispatch"):
                loss = step(x, y, batch_size=micro)
        pending.append(loss)
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
