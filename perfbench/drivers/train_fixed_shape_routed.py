"""Driver ``train_fixed_shape_routed``: driver ``train_fixed_shape`` itself
(its loop, its set-up, its end-to-end arithmetic, its checks: this file runs
that file's code), with ONE difference: the comparison with the plain
reference is the configuration's own ``compare``.

For a model that makes discrete choices from rounded activations (a router's
top-k).  At a near tie a bf16 program and the float32 reference may both be
right and differ by an expert's whole output, so a maximum over every
position says nothing; the configuration's ``reference_parts`` names such
positions from the REFERENCE's own margins and ``compare`` holds them and
the rest to separate limits (``configs/<name>.py``; the limits and their
reasons are in the JSON's ``check``).  Everything else of the check is
``train_fixed_shape``'s: the seeded batch, the check's own weights, the
program's forward at the configuration's widths and precision, the float32
reference at the highest matmul precision.

The run's ``obs`` also gets the cell's ``sizes`` and ``mix`` and, as
``moe_traced``, what the expert layers counted on the device during the
traced steps alone: this cell's roofline readers count operations from
them and name no cell.  A run in which the accepted driver called neither
hook is refused, not reported.
"""
from __future__ import annotations

import math

from perfbench import manifest


def run(cell, opts, devices, peak, say):
    base = manifest.load_module("drivers", "train_fixed_shape")  # a private copy
    # a cell of ONE sequence a step has no smaller batch to rehearse with,
    # and a mix's rehearsal must state a smaller one than the cell's
    # (tests/perfbench/test_manifest.py): it states a fraction, and a
    # fraction of a sequence is run as one sequence
    cell.mix = {**cell.mix,
                "batch_per_chip": math.ceil(cell.mix["batch_per_chip"])}
    base._reference_check = _reference_check(base, opts, say)
    in_trace = {}
    base._traced = _counted(base._traced, in_trace)
    result = base.run(cell, opts, devices, peak, say)
    if "exposed_share" not in result["checks"]["reference_check"] \
            or (opts.trace and not in_trace):
        raise RuntimeError(
            "train_fixed_shape did not call the comparison or the traced "
            "window this driver hands it (has its _reference_check or its "
            "_traced been renamed?): nothing of this run is reported")
    # what this cell's readers need beside the accepted driver's obs: the
    # sizes and the mix they count operations from, and what the expert
    # layers counted during the traced steps alone
    result["obs"].update(sizes=cell.sizes, mix=cell.mix, moe_traced=in_trace)
    import mxnet_tpu as mx

    say(f"memory by device {result['obs']['memory']}")
    say(f"expert layers {_moe_counts(mx)}")
    for e in mx.telemetry.events("fallback")[-3:]:
        say(f"fallback event {e}")
    return result


def _moe_counts(mx):
    return {k: v for k, v in mx.telemetry.snapshot().items()
            if k.startswith("moe.")}


def _counted(traced, into):
    """``train_fixed_shape._traced`` between two readings of the expert
    layers' device counters (no step is in flight at either): ``into`` gets
    what the traced steps alone counted."""
    def counted(*args):
        import mxnet_tpu as mx

        before = _moe_counts(mx)
        out = traced(*args)
        into.update({k: v - before[k] for k, v in _moe_counts(mx).items()
                     if k in ("moe.rows_held", "moe.steps")})
        return out

    return counted


def _reference_check(base, opts, say):
    def check(mx, cfg, net, head_loss, sizes, mix, seed):
        import gc

        import jax
        import numpy as np

        spec = sizes["check"]
        x, y = cfg.check_batch(seed, sizes, mix)
        x_nd, y_nd = mx.nd.array(x), mx.nd.array(y)
        mode = mx.autograd.train_mode if spec["mode"] == "train" \
            else mx.autograd.predict_mode
        with mode():
            net(x_nd)
        initial = base._check_weights(net, spec, seed)
        reference = jax.jit(
            lambda p, x, y: cfg.reference_parts(p, x, y, sizes))
        try:
            with mode():
                logits = net(x_nd)
                loss = float(head_loss(logits, y_nd).asnumpy())
            params = {n: p.data()._data
                      for n, p in net.collect_params().items()}
            with jax.default_matmul_precision("highest"):
                ref_loss, ref_logits, margins = reference(params, x, y)
            out = cfg.compare(logits._data, loss, float(ref_loss),
                              ref_logits, margins, sizes)
        finally:
            for p, value in initial:
                p.set_data(value)
        # every position's error and margins stay in the run's directory,
        # for whoever sets the limits (PERF.md says how they were set)
        np.savez(f"{opts.out_dir}/reference_check.npz",
                 **{k: np.asarray(v)
                    for k, v in out.pop("per_position").items()})
        # the check's two programs (the net's forward at the cell's length
        # and the reference) each hold device scratch while they are
        # loaded, on top of the step's: a user's process has neither, so
        # they are unloaded before the step is built
        del logits, ref_logits, margins, params
        reference.clear_cache()
        net.hybridize()
        gc.collect()
        return out

    return check

