"""Driver ``train_fixed_shape_seq``: driver ``train_fixed_shape`` itself (its
loop, its set-up, its end-to-end arithmetic, its checks and its comparison
with the plain reference: this file runs that file's code through a private
copy, as ``train_fixed_shape_routed`` does), for a dense model whose cell is
ONE sequence a step.  What differs:

- a cell of one sequence has no smaller batch to rehearse with, and a mix's
  rehearsal must state a smaller one than the cell's
  (``tests/perfbench/test_manifest.py``): it states a fraction, and a
  fraction of a sequence is run as one sequence;
- the run's ``obs`` also gets the cell's ``sizes`` and ``mix``, which the
  cell's roofline reader counts operations from (it names no cell);
- the check's two programs (the net's forward at the cell's length and the
  reference) are unloaded before the step is built, as
  ``train_fixed_shape_routed`` unloads its own: each holds device scratch
  while it is loaded, on top of the step's, and a user's process has
  neither.

The model makes no discrete choice from rounded activations, so
``train_fixed_shape``'s maximum over positions is a fair limit and no
comparison of the configuration's own is wanted.
"""
from __future__ import annotations

import gc
import math

from perfbench import manifest


def run(cell, opts, devices, peak, say):
    base = manifest.load_module("drivers", "train_fixed_shape")  # a private copy
    cell.mix = {**cell.mix,
                "batch_per_chip": math.ceil(cell.mix["batch_per_chip"])}
    check = base._reference_check

    def unloaded(mx, cfg, net, *rest):
        out = check(mx, cfg, net, *rest)
        net.hybridize()
        gc.collect()
        return out

    base._reference_check = unloaded
    result = base.run(cell, opts, devices, peak, say)
    result["obs"].update(sizes=cell.sizes, mix=cell.mix)
    say(f"memory by device {result['obs']['memory']}")
    return result
