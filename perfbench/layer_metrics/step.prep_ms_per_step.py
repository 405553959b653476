"""Step scheduler (cached_step.TrainStep): milliseconds a step the host spent
before the step's first per-step device value: the checks, padding, update
counts, flattening the arguments, ``_prep()``, the signature, the learning
rates and the scaler's branch.  The
program's own span ``train_step.prep``, the mean over the untraced window's
steps (a window of the accumulation cell is a step)."""
from perfbench import host_view


def read(obs):
    return host_view.phase_ms(obs, "prep")
