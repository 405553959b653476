"""Step scheduler (cached_step.TrainStep): milliseconds a step the host spent
in ``_ensure_program`` and in the call of the compiled program (a window of
the accumulation cell holds its grad launches and its update launch).  The
program's own span ``train_step.launch``, the mean over the untraced window's
steps (a window of the accumulation cell is a step)."""
from perfbench import host_view


def read(obs):
    return host_view.phase_ms(obs, "launch")
