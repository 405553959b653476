"""Step scheduler (cached_step.TrainStep): milliseconds a step the host spent
handing the program's outputs back: weights, optimizer state, mutated
values, the wrapped loss, and dropping the step's references to the donated
buffers as its frames return.  The
program's own span ``train_step.writeback``, the mean over the untraced window's
steps (a window of the accumulation cell is a step)."""
from perfbench import host_view


def read(obs):
    return host_view.phase_ms(obs, "writeback")
