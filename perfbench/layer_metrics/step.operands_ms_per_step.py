"""Step scheduler (cached_step.TrainStep): milliseconds a step the host spent
making and reading the step program's operands: every ``jnp.asarray`` /
``device_put``, the next PRNG key, the parameters' and states' buffers, the
batch's placement.  The small device programs beside the step are launched
here.  The
program's own span ``train_step.operands``, the mean over the untraced window's
steps (a window of the accumulation cell is a step)."""
from perfbench import host_view


def read(obs):
    return host_view.phase_ms(obs, "operands")
