"""Device: of the worst chip's idle time in the traced window, the share in
which the step program the gap ends had already been launched
(``host_view``): the chip waited for the batch or a peer, not for the host.
The rest is the host's to shorten.  The refill before the first traced step
program, which is where the trace starts and not what the loop does, is left
out of both (``host_view``'s docstring)."""
from perfbench import host_view


def read(obs):
    v = host_view.traced(obs)
    return v["launched_share"] if v else None
