"""Input layer (engine.prefetch): of the prefetcher thread's time in the
untraced window, the share it waited for a free slot of the FIFO
(``input.slot_wait`` over ``input.transfer`` + ``input.slot_wait``): near 100
the thread is ahead of the loop, near 0 the input sets the pace."""
from perfbench import host_view


def read(obs):
    w = host_view.window(obs)
    if not w or w["transfer_s"] is None:
        return None
    total = w["transfer_s"] + w["slot_wait_s"]
    return 100.0 * w["slot_wait_s"] / total if total > 0 else None
