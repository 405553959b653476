"""Input layer (engine.prefetch): milliseconds a step the prefetcher's thread
spent in ``transfer(item)``, from its own ``input.transfer`` spans that end
inside the untraced window.  The call returns when the copy is handed over,
not when it has landed."""
from perfbench import host_view


def read(obs):
    w = host_view.window(obs)
    if not w or w["transfer_s"] is None:
        return None
    return 1e3 * w["transfer_s"] / w["steps"]
