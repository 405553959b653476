"""Input layer (engine.prefetch): milliseconds a step waited in
``next(prefetcher)``, the mean over the untraced window (host clock)."""


def read(obs):
    waits = obs["spans"].get("input_wait")
    return 1e3 * sum(waits) / obs["steps"] if waits else None
