"""Step scheduler: compiled launches per step in the window, from
``cached_step.dispatch_count`` (expected 1.0; any rise is a fault)."""


def read(obs):
    return obs["counters"]["dispatches"] / obs["steps"]
