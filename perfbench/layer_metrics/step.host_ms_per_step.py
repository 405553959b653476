"""Step scheduler (cached_step.TrainStep): milliseconds of host time inside
the ``step(...)`` call, which does not block on the device, the mean over the
untraced window (host clock)."""


def read(obs):
    calls = obs["spans"].get("dispatch")
    return 1e3 * sum(calls) / obs["steps"] if calls else None
