"""Step scheduler: device dispatches an accumulation window, from the
program's own counter (``cached_step.dispatch_count``): ``accum_steps``
microbatch programs and one update, so ``accum_steps + 1``.  Only a driver
whose steps are windows (``train_accum``) gives something to read."""


def read(obs):
    if "accum_steps" not in obs or not obs["steps"]:
        return None
    return obs["counters"]["dispatches"] / obs["steps"]
