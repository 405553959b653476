"""Step scheduler: XLA programs the device ran per step in the traced window
(``XLA Modules`` events over steps, the busiest chip).  ``dispatches_per_step``
counts the compiled train step alone; this also sees every small program the
host path launches beside it."""


def read(obs):
    trace = obs["trace"]
    if not trace or not trace["steps"]:
        return None
    return max(d["module_runs"] for d in trace["devices"]) / trace["steps"]
