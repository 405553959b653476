"""Kernels: the least time the chip could take for one step, over the time it
was busy for one.  Compute bounds all four training cells (operations per
step over the bf16 peak is far above bytes over the HBM peak), so the least
time is operations / peak; busy time per step comes from the device trace."""


def read(obs):
    trace = obs["trace"]
    if not trace or not trace["steps"]:
        return None
    least_s = obs["ops_per_step"] / obs["chips"] / obs["peak"]["bf16_flops_per_s"]
    return 100.0 * least_s / (trace["busy_s"] / trace["steps"])
