"""Kernels: share of the device's busy time spent in convolution and dot
fusions (``trace_reduce.categorise``)."""


def read(obs):
    trace = obs["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["category_s"]["matrix"] / trace["busy_s"]
