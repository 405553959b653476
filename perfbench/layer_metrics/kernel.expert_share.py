"""Kernels: share of the device's busy time under a sparse-expert layer,
either pass: router, dispatch, the grouped products, the shared expert, the
combine."""
from perfbench import scope_view

MOE = "NemotronHMoE"


def read(obs):
    return scope_view.share(obs, lambda row: MOE in row["classes"])
