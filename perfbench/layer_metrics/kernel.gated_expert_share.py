"""Kernels: share of the device's busy time under a gated sparse-expert
layer, the multi-token-prediction module's included, either pass: router,
dispatch, the grouped products, the shared expert, the combine."""
from perfbench import scope_view

MOE = "Glm4MoeLiteMoE"


def read(obs):
    return scope_view.share(obs, lambda row: MOE in row["classes"])
