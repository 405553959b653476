"""Kernels: share of the device's busy time in the full-attention block
ITSELF, either pass: the causal kernels (or the unfused expression) and the
delta reduction.  Its ``Dense`` and ``RMSNorm`` children (the four
projections, QK-norm) are not in it."""
from perfbench import scope_view

ATTENTION = "OlmoHybridAttention"


def read(obs):
    return scope_view.share(
        obs, lambda row: row["classes"][-1:] == [ATTENTION])
