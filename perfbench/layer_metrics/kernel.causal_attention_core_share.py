"""Kernels: share of the device's busy time in the causal attention block
ITSELF, either pass: the grouped-head kernels (or the unfused expression)
and what lies around them.  Its ``Dense`` children (the four projections)
are not in it."""
from perfbench import scope_view

ATTENTION = "NemotronHAttention"


def read(obs):
    return scope_view.share(
        obs, lambda row: row["classes"][-1:] == [ATTENTION])
