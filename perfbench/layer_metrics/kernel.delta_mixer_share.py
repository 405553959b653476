"""Kernels: share of the device's busy time under a gated delta-rule mixer,
either pass: its seven projections, the three short convolutions, the L2
norms and gates, the rule, the gated head norm."""
from perfbench import scope_view

MIXER = "GatedDeltaNet"


def read(obs):
    return scope_view.share(obs, lambda row: MIXER in row["classes"])
