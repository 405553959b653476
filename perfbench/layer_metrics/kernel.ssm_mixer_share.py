"""Kernels: share of the device's busy time under a Mamba-2 mixer, either
pass: its two projections, the causal convolution, the scan, the gated
norm."""
from perfbench import scope_view

MIXER = "NemotronHMamba2Mixer"


def read(obs):
    return scope_view.share(obs, lambda row: MIXER in row["classes"])
