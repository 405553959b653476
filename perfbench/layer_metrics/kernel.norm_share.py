"""Kernels: share of the device's busy time in operations whose innermost
block is a normalisation layer, either pass: every normalisation block class
of ``gluon.nn``.  Norm work that XLA fused into a neighbour's fusion (a
convolution's epilogue, the next block's backward) counts with the
neighbour; a norm written as an operator inside another block (the Mamba-2
mixer's gated norm, the delta rule's L2 norms) counts with that block."""
from perfbench import scope_view

NORMS = ("BatchNorm", "SyncBatchNorm", "BatchNormReLU", "LayerNorm",
         "RMSNorm", "GroupNorm", "InstanceNorm")


def read(obs):
    return scope_view.share(
        obs, lambda row: bool(row["classes"]) and row["classes"][-1] in NORMS)
