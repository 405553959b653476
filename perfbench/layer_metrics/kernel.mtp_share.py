"""Kernels: share of the device's busy time in the multi-token-prediction
module whole, either pass: the second embedding lookup, the two norms and
the projection of their concatenation, the module's decoder layer, its final
norm, its pass through the shared head (all under ``MTPModule``) and its
loss (``MultiTokenLoss``)."""
from perfbench import scope_view

MODULE = {"MTPModule", "MultiTokenLoss"}


def read(obs):
    return scope_view.share(obs, lambda row: MODULE & set(row["classes"]))
