"""Kernels: share of the device's busy time under the dense gated
feed-forward, either pass: its two products and the gate between them."""
from perfbench import scope_view

MLP = "OlmoHybridMLP"


def read(obs):
    return scope_view.share(obs, lambda row: MLP in row["classes"])
