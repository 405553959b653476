"""Kernels: share of the device's busy time under the gated delta rule's own
scope, either pass, whatever implements the rule: on a TPU the Pallas
kernel pair (the forward call, its recomputation, one backward call a
layer) and the decay sums around it; elsewhere XLA's chunked products,
decay masks, triangular inverse and the scan that carries the state."""
from perfbench import scope_view

RULE = "DeltaRule"


def read(obs):
    return scope_view.share(obs, lambda row: RULE in row["classes"])
