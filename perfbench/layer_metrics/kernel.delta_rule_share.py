"""Kernels: share of the device's busy time under the gated delta rule's own
scope, either pass: the chunks' products, the decay masks, the triangular
inverse and the scan that carries the state, whatever implements the rule."""
from perfbench import scope_view

RULE = "DeltaRule"


def read(obs):
    return scope_view.share(obs, lambda row: RULE in row["classes"])
