"""Kernels: share of the device's busy time under the scan's own scope
(``ops/ssm.py``'s ``SsdScan``), either pass: the chunk products, the decay
masks, the state carried between chunks, and the forward that the backward
pass computes again."""
from perfbench import scope_view

SCAN = "SsdScan"


def read(obs):
    return scope_view.share(obs, lambda row: SCAN in row["classes"])
