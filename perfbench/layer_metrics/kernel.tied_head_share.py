"""Kernels: share of the device's busy time over the vocabulary of a model
whose head is its embedding, either pass: the head's product and its
scaling, which stand directly under the causal-LM block (no block of their
own: the weight is the embedding's), and every loss block.  The lookup and
its gradient stay under ``Embedding`` and are not in it."""
from perfbench import scope_view

LM = "GraniteHybridForCausalLM"


def read(obs):
    return scope_view.share(
        obs, lambda row: row["classes"][-1:] == [LM]
        or any(c.endswith("Loss") for c in row["classes"]))
