"""Kernels: share of the device's busy time over the vocabulary, either
pass: the untied head's product (the one ``Dense`` directly under the
causal-LM block) and the loss block."""
from perfbench import scope_view

LM = "NemotronHForCausalLM"


def read(obs):
    return scope_view.share(
        obs, lambda row: row["classes"][-2:] == [LM, "Dense"]
        or any(c.endswith("Loss") for c in row["classes"]))
