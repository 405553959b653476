"""Kernels: share of the device's busy time over the vocabulary, either
pass: BOTH products of the one untied head (the ``Dense`` directly under the
causal-LM block, and the same block called again directly under
``MTPModule``) and both losses (the loss block)."""
from perfbench import scope_view

HEAD_UNDER = ("Glm4MoeLiteForCausalLM", "MTPModule")


def _is_head(classes):
    return len(classes) >= 2 and classes[-1] == "Dense" \
        and classes[-2] in HEAD_UNDER


def read(obs):
    return scope_view.share(
        obs, lambda row: _is_head(row["classes"])
        or any(c.endswith("Loss") for c in row["classes"]))
