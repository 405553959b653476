"""Kernels: the least time the chip could take for the causal attention
core's score and value products of one step (the configuration's own
``attention_core_macs``, at half the square) at the bf16 peak, over the
device time a step in the attention block ITSELF (innermost block
``NemotronHAttention``: the grouped-head kernels or the unfused expression
and what lies around them, not its ``Dense`` children), whatever implements
the core; the layers are the configuration's ``attention`` entries or ``*``
letters (``perfbench.layer_roofline``)."""
from perfbench import layer_roofline


def read(obs):
    return layer_roofline.read(
        obs, lambda classes: classes[-1:] == ["NemotronHAttention"],
        "attention_core_macs", "attention", "*")
