"""Kernels: share of the device's busy time in the latent-attention core
ITSELF (``causal_latent_selfatt``'s ``LatentCore`` scope), either pass: the
causal flash kernels at 20 heads of 256 (or the unfused expression), the
forward that the backward pass computes again included."""
from perfbench import scope_view

CORE = "LatentCore"


def read(obs):
    return scope_view.share(obs, lambda row: CORE in row["classes"])
