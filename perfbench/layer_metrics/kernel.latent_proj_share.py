"""Kernels: share of the device's busy time in the latent-attention mixer
around its core, either pass: the two down-projections with their latent
norms, the two up-projections, rotary positions and the building of queries
and keys with the shared rotary key: everything under the mixer's block but
the core (``LatentCore``) and the output projection (``LatentOut``)."""
from perfbench import scope_view

MIXER, NOT = "LatentAttention", {"LatentCore", "LatentOut"}


def read(obs):
    return scope_view.share(
        obs, lambda row: MIXER in row["classes"]
        and not NOT & set(row["classes"]))
