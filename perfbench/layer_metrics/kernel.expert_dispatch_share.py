"""Kernels: share of the device's busy time an expert layer spends on
anything but products: the router's scores and top-k, the counting sort, the
gather into the held experts' buffer and the weighted scatter back.  The
expert layer less its grouped products (scope ``MoEExperts``) and its shared
expert."""
from perfbench import scope_view

MOE, PRODUCTS, SHARED = "NemotronHMoE", "MoEExperts", "NemotronHMLP"


def read(obs):
    return scope_view.share(
        obs, lambda row: MOE in row["classes"]
        and PRODUCTS not in row["classes"] and SHARED not in row["classes"])
