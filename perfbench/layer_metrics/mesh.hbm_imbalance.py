"""Placement: the fullest device over the emptiest, by live buffers plus
reserved program scratch after the window (1.0 on one chip)."""


def read(obs):
    used = [m["bytes_in_use"] + m["bytes_reserved"] for m in obs["memory"]]
    return max(used) / min(used) if min(used) > 0 else None
