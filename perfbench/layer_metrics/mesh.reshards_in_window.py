"""Placement (parallel/spmd, TrainStep._prep): re-placements counted by
``spmd.reshard_count`` inside the window (expected 0)."""


def read(obs):
    return obs["counters"]["reshards"]
