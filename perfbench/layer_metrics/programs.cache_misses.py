"""Program store: fresh XLA compiles during set-up, the persistent cache's
misses (expected 0 on every run of a cell after its first in a checkout)."""


def read(obs):
    return obs["programs"]["cache_misses"]
