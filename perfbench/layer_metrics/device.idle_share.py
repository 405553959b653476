"""Device: 1 minus the union of device-op intervals over the traced steady
window, on the most idle chip."""


def read(obs):
    trace = obs["trace"]
    if not trace:
        return None
    return 100.0 * max(d["idle_share"] for d in trace["devices"])
