"""Device: collective time during which no other operation runs on that
chip, over the traced window, on the worst chip (0 on one chip)."""


def read(obs):
    trace = obs["trace"]
    if not trace:
        return None
    return 100.0 * max(d["collective_exposed_s"] for d in trace["devices"]) \
        / trace["window_s"]
