"""Device busy time of the traced dispatch per simulated step of the
batch (the steps of its longest lane, which the shared loop must run)."""


def read(run):
    tr = run["trace"]
    steps = max(run["lane_steps"])
    if not tr or steps <= 0:
        return None
    return 1e3 * tr["busy_s"] / steps
