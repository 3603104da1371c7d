"""Device time of stages 1-2 (delayed signals and the CC update) as a
share of the device's busy time in the traced dispatch."""
from bench.roofline import stage12_time


def read(run):
    tr = run["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    secs, _ = stage12_time(tr)
    if secs <= 0:
        return None
    return 100.0 * secs / tr["busy_s"]
