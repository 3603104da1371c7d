"""Stages 1-2 (delayed signals and the CC update) against HBM bandwidth:
the bytes one call must move (``bench/roofline.py``) over the peak, as a
share of the call's mean device time in the trace."""
from bench.roofline import stage12_time


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    secs, calls = stage12_time(tr)
    if calls <= 0 or secs <= 0:
        return None
    least = run["stage12_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / calls)
