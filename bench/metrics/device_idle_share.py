"""Share of the traced dispatch in which the device ran no operation
(1 - busy union / span), mean over the chips the cell uses."""


def read(run):
    tr = run["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
