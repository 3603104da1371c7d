"""Device idle time the host adds around one dispatch into
``SweepRunner``: from the harness's dispatch span opening to the device's
first operation, plus from its last operation to the span closing (the
results pulled to the host), mean over chips."""


def read(run):
    tr = run["trace"]
    if not tr:
        return None
    return 1e3 * (tr["lead_s"] + tr["tail_s"])
