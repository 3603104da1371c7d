"""The comparison that decides ``correct``: lanes the timed dispatches
produced against the configuration's plain reference (the module its
``reference`` key names; see ``bench/modules.py``).

Numbers compared, per sampled lane (the largest over the sample counts):

* ``finish_gap_steps``: the widest gap between a flow's finish time in the
  program and in the reference, in simulated steps.  A flow that never
  finished counts as finishing at the end of the step budget, so the
  number stays finite.
* ``pause_gap``: the widest gap in PAUSE frames sent to a device, as a
  share of the reference's largest per-device count (of 1 where it has
  none).
* ``status_mismatch``: lanes whose completion or pause-cycle verdict
  differs from the reference's (exact; limit 0).

A cell compares the numbers its limits file names.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("finish_gap_steps", "pause_gap", "status_mismatch")


def lane_numbers(got: dict, want: dict, dt: float, budget_steps: int) -> dict:
    """``got``/``want``: ``t_finish`` (F,) seconds (inf: never finished),
    ``pause_count`` (D,), ``finished`` and ``deadlocked`` (bool)."""
    end = budget_steps * dt
    tg = np.where(np.isfinite(got["t_finish"]), got["t_finish"], end)
    tw = np.where(np.isfinite(want["t_finish"]), want["t_finish"], end)
    pg = np.asarray(got["pause_count"], np.float64)
    pw = np.asarray(want["pause_count"], np.float64)
    return {
        "finish_gap_steps": float(np.max(np.abs(tg - tw)) / dt),
        "pause_gap": float(np.max(np.abs(pg - pw))
                           / max(float(np.max(pw)), 1.0)),
        "status_mismatch": int(bool(got["finished"]) != bool(want["finished"])
                               or bool(got["deadlocked"])
                               != bool(want["deadlocked"])),
    }


def worst(readings: list[dict]) -> dict:
    """The largest of each number over several lanes (or dispatches)."""
    return {k: max(r[k] for r in readings) for k in NUMBERS}


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def report(numbers: dict, limits: dict) -> dict:
    """The result line's ``check`` entry: each number beside its limit."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
