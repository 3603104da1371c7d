"""Where a traced dispatch's time goes, by the program's own trace tags: the
engine's stages (``jax.named_scope`` in ``repro.core.engine``) and the
sweep's host phases (``sweep.*`` spans of ``repro.core.sweep.SweepRunner``).

These readers build on ``bench/trace.py``; the harness does not pass them
what they read yet (the executable's text, all host spans), so no
per-layer metric of ``BENCHMARK.json`` reads them (``PERF.md``, section
7).  ``scripts/record_trace_excerpt.py`` prints them for one dispatch, and
the tests hold them to a recorded chip trace.

* ``host_spans``: the host spans of a profile, ``bench.*`` and ``sweep.*``
  (``trace.load`` keeps ``bench.*`` only); with them in ``load``'s
  ``"spans"``, ``trace.reduce`` names idle gaps by the innermost span.
* ``idle_by_span``: per span name, the device idle time inside the span's
  self interval (the span less its child spans), mean over devices, which
  over all spans adds up to the traced span's idle time; and the part of
  it before the device's first operation or after its last (over all
  spans ``lead_s + tail_s``): the idle the host's own work leaves, where
  the first also holds the device's gaps between its operations.
* ``op_stages``: each instruction of the executable that ran
  (``Compiled.as_text()``) -> the stage scope that holds it (``STAGES``),
  or ``UNSCOPED``; ``shares`` charges a reduced trace's device time to
  the stage groups of ``SHARES``, which partition it.
"""
from __future__ import annotations

import bisect
import re

from bench import trace

SPAN_PREFIXES = ("bench.", "sweep.")
# the engine's stage scopes (repro.core.engine._make_step) and the loop's
# lane gate (_make_run); an instruction in none of them is UNSCOPED
STAGES = ("s1_signals", "s2_cc", "s12_fused", "s3_inject", "s4_gates",
          "s5_forward", "s5b_loss", "s6_queues", "s7_pfc", "s8_completion",
          "s9_history", "s10_health", "lane_gate")
UNSCOPED = "unscoped"
UNMAPPED = "unmapped"          # a traced operation the text does not name
SHARES = {
    "signals_share": ("s1_signals", "s2_cc", "s12_fused"),
    "forward_share": ("s3_inject", "s4_gates", "s5_forward", "s5b_loss"),
    "pfc_share": ("s6_queues", "s7_pfc"),
    "completion_share": ("s8_completion", "s9_history"),
    "health_share": ("s10_health",),
    "lane_gate_share": ("lane_gate",),
    "unscoped_share": (UNSCOPED, UNMAPPED),
}
HOST = {"host_prep_ms": ("sweep.inputs", "sweep.dispatch"),
        "host_pull_ms": ("sweep.pull", "sweep.results")}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def host_spans(profile) -> list:
    """``[(name, start_ns, end_ns), ...]`` of a ``ProfileData``'s host
    spans named ``bench.*`` or ``sweep.*``, by start."""
    spans = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(SPAN_PREFIXES)]
    return sorted(spans, key=lambda s: s[1])


def _busy_before(merged, starts, cum, t):
    """Busy time of the merged intervals before time ``t``."""
    i = bisect.bisect_right(starts, t)
    if i == 0:
        return 0.0
    s, e = merged[i - 1]
    return cum[i - 1] + min(e, t) - s


def idle_by_span(events: dict, span: str = "bench.dispatch"):
    """``(idle, edge)``: ``{span name: seconds}`` of device idle time in
    each span's self interval inside the first span named ``span``, and
    of the part of it before the device's first operation or after its
    last; ``({}, {})`` where there is no such span or device."""
    window = next(((s, e) for n, s, e in events["spans"] if n == span), None)
    if window is None or not events["devices"]:
        return {}, {}
    lo, hi = window
    pts = sorted({lo, hi} | {min(max(t, lo), hi)
                             for _, s, e in events["spans"] for t in (s, e)})
    pieces = [(a, b, trace._span_at(events["spans"], (a + b) / 2,
                                     "outside spans"))
              for a, b in zip(pts, pts[1:]) if b > a]
    idle = dict.fromkeys((n for _, _, n in pieces), 0.0)
    edge = dict(idle)
    for ops in events["devices"].values():
        merged = trace._union(ops, lo, hi)
        starts = [s for s, _ in merged]
        cum = [0.0]
        for s, e in merged:
            cum.append(cum[-1] + e - s)
        first, last = (merged[0][0], merged[-1][1]) if merged else (hi, hi)
        for a, b, name in pieces:
            idle[name] += (b - a) - (_busy_before(merged, starts, cum, b)
                                     - _busy_before(merged, starts, cum, a))
            edge[name] += max(min(b, first) - a, 0.0) \
                + max(b - max(a, last), 0.0)
    n = len(events["devices"]) * 1e9
    return ({k: v / n for k, v in idle.items()},
            {k: v / n for k, v in edge.items()})


def host_idle_ms(edge: dict) -> dict:
    """``HOST``'s readings from ``idle_by_span``'s ``edge``, in ms; a
    reading whose spans the trace lacks is left out."""
    return {m: 1e3 * sum(edge[s] for s in spans if s in edge)
            for m, spans in HOST.items() if any(s in edge for s in spans)}


def _scope_of(op_name: str) -> str | None:
    """The innermost stage scope in an ``op_name`` path; XLA joins the
    paths of merged instructions with ``;``, and the first is taken."""
    for part in reversed(op_name.split(";")[0].split("/")):
        if part in STAGES:
            return part
    return None


def op_stages(hlo_text: str) -> dict:
    """``{instruction name: stage}`` for every instruction of an
    executable's HLO text, from the ``op_name`` metadata that
    ``jax.named_scope`` leaves.  An instruction that calls a computation (a
    fusion, an async wrapper) is charged to the stage of that
    computation's root, or to its own where the root names none (a
    multi-output fusion's tuple).  The trace names device operations by
    the same instruction names (``op_key``)."""
    own, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line.rstrip().endswith("{"):
                head = line.split()
                comp = head[1 if head[0] == "ENTRY" else 0].lstrip("%")
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = _scope_of(op.group(1)) if op else None
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        if line.lstrip().startswith("ROOT "):
            roots[comp] = name

    def stage(name, depth=0):
        root = roots.get(calls.get(name))
        if root is not None and depth < 8:
            got = stage(root, depth + 1)
            if got is not None:
                return got
        return own.get(name)

    return {n: stage(n) or UNSCOPED for n in own}


def op_key(name: str) -> str:
    """``%fusion.262 = f32[...] fusion(...)`` -> ``fusion.262``."""
    return name.partition(" = ")[0].lstrip("%")


def stage_seconds(reduced: dict, op_stage: dict) -> dict:
    """Device seconds per stage of a reduced trace (``trace.reduce``), mean
    over devices; ``UNMAPPED`` holds operations ``op_stage`` lacks."""
    out: dict = {}
    for name, secs in reduced["device_ops"]:
        st = op_stage.get(op_key(name), UNMAPPED)
        out[st] = out.get(st, 0.0) + secs
    return out


def shares(reduced: dict, op_stage: dict) -> dict | None:
    """``SHARES``' readings (%) of the operations' device time, which add up
    to 100; ``None`` where the executable names no stage (a program
    without the scopes)."""
    if set(op_stage.values()) <= {UNSCOPED}:
        return None
    secs = stage_seconds(reduced, op_stage)
    total = sum(secs.values())
    if total <= 0:
        return None
    return {m: 100.0 * sum(secs.get(s, 0.0) for s in scopes) / total
            for m, scopes in SHARES.items()}
