"""From a profiler trace to the numbers the per-layer metrics read.

``capture`` traces one call with the JAX profiler.  ``load`` reads the
trace (``.xplane.pb``) into plain lists: the operations each device ran
(its ``XLA Ops`` line, named by their HLO text) and the harness's own host
spans (``bench.*`` ``TraceAnnotation`` events).  Control-flow operations
(``while``, ``conditional``, ``call``) only enclose the operations they
run, so they are left out.  ``reduce`` turns the rest into:

* ``busy_s``: the union of each device's operation intervals inside the
  traced span, averaged over the devices;
* ``window_s``: the length of the traced span;
* ``lead_s`` / ``tail_s``: per device, idle time from the span's start to
  its first operation and from its last operation to the span's end
  (mean over devices);
* ``device_ops``: seconds per operation (mean over devices), longest
  first, and ``op_counts``: how often each ran (mean over devices);
* ``idle_gaps``: the longest idle gaps, each named by the harness span the
  host was in and by where in it the gap lies.

``short_name`` gives an operation's name for the result's breakdown.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
CONTAINERS = {"while", "conditional", "call"}
_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9_\-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an operation named by its HLO text."""
    m = _OPCODE.search(name)
    return m.group(1) if m else name


def short_name(name: str) -> str:
    """``%fusion.262 = f32[73728,12]{...} fusion(...)`` ->
    ``%fusion.262 f32[73728,12] fusion``; Mosaic kernels are marked."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name
    kind = "mosaic kernel" if 'custom_call_target="tpu_custom_call"' in rest \
        else opcode(name)
    return f"{head} {rest.split('{')[0][:48]} {kind}"


def capture(fn, span: str = "bench.dispatch"):
    """Run ``fn`` under the profiler inside the span ``span``.  Returns
    ``(fn's result, loaded trace)``; the trace files are deleted."""
    import jax

    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(logdir)
        try:
            with jax.profiler.TraceAnnotation(span):
                out = fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        from jax.profiler import ProfileData
        return out, load(ProfileData.from_file(max(paths,
                                                   key=os.path.getmtime)))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def load(profile) -> dict:
    """``{"devices": {plane: [(start_ns, end_ns, name), ...]},
    "spans": [(name, start_ns, end_ns), ...]}`` of a ``ProfileData``."""
    devices, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events
                   if opcode(e.name) not in CONTAINERS]
            if ops:
                devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, float(e.start_ns),
                       float(e.start_ns + e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def _union(ops, lo, hi):
    """Merged busy intervals of ``ops`` clipped to [lo, hi]."""
    out = []
    for s, e, _ in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _span_at(spans, t, outer):
    """The innermost harness span holding time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else outer


def reduce(events: dict, span: str = "bench.dispatch", top: int = 10) -> dict:
    """Reduce ``load``'s output over the first host span named ``span``."""
    window = next(((s, e) for n, s, e in events["spans"] if n == span), None)
    if window is None or not events["devices"]:
        return {}
    lo, hi = window
    n_dev = len(events["devices"])
    busy = lead = tail = 0.0
    per_op: dict = {}
    counts: dict = {}
    gaps = []
    for plane, ops in events["devices"].items():
        merged = _union(ops, lo, hi)
        busy += sum(e - s for s, e in merged)
        if not merged:
            lead += hi - lo
            continue
        lead += merged[0][0] - lo
        tail += hi - merged[-1][1]
        edges = ([(lo, merged[0][0], "before first op")]
                 + [(a[1], b[0], "between ops")
                    for a, b in zip(merged, merged[1:])]
                 + [(merged[-1][1], hi, "after last op")])
        for s, e, where in edges:
            if e > s:
                name = _span_at(events["spans"], (s + e) / 2, "outside spans")
                gaps.append((e - s, f"{name}: {where}"))
        for s, e, name in ops:
            if e > lo and s < hi:
                per_op[name] = per_op.get(name, 0.0) + (min(e, hi)
                                                        - max(s, lo))
                counts[name] = counts.get(name, 0) + 1
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy / n_dev / 1e9,
        "window_s": (hi - lo) / 1e9,
        "lead_s": lead / n_dev / 1e9,
        "tail_s": tail / n_dev / 1e9,
        "n_devices": n_dev,
        "device_ops": [[n, v / n_dev / 1e9] for n, v in ops_sorted],
        "op_counts": {n: c / n_dev for n, c in counts.items()},
        "idle_gaps": [[n, g / 1e9] for g, n in sorted(gaps,
                                                      reverse=True)[:top]],
    }
