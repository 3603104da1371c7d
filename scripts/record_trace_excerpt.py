"""Record a small excerpt of a benchmark cell's traced dispatch on the chip,
for the CPU tests of the trace readers (``bench/tests/data/``), and print
where the whole dispatch's time went by the program's trace tags.

    python3 scripts/record_trace_excerpt.py --workload ring128_ar.atlas_dcqcn \
        --seed 1 --out excerpt/trace_excerpt_scoped

Runs on a TPU, in one process: builds the cell as ``bench/run.py`` does,
compiles its executable, dispatches the batch once untraced, then once
under the profiler inside a ``bench.dispatch`` span, and prints the lane
steps per second of each dispatch (what an open profiler session costs
the end-to-end metric; the session's start and stop are not timed).  It
writes
``<out>.pbtxt``: the device's ``XLA Ops`` line cut to its first and last
``--edge`` operations and one loop step's worth (the ops over
``steps_run``) from the middle, with the time of the cut-out operations
taken out of every later timestamp (device and host alike), so that the
lead, the tail and the gaps between kept operations stay as recorded; and
the host's ``bench.*`` / ``sweep.*`` spans.  Control-flow operations, which
only enclose others, are left out.  ``<out>.op_stage.json`` holds the
executable's op -> stage map (``bench.stages.op_stages``) for the kept
operations.  It prints the whole dispatch's readings (``bench/stages.py``:
the stage shares, the device idle time by host span, busy time per loop
step) as JSON, and the stats the trace carries on device operations.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import stages  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.lanes import make_lanes  # noqa: E402
from bench.run import (Program, lane_steps, load_cell,  # noqa: E402
                       use_compilation_cache)


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _plane(pid: int, name: str, events: list, base_ns: float) -> str:
    """One plane of an XSpace text proto with one line of ``events``
    (``(line name, start_ns, end_ns, event name)``)."""
    names = sorted({n for _, _, _, n in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f"planes {{\n  id: {pid}\n  name: {_quote(name)}"]
    for lid, line in enumerate(sorted({ln for ln, _, _, _ in events}), 1):
        out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(line)}\n"
                   "    timestamp_ns: 0")
        for ln, s, e, n in events:
            if ln == line:
                out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                           f"{round((s - base_ns) * 1000)} duration_ps: "
                           f"{round((e - s) * 1000)} }}")
        out.append("  }")
    for n in names:
        out.append(f"  event_metadata {{ key: {ids[n]} value {{ id: "
                   f"{ids[n]} name: {_quote(n)} }} }}")
    out.append("}")
    return "\n".join(out)


def load(profile) -> dict:
    """``trace.load`` with every ``bench.*`` and ``sweep.*`` host span."""
    events = trace_mod.load(profile)
    events["spans"] = stages.host_spans(profile)
    return events


def readings(events: dict, op_stage: dict, steps_run: int,
             top: int = 8) -> dict:
    """The whole traced dispatch by stage and by host span."""
    red = trace_mod.reduce(events)
    secs = stages.stage_seconds(red, op_stage)
    idle, edge = stages.idle_by_span(events)
    unscoped = [[trace_mod.short_name(n), v] for n, v in red["device_ops"]
                if op_stage.get(stages.op_key(n), stages.UNMAPPED)
                in (stages.UNSCOPED, stages.UNMAPPED)]
    return {"busy_s": red["busy_s"], "window_s": red["window_s"],
            "dispatch_gap_ms": 1e3 * (red["lead_s"] + red["tail_s"]),
            "steps_run": steps_run,
            "loop_step_ms": 1e3 * red["busy_s"] / steps_run,
            "shares": stages.shares(red, op_stage),
            "stage_s": dict(sorted(secs.items(), key=lambda kv: -kv[1])),
            **stages.host_idle_ms(edge),
            "idle_by_span_s": idle, "edge_idle_by_span_s": edge,
            "idle_gaps": red["idle_gaps"][:4],
            "unscoped_ops": unscoped[:top],
            "device_ops": [[trace_mod.short_name(n), v]
                           for n, v in red["device_ops"][:top]]}


def excerpt(events: dict, steps_run: int, edge: int) -> tuple[str, list]:
    """``(text proto, kept op names)`` of a traced dispatch's events."""
    (dev, ops), = events["devices"].items()
    lo, hi = next((s, e) for n, s, e in events["spans"]
                  if n == "bench.dispatch")
    ops = [op for op in ops if op[1] > lo and op[0] < hi]
    per_step = -(-len(ops) // steps_run)
    mid = len(ops) // 2
    keep = sorted(set(range(edge)) | set(range(mid, mid + per_step))
                  | set(range(len(ops) - edge, len(ops))))
    # take the time of every cut-out run of operations out of what follows
    shifts, removed = [], 0.0
    for a, b in zip(keep, keep[1:]):
        if b > a + 1:
            removed += ops[b][0] - ops[a + 1][0]
            shifts.append((ops[b][0], removed))

    def moved(t):
        return t - max((r for at, r in shifts if t >= at), default=0.0)

    kept = [ops[i] for i in keep]
    dev_events = [(trace_mod.OPS_LINE, moved(s), moved(e), n)
                  for s, e, n in kept]
    host_events = [("python", moved(s), moved(e), n)
                   for n, s, e in events["spans"]]
    base = min(lo, kept[0][0])
    text = (_plane(1, dev, dev_events, base) + "\n"
            + _plane(2, "/host:CPU", host_events, base) + "\n")
    return text, [n for _, _, n in kept]


def device_op_stats(profile) -> list:
    """The stat names carried by the device's operation events."""
    names = set()
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    for e in list(line.events)[:1000]:
                        names |= {k for k, _ in e.stats}
    return sorted(names)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--edge", type=int, default=150)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    use_compilation_cache()
    import jax
    from jax.profiler import ProfileData
    if jax.devices()[0].platform != "tpu":
        print("record_trace_excerpt: no TPU; nothing recorded",
              file=sys.stderr)
        return 2
    lanes = make_lanes(cell["mix"], args.seed)
    prog = Program(cell["config"], cell["mix"], lanes)
    e = cell["config"]["engine"]
    budget = int(e["max_steps"]) * (int(e["max_extends"]) + 1)
    compiled = prog.compile()
    prog.dispatch()                                  # warm

    def rate():
        t0 = time.perf_counter()
        batch = prog.dispatch()
        secs = time.perf_counter() - t0
        return batch, sum(lane_steps(batch, float(e["dt"]), budget)) / secs

    _, untraced = rate()
    logdir = tempfile.mkdtemp(prefix="trace_excerpt_")
    try:
        jax.profiler.start_trace(logdir)
        try:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                batch, traced = rate()
        finally:
            jax.profiler.stop_trace()
        print(f"lane_steps_per_s of one dispatch: untraced {untraced}, "
              f"traced {traced}")
        path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        profile = ProfileData.from_file(path)
        print(f"device op stats: {device_op_stats(profile)}")
        events = load(profile)
        del profile
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    steps_run = int(max(batch.steps_run))
    op_stage = stages.op_stages(compiled.as_text())
    print(json.dumps(readings(events, op_stage, steps_run)))
    text, names = excerpt(events, steps_run, args.edge)
    keys = sorted({stages.op_key(n) for n in names})
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out + ".pbtxt", "w") as f:
        f.write(text)
    with open(args.out + ".op_stage.json", "w") as f:
        json.dump({k: op_stage.get(k, "unmapped") for k in keys}, f,
                  indent=0, sort_keys=True)
    print(f"{len(names)} ops kept; steps_run {list(batch.steps_run)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
