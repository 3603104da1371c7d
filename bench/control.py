"""Readings the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --out <file.jsonl>

One process: the cell's executable is compiled once and dispatched once
per seed, at the cell's own size, on the chip.  Then, on the host, in a
pool of worker processes (every seed's lanes together), every lane of
the batch:

* ``sound``: each seed's lanes, as the program computed them, against the
  configuration's reference (its ``reference`` module) in float32;
* ``control``: for the control seeds, the reference computed in bfloat16,
  the precision below the configuration's float32, put in the program's
  place and compared the same way.  A control lane runs to twice the steps
  the float32 reference took; a flow unfinished by then counts as
  finishing there, which can only lower its reading.

Each seed's reading is one JSON line: the worst over its lanes, as a run
reads it, and each lane's numbers.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import multiprocessing
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, modules  # noqa: E402
from bench.lanes import make_lanes  # noqa: E402
from bench.run import (Program, load_cell, say,  # noqa: E402
                       use_compilation_cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--workers", type=int, default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    cell = load_cell(args.workload)
    config, mix = cell["config"], cell["mix"]
    e = config["engine"]
    dt = float(e["dt"])
    budget = int(e["max_steps"]) * (int(e["max_extends"]) + 1)
    use_compilation_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        say("control: JAX found no TPU")
        return 2
    from repro.core import sweep
    sweep.reset_calibration()

    runs = {}
    prog = None
    for seed in seeds:
        lanes = make_lanes(mix, seed)
        prog = Program(config, mix, lanes, cell["bench"])
        if not runs:
            prog.compile()
        t0 = time.perf_counter()
        batch = prog.dispatch()
        runs[seed] = (lanes, [Program.lane(batch, i)
                              for i in range(len(lanes))])
        say(f"seed {seed}: dispatch {time.perf_counter() - t0} s")
    del prog

    def record(out, row):
        out.write(json.dumps(row) + "\n")
        out.flush()
        say(json.dumps({k: v for k, v in row.items() if k != "lanes"}))

    # every seed's lanes in one pool, so that a host with many cores works
    # through them together
    t0 = time.perf_counter()
    with cf.ProcessPoolExecutor(
            args.workers or os.cpu_count() or 1,
            mp_context=multiprocessing.get_context("spawn")) as pool, \
            open(args.out, "a") as out:
        sound = {s: [pool.submit(modules.reference_lane, cell["bench"],
                                 config, ln)
                     for ln in runs[s][0]] for s in seeds}
        low = {}
        for s in seeds:
            lanes, got = runs[s]
            wants = [f.result() for f in sound[s]]
            nums = [check.lane_numbers(g, w, dt, budget)
                    for g, w in zip(got, wants)]
            record(out, {"cell": args.workload, "kind": "sound", "seed": s,
                         "ref_steps": [w["steps"] for w in wants],
                         "ref_seconds": [w["seconds"] for w in wants],
                         "elapsed_s": time.perf_counter() - t0,
                         **check.worst(nums), "lanes": nums})
            if s in control_seeds:
                low[s] = (wants, [pool.submit(
                    modules.reference_lane, cell["bench"], config, ln,
                    "bfloat16", 2 * w["steps"])
                    for ln, w in zip(lanes, wants)])
        for s, (wants, futs) in low.items():
            lows = [f.result() for f in futs]
            nums = [check.lane_numbers(lo, w, dt, 2 * w["steps"])
                    for lo, w in zip(lows, wants)]
            record(out, {"cell": args.workload, "kind": "control", "seed": s,
                         "control_steps": [lo["steps"] for lo in lows],
                         "control_finished": [lo["finished"] for lo in lows],
                         "control_seconds": [lo["seconds"] for lo in lows],
                         "elapsed_s": time.perf_counter() - t0,
                         **check.worst(nums), "lanes": nums})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
