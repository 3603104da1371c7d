"""Policy x tuned-param x fabric atlas slices through the campaign layer.

The regime atlas the ROADMAP calls for, one committed slice at a time:
for each CC policy, a key tuning parameter (spanned around its paper
default) is crossed with a fig-12-style fabric grid — paired ECN ramps
(kmin, 4*kmin) x PFC thresholds (xoff) — on the paper's CLOS topology,
every (policy, param, fabric) cell one lane of a sharded
``SweepRunner(mesh="auto")`` dispatch.  Emits one CSV row per cell plus a
JSON sidecar with the wall-clock/scaling record.

Since PR 10 the dispatch runs through ``repro.core.campaign``: every
chunk is journaled (atomic write under
``experiments/atlas/<campaign>/journal/``), so a killed run resumes with
``--resume`` instead of starting over, failed chunks degrade down the
retry ladder instead of aborting the slice, unhealthy lanes get one
relaxed-budget quarantine retry, and ``manifest.json`` records exactly
what the committed CSV covers.  The CSV/JSON schema is unchanged from
the pre-campaign atlas.

The learned policy rides the same axes: the ``mlp`` slice spans its
``out_gain`` (the target-tracking speed — 0.5x/1x/2x the trained
default) over the identical fabric grid, so the atlas directly answers
whether the trained policy's ranking survives fabric mistuning the way
the classical policies' rankings do.

Usage (the committed ``experiments/atlas/`` slice):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    REPRO_BENCH_SCALE=paper \\
    PYTHONPATH=src python benchmarks/atlas.py [--resume] [--deadline S]

``REPRO_BENCH_SCALE=small`` gives a CI-sized smoke of the same shape.
The workload is the topology-aware ring All-Reduce (tractable at 128
ranks on a single-core host, unlike the 1D algorithm's ~130k flows at
O(ranks^2)); completion times are end-of-collective, lane health is
recorded per cell (an 'exhausted'/'diverged' cell is a truncation
artifact, not a measurement).
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import time

import jax
import numpy as np

try:                             # run.py imports us as benchmarks.*;
    from benchmarks.common import SCALE, collective_size, paper_fabric
except ImportError:              # direct script run: sys.path[0]=benchmarks/
    from common import SCALE, collective_size, paper_fabric

from repro.common.cache import enable_compilation_cache
from repro.core.campaign import CampaignTask, run_campaign
from repro.core.cc import get_policy
from repro.core.collectives import allreduce_ring
from repro.core.engine import EngineConfig
from repro.core.sweep import BatchResults, SweepRunner

OUTDIR = os.environ.get("REPRO_ATLAS_OUT", "experiments/atlas")

# one key tunable per policy, spanned geometrically around the paper
# default (x0.5, x1, x2) — the Hoefler/Mittal sensitivity question in
# miniature: does the fabric-tuning ranking survive the policy's own
# tuning?  Defaults from the declared ParamSpec tables.
KEY_PARAM = {"dcqcn": "rai_frac", "hpcc": "eta", "timely": "beta",
             "mlp": "out_gain"}
PARAM_SPAN = (0.5, 1.0, 2.0)

# fig-12-style paired ECN ramps x PFC thresholds (not a kmin x kmax
# factorial, which would include inverted ramps)
FABRIC_PTS = [(k, 4.0 * k, x)
              for k in (100e3, 1000e3)
              for x in (0.25e6, 4e6)]


def atlas_cfg() -> EngineConfig:
    if SCALE == "small":
        return EngineConfig(dt=2e-6, max_steps=4000, max_extends=6,
                            queue_stride=0)
    return EngineConfig(dt=4e-6, max_steps=6000, max_extends=6,
                        queue_stride=0)


def _key_param_values(pol: str) -> list[float]:
    policy = get_policy(pol)
    spec = policy.param_spec(KEY_PARAM[pol])
    return [min(max(spec.default * s, spec.lo), spec.hi)
            for s in PARAM_SPAN]


def build_tasks(topo, sched) -> list[CampaignTask]:
    """One campaign task per policy: its key-param span x fabric grid."""
    tasks = []
    for pol in KEY_PARAM:
        key = KEY_PARAM[pol]
        lanes = [(v, f) for v in _key_param_values(pol)
                 for f in FABRIC_PTS]
        pts = np.asarray([f for _, f in lanes], np.float32)
        tasks.append(CampaignTask(
            pol, topo, sched, get_policy(pol),
            stacked_params={key: np.asarray([v for v, _ in lanes],
                                            np.float32)},
            stacked_fabric={"kmin": pts[:, 0], "kmax": pts[:, 1],
                            "xoff": pts[:, 2]}))
    return tasks


def policy_rows(pol: str, batch: BatchResults, wall_s: float) -> dict:
    """CSV rows + summary for one policy's merged slice (schema identical
    to the pre-campaign atlas)."""
    key = KEY_PARAM[pol]
    spec = get_policy(pol).param_spec(key)
    rows = []
    status = batch.lane_status()
    for i in range(batch.n):
        rows.append({
            "policy": pol, "param": key,
            "param_value": float(batch.params[key][i]),
            "param_rel_default": round(float(batch.params[key][i])
                                       / spec.default, 3),
            "kmin": float(batch.fabric["kmin"][i]),
            "kmax": float(batch.fabric["kmax"][i]),
            "xoff": float(batch.fabric["xoff"][i]),
            "completion_ms": round(float(batch.completion_time[i]) * 1e3, 4),
            "pfc_frames": int(batch.pause_count[i].sum()),
            "lane_status": status[i],
        })
    fin = batch.finished
    out = {"rows": rows, "wall_s": round(wall_s, 1), "n_lanes": batch.n,
           "n_unfinished": int((~fin).sum())}
    if fin.any():
        best = batch.best()
        out["best"] = {
            "completion_ms": round(
                float(batch.completion_time[best]) * 1e3, 4),
            "param_value": float(batch.params[key][best]),
            "kmin": float(batch.fabric["kmin"][best]),
            "xoff": float(batch.fabric["xoff"][best])}
        out["spread_pct"] = round(float(
            (batch.completion_time[fin].max()
             / batch.completion_time[fin].min() - 1) * 100), 2)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--resume", action="store_true",
                    help="replay journaled chunks of a killed run")
    ap.add_argument("--fresh", action="store_true",
                    help="discard an existing journal and restart")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="wall-clock budget; checkpoint-and-exit after")
    ap.add_argument("--chunk-timeout", type=float, default=None,
                    metavar="S", help="per-chunk watchdog timeout")
    ap.add_argument("--max-retries", type=int, default=3)
    ap.add_argument("--chunk-lanes", type=int, default=None,
                    help="lanes per journaled chunk (default: auto)")
    args = ap.parse_args(argv)

    enable_compilation_cache()
    fab = paper_fabric()
    topo = fab.build()
    # n_chunks=1: 2*(R-1)*R flows (~32.5k at 128 ranks) instead of 4x
    # that — the chunking controls pipelining depth, not bytes moved
    sched = allreduce_ring(topo, list(range(fab.n_gpus)), collective_size(),
                           n_chunks=1)
    cfg = atlas_cfg()
    runner = SweepRunner(cfg, mesh="auto",
                         chunk_lanes=args.chunk_lanes or "auto")
    n_dev = runner.n_mesh_devices
    print(f"atlas: scale={SCALE} gpus={fab.n_gpus} flows={sched.n_flows} "
          f"devices={n_dev} mesh={runner.mesh}")
    os.makedirs(OUTDIR, exist_ok=True)
    tag = f"{SCALE}_ring{fab.n_gpus}"
    t00 = time.time()
    res = run_campaign(build_tasks(topo, sched), name=f"atlas_{tag}",
                       out_dir=OUTDIR, runner=runner, cfg=cfg,
                       chunk_lanes=args.chunk_lanes,
                       resume=args.resume, fresh=args.fresh,
                       max_retries=args.max_retries,
                       deadline_s=args.deadline,
                       chunk_timeout_s=args.chunk_timeout,
                       progress=lambda m: print(f"  [campaign] {m}"))
    total = time.time() - t00
    all_rows, meta = [], {}
    for pol in KEY_PARAM:
        ts = res.manifest["tasks"].get(pol, {})
        wall = sum(c.get("wall_s", 0.0) for c in ts.get("chunks", ()))
        s = policy_rows(pol, res.results[pol], wall)
        all_rows += s["rows"]
        meta[pol] = {k: v for k, v in s.items() if k != "rows"}
        best = s.get("best", {}).get("completion_ms", "n/a")
        print(f"  {pol:8s} B={s['n_lanes']} wall {s['wall_s']}s "
              f"best {best}ms spread {s.get('spread_pct', 'n/a')}% "
              f"unfinished {s['n_unfinished']}")
    csv_path = os.path.join(OUTDIR, f"atlas_{tag}.csv")
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(all_rows[0]))
        w.writeheader()
        w.writerows(all_rows)
    side = {
        "scale": SCALE, "n_gpus": fab.n_gpus, "n_flows": sched.n_flows,
        "workload": f"allreduce_ring {collective_size()/1e6:.0f}MB",
        "cfg": {"dt": cfg.dt, "max_steps": cfg.max_steps,
                "max_extends": cfg.max_extends},
        "backend": jax.default_backend(), "devices": n_dev,
        "mesh_shape": ({runner.mesh.axis_names[0]: n_dev}
                       if runner.mesh is not None else None),
        "sharded": runner.mesh is not None,
        "total_wall_s": round(total, 1),
        "cells": len(all_rows),
        "per_policy": meta,
        "campaign": {
            "status": res.status,
            "coverage": res.manifest["coverage"],
            "fingerprint": res.manifest["fingerprint"],
            "manifest": os.path.join(res.out_dir, "manifest.json"),
            "demotions": sum(len(t["demotions"])
                             for t in res.manifest["tasks"].values()),
            "quarantined": {p: t["quarantine"]["lanes"]
                            for p, t in res.manifest["tasks"].items()
                            if t.get("quarantine")},
        },
        "note": "emulated host devices share one core: the sharded "
                "dispatch here validates placement/equivalence at paper "
                "scale, wall-clock parallel speedup needs real devices",
    }
    with open(os.path.join(OUTDIR, f"atlas_{tag}.json"), "w") as f:
        json.dump(side, f, indent=1)
    print(f"wrote {csv_path} ({len(all_rows)} cells) in {total:.0f}s "
          f"[campaign {res.status}, coverage "
          f"{res.manifest['coverage']:.0%}]")
    return 0 if res.ok else 2


if __name__ == "__main__":
    raise SystemExit(main())
