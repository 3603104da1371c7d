"""Chip smoke: the fluid simulator's main path on one TPU at paper scale.

Everything runs in this one process (a chip belongs to one process):

* ``atlas``: the committed paper-scale atlas cells (the 128-GPU, 8-rack
  CLOS ring all-reduce of 128 MB, 32,512 flows per lane; built by
  ``benchmarks/atlas.py``).  Each policy's 12 lanes run as one vmapped
  ``SweepRunner.run_batch`` on the default step, and every cell is
  compared with ``experiments/atlas/atlas_paper_ring128.csv``.
* ``impl``: one dcqcn lane at the atlas defaults on the jnp step and on
  the Pallas step.
* ``policy_axis``: every registered policy in one stacked
  ``run_policy_axis`` dispatch (which runs the jnp step), lane by lane
  against serial per-policy runs.

``--four-chips`` runs only the sharded atlas check instead: one policy's
12 lanes with ``SweepRunner(mesh=4)`` against ``mesh=None`` on one chip
of the same host.

Any mismatch, unhealthy lane or exception exits non-zero, and so does a
process in which JAX finds no TPU.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times are host-clock seconds on the device named in the first line.

    python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ATLAS_CSV = os.path.join(ROOT, "experiments", "atlas",
                         "atlas_paper_ring128.csv")
ATLAS_POLICIES = ("dcqcn", "hpcc", "timely", "mlp")
FOUR_CHIP_POLICY = "hpcc"      # its 12 lanes differ, so a misrouted lane shows


def say(*parts):
    print(*parts, flush=True)


def peak_bytes(device=None):
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@dataclasses.dataclass
class Timed:
    out: object            # what ``run_fn`` returned
    compiled: object       # the executable ``compile_fn`` returned
    compile_s: float       # tracing, lowering and compiling (or loading)
    cold_compiles: int     # XLA compiles in it; 0: the disk cache had all
    warm_s: float          # the run, compiled; host arrays at its end
    device: str            # the device kind the times were taken on

    def times(self) -> str:
        return (f"compile_s={self.compile_s} "
                f"cold_compiles={self.cold_compiles} warm_s={self.warm_s} "
                f"on {self.device!r}")


def timed(compile_fn, run_fn) -> Timed:
    """Compile ahead of time, then run warm.  ``run_fn`` returns host
    arrays (``SweepRunner`` pulls its results), so the device work has
    finished when the clock stops; the warm window must hold no
    compile."""
    import jax

    from repro.common.cache import backend_compiles

    with backend_compiles() as cold:
        t0 = time.perf_counter()
        compiled = compile_fn()
        compile_s = time.perf_counter() - t0
    with backend_compiles() as warm:
        t0 = time.perf_counter()
        out = run_fn()
        warm_s = time.perf_counter() - t0
    if warm:
        raise RuntimeError(f"{len(warm)} compiles in the warm window")
    return Timed(out, compiled, compile_s, len(cold), warm_s,
                 jax.devices()[0].device_kind)


def timed_batch(runner, topo, sched, task) -> Timed:
    """One atlas task's lanes as one ``run_batch``."""
    args = (topo, sched, task.policy, task.stacked_params,
            task.stacked_fabric)
    return timed(lambda: runner.compile_batch(*args),
                 lambda: runner.run_batch(*args))


def has_mosaic(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def load_reference(path=ATLAS_CSV) -> dict:
    """The committed atlas CSV, rows grouped by policy in file order."""
    with open(path, newline="") as f:
        rows = list(csv.DictReader(f))
    out: dict = {}
    for r in rows:
        out.setdefault(r["policy"], []).append(r)
    return out


def compare_cells(pol, rows, reference):
    """Every cell against the CSV: same lane point, identical lane
    status, completion within 1e-3 relative plus the CSV's 0.01 ms
    rounding, PFC frames within 1e-3 relative plus one."""
    if len(rows) != len(reference):
        raise AssertionError(f"atlas {pol}: {len(rows)} cells, reference "
                             f"has {len(reference)}")
    bad, d_ms, d_pfc = [], 0.0, 0.0
    for i, (got, want) in enumerate(zip(rows, reference)):
        for k in ("param_value", "kmin", "kmax", "xoff"):
            if got[k] != float(want[k]):
                bad.append(f"cell {i}: {k} {got[k]} != {want[k]}")
        if got["lane_status"] != want["lane_status"]:
            bad.append(f"cell {i}: lane_status {got['lane_status']} != "
                       f"{want['lane_status']}")
        ms, ms_ref = got["completion_ms"], float(want["completion_ms"])
        pfc, pfc_ref = got["pfc_frames"], float(want["pfc_frames"])
        d_ms, d_pfc = max(d_ms, abs(ms - ms_ref)), max(d_pfc,
                                                       abs(pfc - pfc_ref))
        if abs(ms - ms_ref) > 1e-3 * abs(ms_ref) + 0.01:
            bad.append(f"cell {i}: completion_ms {ms} vs {ms_ref}")
        if abs(pfc - pfc_ref) > 1e-3 * abs(pfc_ref) + 1:
            bad.append(f"cell {i}: pfc_frames {pfc} vs {pfc_ref}")
    if bad:
        raise AssertionError(f"atlas {pol}: " + "; ".join(bad))
    return d_ms, d_pfc


def compare_lane(what, got, want, dt, status_got, status_want):
    """Serial-run tolerances: equal finish and status, completion to rtol
    1e-4, every t_finish within one step, PAUSE counts to rtol 1e-3 with
    atol 1."""
    import numpy as np
    if status_got != "ok" or status_want != "ok":
        raise AssertionError(f"{what}: unhealthy lane ({status_got} vs "
                             f"{status_want})")
    if bool(got["finished"]) != bool(want["finished"]):
        raise AssertionError(f"{what}: finished differs")
    np.testing.assert_allclose(got["completion_time"],
                               want["completion_time"], rtol=1e-4,
                               err_msg=f"{what}: completion_time")
    # both sides are step ends (it + 1) * dt in float32
    np.testing.assert_allclose(got["t_finish"], want["t_finish"], rtol=0,
                               atol=dt * (1 + 1e-3),
                               err_msg=f"{what}: t_finish beyond one step")
    np.testing.assert_allclose(got["pause_count"], want["pause_count"],
                               rtol=1e-3, atol=1.0,
                               err_msg=f"{what}: pause_count")


def _of_results(r):
    return {"finished": r.finished, "completion_time": r.completion_time,
            "t_finish": r.t_finish, "pause_count": r.pause_count}


def _of_lane(batch, i):
    return {"finished": batch.finished[i],
            "completion_time": batch.completion_time[i],
            "t_finish": batch.t_finish[i],
            "pause_count": batch.pause_count[i]}


def phase_atlas(topo, sched, cfg, reference):
    from benchmarks.atlas import build_tasks, policy_rows
    from repro.core.engine import effective_step_impl
    from repro.core.sweep import SweepRunner

    runner = SweepRunner(cfg)                      # mesh=None: one chip
    for task in build_tasks(topo, sched):
        if task.name not in ATLAS_POLICIES:
            continue
        impl = effective_step_impl(task.policy, cfg)
        t = timed_batch(runner, topo, sched, task)
        batch, mosaic = t.out, has_mosaic(t.compiled)
        rows = policy_rows(task.name, batch, t.warm_s)["rows"]
        d_ms, d_pfc = compare_cells(task.name, rows, reference[task.name])
        say(f"atlas {task.name}: lanes={batch.n} step_impl={impl} "
            f"tpu_custom_call={mosaic} {t.times()} "
            f"peak_bytes_in_use={peak_bytes()} "
            f"max_abs_diff completion_ms={d_ms} pfc_frames={d_pfc} "
            f"lane_status={sorted({s.value for s in batch.lane_status()})}")
        if impl != "pallas" or not mosaic:
            raise AssertionError(f"atlas {task.name}: the Pallas step did "
                                 "not run as a Mosaic kernel")


def phase_impl(topo, sched, cfg):
    """Returns the Pallas-step Results (the serial dcqcn run the policy
    axis is compared with)."""
    from repro.core.cc import get_policy
    from repro.core.engine import effective_step_impl
    from repro.core.sweep import SweepRunner

    runner = SweepRunner(cfg)
    dcqcn = get_policy("dcqcn")
    got = {}
    for impl in ("jnp", "pallas"):
        c = dataclasses.replace(cfg, step_impl=impl)
        sim = runner.simulator(topo, sched, dcqcn, c)
        t = timed(sim.compile,
                  lambda: runner.run(topo, sched, "dcqcn", cfg=c))
        res = got[impl] = t.out
        mosaic = has_mosaic(t.compiled)
        say(f"impl dcqcn {impl}: "
            f"step_impl={effective_step_impl(dcqcn, c)} "
            f"tpu_custom_call={mosaic} {t.times()} "
            f"peak_bytes_in_use={peak_bytes()} "
            f"completion_ms={res.completion_time * 1e3} "
            f"status={res.status.value}")
        if mosaic != (impl == "pallas"):
            raise AssertionError(f"impl {impl}: tpu_custom_call={mosaic}")
    compare_lane("impl dcqcn pallas vs jnp", _of_results(got["pallas"]),
                 _of_results(got["jnp"]), cfg.dt, got["pallas"].status,
                 got["jnp"].status)
    say("impl: pallas matches jnp")
    return got["pallas"]


def phase_policy_axis(topo, sched, cfg, serial):
    """``serial`` maps policy -> an already computed serial ``Results``
    under ``cfg`` (reused instead of run again)."""
    from repro.core.cc import ALL_POLICIES
    from repro.core.engine import effective_step_impl
    from repro.core.sweep import SweepRunner, stack_policy_axis

    runner = SweepRunner(cfg)
    stacked, params, labels = stack_policy_axis(ALL_POLICIES)
    t = timed(lambda: runner.compile_batch(topo, sched, stacked, params),
              lambda: runner.run_policy_axis(topo, sched,
                                             list(ALL_POLICIES)))
    batch = t.out
    say(f"policy_axis: {len(labels)} policies in one run_policy_axis "
        f"dispatch, step_impl={effective_step_impl(stacked, cfg)} (a "
        f"stacked policy runs the jnp step) {t.times()} "
        f"peak_bytes_in_use={peak_bytes()}")
    status = batch.lane_status()
    for i, name in enumerate(labels):
        res = serial.get(name)
        if res is None:
            res = runner.run(topo, sched, name, cfg=cfg)
        compare_lane(f"policy_axis {name}", _of_lane(batch, i),
                     _of_results(res), cfg.dt, status[i], res.status)
        say(f"policy_axis {name}: completion_ms "
            f"{batch.completion_time[i] * 1e3} stacked vs "
            f"{res.completion_time * 1e3} serial, matches")


def phase_four_chips(topo, sched, cfg, policy=FOUR_CHIP_POLICY):
    import jax
    import numpy as np

    from benchmarks.atlas import build_tasks
    from repro.core.sweep import SweepRunner

    task = next(t for t in build_tasks(topo, sched) if t.name == policy)
    out = {}
    for mesh in (None, 4):
        runner = SweepRunner(cfg, mesh=mesh)
        t = timed_batch(runner, topo, sched, task)
        batch = out[mesh] = t.out
        say(f"four_chips {policy} mesh={mesh}: lanes={batch.n} "
            f"{t.times()} "
            f"lane_status={sorted({s.value for s in batch.lane_status()})}")
        if mesh is not None:
            sharding = t.compiled.output_shardings["t_finish"]
            shape = (runner._chunk_size(batch.n), sched.n_flows)
            placed = sharding.devices_indices_map(shape)
            for dev, idx in sorted(placed.items(), key=lambda kv: kv[0].id):
                say(f"four_chips t_finish shard: device {dev.id} "
                    f"({dev.device_kind}) lanes {idx[0]}")
            if len({d.id for d in placed}) != 4:
                raise AssertionError("sharded lanes do not sit on four "
                                     "distinct devices")
    for dev in jax.devices()[:4]:
        say(f"four_chips device {dev.id}: peak_bytes_in_use="
            f"{peak_bytes(dev)}")
    one, four = out[None], out[4]
    if list(one.lane_status()) != list(four.lane_status()):
        raise AssertionError("four_chips: lane status differs")
    for k in ("completion_time", "t_finish", "pause_count", "delivered"):
        np.testing.assert_allclose(getattr(four, k), getattr(one, k),
                                   rtol=1e-5, err_msg=f"four_chips {k}")
    say(f"four_chips: mesh=4 matches mesh=None on {one.n} lanes "
        "(rtol 1e-5, identical lane status)")


def paper_scenario():
    """The atlas's paper-scale topology, schedule and config."""
    from benchmarks.atlas import atlas_cfg
    from benchmarks.common import collective_size, paper_fabric
    from repro.core.collectives import allreduce_ring

    fab = paper_fabric()
    topo = fab.build()
    sched = allreduce_ring(topo, list(range(fab.n_gpus)), collective_size(),
                           n_chunks=1)
    return topo, sched, atlas_cfg()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded atlas check on 4 chips")
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # the committed reference is the paper-scale atlas
    os.environ["REPRO_BENCH_SCALE"] = "paper"
    from repro.common.cache import enable_compilation_cache
    say(f"compilation cache: {enable_compilation_cache()}")

    import jax
    devices = jax.devices()
    dev = devices[0]
    say(f"jax {jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} devices={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.core import sweep
    sweep.reset_calibration()       # no persisted calibration is read

    t00 = time.perf_counter()
    topo, sched, cfg = paper_scenario()
    say(f"scenario: {topo.name} flows={sched.n_flows} dt={cfg.dt} "
        f"max_steps={cfg.max_steps} max_extends={cfg.max_extends}")
    if args.four_chips:
        phase_four_chips(topo, sched, cfg)
    else:
        phase_atlas(topo, sched, cfg, load_reference())
        say(f"atlas done at {time.perf_counter() - t00} s")
        pallas = phase_impl(topo, sched, cfg)
        say(f"impl done at {time.perf_counter() - t00} s")
        phase_policy_axis(topo, sched, cfg, {"dcqcn": pallas})
        say(f"policy_axis done at {time.perf_counter() - t00} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
