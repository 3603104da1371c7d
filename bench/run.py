"""The chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: fabric,
collective, engine settings, and the names of its scenario module under
``bench/scenarios/`` and its reference module under ``bench/``) and a
traffic mix (``bench/traffic/<mix>.json``: the sweep's policies, parameter
span, fabric corners, entry point and mesh).  The harness finds all of
them by name, builds the scenario and the lanes from them and the seed,
compiles the cell's one executable ahead of time, then dispatches the
whole batch again and again while the window is open: each dispatch is
one call of the mix's entry (``ENTRIES``: ``SweepRunner.run_batch`` or
``run_policy_axis``) that returns host arrays, and the window closes when
the last one has returned.  A compile inside the window fails the run.

``--trace 0`` reports the end-to-end metrics: ``lane_steps_per_s`` (every
lane's simulated steps up to its own end, summed over the window's
dispatches, over the window's wall time) and ``setup_s`` (process start to
the first dispatch).  ``--trace 1`` then traces one more dispatch and
reports the cell's per-layer metrics, each read by its own
``bench/metrics/<metric>.py``.

``correct``: every lane of the batch, as every dispatch of the window
returned it, against the configuration's plain reference, run on
the host after the window in a pool of worker processes, one lane each;
``bench/limits/<cell>.json`` holds the limits.  The
numbers compared and their limits are the last lines of standard error and
the result's last key.  The result is the last line of standard output.

The run exits 2, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures as cf  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".cache", "jax_compilation")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check, modules  # noqa: E402
from bench.lanes import make_lanes  # noqa: E402
from bench.roofline import stage12_bytes, stage12_flops  # noqa: E402


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry in ``BENCHMARK.json`` with its configuration,
    traffic mix, limits and per-layer metric entries loaded by name."""
    spec = load_json(root, "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    bench = os.path.join(root, "bench")
    return {
        "bench": bench,
        "cell": cell,
        "config": load_json(bench, "configs", f"{cell['config']}.json"),
        "mix": load_json(bench, "traffic", f"{cell['traffic']}.json"),
        "limits": load_json(bench, "limits", f"{name}.json"),
        "end_to_end": spec["end_to_end"],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def read_metric(name: str, run: dict):
    return modules.load(os.path.join(BENCH, "metrics", f"{name}.py")).read(
        run)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def _entry_run_batch(runner, topo, sched, lanes, fab):
    """``run_batch``: the lanes share one policy and stack its parameters."""
    pols = {ln.policy for ln in lanes}
    if len(pols) != 1:
        raise ValueError("run_batch lanes share one policy")
    pol = pols.pop()
    params = {key: np.asarray([ln.params[key] for ln in lanes], np.float32)
              for key in lanes[0].params}
    return (pol,
            lambda: runner.compile_batch(topo, sched, pol, params,
                                         stacked_fabric=fab),
            lambda: runner.run_batch(topo, sched, pol, params,
                                     stacked_fabric=fab))


def _entry_run_policy_axis(runner, topo, sched, lanes, fab):
    """``run_policy_axis``: lane i runs member i of the stacked policy,
    with the lane's parameters as that member's overrides."""
    from repro.core.sweep import stack_policy_axis
    pols = [ln.policy for ln in lanes]
    overrides = [dict(ln.params) for ln in lanes]
    stacked, params, _ = stack_policy_axis(pols, overrides)
    return (None,
            lambda: runner.compile_batch(topo, sched, stacked, params,
                                         stacked_fabric=fab),
            lambda: runner.run_policy_axis(topo, sched, pols, overrides,
                                           stacked_fabric=fab))


# a traffic mix's ``entry`` -> (the one policy, or None; the ahead-of-time
# compile; the dispatch), from the runner, the scenario, the lanes and
# their stacked fabric thresholds
ENTRIES = {"run_batch": _entry_run_batch,
           "run_policy_axis": _entry_run_policy_axis}


class Program:
    """The cell's scenario, built by the program from the configuration
    (through the configuration's scenario module, found under ``bench``),
    and the one call the window drives."""

    def __init__(self, config: dict, mix: dict, lanes: list,
                 bench: str = BENCH):
        if mix["entry"] not in ENTRIES:
            raise ValueError(f"unknown entry {mix['entry']!r}")
        from repro.core.engine import EngineConfig
        from repro.core.sweep import SweepRunner

        e, k = config["engine"], config["fabric_knobs"]
        self.topo, self.sched = modules.scenario(config, bench).build(config)
        self.cfg = EngineConfig(
            dt=e["dt"], max_steps=e["max_steps"],
            max_extends=e["max_extends"], queue_stride=e["queue_stride"],
            t_base_util=e["t_base_util"], eps_done=e["eps_done"],
            pause_resend=e["pause_resend"],
            deadlock_check_every=e["deadlock_check_every"],
            pmax=k["pmax"], xon=k["xon"])
        self.runner = SweepRunner(self.cfg, mesh=mix["mesh"])
        self.n_lanes = len(lanes)
        fab = {k: np.asarray([getattr(ln, k) for ln in lanes], np.float32)
               for k in ("kmin", "kmax", "xoff")}
        self.policy, self.compile, self.dispatch = ENTRIES[mix["entry"]](
            self.runner, self.topo, self.sched, lanes, fab)

    def kernel_policy(self) -> str | None:
        """The cell's one policy where the engine-step kernel runs its
        update (stages 1-2), else None."""
        from repro.core.cc import get_policy
        from repro.core.engine import effective_step_impl
        if self.policy is None or effective_step_impl(
                get_policy(self.policy), self.cfg) != "pallas":
            return None
        return self.policy

    @property
    def lanes_per_device(self) -> int:
        n = self.runner.n_mesh_devices
        return -(-self.n_lanes // n)

    @staticmethod
    def lane(batch, i: int) -> dict:
        return {"t_finish": np.asarray(batch.t_finish[i], np.float64),
                "pause_count": np.asarray(batch.pause_count[i], np.float64),
                "finished": bool(batch.finished[i]),
                "deadlocked": bool(batch.deadlocked[i])}


def lane_steps(batch, dt: float, budget: int) -> list[int]:
    """Each lane's simulated steps up to its own end: completion, or the
    step budget where it ran out."""
    return [int(round(float(batch.completion_time[i]) / dt))
            if batch.finished[i] else budget for i in range(batch.n)]


def reference_lanes(config: dict, lanes: list, workers: int | None = None,
                    bench: str = BENCH) -> list[dict]:
    """Every lane through the configuration's plain reference, one lane
    per task in a pool of worker processes (spawned: they import no JAX),
    in lane order.  The pool is shut down, and every worker has ended,
    before this returns."""
    n = min(len(lanes), workers or os.cpu_count() or 1)
    with cf.ProcessPoolExecutor(
            n, mp_context=multiprocessing.get_context("spawn")) as pool:
        futs = [pool.submit(modules.reference_lane, bench, config, ln)
                for ln in lanes]
        return [f.result() for f in futs]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float = T_START,
             workers: int | None = None) -> dict:
    """Everything after the look for a chip.  ``devices``: all that JAX
    reports (the cell uses the first ``chips``); ``workers``: processes for
    the reference (default: one per core, at most one per lane).  Returns
    the result line."""
    from repro.common.cache import backend_compiles

    config, mix, limits = cell["config"], cell["mix"], cell["limits"]
    e = config["engine"]
    dt = float(e["dt"])
    budget = int(e["max_steps"]) * (int(e["max_extends"]) + 1)
    lanes = make_lanes(mix, seed)
    prog = Program(config, mix, lanes, cell["bench"])
    with backend_compiles() as cold:
        t0 = time.perf_counter()
        prog.compile()
        say(f"compile: {time.perf_counter() - t0} s, "
            f"{len(cold)} XLA compiles (0: all from the cache)")

    # the window: the whole batch, again and again
    batches, durations = [], []
    with backend_compiles() as warm:
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t_start
        while True:
            t0 = time.perf_counter()
            batches.append(prog.dispatch())
            durations.append(time.perf_counter() - t0)
            if time.perf_counter() - t_w0 >= seconds:
                break
        window_s = time.perf_counter() - t_w0
    if warm:
        raise RuntimeError(f"{len(warm)} XLA compiles inside the window")
    steps = lane_steps(batches[0], dt, budget)
    useful = sum(sum(lane_steps(b, dt, budget)) for b in batches)
    say(f"window: {len(batches)} dispatches of {len(lanes)} lanes in "
        f"{window_s} s, each {durations}; lane steps {steps}")

    result = {"metrics": {}, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}
    if not trace:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        result["metrics"] = {
            "lane_steps_per_s": {"value": useful / window_s,
                                 "unit": units["lane_steps_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
    else:
        from bench import trace as trace_mod
        from bench.peaks import peaks
        with backend_compiles() as traced_compiles:
            traced, events = trace_mod.capture(prog.dispatch)
        if traced_compiles:
            raise RuntimeError("XLA compiles inside the traced dispatch")
        tr = trace_mod.reduce(events)
        if not tr:
            raise RuntimeError("the trace holds no device operation inside "
                               "the dispatch span")
        run = {"trace": tr, "lane_steps": steps,
               "peaks": peaks(devices[0].device_kind)}
        pol = prog.kernel_policy()
        if pol is not None:
            # the work of stages 1-2, counted from the reference's shapes
            ref = modules.reference(config, cell["bench"])
            n_state = len(ref.POLICIES[pol]().init(reference_ctx(
                prog.sched.n_flows)))
            run["stage12_bytes"] = stage12_bytes(
                prog.sched.n_flows, ref.MAXHOP, n_state,
                prog.lanes_per_device)
            flops = stage12_flops(prog.sched.n_flows, ref.MAXHOP, pol,
                                  prog.lanes_per_device)
            say(f"stages 1-2 per call: {run['stage12_bytes']} bytes, "
                f"{flops} FLOP")
        for m in cell["per_layer"]:
            v = read_metric(m["name"], run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {
            "device_ops": [[trace_mod.short_name(n), v]
                           for n, v in tr["device_ops"][:10]],
            "idle_gaps": tr["idle_gaps"][:10]}
        batches.append(traced)            # checked like the window's
    result["device"]["memory_peak_bytes"] = memory_peak(
        devices[:int(cell["cell"]["chips"])])

    # correctness: every lane of every dispatch against the reference
    del prog
    t0 = time.perf_counter()
    wants = reference_lanes(config, lanes, workers, cell["bench"])
    say(f"reference: {len(lanes)} lanes in {time.perf_counter() - t0} s "
        f"(steps {[w['steps'] for w in wants]})")
    readings = [check.lane_numbers(Program.lane(b, i), want, dt, budget)
                for b in batches for i, want in enumerate(wants)]
    numbers = check.worst(readings)
    failed = sum(not check.verdict(r, limits) for r in readings)
    result.update(correct=failed == 0, attempted=len(readings),
                  failed=failed)
    result["check"] = check.report(numbers, limits)
    for k, v in result["check"].items():
        say(f"check {k}: {v['value']} (limit {v['limit']})")
    return {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")
            + (("breakdown",) if "breakdown" in result else ()) + ("check",)}


def reference_ctx(n_flows: int) -> dict:
    """A stand-in flow context, for counting a policy's state arrays."""
    ones = np.ones(n_flows, np.float32)
    return {"F": n_flows, "dtype": np.dtype(np.float32), "line": ones,
            "bdp": ones, "fanin": ones}


def use_compilation_cache() -> str | None:
    """The persistent compilation cache at its fixed path in this checkout,
    whatever the environment points at, and with no size limit: with a
    limit JAX evicts by access-time files, and one entry put there without
    its access-time file (a cache copied in from elsewhere) makes every
    write fail, so that every run compiles anew."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    from repro.common.cache import enable_compilation_cache
    where = enable_compilation_cache()
    import jax
    jax.config.update("jax_compilation_cache_max_size", -1)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    say(f"compilation cache: {use_compilation_cache()}")
    import jax
    devices = jax.devices()
    chips = int(cell["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        say(f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s). Nothing run.")
        return 2
    from repro.core import sweep
    sweep.reset_calibration()        # no persisted calibration steers dispatch
    say(f"jax {jax.__version__}: {len(devices)} x {devices[0].device_kind}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
