"""Sweep-scale driving of the fluid engine: shape-bucketed compile reuse
and vmap-batched CC-parameter x fabric-parameter sweeps.

The paper's result set is a sweep (CC policies x collectives x topologies
x fabric tuning, Figs 3-11); the engine in ``repro.core.engine`` compiles
one executable per ``(policy logic, EngineConfig, static plan)``.
``SweepRunner`` adds the pieces for running *many* scenarios fast:

* **shape buckets** — flow/group counts are padded up to the next power of
  two, and above ``_FINE_BUCKETS_ABOVE`` flows to the next eighth of an
  octave (inert padding, see ``engine._prep``), so schedules of similar
  size share one compiled executable instead of retracing per scenario;
* **vmap batching** — ``run_batch`` stacks CC parameter pytrees *and*
  ``FabricParams`` leaves (ECN kmin/kmax/pmax, PFC xoff/xon) of one policy
  family on a leading axis and runs the whole population in a single
  compiled call, which turns joint CC x fabric grids and population-based
  autotuning into one dispatch — zero recompiles after warmup;
* **scenario specs** — ``run_spec`` / ``run_specs`` / ``grid_spec`` accept
  the declarative ``repro.core.scenario.ScenarioSpec``, so drivers list
  scenarios instead of hand-assembling topology + schedule + policy;
* **a batched policy axis** — ``run_policy_axis`` stacks several CC
  policies into one product policy (``cc.stack_policies``: superset state
  + ``lax.switch`` on a traced selector) and runs the whole comparison as
  ONE vmapped dispatch; ``grid(..., policy_axis=[...])`` crosses that axis
  with CC-param and fabric grids, so the paper's policy-comparison figures
  are a single compiled call with zero recompiles after warmup;
* **spec-driven grids** — ``grid_from_spec(policy, n_points)`` generates
  grid axes from each policy's declared ``ParamSpec`` ranges (log/linear
  spacing, integer rounding) instead of hand-picked value lists;
* **sharded grid scale-out** — ``SweepRunner(mesh="auto")`` lays the
  grid/batch axis over a 1-D device mesh (``shard_map`` on top of the
  per-lane vmap; lanes are embarrassingly parallel, so a B-lane grid
  costs ~B/n_devices lane-times) with round-robin lane placement,
  edge-repeat padding for non-divisible grids (masked back out of
  ``BatchResults``), and streamed fixed-size chunking for grids larger
  than device memory (chunk i+1 dispatches before chunk i's results are
  pulled to host; per-device working set is bounded by
  ``lane_state_bytes x chunk/n_devices`` regardless of grid size).  On a
  CPU-only host, test with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  The sharded
  and vmap paths are allclose-equivalent (rtol 1e-5); ``mesh=None``
  (the default) is bitwise the historical path.

Batched runs never record the per-device queue timeline (it is a
per-member ``(T, D)`` buffer); use a plain ``run`` for Fig 5-7 style plots.

Backend note: vmap batching pays off where per-op dispatch overhead
dominates — small/medium scenarios such as population autotuning and CC
grid sweeps (measured ~2-4.5x over serial at B=8-16 on a CPU container;
on the chip the benchmark is ``bench/run.py``, its readings in
``PERF.md``).  For very large gather-bound
scenarios on CPU the batched stepping loses its early-exit advantage (it
runs until the *slowest* member finishes and computes both sides of the
done-gate); on accelerator backends the batch dimension vectorizes fully.
``batch_pays_off``/``policy_axis_pays_off`` decide serial-vs-batched from
the active backend's crossover table: ``calibrate_backend()`` measures it
(serial vs batched at a few probe sizes, cached per backend,
``BackendCalibration.record()`` gives its JSON form),
``DEFAULT_CROSSOVERS`` is the uncalibrated fallback.

    runner = SweepRunner(EngineConfig(dt=2e-6, max_steps=4000, queue_stride=0))
    results = runner.run_policies(topo, sched, ["pfc", "dcqcn", "hpcc"])
    batch = runner.grid(topo, sched, get_policy("dcqcn"),
                        {"rai_frac": [0.01, 0.03, 0.1]},
                        fabric_grid={"kmin": [100e3, 400e3],
                                     "xoff": [0.5e6, 1e6, 2e6]})
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
import warnings

import jax
import numpy as np
from jax.sharding import PartitionSpec

from repro.common import cache as cache_mod
from repro.common.sharding import resolve_grid_mesh
from repro.core import cc as cc_mod
from repro.core.cc import Policy, stack_policies
from repro.core.engine import (EngineConfig, FabricParams, Results, Simulator,
                               _as_fabric, _cfg_static, _init_carry,
                               _make_run, _next_pow2, _policy_cache_key)
from repro.core.faults import (FaultSpec, LaneStatus, _as_fault,
                               classify_lane, is_faulty)


def _resolve(policy) -> Policy:
    return cc_mod.get_policy(policy) if isinstance(policy, str) else policy


# above this many entries a bucket is an eighth of an octave, not a whole
# one: padding stays under 12.5% where every step's per-flow work dwarfs
# the compiles that finer buckets cost (at most 8 per octave)
_FINE_BUCKETS_ABOVE = 32768


def _bucket(n: int, lo: int = 32) -> int:
    """Padded size of an axis of ``n`` entries: the next power of two (at
    least ``lo``) up to ``_FINE_BUCKETS_ABOVE``, and above it the next
    multiple of that power's eighth, a multiple of the kernel's 8 x 128
    tile."""
    p = max(lo, _next_pow2(max(n, 1)))
    if p <= _FINE_BUCKETS_ABOVE:
        return p
    step = p // 8
    return -(-n // step) * step


@dataclasses.dataclass
class BatchResults:
    """One vmapped sweep over B stacked (CC params, FabricParams,
    FaultSpec) sets, with per-lane run-health status: a diverged,
    deadlocked or budget-exhausted lane is isolated and reported while
    the healthy lanes complete normally."""
    policy: str
    params: dict                  # stacked CC leaves, shape (B,)
    fabric: dict                  # stacked FabricParams leaves, (B,) or (B,C)
    completion_time: np.ndarray   # (B,)
    t_finish: np.ndarray          # (B, F)
    pause_count: np.ndarray       # (B, D)
    delivered: np.ndarray         # (B, F)
    soft_cost: np.ndarray         # (B,)
    finished: np.ndarray          # (B,) bool
    policy_axis: tuple = ()       # per-member policy label (policy sweeps)
    fault: dict = dataclasses.field(default_factory=dict)  # FaultSpec leaves
    diverged: np.ndarray | None = None        # (B,) non-finite lane, frozen
    deadlock_step: np.ndarray | None = None   # (B,) first pause-cycle step
    storm_step: np.ndarray | None = None      # (B,) first pause-storm step
    extend_exhausted: np.ndarray | None = None  # (B,) budget ran out
    # (B,) steps the lane's stepping loop ran: whole chunk_steps chunks
    # (capped at the budget), shared by the lanes of one dispatched chunk
    # on one device, as the loop runs while any of them is live
    steps_run: np.ndarray | None = None

    @property
    def n(self) -> int:
        return len(self.completion_time)

    @property
    def deadlocked(self) -> np.ndarray:
        """(B,) bool: a PFC pause-graph cycle was detected in that lane."""
        if self.deadlock_step is None:
            return np.zeros(self.n, bool)
        return self.deadlock_step >= 0

    def lane_status(self) -> list[LaneStatus]:
        """Per-lane health as typed ``faults.LaneStatus`` (a ``str``
        subclass, so ``== "ok"`` / JSON / CSV consumers are unchanged).
        A deadlocked-but-finished lane still reads ``DEADLOCKED`` (the
        cycle resolved only because flows drained)."""
        dead = self.deadlocked
        div = (np.zeros(self.n, bool) if self.diverged is None
               else self.diverged)
        return [classify_lane(bool(div[i]), bool(dead[i]),
                              bool(self.finished[i]))
                for i in range(self.n)]

    def best(self) -> int:
        """Index of the fastest *finished* member (lowest completion)."""
        if not self.finished.any():
            raise ValueError("no sweep member finished within the step "
                             "budget; raise max_steps/max_extends")
        ct = np.where(self.finished, self.completion_time, np.inf)
        return int(np.argmin(ct))

    def policy_of(self, i: int) -> str:
        """Policy label of member ``i`` (== ``policy`` without an axis)."""
        if self.policy_axis:
            return self.policy_axis[int(np.asarray(
                self.params["_which"])[i])]
        return self.policy

    def param_set(self, i: int) -> dict:
        return {k: float(np.asarray(v)[i]) for k, v in self.params.items()}

    def fabric_set(self, i: int) -> FabricParams:
        return FabricParams(**{k: np.asarray(v)[i]
                               for k, v in self.fabric.items()})

    def fault_set(self, i: int) -> FaultSpec:
        """The FaultSpec lane ``i`` ran under (inert spec if no faults)."""
        if not self.fault:
            return FaultSpec()
        return FaultSpec(**{k: np.asarray(v)[i]
                            for k, v in self.fault.items()})


_BATCH_CACHE: dict = {}
_SHARD_CACHE: dict = {}
# compiled-callable cache bounds, FIFO like the scenario cache
# (SweepRunner.MAX_SIMS): a long campaign across many shapes/policies
# would otherwise accumulate jitted executables without limit.  Eviction
# counts surface in compile_stats()["evictions"].
BATCH_CACHE_MAX = 64
SHARD_CACHE_MAX = 64
_CACHE_EVICTIONS = {"batch": 0, "shard": 0}


def _cache_put(cache: dict, key, value, kind: str, bound: int):
    while len(cache) >= max(bound, 1):
        cache.pop(next(iter(cache)))
        _CACHE_EVICTIONS[kind] += 1
    cache[key] = value
    return value


# unhealthy-lane warning dedupe: one warning per (policy, status-kind set)
# per process, so a 1000-chunk campaign hitting the same unhealthy regime
# every chunk warns once instead of 1000 times.  reset_unhealthy_warnings
# re-arms (tests asserting on the warning call it between runs).
_UNHEALTHY_WARNED: set = set()


def reset_unhealthy_warnings() -> None:
    """Re-arm the deduplicated unhealthy-lane ``RuntimeWarning``."""
    _UNHEALTHY_WARNED.clear()


def _fmt_lane_indices(idx: list, cap: int = 8) -> str:
    head = ", ".join(str(i) for i in idx[:cap])
    return f"[{head}{', ...' if len(idx) > cap else ''}]"


def _warn_unhealthy_lanes(batch: "BatchResults", B: int) -> None:
    unhealthy = [(i, s) for i, s in enumerate(batch.lane_status())
                 if s is not LaneStatus.OK]
    if not unhealthy:
        return
    key = (batch.policy, frozenset(s for _, s in unhealthy))
    if key in _UNHEALTHY_WARNED:
        return
    _UNHEALTHY_WARNED.add(key)
    by_status: dict = {}
    for i, s in unhealthy:
        by_status.setdefault(s, []).append(i)
    detail = "; ".join(f"{s}: lanes {_fmt_lane_indices(idx)}"
                       for s, idx in by_status.items())
    warnings.warn(
        f"{len(unhealthy)}/{B} sweep lanes unhealthy ({detail}); healthy "
        "lanes completed normally — inspect BatchResults.lane_status(). "
        "Further identical warnings for this (policy, status) combination "
        "are suppressed (sweep.reset_unhealthy_warnings() re-arms).",
        RuntimeWarning, stacklevel=3)


_LANES = "lanes"      # the vmap axis of sweep lanes (engine._make_run)


def _span(name: str, call: int, lo: int | None = None, hi: int | None = None):
    """A host span of ``run_batch`` call number ``call`` (and of its lanes
    ``lo:hi`` where the batch is dispatched in chunks) in the profiler's
    trace.  It records only while a ``jax.profiler`` trace is open; the
    benchmark's readers name the device's idle time by these spans
    (``bench/stages.py``), so their names are part of its yardstick."""
    if lo is None:
        return jax.profiler.TraceAnnotation(name, batch=call)
    return jax.profiler.TraceAnnotation(name, batch=call, lo=lo, hi=hi)


def _one_lane(policy: Policy, cfg: EngineConfig, plan, faulty: bool):
    """The per-lane body both batch paths vmap over (axis ``_LANES``):
    build a fresh carry, run the jitted stepping loop (which donates it
    internally) and keep only the per-lane finals and the steps the loop
    ran."""
    run = _make_run(policy, cfg, plan, early_exit=True, faulty=faulty,
                    lane_axis=_LANES)

    def one(pp, params, fab, flt):
        carry = _init_carry(pp, plan, policy, cfg, params, faulty)
        carry, steps_run = run(carry, pp, params, fab, flt)
        out = {"t_finish": carry["t_finish"], "done": carry["done"],
               "pause_count": carry["pause_count"],
               "delivered": carry["delivered"], "soft": carry["soft"],
               "diverged": carry["diverged"],
               "deadlock_step": carry["deadlock_step"],
               "storm_step": carry["storm_step"], "steps_run": steps_run}
        if faulty:
            out["lost"] = carry["lost"]
        return out

    return one


def _compiled_batch(policy: Policy, cfg: EngineConfig, plan,
                    faulty: bool = False):
    """vmapped (pp, stacked_params, stacked_fabric, stacked_fault) ->
    stacked finals, cached like ``engine.compiled_run`` so same-shaped
    scenarios share the executable (fabric scalars on cfg are normalized
    out of the key; ``faulty`` keys the fault-injection compile path)."""
    key = (_policy_cache_key(policy), _cfg_static(cfg), plan, faulty)
    fn = _BATCH_CACHE.get(key)
    if fn is None:
        one = _one_lane(policy, cfg, plan, faulty)
        vm = jax.vmap(one, in_axes=(None, 0, 0, 0), axis_name=_LANES)
        fn = _cache_put(_BATCH_CACHE, key, jax.jit(vm), "batch",
                        BATCH_CACHE_MAX)
    return fn


def _mesh_key(mesh):
    return (tuple(mesh.axis_names),
            tuple(d.id for d in np.asarray(mesh.devices).reshape(-1)))


def _compiled_sharded_batch(policy: Policy, cfg: EngineConfig, plan,
                            faulty: bool, mesh):
    """The vmapped batch laid over a 1-D device mesh via ``shard_map``:
    each device runs the per-lane vmap over its local block of lanes (the
    lanes are embarrassingly parallel — no cross-device collectives), so a
    B-lane grid costs ~B/n_devices lane-times of wall clock.  The lane
    axis of every stacked input/output is sharded on the mesh's grid
    axis; ``pp`` (the prepared scenario) is replicated.  Cached alongside
    ``_BATCH_CACHE`` with the mesh identity in the key."""
    key = (_policy_cache_key(policy), _cfg_static(cfg), plan, faulty,
           _mesh_key(mesh))
    fn = _SHARD_CACHE.get(key)
    if fn is None:
        one = _one_lane(policy, cfg, plan, faulty)
        vm = jax.vmap(one, in_axes=(None, 0, 0, 0), axis_name=_LANES)
        axis = mesh.axis_names[0]
        lanes = PartitionSpec(axis)
        sharded = jax.shard_map(vm, mesh=mesh,
                                in_specs=(PartitionSpec(), lanes, lanes,
                                          lanes),
                                out_specs=lanes, check_vma=False)
        fn = _cache_put(_SHARD_CACHE, key, jax.jit(sharded),
                        "shard", SHARD_CACHE_MAX)
    return fn


def compile_stats() -> dict:
    """Compile-cache counters (for asserting zero-recompile sweeps)."""
    from repro.core import engine as engine_mod

    def n_exec(fns):
        return sum(f._cache_size() for f in fns
                   if hasattr(f, "_cache_size"))

    return {
        "run_cache": len(engine_mod._RUN_CACHE),
        "batch_cache": len(_BATCH_CACHE),
        "shard_cache": len(_SHARD_CACHE),
        "compiled_executables": n_exec(engine_mod._RUN_CACHE.values())
        + n_exec(_BATCH_CACHE.values())
        + n_exec(_SHARD_CACHE.values()),
        "evictions": dict(_CACHE_EVICTIONS),
    }


def grid_from_spec(policy: Policy | str, n_points: int = 3,
                   keys: list | None = None) -> dict:
    """Generate grid axes from a policy's declared ``ParamSpec`` ranges.

    Each selected tunable, *bounded* param gets ``n_points`` values
    spanning [lo, hi] — geometrically spaced where the spec declares
    ``scale="log"``, linearly otherwise, rounded + deduplicated for
    integer params.  Feed the result straight to ``SweepRunner.grid``:

        runner.grid(topo, sched, "dcqcn", grid_from_spec("dcqcn", 3,
                                                         ["rai_frac", "g"]))
    """
    policy = _resolve(policy)
    if keys is None:
        keys = [k for k, s in policy.spec.items()
                if not s.init_baked and s.bounded and not k.startswith("_")]
    else:
        policy.check_tunable(keys)
    axes = {}
    for k in keys:
        s = policy.param_spec(k)
        if not s.bounded:
            raise ValueError(f"{policy.name} param {k!r} declares no "
                             "lo/hi bounds; pass explicit grid values")
        if s.scale == "log":
            vals = np.geomspace(s.lo, s.hi, n_points)
        else:
            vals = np.linspace(s.lo, s.hi, n_points)
        if s.integer:
            vals = np.unique(np.round(vals))
        axes[k] = [float(v) for v in vals]
    if not axes:
        raise ValueError(f"{policy.name} has no bounded tunable params")
    return axes


def _stack_fabric(base: FabricParams, stacked: dict | None, B: int) -> FabricParams:
    """Stack FabricParams leaves on a leading B axis; leaves absent from
    ``stacked`` broadcast the base value.  Stacked leaves may be (B,)
    scalars-per-member or (B, N_LINK_CLASSES) per-class arrays."""
    stacked = stacked or {}
    FabricParams.check_fields(stacked)
    leaves = {}
    for f in FabricParams.FIELDS:
        if f in stacked:
            v = np.asarray(stacked[f], np.float32)
            if v.shape[0] != B:
                raise ValueError(f"fabric param {f!r} has leading dim "
                                 f"{v.shape[0]}, expected batch {B}")
        else:
            b = np.asarray(getattr(base, f), np.float32)
            v = np.broadcast_to(b, (B,) + b.shape)
        leaves[f] = v
    return FabricParams(**leaves)


def _stack_fault(base: FaultSpec, stacked: dict | None, B: int) -> FaultSpec:
    """Stack FaultSpec leaves on a leading B axis, mirroring
    ``_stack_fabric``: leaves absent from ``stacked`` broadcast the base
    value; stacked leaves may be (B,) scalars-per-member or
    (B, N_LINK_CLASSES) per-class arrays."""
    stacked = stacked or {}
    FaultSpec.check_fields(stacked)
    leaves = {}
    for f in FaultSpec.FIELDS:
        if f in stacked:
            v = np.asarray(stacked[f], np.float32)
            if v.shape[0] != B:
                raise ValueError(f"fault param {f!r} has leading dim "
                                 f"{v.shape[0]}, expected batch {B}")
        else:
            b = np.asarray(getattr(base, f), np.float32)
            v = np.broadcast_to(b, (B,) + b.shape)
        leaves[f] = v
    return FaultSpec(**leaves)


def stack_policy_axis(policies=None, cc_overrides: list | None = None):
    """Build the vmappable policy-axis inputs without dispatching.

    Stacks ``policies`` into one product policy (``cc.stack_policies``)
    and assembles its per-lane selector params: the traced ``_which``
    column, the paired ``_wire`` factors, and member-namespaced
    ``"<policy>.<param>"`` columns for any ``cc_overrides`` (positionally
    aligned with ``policies``; only lane i reads member i's params).
    Returns ``(stacked_policy, params, labels)`` — ready for
    ``run_batch(..., policy_axis=labels)``.  ``run_policy_axis`` is the
    dispatching wrapper; the campaign layer uses this to journal and
    re-dispatch policy-axis chunks independently."""
    members = [_resolve(p) for p in (policies or cc_mod.ALL_POLICIES)]
    stacked_pol = stack_policies(members)
    labels = stacked_pol.members
    B = len(members)
    params = {
        "_which": np.arange(B, dtype=np.float32),
        "_wire": np.asarray([m.wire_factor for m in members],
                            np.float32),
    }
    if cc_overrides:
        if len(cc_overrides) != B:
            raise ValueError(f"cc_overrides has {len(cc_overrides)} "
                             f"entries for {B} policies")
        for i, (lab, m, over) in enumerate(
                zip(labels, members, cc_overrides)):
            if not over:
                continue
            m.check_tunable(over)
            for k, v in over.items():
                key = f"{lab}.{k}"
                col = params.get(key)
                if col is None:
                    col = np.full(B, float(m.params[k]), np.float32)
                col[i] = float(v)     # only lane i reads member i's params
                params[key] = col
    return stacked_pol, params, tuple(labels)


# -- backend calibration ----------------------------------------------------

_INF = float("inf")

# Fallback crossover tables (largest n_flows at which the batched path
# still wins wall-clock) used before any measurement has run on a backend.
# "sweep" = same-policy vmapped parameter sweep vs a serial loop;
# "policy_axis" = stacked lax.switch product policy vs per-policy runs;
# "sharded" = the shard_map grid layout vs the single-device vmap (only
# measurable with >1 device; unlisted -> inf, i.e. shard whenever a mesh
# was configured).  CPU numbers were measured on a CPU container by
# calibrate_backend (the sweep wins 4-5x below ~2k flows and loses 0.3x on the
# 7936-flow All-Reduce; the policy axis loses at every measured CPU
# scale).  Backends not listed (TPU/GPU) vectorize the batch axis fully,
# so batching always pays off there (inf).
DEFAULT_CROSSOVERS: dict = {
    "cpu": {"sweep": 2048.0, "policy_axis": 0.0},
}


@dataclasses.dataclass(frozen=True)
class BackendCalibration:
    """Serial-vs-batched crossover table for one JAX backend, either
    measured (``calibrate_backend``) or the ``DEFAULT_CROSSOVERS``
    fallback.  ``crossover[kind]`` is the largest flow count at which the
    batched path still wins: ``inf`` = batching always pays off, ``0.0`` =
    never."""
    backend: str
    source: str = "default"            # "default" | "measured"
    crossover: dict = dataclasses.field(default_factory=dict)
    probes: tuple = ()                 # (kind, n_flows, serial_s, batched_s)

    def pays_off(self, kind: str, n_flows: int | None = None) -> bool:
        """Should the batched path run for ``kind`` at ``n_flows``?  With
        ``n_flows=None`` (scenario-independent callers) batching is
        recommended only when it wins at *every* scale."""
        thr = float(self.crossover.get(kind, _INF))
        if n_flows is None:
            return thr == _INF
        return n_flows <= thr

    def record(self) -> dict:
        """JSON-safe dict (inf encoded as "inf")."""
        enc = {k: ("inf" if float(v) == _INF else float(v))
               for k, v in self.crossover.items()}
        return {"backend": self.backend, "source": self.source,
                "crossover": enc,
                "probes": [{"kind": k, "n_flows": n, "serial_s": s,
                            "batched_s": b}
                           for k, n, s, b in self.probes]}


_CALIBRATION: dict = {}
# backends for which the on-disk table must NOT be consulted: either the
# load was already attempted once, or reset_calibration() pinned the
# process back to the defaults ("*" = every backend)
_NO_DISK: set = set()


def calibration_cache_path(backend: str | None = None,
                           cache_dir: str | None = None) -> str:
    """Where ``calibrate_backend`` persists its measured table
    (``<checkout>/.cache/repro_calibration_<backend>.json``, the fixed
    ``repro.common.cache.CACHE_ROOT``) so fresh processes warm-start
    instead of re-measuring."""
    backend = backend or jax.default_backend()
    cache_dir = cache_dir or cache_mod.CACHE_ROOT
    return os.path.join(cache_dir, f"repro_calibration_{backend}.json")


def save_calibration(cal: BackendCalibration,
                     path: str | None = None) -> str | None:
    """Persist a measured calibration to disk (JSON; inf encoded).  Best
    effort: an unwritable cache dir is silently skipped (returns None).
    Written tmp-file + atomic rename, so a run killed mid-write leaves
    the previous table intact instead of a truncated JSON."""
    path = path or calibration_cache_path(cal.backend)
    rec = cal.record()
    rec["saved_at"] = time.time()
    rec["jax"] = jax.__version__
    rec["n_devices"] = len(jax.devices())
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(rec, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return path


def load_calibration(backend: str | None = None, path: str | None = None,
                     max_age_days: float | None = None
                     ) -> BackendCalibration | None:
    """Load a persisted calibration, or None when absent/stale/invalid.

    A table is rejected when it was measured under a different jax
    version or device count (both change the crossover), or — with
    ``max_age_days`` — when older than that.  A corrupt or truncated
    file (e.g. left by a killed run predating the atomic-rename save)
    is logged and ignored, never raised — a stale warm-start cache must
    not take down the first sweep of a fresh process."""
    backend = backend or jax.default_backend()
    path = path or calibration_cache_path(backend)
    try:
        with open(path) as f:
            rec = json.load(f)
    except OSError:
        return None                     # absent cache: the normal cold start
    except ValueError:
        warnings.warn(f"ignoring corrupt calibration cache {path} "
                      "(unparseable JSON; re-measure or delete it)",
                      RuntimeWarning, stacklevel=2)
        return None
    try:
        if rec.get("backend") != backend:
            return None
        if rec.get("jax") != jax.__version__:
            return None
        if rec.get("n_devices") != len(jax.devices()):
            return None
        if max_age_days is not None:
            age = time.time() - float(rec.get("saved_at", 0.0))
            if age > max_age_days * 86400.0:
                return None
        crossover = {k: (_INF if v == "inf" else float(v))
                     for k, v in rec.get("crossover", {}).items()}
        probes = tuple((p["kind"], int(p["n_flows"]), float(p["serial_s"]),
                        float(p["batched_s"])) for p in rec.get("probes", ()))
    except Exception:                   # valid JSON, wrong shape/types
        warnings.warn(f"ignoring malformed calibration cache {path} "
                      "(unexpected record shape; re-measure or delete it)",
                      RuntimeWarning, stacklevel=2)
        return None
    return BackendCalibration(backend=backend,
                              source=rec.get("source", "measured"),
                              crossover=crossover, probes=probes)


def get_calibration(backend: str | None = None) -> BackendCalibration:
    """The active crossover table for ``backend`` (default: the running
    JAX backend): the cached ``calibrate_backend`` measurement if one
    exists, else a table persisted to disk by a previous process
    (``calibration_cache_path``; disable with REPRO_CALIBRATION_CACHE=0),
    else the ``DEFAULT_CROSSOVERS`` entry (unlisted backends get inf
    thresholds — batching always on, accelerator behavior)."""
    backend = backend or jax.default_backend()
    cal = _CALIBRATION.get(backend)
    if (cal is None and "*" not in _NO_DISK and backend not in _NO_DISK
            and os.environ.get("REPRO_CALIBRATION_CACHE", "1") != "0"):
        _NO_DISK.add(backend)          # one load attempt per process
        cal = load_calibration(backend)
        if cal is not None:
            _CALIBRATION[backend] = cal
    if cal is None:
        table = dict(DEFAULT_CROSSOVERS.get(
            backend, {"sweep": _INF, "policy_axis": _INF}))
        cal = BackendCalibration(backend=backend, crossover=table)
    return cal


def set_calibration(cal: BackendCalibration) -> None:
    """Install a crossover table for ``cal.backend`` (e.g. one built from
    an earlier ``BackendCalibration.record()``)."""
    _CALIBRATION[cal.backend] = cal


def reset_calibration(backend: str | None = None) -> None:
    """Drop cached calibrations (all backends when ``backend`` is None),
    reverting ``get_calibration`` to the defaults — the on-disk table is
    not reconsulted until the process restarts (tests rely on reset
    meaning *defaults*, not *whatever a previous bench run persisted*)."""
    if backend is None:
        _CALIBRATION.clear()
        _NO_DISK.add("*")
    else:
        _CALIBRATION.pop(backend, None)
        _NO_DISK.add(backend)


def _measure_crossover(kind: str, n_flows: int, B: int,
                       cfg: EngineConfig) -> tuple:
    """Default calibration probe: time a serial loop against one batched
    dispatch for a ``kind`` sweep on a 1D All-Reduce of ~``n_flows``
    flows — the autotune/grid-sweep regime these heuristics actually
    gate (bytes scale with ranks so the step budget stays occupied and
    the comparison is not dominated by trivial-run early-exit).  Returns
    ``(actual_n_flows, serial_s, batched_s)``, both sides timed
    post-warmup (compiles excluded)."""
    import time as _time

    from repro.core.collectives import allreduce_1d
    from repro.core.topology import single_switch

    # allreduce_1d over R ranks with 4 chunks ~= 8*R*(R-1) flows
    R = max(2, int(round(0.5 + (0.25 + n_flows / 8.0) ** 0.5)))
    topo = single_switch(R)
    sched = allreduce_1d(topo, list(range(R)), 1e6 * R)
    runner = SweepRunner(cfg)
    if kind == "sweep":
        policy = cc_mod.get_policy("dcqcn")
        scale = np.linspace(0.5, 2.0, B).astype(np.float32)

        def serial():
            for s in scale:
                runner.run(topo, sched, policy,
                           dict(policy.params, rai_frac=float(0.03 * s)))

        def batched():
            runner.run_batch(topo, sched, policy,
                             {"rai_frac": 0.03 * scale})
    elif kind == "policy_axis":
        pols = list(cc_mod.ALL_POLICIES)[:max(2, B)]

        def serial():
            runner.run_policies(topo, sched, pols)

        def batched():
            runner.run_policy_axis(topo, sched, pols)
    elif kind == "sharded":
        # the shard_map grid layout vs the single-device vmap, same B-lane
        # sweep on both sides; "serial" here means the un-sharded vmap
        sharded = SweepRunner(cfg, mesh="auto")
        if sharded.mesh is None:
            raise RuntimeError("sharded calibration needs >1 JAX device "
                               "(emulate: XLA_FLAGS="
                               "--xla_force_host_platform_device_count=8)")
        policy = cc_mod.get_policy("dcqcn")
        Bs = max(B, sharded.n_mesh_devices)
        scale = np.linspace(0.5, 2.0, Bs).astype(np.float32)
        stacked = {"rai_frac": 0.03 * scale}

        def serial():
            runner.run_batch(topo, sched, policy, stacked)

        def batched():
            sharded.run_batch(topo, sched, policy, stacked)
    else:
        raise ValueError(f"unknown calibration kind: {kind!r}")

    out = []
    for fn in (serial, batched):
        fn()                                    # warmup: compile
        t0 = _time.perf_counter()
        fn()
        out.append(_time.perf_counter() - t0)
    return sched.n_flows, out[0], out[1]


def calibrate_backend(probe_flows=(90, 1806), B: int = 6,
                      cfg: EngineConfig | None = None,
                      kinds=None,
                      backend: str | None = None,
                      persist: bool = True,
                      _measure=None) -> BackendCalibration:
    """Measure the serial-vs-batched wall-clock crossover on the running
    backend and cache it; ``SweepRunner.batch_pays_off`` /
    ``policy_axis_pays_off`` / ``sharded_pays_off`` consult the cached
    table from then on.

    For each ``kind`` the batched path is timed against the serial loop at
    each probe size; the crossover is the geometric mean of the largest
    winning and smallest losing probe (all probes win -> inf, all lose ->
    0.0).  ``kinds=None`` probes "sweep" and "policy_axis", plus "sharded"
    (shard_map grid layout vs single-device vmap) when more than one JAX
    device is visible.  The measured table is persisted to
    ``calibration_cache_path()`` (``persist=False`` to skip) so later
    processes warm-start via ``get_calibration`` instead of re-measuring.
    ``_measure(kind, n_flows, B, cfg)`` is injectable for tests and
    deterministic benchmarks; ``BackendCalibration.record()`` gives its
    JSON form.
    """
    backend = backend or jax.default_backend()
    cfg = cfg or EngineConfig(dt=2e-6, max_steps=600, max_extends=1,
                              queue_stride=0)
    if kinds is None:
        kinds = ("sweep", "policy_axis")
        if len(jax.devices()) > 1:
            kinds += ("sharded",)
    measure = _measure or _measure_crossover
    probes, table = [], {}
    for kind in kinds:
        wins, losses = [], []
        for n in probe_flows:
            nf, serial_s, batched_s = measure(kind, n, B, cfg)
            probes.append((kind, int(nf), float(serial_s), float(batched_s)))
            (wins if batched_s < serial_s else losses).append(float(nf))
        if not losses:
            table[kind] = _INF
        elif not wins:
            table[kind] = 0.0
        else:
            table[kind] = float((max(wins) * min(losses)) ** 0.5)
    cal = BackendCalibration(backend=backend, source="measured",
                             crossover=table, probes=tuple(probes))
    set_calibration(cal)
    if persist and _measure is None:    # injected probes are synthetic —
        save_calibration(cal)           # never persist them to disk
    return cal


class SweepRunner:
    """Compile-once, run-many driver for ``repro.core.engine``.

    One instance caches prepared scenarios (``_prep`` output) by content
    fingerprint and leans on the engine's global compile cache for the
    jitted stepping loops, so sweeping P policies over S same-shaped
    scenarios compiles each policy once, not P x S times.
    """

    # prepared-scenario cache bound: entries hold (Fp, MAXHOP)-scale arrays,
    # so cap the count and evict FIFO; compiled executables live in the
    # engine's global cache and survive eviction
    MAX_SIMS = 64

    # chunk_lanes="auto": stream grids bigger than this many lanes per
    # device in fixed-size chunks (per-device working set stays bounded
    # regardless of grid size)
    AUTO_CHUNK_PER_DEVICE = 256

    def __init__(self, cfg: EngineConfig | None = None, bucket: bool = True,
                 mesh=None, chunk_lanes: int | str | None = "auto",
                 dispatch_hook=None):
        self.cfg = cfg or EngineConfig()
        self.bucket = bucket
        self._sims: dict = {}
        # mesh=None -> single-device vmap (the historical path, bitwise
        # unchanged); "auto" -> all local devices when >1; int/Mesh -> as
        # given.  See resolve_grid_mesh.
        self.mesh = resolve_grid_mesh(mesh)
        self.chunk_lanes = chunk_lanes
        # called as dispatch_hook(lo, hi, B) immediately before each lane
        # chunk is dispatched — the campaign layer's injectable failure
        # point (an exception raised here aborts the dispatch exactly like
        # an XLA OOM/compile failure would) and kill/progress probe
        self.dispatch_hook = dispatch_hook
        self._calls = 0               # run_batch calls, for the host spans

    def _pre_dispatch(self, lo: int, hi: int, B: int) -> None:
        if self.dispatch_hook is not None:
            self.dispatch_hook(lo, hi, B)

    @property
    def n_mesh_devices(self) -> int:
        """Devices the grid axis is laid over (1 == un-sharded vmap)."""
        if self.mesh is None:
            return 1
        return int(np.asarray(self.mesh.devices).size)

    def _chunk_size(self, B: int) -> int:
        """Lanes per dispatched chunk: a multiple of the mesh size, ``B``
        itself (padded up) when no chunking applies."""
        n_dev = self.n_mesh_devices
        pad_to = -(-B // n_dev) * n_dev                   # ceil to mesh
        if self.chunk_lanes in (None, 0):
            return pad_to
        if self.chunk_lanes == "auto":
            limit = self.AUTO_CHUNK_PER_DEVICE * n_dev
        else:
            limit = max(int(self.chunk_lanes), 1)
            limit = -(-limit // n_dev) * n_dev            # ceil to mesh
        return min(pad_to, limit)

    @staticmethod
    def _scenario_key(topo, sched):
        """Content fingerprint, so schedules rebuilt per call (e.g. the
        DLRM iteration in figs 10/11) still hit the cache."""
        h = hashlib.sha1()
        for a in (sched.path, sched.size, sched.group, sched.dep,
                  sched.delay, topo.cap, topo.lat, topo.src_dev,
                  topo.dst_dev, topo.ecn_on, topo.fabric, topo.link_class,
                  topo.dev_is_switch, topo.dev_buf):
            h.update(np.ascontiguousarray(a).tobytes())
        return (topo.name, sched.n_flows, sched.n_groups, h.hexdigest())

    # -- scenario preparation ------------------------------------------------
    def simulator(self, topo, sched, policy: Policy,
                  cfg: EngineConfig | None = None) -> Simulator:
        cfg = cfg or self.cfg
        # fabric scalars are traced (passed per run), so configs differing
        # only there share one prepared Simulator
        key = (self._scenario_key(topo, sched), _cfg_static(cfg),
               _policy_cache_key(policy))
        sim = self._sims.get(key)
        if sim is None:
            pf = _bucket(sched.n_flows) if self.bucket else None
            pg = _bucket(sched.n_groups, lo=8) if self.bucket else None
            sim = Simulator(topo, sched, policy, cfg,
                            pad_flows=pf, pad_groups=pg)
            while len(self._sims) >= self.MAX_SIMS:
                self._sims.pop(next(iter(self._sims)))
            self._sims[key] = sim
        return sim

    # -- single runs ---------------------------------------------------------
    def run(self, topo, sched, policy: Policy | str,
            cc_params: dict | None = None,
            cfg: EngineConfig | None = None,
            fabric_params: FabricParams | None = None,
            fault_spec: FaultSpec | None = None) -> Results:
        policy = _resolve(policy)
        cfg = cfg or self.cfg
        # resolve the fabric from the *caller's* cfg: the cached Simulator
        # may have been built under a different default
        fab = _as_fabric(fabric_params, cfg)
        return self.simulator(topo, sched, policy, cfg).run(
            cc_params, fabric_params=fab, fault_spec=_as_fault(fault_spec))

    def run_policies(self, topo, sched, policies=None,
                     cfg: EngineConfig | None = None,
                     fabric_params: FabricParams | None = None) -> list[Results]:
        """One scenario under each CC policy, serially — full ``Results``
        per policy (queue timelines included); ``run_policy_axis`` runs the
        same comparison as one vmapped dispatch."""
        out = []
        for p in (policies or cc_mod.ALL_POLICIES):
            out.append(self.run(topo, sched, p, cfg=cfg,
                                fabric_params=fabric_params))
        return out

    def batch_pays_off(self, sched) -> bool:
        """Should a *same-policy* parameter sweep over this scenario run
        batched (one vmapped dispatch) or serial?  Decided from the active
        backend's crossover table — the cached ``calibrate_backend``
        measurement, or ``DEFAULT_CROSSOVERS`` when uncalibrated."""
        return get_calibration().pays_off("sweep", sched.n_flows)

    def policy_axis_pays_off(self, sched=None) -> bool:
        """Like ``batch_pays_off`` but for the stacked policy axis, which
        additionally evaluates *every* member's update per lane (vmapped
        ``lax.switch`` runs all branches).  Called without ``sched`` the
        axis is recommended only where it wins at every measured scale: on
        CPU it loses wall-clock everywhere (measured by calibrate_backend)
        — the win there is architectural (one compile, zero recompiles
        across policy x param x fabric grids), not wall-clock."""
        return get_calibration().pays_off(
            "policy_axis", None if sched is None else sched.n_flows)

    def sharded_pays_off(self, sched=None) -> bool:
        """Would laying the grid axis over the device mesh beat one
        device's vmap?  Trivially False without a mesh; otherwise decided
        from the backend crossover table (kind ``"sharded"``, default:
        always — real multi-device backends parallelize lanes).  Like
        ``batch_pays_off`` this is *advice for drivers* deciding whether
        to construct a runner with a mesh; ``run_batch`` itself never
        second-guesses an explicitly configured mesh (the emulated-device
        testing recipe depends on that).  Wall-clock choice only: both
        paths are allclose-equivalent."""
        if self.mesh is None:
            return False
        return get_calibration().pays_off(
            "sharded", None if sched is None else sched.n_flows)

    def lane_state_bytes(self, topo, sched, policy: Policy | str,
                         cfg: EngineConfig | None = None,
                         faulty: bool = False) -> int:
        """Device bytes one sweep lane's stepping carry occupies (via
        ``jax.eval_shape`` — nothing is allocated).  The chunked-streaming
        memory bound per device is ``chunk_size / n_devices * lane_state_bytes``
        plus the replicated scenario, independent of total grid size."""
        policy = _resolve(policy)
        cfg = dataclasses.replace(cfg or self.cfg, queue_stride=0)
        sim = self.simulator(topo, sched, policy, cfg)
        params = {k: np.float32(v) for k, v in policy.params.items()}
        shapes = jax.eval_shape(
            lambda pp, par: _init_carry(pp, sim.plan, policy, cfg, par,
                                        faulty),
            sim.pp, params)
        return int(sum(np.prod(s.shape) * s.dtype.itemsize
                       for s in jax.tree.leaves(shapes)))

    # -- the batched policy axis --------------------------------------------
    def run_policy_axis(self, topo, sched, policies=None,
                        cc_overrides: list | None = None,
                        cfg: EngineConfig | None = None,
                        fabric_params: FabricParams | None = None,
                        stacked_fabric: dict | None = None,
                        fault_spec: FaultSpec | None = None,
                        stacked_fault: dict | None = None) -> BatchResults:
        """The paper's per-figure policy comparison as ONE vmapped dispatch.

        Stacks ``policies`` into a product policy (``cc.stack_policies``)
        and vmaps over its traced ``_which`` selector: B = len(policies)
        lanes, each simulating one member, sharing a single compiled
        executable.  ``cc_overrides`` optionally gives a per-member
        cc_params dict (positionally aligned with ``policies``);
        ``stacked_fabric`` may additionally stack per-lane FabricParams
        leaves (length B, aligned with the policy lanes).  The result's
        ``policy_axis``/``policy_of`` label each lane.
        """
        stacked_pol, params, labels = stack_policy_axis(policies,
                                                        cc_overrides)
        return self.run_batch(topo, sched, stacked_pol, params,
                              stacked_fabric=stacked_fabric,
                              fabric_params=fabric_params, cfg=cfg,
                              policy_axis=tuple(labels),
                              stacked_fault=stacked_fault,
                              fault_spec=fault_spec)

    # -- declarative scenarios ----------------------------------------------
    def run_spec(self, spec, cfg: EngineConfig | None = None) -> Results:
        """Simulate one ``ScenarioSpec`` (shape-bucketed + compile-cached)."""
        if isinstance(spec.policy, (tuple, list)):
            raise ValueError(
                "spec declares a policy axis (tuple policy); run it batched "
                "via grid_spec/run_policy_axis, or pick one member")
        topo, sched, policy = spec.build()
        cc = None
        if spec.cc_params:
            policy.check_tunable(spec.cc_params)
            cc = dict(policy.params, **spec.cc_params)
        return self.run(topo, sched, policy, cc_params=cc, cfg=cfg,
                        fabric_params=spec.fabric_params,
                        fault_spec=spec.fault_spec)

    def run_specs(self, specs, cfg: EngineConfig | None = None) -> list:
        """Simulate a list of ``ScenarioSpec``s; same-shaped specs share
        compiled engines via the shape-bucketed scenario cache.  A
        tuple-policy spec (``scenario_matrix(stacked=True)``) runs its
        policy axis as one batched — and, with a mesh, sharded — dispatch
        and contributes a ``BatchResults`` entry instead of ``Results``."""
        return [self.grid_spec(s, cfg=cfg)
                if isinstance(s.policy, (tuple, list))
                else self.run_spec(s, cfg=cfg) for s in specs]

    def grid_spec(self, spec, param_grid: dict | None = None,
                  fabric_grid: dict | None = None,
                  cfg: EngineConfig | None = None,
                  fault_grid: dict | None = None) -> BatchResults:
        """Full-factorial CC x fabric x fault grid on one ``ScenarioSpec``.
        A spec whose ``policy`` is a tuple/list sweeps the policy axis too
        (one vmapped policy x CC-param x fabric x fault dispatch); the
        spec's ``fault_spec`` broadcasts to every lane not covered by
        ``fault_grid`` axes."""
        if isinstance(spec.policy, (tuple, list)):
            topo, sched, _ = spec.build()
            return self.grid(topo, sched, None, param_grid, fabric_grid,
                             fabric_params=spec.fabric_params,
                             cc_params=spec.cc_params, cfg=cfg,
                             policy_axis=list(spec.policy),
                             fault_grid=fault_grid,
                             fault_spec=spec.fault_spec)
        topo, sched, policy = spec.build()
        return self.grid(topo, sched, policy, param_grid, fabric_grid,
                         fabric_params=spec.fabric_params,
                         cc_params=spec.cc_params, cfg=cfg,
                         fault_grid=fault_grid,
                         fault_spec=spec.fault_spec)

    # -- batched parameter sweeps -------------------------------------------
    def _dispatch_lanes(self, policy: Policy, cfg: EngineConfig, sim,
                        full: dict, fab: FabricParams, flt: FaultSpec,
                        faulty: bool, B: int) -> dict:
        """Dispatch B stacked lanes, gather stacked finals to host numpy.

        Un-sharded (``mesh=None``) and fitting one chunk, this is exactly
        the historical single-dispatch vmap — bitwise unchanged.  With a
        mesh, the lane axis is laid over the devices via ``shard_map``
        with ROUND-ROBIN lane placement: grid lanes arrive sorted along
        sweep axes, so blocks of consecutive lanes share a regime and
        block placement would pile a slow region onto one device; the
        round-robin permutation interleaves them (lane i -> device
        i % n_dev), then the inverse permutation restores input order on
        the way out.  Grids larger than one chunk stream: chunk i+1 is
        dispatched (JAX dispatch is async) before chunk i's buffers are
        pulled to host, overlapping transfer with compute, and only one
        chunk of lane state lives on the devices at a time
        (``lane_state_bytes`` x chunk/n_dev per device).  The trailing
        chunk is padded by edge-repeating the final lane — inert work
        whose results are dropped before returning, so callers always see
        exactly B lanes in input order.
        """
        # an explicitly configured mesh is an explicit choice: it is used
        # unconditionally (the emulated-device testing recipe depends on
        # that).  sharded_pays_off is *advice for drivers* deciding
        # whether to construct a mesh, mirroring batch_pays_off — run_batch
        # never second-guesses its caller.
        lanes = (full, fab, flt)
        call = self._calls
        if self.mesh is None:
            fn = _compiled_batch(policy, cfg, sim.plan, faulty)
            chunk = self._chunk_size(B)
            if chunk >= B:                        # one dispatch, no padding
                self._pre_dispatch(0, B, B)
                with _span("sweep.dispatch", call):
                    out = fn(sim.pp, *lanes)
                with _span("sweep.pull", call):
                    return jax.tree.map(np.asarray, out)
            parts, pending = [], None
            for lo in range(0, B, chunk):
                hi = min(lo + chunk, B)
                take = np.arange(lo, hi)
                if hi - lo < chunk:               # edge-repeat trailing pad
                    take = np.concatenate(
                        [take, np.full(chunk - (hi - lo), hi - 1)])
                self._pre_dispatch(lo, hi, B)
                with _span("sweep.dispatch", call, lo, hi):
                    got = fn(sim.pp, *jax.tree.map(lambda a: a[take], lanes))
                if pending is not None:
                    lo0, hi0, out0 = pending
                    with _span("sweep.pull", call, lo0, hi0):
                        parts.append(jax.tree.map(
                            lambda a: np.asarray(a)[:hi0 - lo0], out0))
                pending = (lo, hi, got)
            lo0, hi0, out0 = pending
            with _span("sweep.pull", call, lo0, hi0):
                parts.append(jax.tree.map(
                    lambda a: np.asarray(a)[:hi0 - lo0], out0))
            return jax.tree.map(
                lambda *xs: np.concatenate(xs, axis=0), *parts)
        n_dev = self.n_mesh_devices
        chunk = self._chunk_size(B)
        fn = _compiled_sharded_batch(policy, cfg, sim.plan, faulty,
                                     self.mesh)
        # within a chunk: permute so block-sharding over the mesh assigns
        # device d the round-robin lanes {d, d+n_dev, ...}; inv undoes it
        order = np.arange(chunk).reshape(-1, n_dev).T.reshape(-1)
        inv = np.argsort(order)
        parts, pending = [], None
        for lo in range(0, B, chunk):
            hi = min(lo + chunk, B)
            take = np.arange(lo, hi)
            if hi - lo < chunk:                   # edge-repeat trailing pad
                take = np.concatenate(
                    [take, np.full(chunk - (hi - lo), hi - 1)])
            self._pre_dispatch(lo, hi, B)
            with _span("sweep.dispatch", call, lo, hi):
                got = fn(sim.pp,
                         *jax.tree.map(lambda a: a[take[order]], lanes))
            if pending is not None:               # stream: gather the chunk
                lo0, hi0, out0 = pending          # dispatched *last* round
                with _span("sweep.pull", call, lo0, hi0):
                    parts.append(jax.tree.map(
                        lambda a: np.asarray(a)[inv][:hi0 - lo0], out0))
            pending = (lo, hi, got)
        lo0, hi0, out0 = pending
        with _span("sweep.pull", call, lo0, hi0):
            parts.append(jax.tree.map(
                lambda a: np.asarray(a)[inv][:hi0 - lo0], out0))
        if len(parts) == 1:
            return parts[0]
        return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *parts)

    def _batch_inputs(self, topo, sched, policy, stacked_params,
                      stacked_fabric, fabric_params, cc_params, cfg,
                      stacked_fault, fault_spec):
        """Validate and stack ``run_batch``'s arguments: returns ``(policy,
        cfg, sim, full, fab, flt, faulty, B)``."""
        policy = _resolve(policy)
        stacked_params = stacked_params or {}
        policy.check_tunable(stacked_params)
        if cc_params:
            policy.check_tunable(cc_params)
        sizes = [len(np.asarray(v)) for v in stacked_params.values()]
        sizes += [np.asarray(v).shape[0] for v in (stacked_fabric or {}).values()]
        sizes += [np.asarray(v).shape[0] for v in (stacked_fault or {}).values()]
        if not sizes:
            raise ValueError("empty batch: provide stacked_params, "
                             "stacked_fabric and/or stacked_fault")
        if len(set(sizes)) > 1:
            raise ValueError(f"inconsistent batch sizes {sorted(set(sizes))}")
        B = sizes[0]
        base_cc = dict(policy.params, **(cc_params or {}))
        full = {k: np.asarray(stacked_params.get(k, np.full(B, float(v))),
                              np.float32)
                for k, v in base_cc.items()}
        cfg = dataclasses.replace(cfg or self.cfg, queue_stride=0)
        fab = _stack_fabric(_as_fabric(fabric_params, cfg), stacked_fabric, B)
        flt = _stack_fault(_as_fault(fault_spec), stacked_fault, B)
        faulty = is_faulty(flt)
        sim = self.simulator(topo, sched, policy, cfg)
        return policy, cfg, sim, full, fab, flt, faulty, B

    def compile_batch(self, topo, sched, policy: Policy | str,
                      stacked_params: dict | None = None,
                      stacked_fabric: dict | None = None,
                      fabric_params: FabricParams | None = None,
                      cc_params: dict | None = None,
                      cfg: EngineConfig | None = None,
                      stacked_fault: dict | None = None,
                      fault_spec: FaultSpec | None = None
                      ) -> jax.stages.Compiled:
        """Ahead-of-time compile the executable ``run_batch`` dispatches
        for the same arguments (one chunk of lanes, on this runner's mesh
        if it has one), so compile time is measured apart from the run
        and the executable can be inspected (``as_text()``,
        ``output_shardings``); the ``run_batch`` that follows finds it
        compiled."""
        policy, cfg, sim, full, fab, flt, faulty, B = self._batch_inputs(
            topo, sched, policy, stacked_params, stacked_fabric,
            fabric_params, cc_params, cfg, stacked_fault, fault_spec)
        take = np.minimum(np.arange(self._chunk_size(B)), B - 1)
        lanes = jax.tree.map(lambda a: np.asarray(a)[take], (full, fab, flt))
        if self.mesh is None:
            fn = _compiled_batch(policy, cfg, sim.plan, faulty)
        else:
            fn = _compiled_sharded_batch(policy, cfg, sim.plan, faulty,
                                         self.mesh)
        return fn.lower(sim.pp, *lanes).compile()

    def run_batch(self, topo, sched, policy: Policy | str,
                  stacked_params: dict | None = None,
                  stacked_fabric: dict | None = None,
                  fabric_params: FabricParams | None = None,
                  cc_params: dict | None = None,
                  cfg: EngineConfig | None = None,
                  policy_axis: tuple = (),
                  stacked_fault: dict | None = None,
                  fault_spec: FaultSpec | None = None) -> BatchResults:
        """Simulate B (CC params, FabricParams, FaultSpec) sets in one
        vmapped call.

        ``stacked_params`` maps CC param name -> length-B array;
        ``stacked_fabric`` maps FabricParams field -> (B,) or (B, C) array;
        ``stacked_fault`` maps FaultSpec field -> (B,) or (B, C) array.
        Missing CC params broadcast from the policy defaults (overridden by
        ``cc_params``); missing fabric fields broadcast from
        ``fabric_params`` (default: the runner config's scalars); missing
        fault fields broadcast from ``fault_spec`` (default: inert).  Queue
        timelines are never recorded for batched runs (per-member buffers).
        ``policy_axis`` carries the per-lane policy labels when ``policy``
        is a stacked product policy (see ``run_policy_axis``).

        Lane isolation: a diverged (non-finite) lane freezes in place, a
        deadlocked or budget-exhausted lane is flagged, and the healthy
        lanes complete normally — see ``BatchResults.lane_status``.
        """
        self._calls += 1
        call = self._calls
        with _span("sweep.inputs", call) as span:
            policy, cfg, sim, full, fab, flt, faulty, B = self._batch_inputs(
                topo, sched, policy, stacked_params, stacked_fabric,
                fabric_params, cc_params, cfg, stacked_fault, fault_spec)
            # how much of the dispatch's per-flow work is inert padding
            span.set_metadata(flows=sim.plan.n_flows,
                              flows_padded=sim.plan.n_flows_pad)
        out = self._dispatch_lanes(policy, cfg, sim, full, fab, flt,
                                   faulty, B)
        with _span("sweep.results", call):
            F = sim.plan.n_flows
            t_fin = np.asarray(out["t_finish"])[:, :F]
            done = np.asarray(out["done"])[:, :F]
            ct = np.max(np.where(np.isfinite(t_fin), t_fin, 0.0), axis=1)
            finished = done.all(axis=1)
            diverged = np.asarray(out["diverged"])
            deadlock_step = np.asarray(out["deadlock_step"])
            storm_step = np.asarray(out["storm_step"])
            extend_exhausted = ~finished & ~diverged
            batch = BatchResults(
                policy=policy.name, params=full,
                fabric={k: np.asarray(getattr(fab, k))
                        for k in FabricParams.FIELDS},
                completion_time=ct, t_finish=t_fin,
                pause_count=np.asarray(out["pause_count"]),
                delivered=np.asarray(out["delivered"])[:, :F],
                soft_cost=np.asarray(out["soft"]),
                finished=finished,
                policy_axis=tuple(policy_axis),
                fault=({k: np.asarray(getattr(flt, k))
                        for k in FaultSpec.FIELDS} if faulty else {}),
                diverged=diverged, deadlock_step=deadlock_step,
                storm_step=storm_step, extend_exhausted=extend_exhausted,
                steps_run=np.asarray(out["steps_run"]),
            )
            _warn_unhealthy_lanes(batch, B)
        return batch

    def grid(self, topo, sched, policy: Policy | str | None = None,
             param_grid: dict | None = None,
             fabric_grid: dict | None = None,
             fabric_params: FabricParams | None = None,
             cc_params: dict | None = None,
             cfg: EngineConfig | None = None,
             policy_axis: list | None = None,
             fault_grid: dict | None = None,
             fault_spec: FaultSpec | None = None) -> BatchResults:
        """Full-factorial joint sweep: CC ``{param: [values...]}`` x fabric
        ``{field: [values...]}`` x fault ``{field: [values...]}`` -> ONE
        vmapped batched run.

        Fabric/fault grid axes may list scalars or per-class arrays (each
        entry one grid point).  With several grids given, the batch
        enumerates the full cross product — e.g. 3 kmin x 3 xoff x 4 CC
        points = B=36 in a single compiled dispatch; a ``fault_grid`` like
        ``{"loss_rate": [0, 1e-5, 1e-3], "gbn": [0, 1]}`` crosses fault
        regimes into the same dispatch (non-grid fault fields broadcast
        from ``fault_spec``).

        ``policy_axis`` adds the *policy* as a grid dimension: the named
        policies are stacked into one product policy and the cross product
        gains a lane per member (policy x CC-param x fabric x fault, still
        one dispatch).  With a policy axis, ``policy`` must be None and
        ``param_grid`` keys must be member-namespaced (``"dcqcn.rai_frac"``
        — only that member's lanes respond to the axis).
        """
        param_grid = param_grid or {}
        fabric_grid = fabric_grid or {}
        fault_grid = fault_grid or {}
        FaultSpec.check_fields(fault_grid)
        for a, b, what in (((param_grid, fabric_grid, "CC and fabric")),
                           ((param_grid, fault_grid, "CC and fault")),
                           ((fabric_grid, fault_grid, "fabric and fault"))):
            overlap = set(a) & set(b)
            if overlap:
                raise ValueError(f"params {sorted(overlap)} appear in both "
                                 f"the {what} grids")
        labels, wires = (), None
        if policy_axis is not None:
            if policy is not None:
                raise ValueError("pass either policy or policy_axis, "
                                 "not both")
            members = [_resolve(p) for p in policy_axis]
            wires = np.asarray([m.wire_factor for m in members], np.float32)
            policy = stack_policies(members)
            labels = policy.members
            bad = {k for k in param_grid if "." not in k}
            if bad:
                raise ValueError(
                    f"param_grid keys {sorted(bad)} are not member-"
                    "namespaced; with a policy_axis use '<policy>.<param>' "
                    f"(members: {list(labels)})")
        elif policy is None:
            raise ValueError("policy is required without a policy_axis")
        axes = [np.asarray(v, np.float32)
                for v in list(param_grid.values()) + list(fabric_grid.values())
                + list(fault_grid.values())]
        names = list(param_grid) + list(fabric_grid) + list(fault_grid)
        if policy_axis is not None:
            names.append("_which")
            axes.append(np.arange(len(labels), dtype=np.float32))
        if not axes:
            raise ValueError("empty grid")
        # index-space meshgrid so per-class (point, C)-shaped fabric/fault
        # axes enumerate points along axis 0
        idx = np.meshgrid(*[np.arange(len(a)) for a in axes], indexing="ij")
        flat = [i.reshape(-1) for i in idx]
        stacked = {k: axes[j][flat[j]] for j, k in enumerate(names)}
        stacked_cc = {k: stacked[k] for k in names
                      if k not in fabric_grid and k not in fault_grid}
        if wires is not None:
            # the wire factor is paired with the selected member, never an
            # independent axis
            stacked_cc["_wire"] = wires[stacked["_which"].astype(np.int64)]
        return self.run_batch(
            topo, sched, policy, stacked_cc,
            stacked_fabric={k: stacked[k] for k in fabric_grid},
            fabric_params=fabric_params, cc_params=cc_params, cfg=cfg,
            policy_axis=labels,
            stacked_fault={k: stacked[k] for k in fault_grid},
            fault_spec=fault_spec)
