"""Network layer: fixed-timestep, fully-vectorized fluid-flow simulator.

JAX/TPU-native adaptation of the paper's NS3 packet-level layer (DESIGN.md
§2): per-flow/per-link flat arrays stepped inside one ``lax.scan``.

Per step Δt:
  1. delayed signals (ECN fraction, RTT, HPCC INT utilisation) read from a
     per-link history ring at t - base_rtt(flow)
  2. CC policy update -> per-flow rate / window
  3. paced, window-gated injection into the source NIC egress queue
  4. hop-ordered fluid forwarding with per-link capacity accounting and
     proportional backlog drain (per-flow per-hop backlog => exact byte
     conservation)
  5. PFC: per-switch buffer hysteresis (X_OFF/X_ON) pauses all upstream
     links into that switch; pause transitions are counted (Fig 9 metric)
  6. dependency groups: flows start when their dep group completes (+ a
     compute delay), giving chunk pipelining and workload DAGs

The engine is differentiable w.r.t. CC policy parameters: `soft_cost`
integrates the undelivered fraction over time (see core/autotune.py).

Hot path
--------
All per-link reductions (hop demand, queue occupancy, PFC port pressure,
group completion counts) go through *static gather plans* built once in
``_prep``: flow->link membership is known ahead of time, so each reduction
is a padded gather + row-sum over a precomputed ``(segments, Cmax)`` index
matrix instead of an XLA scatter-add (an order of magnitude faster on CPU;
pathological fan-ins fall back to scatter, chosen statically per scenario).
The feedback history ring is sized to the actual maximum ``delay_steps``
(next power of two) rather than a fixed ``cfg.hist`` slots.

Early-exit semantics
--------------------
``Simulator.run`` integrates ``max_steps * (max_extends + 1)`` total steps,
but inside one jitted call: a ``lax.while_loop`` over ``cfg.chunk_steps``-
sized ``lax.scan`` chunks stops as soon as every flow has completed, and
each step is additionally gated on ``done.all()`` via ``lax.cond`` so the
tail of the final chunk costs ~nothing.  Because finished steps are exact
no-ops, an early-exited run is *bitwise identical* to a monolithic scan of
the full step budget (``run(early_exit=False)``), and results never depend
on ``chunk_steps``.  The carry is donated to the compiled call.

The per-device queue timeline (``Results.dev_queue``, consumed only by the
Fig 5-7 style plots) is recorded every ``cfg.queue_stride`` steps, or not
at all with ``queue_stride=0`` — the recommended setting for sweeps.

Dynamic fabric parameters
-------------------------
ECN marking (kmin/kmax/pmax) and PFC thresholds (xoff/xon) are *traced*
inputs — a ``FabricParams`` pytree passed alongside ``cc_params`` — not
static config.  Leaves may be scalars or per-link-class arrays (indexed by
``topology.LINK_CLASSES``), so fabric-tuning grids vmap-batch through
``SweepRunner`` without recompiling and ``soft_cost`` differentiates
through fabric knobs as well as CC parameters.

Batched sweeps over CC parameters (vmap) and the cross-scenario compile
cache live in ``repro.core.sweep`` (``SweepRunner``); compiled step
functions here are keyed on ``(policy, cfg, static plan)`` so same-shaped
scenarios never retrace.  Declarative scenario construction
(``ScenarioSpec``) lives in ``repro.core.scenario``.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.cc import (FlowCtx, ParamSpec, Policy, Signals,
                           kernel_eligible)
from repro.core.collectives import Schedule
from repro.core.faults import (FaultSpec, LaneStatus, _as_fault,
                               classify_lane, is_faulty)
from repro.core.topology import (LINK_CLASS_ID, MAXHOP, N_LINK_CLASSES,
                                 Topology)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    dt: float = 1e-6
    max_steps: int = 20_000
    max_extends: int = 4          # extra step budget: total = max_steps*(1+extends)
    hist: int = 512               # feedback delay ring cap (steps)
    # ECN / PFC *defaults*: these scalars only seed the default
    # ``FabricParams`` (the dynamic, traced fabric knobs passed alongside
    # cc_params); the compiled step never reads them, so two configs
    # differing only here share one executable (see ``_cfg_static``)
    kmin: float = 400e3           # ECN / RED marking at switch egress queues
    kmax: float = 1600e3
    pmax: float = 0.2
    # PFC per-ingress-port hysteresis (bytes queued in the switch that
    # arrived through that port; pause is sent to that port's sender only)
    xoff: float = 1e6
    xon: float = 0.8e6
    t_base_util: float = 10e-6    # HPCC qlen->util horizon
    eps_done: float = 512.0       # completion slack (bytes)
    pause_resend: float = 5e-6    # PAUSE frame refresh while a port is paused
    # hot-path knobs (do not change simulated physics)
    chunk_steps: int = 256        # early-exit check granularity (in-jit)
    queue_stride: int = 1         # record dev_queue every k steps; 0 = off
    # step backend: "auto" resolves per jax.default_backend() — the fused
    # Pallas engine-step kernel (repro.kernels.engine_step) on TPU, the
    # historical jnp path elsewhere, so CPU results stay bitwise identical
    # to the engine goldens.  "pallas" forces the kernel path
    # (interpret-mode off-TPU: the CI correctness configuration); "jnp"
    # forces the reference path on any backend.  ``effective_step_impl``
    # says which step a given policy actually runs.
    step_impl: str = "auto"       # "auto" | "jnp" | "pallas"
    # run-health detection (observers only; never change simulated physics)
    deadlock_check_every: int = 64   # pause-cycle check cadence (steps)
    storm_frac: float = 0.5          # pause storm: fraction of ports paused
    storm_steps: int = 50            # ... for this many consecutive steps


_FABRIC_DEFAULTS = dict(kmin=400e3, kmax=1600e3, pmax=0.2, xoff=1e6, xon=0.8e6)

# declarative search spaces for the fabric knobs — same ParamSpec currency
# as the CC policies, consumed by ``autotune`` (scale + bounds projection)
# and ``sweep.grid_from_spec``
FABRIC_PARAM_SPECS = {
    "kmin": ParamSpec(_FABRIC_DEFAULTS["kmin"], lo=1e3, hi=64e6, scale="log"),
    "kmax": ParamSpec(_FABRIC_DEFAULTS["kmax"], lo=4e3, hi=256e6, scale="log"),
    "pmax": ParamSpec(_FABRIC_DEFAULTS["pmax"], lo=0.01, hi=1.0,
                      scale="linear"),
    "xoff": ParamSpec(_FABRIC_DEFAULTS["xoff"], lo=10e3, hi=64e6,
                      scale="log"),
    "xon": ParamSpec(_FABRIC_DEFAULTS["xon"], lo=10e3, hi=64e6, scale="log"),
}


@dataclasses.dataclass(frozen=True)
class FabricParams:
    """Dynamic fabric tuning knobs: a pytree traced alongside ``cc_params``.

    Each leaf is either a scalar (uniform fabric) or a per-link-class array
    of shape ``(N_LINK_CLASSES,)`` indexed by ``topology.LINK_CLASSES``, so
    e.g. spine downlinks can mark earlier than ToR downlinks.  Leaves ride
    through jit/vmap/grad: fabric-parameter grids batch through
    ``SweepRunner`` without recompiling, and ``soft_cost`` differentiates
    through them.  Scalar defaults reproduce the historical
    ``EngineConfig`` behavior bit-for-bit.
    """
    kmin: object = _FABRIC_DEFAULTS["kmin"]   # ECN marking ramp start (bytes)
    kmax: object = _FABRIC_DEFAULTS["kmax"]   # ECN marking ramp end (bytes)
    pmax: object = _FABRIC_DEFAULTS["pmax"]   # max marking probability
    xoff: object = _FABRIC_DEFAULTS["xoff"]   # PFC pause threshold (bytes)
    xon: object = _FABRIC_DEFAULTS["xon"]     # PFC resume threshold (bytes)

    FIELDS = ("kmin", "kmax", "pmax", "xoff", "xon")

    @classmethod
    def from_config(cls, cfg: EngineConfig) -> "FabricParams":
        return cls(kmin=cfg.kmin, kmax=cfg.kmax, pmax=cfg.pmax,
                   xoff=cfg.xoff, xon=cfg.xon)

    @classmethod
    def check_fields(cls, keys):
        """Reject names that are not FabricParams fields."""
        unknown = set(keys) - set(cls.FIELDS)
        if unknown:
            raise ValueError(f"unknown fabric params {sorted(unknown)}; "
                             f"known: {list(cls.FIELDS)}")

    def replace(self, **kw) -> "FabricParams":
        return dataclasses.replace(self, **kw)

    def with_class(self, **field_overrides) -> "FabricParams":
        """Per-link-class overrides: ``fab.with_class(kmin={"spine_down":
        100e3})`` expands ``kmin`` to a per-class array with the named
        classes replaced and every other class at this instance's value."""
        out = {}
        for field, overrides in field_overrides.items():
            base = np.broadcast_to(
                np.asarray(getattr(self, field), np.float32),
                (N_LINK_CLASSES,)).copy()
            for cls_name, v in overrides.items():
                base[LINK_CLASS_ID[cls_name]] = v
            out[field] = base
        return dataclasses.replace(self, **out)


jax.tree_util.register_dataclass(FabricParams,
                                 data_fields=FabricParams.FIELDS,
                                 meta_fields=())


def _as_fabric(fabric_params, cfg: EngineConfig) -> FabricParams:
    return (FabricParams.from_config(cfg) if fabric_params is None
            else fabric_params)


def _per_class(v):
    """Broadcast a FabricParams leaf to one value per link class."""
    return jnp.broadcast_to(jnp.asarray(v, jnp.float32), (N_LINK_CLASSES,))


def _fabric_tables(pp, fab: FabricParams, flt, faulty: bool) -> dict:
    """Per-hop (F, MAXHOP) and per-link (Lk+1,) values of the per-class
    fabric and fault knobs.  They are the same at every step, so each run
    gathers them once, outside the step loop."""
    def hop(v):
        return _per_class(v)[pp["cls_path"]]

    def link(v):
        return _per_class(v)[pp["link_class"]]

    tab = dict(kmin_h=hop(fab.kmin), kmax_h=hop(fab.kmax),
               pmax_h=hop(fab.pmax), xoff_l=link(fab.xoff),
               xon_l=link(fab.xon))
    if faulty:
        tab.update(ecn_scale_h=hop(flt.ecn_scale), degrade_l=link(flt.degrade),
                   loss_h=hop(flt.loss_rate), pfc_on_l=link(flt.pfc_on))
    return tab


def resolve_step_impl(cfg: EngineConfig) -> str:
    """Backend dispatch for the engine step: "auto" picks the fused Pallas
    kernel on TPU and the jnp reference path elsewhere (so the default
    path reproduces the engine goldens bitwise on CPU)."""
    impl = cfg.step_impl
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"step_impl must be 'auto', 'jnp' or 'pallas', "
                         f"got {impl!r}")
    return impl


def effective_step_impl(policy: Policy, cfg: EngineConfig) -> str:
    """The step ``policy`` actually runs under ``cfg``: "pallas" only when
    the resolved impl is "pallas" and the policy's update fits the
    kernel's flat-array form; stacked product policies (tuple state +
    ``lax.switch``) always run the jnp step."""
    if resolve_step_impl(cfg) == "pallas" and kernel_eligible(policy):
        return "pallas"
    return "jnp"


def _cfg_static(cfg: EngineConfig) -> EngineConfig:
    """The compile-cache view of a config: fabric scalars are dynamic
    (delivered via FabricParams), so they are normalized out of the key;
    ``step_impl`` is resolved so "auto" shares the executable of the
    backend it resolves to."""
    return dataclasses.replace(cfg, step_impl=resolve_step_impl(cfg),
                               **_FABRIC_DEFAULTS)


@dataclasses.dataclass
class Results:
    finished: bool
    completion_time: float        # max flow finish (s)
    t_finish: np.ndarray          # (F,)
    group_time: np.ndarray        # (G,)
    group_names: list
    pause_count: np.ndarray       # (D,) PFC pause transitions per device
    dev_queue: np.ndarray         # (T//queue_stride, D) queue-bytes timeline
    dt: float
    delivered: np.ndarray
    soft_cost: float
    meta: dict
    # run health (observers; see EngineConfig deadlock/storm knobs)
    deadlocked: bool = False      # a PFC pause-graph cycle was detected
    deadlock_step: int = -1       # first step the cycle was seen (-1 = never)
    storm_step: int = -1          # first step a pause storm was sustained
    diverged: bool = False        # non-finite state; lane frozen at detection
    extend_exhausted: bool = False  # step budget ran out before completion
    lost: np.ndarray | None = None  # (F,) bytes dropped in-network (lossy mode)

    @property
    def status(self) -> LaneStatus:
        """Typed run-health verdict (``faults.LaneStatus``); the serial
        counterpart of ``BatchResults.lane_status()``."""
        return classify_lane(self.diverged, self.deadlocked, self.finished)


# ---------------------------------------------------------------------------
# static gather plans (scatter-free segment reductions)
# ---------------------------------------------------------------------------

def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


# split-row block width of the two-level plan, which a reduction takes
# whenever it gathers fewer rows than the single-level one: one hot port
# (e.g. a full-fabric incast) cannot inflate the gather to n_out * max_count
# slots, nor a few busy segments among many idle ones
_SPLIT_C = 64


def _padded_rows(kept_ids, kept_pos, counts, n_out, n_in, width):
    """(n_out, width) index matrix; slot ``n_in`` means "+0" (OOB fill)."""
    idx = np.full((n_out, width), n_in, np.int64)
    order = np.argsort(kept_ids, kind="stable")
    sid = kept_ids[order]
    starts = np.concatenate([[0], np.cumsum(counts)])
    slot = np.arange(len(sid)) - starts[sid]
    idx[sid, slot] = kept_pos[order]
    return idx


def _reduce_plan(ids: np.ndarray, n_in: int, n_out: int,
                 drop: np.ndarray | None = None):
    """Build a static plan for ``out[s] = sum(vals[ids == s])``.

    Entries with ``drop`` True (provably-zero contributions: padding flows,
    unused hop slots) are excluded.  Returns ``(arrays, strategy)`` where
    ``strategy`` is hashable and ``arrays`` ride along in ``pp``.

    Three strategies, chosen statically from the (known) fan-in histogram:
      empty    no live entries — the reduction is identically zero
      gather   (n_out, C) padded gather + row sum, C = max segment size
               (pow2)
      gather2  split-row: each segment padded to a multiple of _SPLIT_C,
               one flat gather + block sum, then a tiny second-level
               padded gather over per-block partial sums
    Of the last two, the one that gathers fewer rows in all (single level
    on a tie).
    """
    ids = np.asarray(ids, np.int64).reshape(-1)
    keep = np.ones(ids.shape, bool) if drop is None else ~np.asarray(drop).reshape(-1)
    kept_ids = ids[keep]
    kept_pos = np.nonzero(keep)[0]
    if kept_ids.size == 0:
        return {}, ("empty", n_out)
    counts = np.bincount(kept_ids, minlength=n_out)
    C = _next_pow2(int(counts.max()))
    nblk = -(-counts // _SPLIT_C)                  # ceil; 0 for empty segments
    blk_start = np.concatenate([[0], np.cumsum(nblk)])
    n_blocks = int(blk_start[-1])
    C2 = _next_pow2(int(nblk.max()))
    if n_out * C <= n_blocks * _SPLIT_C + n_out * C2:
        idx = _padded_rows(kept_ids, kept_pos, counts, n_out, n_in, C)
        return {"idx": jnp.asarray(idx.reshape(-1), jnp.int32)}, \
            ("gather", n_out, C)
    # split-row: block-align each segment to _SPLIT_C-wide sub-rows
    perm = np.full(n_blocks * _SPLIT_C, n_in, np.int64)
    order = np.argsort(kept_ids, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])
    for s in np.nonzero(counts)[0]:
        lo = blk_start[s] * _SPLIT_C
        perm[lo:lo + counts[s]] = kept_pos[order[starts[s]:starts[s] + counts[s]]]
    bidx = np.full((n_out, C2), n_blocks, np.int64)
    for s in np.nonzero(nblk)[0]:
        bidx[s, :nblk[s]] = np.arange(blk_start[s], blk_start[s + 1])
    return {"perm": jnp.asarray(perm, jnp.int32),
            "bidx": jnp.asarray(bidx.reshape(-1), jnp.int32)}, \
        ("gather2", n_out, n_blocks, C2)


def _reduce(strategy, arrs, vals):
    """Apply a ``_reduce_plan``: (n_in,) vals -> (n_out,) segment sums."""
    kind = strategy[0]
    if kind == "empty":
        return jnp.zeros((strategy[1],), vals.dtype)
    if kind == "gather":
        _, n_out, C = strategy
        rows = vals.at[arrs["idx"]].get(mode="fill", fill_value=0.0)
        return rows.reshape(n_out, C).sum(axis=1)
    _, n_out, n_blocks, C2 = strategy
    sub = vals.at[arrs["perm"]].get(mode="fill", fill_value=0.0)
    bsum = sub.reshape(n_blocks, _SPLIT_C).sum(axis=1)
    rows = bsum.at[arrs["bidx"]].get(mode="fill", fill_value=0.0)
    return rows.reshape(n_out, C2).sum(axis=1)


def _queues(plan: "_Plan", pp: dict, backlog):
    """Stage 6: ``(q_link, q_port)`` from the ``(F, MAXHOP)`` backlog.

    ``q_link[l]`` is the backlog queued at link ``l`` over every hop;
    ``q_port[l]`` the occupancy of ingress port ``l`` at the receiving
    switch, where hop ``h >= 1`` arrived via link ``path[:, h-1]`` (hop-0
    backlog is the host's own send queue).  Each plan reduces one
    contiguous hop column, never the flattened backlog: in a vmapped batch
    the compiler leaves that in HBM, where a gather costs several times as
    much per row as from VMEM.
    """
    q_link = jnp.zeros((plan.n_links + 1,), jnp.float32)
    q_port = q_link
    for h in range(MAXHOP):
        if plan.hop[h][0] != "empty":
            q_link = q_link + _reduce(plan.hop[h], pp["r_hop"][h],
                                      backlog[:, h])
        if h >= 1 and plan.ingress[h - 1][0] != "empty":
            q_port = q_port + _reduce(plan.ingress[h - 1],
                                      pp["r_ingress"][h - 1], backlog[:, h])
    return q_link, q_port


@dataclasses.dataclass(frozen=True)
class _Plan:
    """Hashable static description of one prepared scenario.

    Everything shape- or strategy-like lives here (part of the compile
    cache key); everything array-like lives in ``pp`` (traced, so two
    scenarios with equal plans share one compiled executable).
    """
    n_flows: int                  # real flows (pre-padding)
    n_flows_pad: int
    n_groups: int
    n_groups_pad: int
    n_links: int
    n_dev: int
    ring: int                     # feedback history slots (pow2)
    hop: tuple                    # per-hop demand reduction strategies
    ingress: tuple                # per-hop ingress-port strategies, h >= 1
    group: tuple
    pause: tuple
    qdev: tuple


def _prep(topo: Topology, sched: Schedule, cfg: EngineConfig,
          pad_flows: int | None = None, pad_groups: int | None = None):
    """Precompute static per-flow/per-link arrays + gather plans.

    ``pad_flows`` / ``pad_groups`` pad the flow and group axes with inert
    entries (done at t=0, zero bytes, null links) so that differently-sized
    schedules can share one compiled executable (shape-bucket padding; see
    ``repro.core.sweep``).  Padding never changes simulated physics: padded
    flows are excluded from every reduction plan and start out done.
    """
    Lk = topo.n_links
    F = sched.n_flows
    G = sched.n_groups
    Fp = max(pad_flows or F, F)
    Gp = max(pad_groups or G, G)

    path = np.where(sched.path < 0, Lk, sched.path).astype(np.int32)
    cap = np.concatenate([topo.cap, [1e18]]).astype(np.float32)
    lat = np.concatenate([topo.lat, [0.0]]).astype(np.float32)
    ecn_on = np.concatenate([topo.ecn_on, [False]])
    dst_dev = np.concatenate([topo.dst_dev, [topo.n_devices]]).astype(np.int32)
    # fabric-link class per link; the null link (Lk) never marks ECN and
    # never pauses, so its class is irrelevant — use 0
    link_class = np.concatenate([topo.link_class, [0]]).astype(np.int32)

    # ingress map: backlog at hop h arrived via link path[:, h-1] (h >= 1);
    # hop-0 backlog is the host's own send queue (never paused by PFC)
    ingress = np.full_like(path, Lk)
    ingress[:, 1:] = np.where(sched.path[:, 1:] >= 0, path[:, :-1], Lk)
    # a port can be paused only if its receiver is a PFC-capable switch
    dev_sw_ext = np.concatenate([topo.dev_is_switch, [False]])
    fabric_ext = np.concatenate([topo.fabric, [False]])
    can_pause = dev_sw_ext[dst_dev] & fabric_ext
    # pause-cycle (deadlock) wait-for graph support: only switch->switch
    # fabric links can participate in a PFC cycle (hosts do not forward)
    sw_sw = (topo.dev_is_switch[topo.src_dev]
             & topo.dev_is_switch[topo.dst_dev] & topo.fabric)

    # static fan-in: CONCURRENT flows sharing each flow's most-contended
    # link.  Deterministic schedules serialize phases via dep groups, so
    # only same-group flows contend — exactly the knowledge the paper says
    # an optimized CC should exploit (§IV-E).
    link_load = np.zeros(Lk + 1, np.float64)
    for g in range(max(G, 1)):
        in_g = (sched.group == g) & (sched.size > 0)
        if not in_g.any():
            continue
        load_g = np.zeros(Lk + 1, np.float64)
        for h in range(path.shape[1]):
            np.add.at(load_g, path[in_g, h], 1.0)
        link_load = np.maximum(link_load, load_g)
    link_load[Lk] = 1.0
    fanin = np.ones(F, np.float64)
    for h in range(path.shape[1]):
        valid = sched.path[:, h] >= 0
        fanin = np.maximum(fanin, np.where(valid, link_load[path[:, h]], 1.0))

    hopmask = (sched.path >= 0)
    base_rtt = 2.0 * (lat[path] * hopmask).sum(1)
    # serialization/propagation floor so zero-latency markers behave
    base_rtt = np.maximum(base_rtt, 1e-7).astype(np.float32)
    delay_steps = np.clip(np.round(base_rtt / cfg.dt), 1, cfg.hist - 1).astype(np.int32)
    first = path[:, 0]
    line = cap[first].astype(np.float32)
    bdp = (line * base_rtt).astype(np.float32)
    gsize = np.zeros(G, np.float32)
    np.add.at(gsize, sched.group, 1.0)

    # ---- shape-bucket padding (inert flows/groups) ------------------------
    def fpad(a, fill):
        if Fp == a.shape[0]:
            return a
        pad = np.full((Fp - a.shape[0],) + a.shape[1:], fill, a.dtype)
        return np.concatenate([a, pad])

    active = np.zeros(Fp, bool)
    active[:F] = True
    path = fpad(path, Lk)
    ingress = fpad(ingress, Lk)
    hopmask = fpad(hopmask, False)
    n_hops = fpad(sched.n_hops.astype(np.int32), 0)
    base_rtt = fpad(base_rtt, 1e-7)
    delay_steps = fpad(delay_steps, 1)
    line = fpad(line, 1.0)
    bdp = fpad(bdp, 1.0)
    fanin = fpad(fanin.astype(np.float32), 1.0)
    size = fpad(sched.size.astype(np.float32), 0.0)
    group = fpad(sched.group.astype(np.int32), 0)
    dep = fpad(sched.dep.astype(np.int32), -1)
    sdelay = fpad(sched.delay.astype(np.float32), 0.0)
    gsize = np.concatenate([gsize, np.zeros(Gp - G, np.float32)])

    # ---- reduction plans ---------------------------------------------------
    invalid = ~hopmask                     # null-link slots contribute zero
    hop_arrs, hop_strats = [], []
    for h in range(MAXHOP):
        a, s = _reduce_plan(path[:, h], Fp, Lk + 1, drop=invalid[:, h])
        hop_arrs.append(a)
        hop_strats.append(s)
    # stage 6 reduces one contiguous hop column at a time: q_link sums the
    # hop plans above, q_port these ingress plans (hop 0 has no ingress)
    ing_arrs, ing_strats = [], []
    for h in range(1, MAXHOP):
        a, s = _reduce_plan(ingress[:, h], Fp, Lk + 1,
                            drop=ingress[:, h] == Lk)
        ing_arrs.append(a)
        ing_strats.append(s)
    gr_a, gr_s = _reduce_plan(group, Fp, Gp, drop=~active)
    pa_a, pa_s = _reduce_plan(dst_dev[:Lk], Lk, topo.n_devices)
    qd_a, qd_s = _reduce_plan(topo.src_dev, Lk, topo.n_devices)

    ring = _next_pow2(int(delay_steps.max()) + 1)

    plan = _Plan(
        n_flows=F, n_flows_pad=Fp, n_groups=G, n_groups_pad=Gp,
        n_links=Lk, n_dev=topo.n_devices, ring=ring,
        hop=tuple(hop_strats), ingress=tuple(ing_strats),
        group=gr_s, pause=pa_s, qdev=qd_s,
    )
    pp = dict(
        path=jnp.asarray(path), cap=jnp.asarray(cap),
        dst_dev=jnp.asarray(dst_dev), can_pause=jnp.asarray(can_pause),
        hopmask=jnp.asarray(hopmask),
        caps_path=jnp.asarray(cap[path]),
        ecn_mask=jnp.asarray((ecn_on[path] & hopmask).astype(np.float32)),
        link_class=jnp.asarray(link_class),
        src_dev=jnp.asarray(topo.src_dev.astype(np.int32)),
        sw_sw=jnp.asarray(sw_sw),
        fabric_link=jnp.asarray(fabric_ext.astype(np.float32)),
        fabric_path=jnp.asarray((fabric_ext[path] & hopmask)
                                .astype(np.float32)),
        cls_path=jnp.asarray(link_class[path]),
        n_hops=jnp.asarray(n_hops),
        base_rtt=jnp.asarray(base_rtt), delay_steps=jnp.asarray(delay_steps),
        line=jnp.asarray(line), bdp=jnp.asarray(bdp),
        fanin=jnp.asarray(fanin),
        size=jnp.asarray(size),
        group=jnp.asarray(group), dep=jnp.asarray(dep),
        sdelay=jnp.asarray(sdelay),
        gsize=jnp.asarray(gsize),
        active=jnp.asarray(active),
        dev_buf=jnp.asarray(topo.dev_buf.astype(np.float32)),
        r_hop=tuple(hop_arrs), r_ingress=tuple(ing_arrs),
        r_group=gr_a, r_pause=pa_a, r_qdev=qd_a,
    )
    return pp, plan


def _flow_ctx(pp: dict, F: int) -> FlowCtx:
    """The typed per-flow context every policy's ``init`` receives — the
    whole engine->init contract, no signature introspection."""
    return FlowCtx(line=pp["line"], bdp=pp["bdp"], fanin=pp["fanin"],
                   n_flows=F)


def _wire_of(policy: Policy, cc_params: dict | None):
    """Wire factor: static per policy, traced via the ``_wire`` param for
    stacked policies (members differ — HPCC INT carries +4.8%)."""
    if cc_params is not None and "_wire" in cc_params:
        return jnp.asarray(cc_params["_wire"], jnp.float32)
    return jnp.float32(policy.wire_factor)


def _n_qrows(cfg: EngineConfig) -> int:
    total = cfg.max_steps * (cfg.max_extends + 1)
    return -(-total // cfg.queue_stride) if cfg.queue_stride > 0 else 0


def _init_carry(pp, plan: _Plan, policy: Policy, cfg: EngineConfig,
                cc_params: dict | None = None, faulty: bool = False):
    Fp, Lk, D = plan.n_flows_pad, plan.n_links, plan.n_dev
    carry = dict(
        backlog=jnp.zeros((Fp, MAXHOP), jnp.float32),
        remaining=pp["size"] * _wire_of(policy, cc_params),
        injected=jnp.zeros(Fp, jnp.float32),
        delivered=jnp.zeros(Fp, jnp.float32),
        done=~pp["active"],           # padded flows are born finished
        t_finish=jnp.full(Fp, jnp.inf, jnp.float32),
        g_count=jnp.zeros(plan.n_groups_pad, jnp.float32),
        # empty groups (possible after topology mapping) complete at t=0
        g_time=jnp.where(pp["gsize"] < 0.5, 0.0, jnp.inf).astype(jnp.float32),
        paused=jnp.zeros(Lk + 1, bool),
        pause_count=jnp.zeros(D, jnp.float32),
        hist_q=jnp.zeros((plan.ring, Lk + 1), jnp.float32),
        hist_tx=jnp.zeros((plan.ring, Lk + 1), jnp.float32),
        # copy: some policies' init returns state aliasing pp arrays (e.g.
        # DCTCP keeps bdp); the carry is donated, so aliases would delete
        # buffers that pp still needs on the next run
        cc=jax.tree_util.tree_map(lambda x: jnp.asarray(x).copy(),
                                  policy.init(_flow_ctx(pp, Fp))),
        soft=jnp.zeros((), jnp.float32),
        # run health (observers; the step no-op gate also keys on diverged)
        diverged=jnp.zeros((), bool),
        deadlock_step=jnp.full((), -1, jnp.int32),
        storm_run=jnp.zeros((), jnp.int32),
        storm_step=jnp.full((), -1, jnp.int32),
    )
    if faulty:
        carry["lost"] = jnp.zeros(Fp, jnp.float32)      # dropped in-network
        carry["dup"] = jnp.zeros(Fp, jnp.float32)       # GBN resend overhead
        carry["loss_sig"] = jnp.zeros(Fp, jnp.float32)  # EWMA loss fraction
    if cfg.queue_stride > 0:
        carry["qbuf"] = jnp.zeros((_n_qrows(cfg), D), jnp.float32)
    return carry


def _make_step(policy: Policy, cfg: EngineConfig, plan: _Plan,
               faulty: bool = False, batched: bool = False):
    dt = cfg.dt
    Lk = plan.n_links
    stride = cfg.queue_stride
    n_qrows = _n_qrows(cfg)
    D = plan.n_dev
    # pause-cycle reachability via repeated squaring: after k rounds S
    # covers paths of length up to 2^k, so ceil(log2(D)) rounds suffice
    dl_rounds = max(1, (max(D, 2) - 1).bit_length())

    # backend dispatch: route stages 1-2 through the fused Pallas
    # engine-step kernel (see ``effective_step_impl``).  Every other stage,
    # the segment reductions included, is the same XLA code on both
    # paths; the jnp branch below is the historical step, emitted
    # unchanged — goldens stay bitwise.
    use_kernel = effective_step_impl(policy, cfg) == "pallas"
    if use_kernel:
        from repro.kernels.engine_step import ops as es_ops

    def step(carry, it, pp, cc_params, flt, tab):
        with jax.named_scope("engine_step"):
            return _step(carry, it, pp, cc_params, flt, tab)

    def _step(carry, it, pp, cc_params, flt, tab):
        # each stage runs under a named scope of its own, which the
        # benchmark reads from the executable's op metadata
        # (bench/stages.py ``op_stages``): the names are its yardstick
        def _pause_cycle(paused):
            """Any cycle in the switch->switch PFC wait-for graph?  Link l
            paused means src_dev(l) waits on dst_dev(l) to resume."""
            e = (paused[:Lk] & pp["sw_sw"]).astype(jnp.float32)
            adj = jnp.zeros((D, D), jnp.float32)
            adj = adj.at[pp["src_dev"], pp["dst_dev"][:Lk]].add(e)
            S = jnp.minimum(adj, 1.0)
            for _ in range(dl_rounds):
                S = jnp.minimum(S + S @ S, 1.0)
            return jnp.any(jnp.diagonal(S) > 0.5)

        wire = _wire_of(policy, cc_params)
        path, hopmask = pp["path"], pp["hopmask"]
        t = it.astype(jnp.float32) * dt
        # the end of this step's transfer window, rounded once on every
        # backend: dependents compare their start time against it exactly
        t_end = (it + 1).astype(jnp.float32) * dt
        kmin_h, kmax_h, pmax_h = tab["kmin_h"], tab["kmax_h"], tab["pmax_h"]
        # ---- 1. delayed signals ------------------------------------------
        with jax.named_scope("s1_signals"):
            idx = jnp.maximum(it - pp["delay_steps"], 0) % plan.ring
            flat = idx[:, None] * (Lk + 1) + path            # (F, MAXHOP)
            q_d = carry["hist_q"].reshape(-1)[flat]
            tx_d = carry["hist_tx"].reshape(-1)[flat]
            caps = pp["caps_path"]
            if not use_kernel:
                rtt = pp["base_rtt"] + (q_d / caps * hopmask).sum(1)
                mark = jnp.clip(
                    (q_d - kmin_h) / jnp.maximum(kmax_h - kmin_h, 1.0),
                    0.0, 1.0) * pmax_h
                if faulty:
                    # ECN misconfiguration: scale marking probability
                    # (0 = broken)
                    mark = mark * tab["ecn_scale_h"]
                mark = mark * pp["ecn_mask"]
                ecn = 1.0 - jnp.prod(1.0 - mark, axis=1)
                util_l = tx_d / caps + q_d / (caps * cfg.t_base_util)
                util = jnp.max(jnp.where(hopmask, util_l, 0.0), axis=1)
                if faulty:
                    sig = Signals(ecn=ecn, rtt=rtt, util=util, t=t,
                                  dt=jnp.float32(dt), line=pp["line"],
                                  base_rtt=pp["base_rtt"],
                                  loss=carry["loss_sig"])
                else:
                    sig = Signals(ecn=ecn, rtt=rtt, util=util, t=t,
                                  dt=jnp.float32(dt), line=pp["line"],
                                  base_rtt=pp["base_rtt"])
        if use_kernel:
            # ---- 1+2 fused: signals + CC update in one Pallas pass ------
            with jax.named_scope("s12_fused"):
                # ECN misconfiguration folds into the marking ceiling (same
                # product as the jnp path's post-clip scale)
                pmax_eff = pmax_h
                if faulty:
                    pmax_eff = pmax_eff * tab["ecn_scale_h"]
                loss = (carry["loss_sig"] if faulty
                        else jnp.zeros_like(pp["line"]))
                cc, rate, win = es_ops.fused_step(
                    policy, q_d=q_d, tx_d=tx_d, caps=caps,
                    ecn_mask=pp["ecn_mask"], hopmask=hopmask,
                    kmin_h=kmin_h, kmax_h=kmax_h, pmax_h=pmax_eff,
                    base_rtt=pp["base_rtt"], line=pp["line"], loss=loss,
                    state=carry["cc"], params=cc_params, t=t, dt=dt,
                    t_base_util=cfg.t_base_util)
        else:
            # ---- 2. CC update ---------------------------------------------
            with jax.named_scope("s2_cc"):
                cc, rate, win = policy.update(cc_params, carry["cc"], sig)

        # ---- 3. injection --------------------------------------------------
        with jax.named_scope("s3_inject"):
            dep = pp["dep"]
            g_done = carry["g_count"] >= pp["gsize"] - 0.5
            dep_ok = jnp.where(dep >= 0, g_done[jnp.maximum(dep, 0)], True)
            dep_t = jnp.where(dep >= 0, carry["g_time"][jnp.maximum(dep, 0)],
                              0.0)
            started = dep_ok & (t >= dep_t + pp["sdelay"])
            inflight = carry["injected"] - carry["delivered"]
            if faulty:
                # lost bytes are not in flight (the NIC saw the NACK/timeout)
                inflight = inflight - carry["lost"]
            room = jnp.maximum(win - inflight, 0.0)
            inj = jnp.minimum(jnp.minimum(rate * dt, room), carry["remaining"])
            inj = jnp.where(started & (pp["n_hops"] > 0), jnp.maximum(inj, 0.0),
                            0.0)
            backlog = carry["backlog"].at[:, 0].add(inj)
            remaining = carry["remaining"] - inj
            injected = carry["injected"] + inj

        # ---- 4. PFC gates (per-port) ---------------------------------------
        with jax.named_scope("s4_gates"):
            gate = ~carry["paused"]
            rem_cap = pp["cap"] * dt * gate
            if faulty:
                # time-scheduled capacity faults on fabric links: degradation
                # windows and periodic link flaps (down for flap_down out of
                # every flap_period seconds)
                deg = tab["degrade_l"]
                in_deg = (t >= flt.degrade_t0) & (t < flt.degrade_t1)
                capmul = jnp.where(in_deg & (pp["fabric_link"] > 0), deg, 1.0)
                period = jnp.asarray(flt.flap_period, jnp.float32)
                phase = jnp.mod(t - flt.flap_t0, jnp.maximum(period, 1e-9))
                flap_down = ((period > 0) & (t >= flt.flap_t0)
                             & (phase < flt.flap_down))
                capmul = jnp.where(flap_down & (pp["fabric_link"] > 0),
                                   0.0, capmul)
                rem_cap = rem_cap * capmul
            rem_cap = rem_cap.at[Lk].set(1e18)

        # ---- 5. hop-ordered forwarding -------------------------------------
        with jax.named_scope("s5_forward"):
            delivered = carry["delivered"]
            tx_bytes = jnp.zeros(Lk + 1, jnp.float32)
            if faulty:
                # per-hop drop probability: fabric links only (NVLink
                # lossless)
                loss_p = tab["loss_h"] * pp["fabric_path"]
                lost_step = jnp.zeros_like(carry["lost"])
            for h in range(MAXHOP):
                if plan.hop[h][0] == "empty":   # no flow ever uses this slot
                    continue
                dem = _reduce(plan.hop[h], pp["r_hop"][h], backlog[:, h])
                frac = jnp.where(
                    dem > 0, jnp.minimum(1.0, rem_cap / jnp.maximum(dem, 1e-9)),
                    0.0)
                moved = backlog[:, h] * frac[path[:, h]]
                backlog = backlog.at[:, h].add(-moved)
                if faulty:
                    # bytes dropped on this hop consumed upstream capacity
                    # but leave the network; they re-enter `remaining` below
                    drop = moved * loss_p[:, h]
                    lost_step = lost_step + drop
                    moved = moved - drop
                last = pp["n_hops"] == (h + 1)
                delivered = delivered + jnp.where(last, moved, 0.0)
                if h + 1 < MAXHOP:
                    backlog = backlog.at[:, h + 1].add(
                        jnp.where(last, 0.0, moved))
                movedsum = frac * dem          # == per-link sum of `moved`
                rem_cap = jnp.maximum(rem_cap - movedsum, 0.0)
                tx_bytes = tx_bytes + movedsum

        if faulty:
            # ---- 5b. loss recovery (IRN vs go-back-N) ----------------------
            with jax.named_scope("s5b_loss"):
                lost = carry["lost"] + lost_step
                live = jnp.maximum(injected - delivered - lost, 0.0)
                gbn = jnp.asarray(flt.gbn, jnp.float32)
                mtu = jnp.maximum(jnp.asarray(flt.mtu, jnp.float32), 1.0)
                # IRN (selective retransmit): only the lost bytes are resent.
                # go-back-N: each lost packet (lost_step/mtu of them) resends
                # on average half the NIC's outstanding window too.  The
                # window is the in-network bytes capped at the path BDP:
                # fluid "live" includes queued backlog, which a real NIC's
                # send window never covers — uncapped, incast GBN resends
                # faster than the bottleneck drains and can never terminate
                w_out = jnp.minimum(live, pp["line"] * pp["base_rtt"])
                dup_step = gbn * jnp.minimum(lost_step * w_out / (2.0 * mtu),
                                             live)
                remaining = remaining + lost_step + dup_step
                dup = carry["dup"] + dup_step
                # per-flow EWMA loss fraction (the `loss` CC signal, read
                # next step so it is RTT-delayed like the other signals)
                a = jnp.minimum(dt / pp["base_rtt"], 1.0)
                traf = lost_step + (delivered - carry["delivered"])
                frac_l = lost_step / jnp.maximum(traf, 1.0)
                loss_sig = jnp.where(
                    traf > 0, (1.0 - a) * carry["loss_sig"] + a * frac_l,
                    carry["loss_sig"])

        # ---- 6. queues ------------------------------------------------------
        with jax.named_scope("s6_queues"):
            q_link, q_port = _queues(plan, pp, backlog)
            xoff_l, xon_l = tab["xoff_l"], tab["xon_l"]
            can = pp["can_pause"]
            if faulty:
                # PFC misconfiguration / lossy-RoCE: pfc_on=0 disables pausing
                can = can & (tab["pfc_on_l"] > 0.5)

        # ---- 7. PFC per-port hysteresis -------------------------------------
        with jax.named_scope("s7_pfc"):
            over = (q_port > xoff_l) & can
            under = q_port < xon_l
            paused = jnp.where(over, True,
                               jnp.where(under, False, carry["paused"]))
            # PAUSE frames: one on the off-transition + periodic refreshes
            # while the port stays paused (how NS3 counts them)
            frames = ((paused & ~carry["paused"])[:Lk].astype(jnp.float32)
                      + paused[:Lk].astype(jnp.float32)
                      * (dt / cfg.pause_resend))
            pause_count = carry["pause_count"] + _reduce(
                plan.pause, pp["r_pause"], frames)

        # ---- 8. completion --------------------------------------------------
        with jax.named_scope("s8_completion"):
            wire_size = pp["size"] * wire
            if faulty:
                # duplicates arrive at the receiver and are discarded there:
                # goodput = delivered - dup, so completion needs dup extra
                # bytes
                data_done = delivered >= wire_size + dup - cfg.eps_done
            else:
                data_done = delivered >= wire_size - cfg.eps_done
            marker_done = (pp["n_hops"] == 0) & started
            newly = ~carry["done"] & (jnp.where(pp["n_hops"] > 0, data_done,
                                                marker_done))
            done = carry["done"] | newly
            # completion happens at the END of this step's transfer window
            t_finish = jnp.where(newly, t_end, carry["t_finish"])
            g_count = carry["g_count"] + _reduce(plan.group, pp["r_group"],
                                                 newly.astype(jnp.float32))
            g_done_new = ((g_count >= pp["gsize"] - 0.5)
                          & ~(carry["g_count"] >= pp["gsize"] - 0.5))
            g_time = jnp.where(g_done_new, t_end, carry["g_time"])

        # ---- 9. history + soft cost ----------------------------------------
        with jax.named_scope("s9_history"):
            hist_q = lax.dynamic_update_slice_in_dim(
                carry["hist_q"], q_link[None], it % plan.ring, axis=0)
            hist_tx = lax.dynamic_update_slice_in_dim(
                carry["hist_tx"], (tx_bytes / dt)[None], it % plan.ring,
                axis=0)
            if faulty:
                goodput = jnp.clip(delivered - dup, 0.0, wire_size)
            else:
                goodput = jnp.minimum(delivered, wire_size)
            undeliv = jnp.sum(wire_size - goodput)
            soft = carry["soft"] + dt * undeliv / jnp.maximum(
                jnp.sum(wire_size), 1.0)

        # ---- 10. run health (observers; never touch the physics above) ------
        with jax.named_scope("s10_health"):
            # pause storm: >= storm_frac of pausable ports paused for
            # storm_steps consecutive steps
            n_pausable = jnp.maximum(
                jnp.sum(pp["can_pause"][:Lk].astype(jnp.float32)), 1.0)
            pfrac = jnp.sum(paused[:Lk].astype(jnp.float32)) / n_pausable
            storm_run = jnp.where(pfrac >= cfg.storm_frac,
                                  carry["storm_run"] + 1, 0)
            storm_step = jnp.where((carry["storm_step"] < 0)
                                   & (storm_run >= cfg.storm_steps),
                                   it, carry["storm_step"])
            # pause-cycle deadlock: checked every deadlock_check_every steps
            # while switch->switch pauses exist and no cycle was seen yet
            dl_candidates = jnp.any(paused[:Lk] & pp["sw_sw"])
            do_check = ((it % cfg.deadlock_check_every == 0) & dl_candidates
                        & (carry["deadlock_step"] < 0))
            if batched:
                # per-lane predicate: select, see _make_run's lane_axis
                cycle = do_check & _pause_cycle(paused)
            else:
                cycle = lax.cond(do_check, _pause_cycle,
                                 lambda _: jnp.zeros((), bool), paused)
            deadlock_step = jnp.where(cycle & (carry["deadlock_step"] < 0),
                                      it, carry["deadlock_step"])
            # non-finite guard: freeze the lane at the first bad state
            # instead of poisoning a whole vmapped batch (the step no-op
            # gate and the early-exit loop both key on `diverged`)
            probe = (jnp.sum(backlog) + jnp.sum(remaining) + jnp.sum(rate)
                     + jnp.sum(q_link) + soft)
            diverged = carry["diverged"] | ~jnp.isfinite(probe)

        new_carry = dict(
            backlog=backlog, remaining=remaining, injected=injected,
            delivered=delivered, done=done, t_finish=t_finish,
            g_count=g_count, g_time=g_time, paused=paused,
            pause_count=pause_count, hist_q=hist_q, hist_tx=hist_tx,
            cc=cc, soft=soft,
            diverged=diverged, deadlock_step=deadlock_step,
            storm_run=storm_run, storm_step=storm_step)
        if faulty:
            new_carry["lost"] = lost
            new_carry["dup"] = dup
            new_carry["loss_sig"] = loss_sig
        if stride > 0:
            # strided timeline recording; rows for skipped steps are dropped
            with jax.named_scope("s9_history"):
                q_dev = _reduce(plan.qdev, pp["r_qdev"], q_link[:Lk])
                row = jnp.where(it % stride == 0, it // stride, n_qrows)
                new_carry["qbuf"] = carry["qbuf"].at[row].set(q_dev,
                                                              mode="drop")
        return new_carry

    return step


LANE_GATE = "engine_loop/lane_gate"


def _make_run(policy: Policy, cfg: EngineConfig, plan: _Plan,
              early_exit: bool, faulty: bool = False, remat: bool = False,
              lane_axis: str | None = None):
    """Build the full (jittable) stepping loop.

    Each step is gated on ``done.all() | diverged | (it >= total)`` so
    finished (or frozen non-finite) lanes are no-ops; with ``early_exit``
    the chunked while_loop additionally stops integrating at the first
    chunk boundary where every flow is done (or the lane diverged).  Both
    variants therefore produce bitwise-identical carries.

    ``remat`` (fixed-length path only) rematerializes the scan in
    ``cfg.chunk_steps``-sized segments: each segment is wrapped in
    ``jax.checkpoint``, so reverse-mode AD stores one carry per segment
    plus one segment's activations instead of every step's — O(sqrt)
    memory for long-horizon gradients (the ``repro.learn`` trainer's
    path).  The forward computation is the same gated step sequence, so
    forward values match the monolithic scan exactly.

    ``lane_axis`` names the vmap axis when the run is vmapped over sweep
    lanes.  A predicate that differs per lane turns ``lax.cond`` into a
    select whose operands JAX broadcasts per lane, the scenario's index
    arrays included, and XLA:TPU then gathers element by element.  So
    batched lanes select the gated step explicitly, and the early-exit
    loop runs while any lane is live (``lax.pmax`` over the axis): its
    step counter, and the indices derived from it, stay shared.  Carries
    are the same as unbatched.
    """
    if remat and early_exit:
        raise ValueError("remat applies to the fixed-length scan only "
                         "(early_exit=False): lax.while_loop is not "
                         "reverse-mode differentiable anyway")
    step = _make_step(policy, cfg, plan, faulty, batched=lane_axis is not None)
    total = cfg.max_steps * (cfg.max_extends + 1)
    chunk = max(1, min(cfg.chunk_steps, total))

    def run(carry, pp, cc_params, fab, flt):
        tab = _fabric_tables(pp, fab, flt, faulty)

        def body(c, it):
            # the gate runs under a named scope the benchmark reads, as the
            # step's stages do (bench/stages.py ``op_stages``)
            with jax.named_scope(LANE_GATE):
                skip = jnp.all(c["done"]) | c["diverged"] | (it >= total)
            if lane_axis is not None:
                new = step(c, it, pp, cc_params, flt, tab)
                with jax.named_scope(LANE_GATE):
                    return jax.tree.map(
                        lambda old, n: jnp.where(skip, old, n), c, new), None
            with jax.named_scope(LANE_GATE):
                c2 = lax.cond(skip, lambda c: c,
                              lambda c: step(c, it, pp, cc_params, flt, tab),
                              c)
            return c2, None

        if not early_exit:
            if remat:
                # ceil(total/chunk) checkpointed segments; trailing
                # it >= total steps are gated no-ops, so the padded tail
                # is inert and forward values match the monolithic scan
                n_seg = -(-total // chunk)

                @jax.checkpoint
                def seg(c, it0):
                    c, _ = lax.scan(
                        body, c, it0 + jnp.arange(chunk, dtype=jnp.int32))
                    return c, None

                carry2, _ = lax.scan(
                    seg, carry,
                    jnp.arange(n_seg, dtype=jnp.int32) * chunk)
                return carry2, jnp.int32(total)
            carry2, _ = lax.scan(body, carry, jnp.arange(total, dtype=jnp.int32))
            return carry2, jnp.int32(total)

        def w_body(state):
            c, it0 = state
            c, _ = lax.scan(body, c, it0 + jnp.arange(chunk, dtype=jnp.int32))
            return c, it0 + chunk

        def w_cond(state):
            c, it0 = state
            live = (~(jnp.all(c["done"]) | c["diverged"])) & (it0 < total)
            if lane_axis is not None:
                live = lax.pmax(live.astype(jnp.int32), lane_axis) > 0
            return live

        carry2, it_end = lax.while_loop(w_cond, w_body, (carry, jnp.int32(0)))
        return carry2, jnp.minimum(it_end, total)

    return run


# ---------------------------------------------------------------------------
# compile cache: (policy identity, cfg, plan) -> jitted run
# ---------------------------------------------------------------------------

_RUN_CACHE: dict = {}


def _policy_cache_key(policy: Policy):
    """Hashable identity of a policy's *logic* (params ride along traced,
    but ``init`` may bake closure defaults into the carry, so include the
    default params in the key)."""
    return (policy.name, float(policy.wire_factor),
            getattr(policy.init, "__code__", policy.init),
            getattr(policy.update, "__code__", policy.update),
            tuple(sorted((k, float(v)) for k, v in policy.params.items())),
            # stacked policies share closure code objects; their member
            # identity tokens live in key_extra
            policy.key_extra)


def compiled_run(policy: Policy, cfg: EngineConfig, plan: _Plan,
                 early_exit: bool = True, faulty: bool = False):
    """Jitted stepping loop, cached across scenarios with equal plans.

    The carry (arg 0) is donated: every run must pass a freshly built one.
    Fabric scalars on ``cfg`` are normalized out of the key (they arrive
    traced via FabricParams), so a fabric sweep never recompiles.
    ``faulty`` keys the fault-injection compile path: the default (inert)
    FaultSpec runs the historical fault-free step, so lossless results are
    bitwise-identical with the fault layer present.
    """
    key = (_policy_cache_key(policy), _cfg_static(cfg), plan, early_exit,
           faulty)
    if key not in _RUN_CACHE:
        run = _make_run(policy, cfg, plan, early_exit, faulty)
        _RUN_CACHE[key] = jax.jit(run, donate_argnums=(0,))
    return _RUN_CACHE[key]


class Simulator:
    """Compiled fluid simulation of one (topology, schedule, policy).

    ``pad_flows`` / ``pad_groups`` (see ``_prep``) let ``SweepRunner``
    bucket same-shaped scenarios onto one compiled executable.
    """

    def __init__(self, topo: Topology, sched: Schedule, policy: Policy,
                 cfg: EngineConfig = EngineConfig(),
                 pad_flows: int | None = None, pad_groups: int | None = None,
                 fabric_params: FabricParams | None = None,
                 fault_spec: FaultSpec | None = None):
        self.topo, self.sched, self.policy, self.cfg = topo, sched, policy, cfg
        self.fabric = _as_fabric(fabric_params, cfg)
        self.fault = _as_fault(fault_spec)
        self.pp, self.plan = _prep(topo, sched, cfg, pad_flows, pad_groups)
        self._soft_jit = None

    def _call(self, cc_params, early_exit, fabric_params, fault_spec):
        """The jitted stepping loop ``run`` dispatches, and its arguments."""
        params = cc_params if cc_params is not None else self.policy.params
        fab = fabric_params if fabric_params is not None else self.fabric
        flt = fault_spec if fault_spec is not None else self.fault
        faulty = is_faulty(flt)
        fn = compiled_run(self.policy, self.cfg, self.plan, early_exit,
                          faulty)
        carry = _init_carry(self.pp, self.plan, self.policy, self.cfg,
                            params, faulty)
        return fn, (carry, self.pp, params, fab, flt)

    def compile(self, cc_params: dict | None = None, early_exit: bool = True,
                fabric_params: FabricParams | None = None,
                fault_spec: FaultSpec | None = None) -> jax.stages.Compiled:
        """Ahead-of-time compile the executable ``run`` dispatches for the
        same arguments, so compile time is measured apart from the run and
        the executable can be inspected (``as_text()``); the ``run`` that
        follows finds it compiled."""
        fn, args = self._call(cc_params, early_exit, fabric_params,
                              fault_spec)
        return fn.lower(*args).compile()

    def run(self, cc_params: dict | None = None, early_exit: bool = True,
            fabric_params: FabricParams | None = None,
            fault_spec: FaultSpec | None = None) -> Results:
        fn, args = self._call(cc_params, early_exit, fabric_params,
                              fault_spec)
        carry, steps = fn(*args)
        return self._results(carry, int(steps))

    def _results(self, carry, steps_run: int) -> Results:
        F, G = self.plan.n_flows, self.plan.n_groups
        t_fin = np.asarray(carry["t_finish"])[:F]
        done = np.asarray(carry["done"])[:F]
        if self.cfg.queue_stride > 0:
            dev_queue = np.asarray(carry["qbuf"])
            rows = -(-steps_run // self.cfg.queue_stride)
            dev_queue = dev_queue[:rows]
        else:
            dev_queue = np.zeros((0, self.plan.n_dev), np.float32)
        finished = bool(done.all())
        diverged = bool(carry["diverged"])
        deadlock_step = int(carry["deadlock_step"])
        extend_exhausted = not finished and not diverged
        if extend_exhausted:
            total = self.cfg.max_steps * (self.cfg.max_extends + 1)
            warnings.warn(
                f"step budget exhausted: {int((~done).sum())}/{F} flows "
                f"unfinished after {total} steps (max_steps="
                f"{self.cfg.max_steps}, max_extends={self.cfg.max_extends}) "
                f"for policy {self.policy.name!r} on {self.topo.name!r}; "
                "completion_time is a lower bound — raise max_steps/"
                "max_extends or treat this cell as invalid",
                RuntimeWarning, stacklevel=3)
        return Results(
            finished=finished,
            completion_time=float(np.max(np.where(np.isfinite(t_fin), t_fin, 0.0))),
            t_finish=t_fin,
            group_time=np.asarray(carry["g_time"])[:G],
            group_names=self.sched.group_names,
            pause_count=np.asarray(carry["pause_count"]),
            dev_queue=dev_queue,
            dt=self.cfg.dt,
            delivered=np.asarray(carry["delivered"])[:F],
            soft_cost=float(carry["soft"]),
            meta={"policy": self.policy.name, "topo": self.topo.name,
                  "n_flows": self.sched.n_flows, "steps_run": steps_run,
                  "queue_stride": self.cfg.queue_stride},
            deadlocked=deadlock_step >= 0,
            deadlock_step=deadlock_step,
            storm_step=int(carry["storm_step"]),
            diverged=diverged,
            extend_exhausted=extend_exhausted,
            lost=(np.asarray(carry["lost"])[:F] if "lost" in carry
                  else None),
        )

    # -- differentiable objective -------------------------------------------
    def soft_cost_fn(self, remat: bool = False):
        """Pure ``(cc_params, fabric_params=default) -> soft_cost`` suitable
        for grad/vmap/jit — differentiable through the fabric knobs too.

        Uses the monolithic (fixed-length) scan: ``lax.while_loop`` is not
        reverse-mode differentiable.  The integrand freezes once every flow
        completes (steps become no-ops), so the integral is insensitive to
        the step budget's tail.

        ``remat=True`` selects the rematerialized scan (``jax.checkpoint``
        over ``cfg.chunk_steps``-sized segments): same forward value,
        O(total/chunk + chunk) instead of O(total) carries live during the
        backward pass — the memory-feasible path for long-horizon training
        (``repro.learn``).
        """
        faulty = is_faulty(self.fault)
        run = _make_run(self.policy, self.cfg, self.plan, early_exit=False,
                        faulty=faulty, remat=remat)
        pp, plan, policy, cfg = self.pp, self.plan, self.policy, self.cfg
        default_fab, default_flt = self.fabric, self.fault

        def cost(cc_params, fabric_params=default_fab):
            carry = _init_carry(pp, plan, policy, cfg, cc_params, faulty)
            carry, _ = run(carry, pp, cc_params, fabric_params, default_flt)
            return carry["soft"]

        return cost

    def soft_cost(self, cc_params,
                  fabric_params: FabricParams | None = None) -> jnp.ndarray:
        """Differentiable objective: integral of undelivered fraction.

        Jitted and cached per Simulator; compose ``soft_cost_fn`` yourself
        for grad/vmap pipelines (as ``core/autotune.py`` does)."""
        if self._soft_jit is None:
            self._soft_jit = jax.jit(self.soft_cost_fn())
        return self._soft_jit(cc_params,
                              fabric_params if fabric_params is not None
                              else self.fabric)


def simulate(topo, sched, policy, cfg: EngineConfig = EngineConfig(),
             fabric_params: FabricParams | None = None,
             fault_spec: FaultSpec | None = None) -> Results:
    return Simulator(topo, sched, policy, cfg, fabric_params=fabric_params,
                     fault_spec=fault_spec).run()
