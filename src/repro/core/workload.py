"""Workload layer: DLRM training iteration -> flow schedule with
compute/comm dependencies (ASTRA-Sim workload layer analog).

The paper's DLRM iteration (Fig 10, §IV-D):
  fwd:  bottom-MLP compute  ||  embedding lookup -> All-To-All (fwd half)
        -> interaction + top-MLP compute
  bwd:  top-MLP backprop -> All-To-All (bwd half) || bottom-MLP backprop
        -> per-chunk All-Reduce of MLP grads (2D or 1D), overlapping bwd
  Totals per iteration: 109.5 MB All-Reduce + 8 MB All-To-All.

Compute segment durations come from a V100 profile table (the paper uses
NVIDIA V100 profiling); they are constants here, and the *exposed*
communication =  iteration_time - total_compute  is the reported metric.
"""
from __future__ import annotations

import dataclasses
import zlib

from repro.core.collectives import Schedule, ScheduleBuilder, _direct_phase
from repro.core.engine import EngineConfig, simulate
from repro.core.scenario import ScenarioSpec
from repro.core.topology import Topology


# V100-profile compute constants (s) for the paper's DLRM (Table II) with
# per-GPU batch ~256.  Sources: MLPerf DLRM v0.7 V100 per-layer timings,
# scaled to the paper's layer sizes; recorded here as the workload model.
@dataclasses.dataclass(frozen=True)
class DLRMComputeProfile:
    bot_mlp_fwd: float = 350e-6
    emb_lookup: float = 80e-6
    interact_top_fwd: float = 800e-6
    top_bwd: float = 1400e-6
    bot_bwd: float = 700e-6
    opt_update: float = 250e-6

    @property
    def total(self) -> float:
        return (self.bot_mlp_fwd + self.emb_lookup + self.interact_top_fwd
                + self.top_bwd + self.bot_bwd + self.opt_update)


@dataclasses.dataclass(frozen=True)
class DLRMCommSpec:
    allreduce_bytes: float = 109.5 * 1024 * 1024
    alltoall_fwd_bytes: float = 4 * 1024 * 1024
    alltoall_bwd_bytes: float = 4 * 1024 * 1024
    n_chunks: int = 4
    allreduce_algo: str = "2d"    # "1d" | "2d"


def build_dlrm_iteration(topo: Topology, gpus: list,
                         prof: DLRMComputeProfile = DLRMComputeProfile(),
                         comm: DLRMCommSpec = DLRMCommSpec()) -> Schedule:
    """One DLRM training iteration as a dependency-tagged flow schedule."""
    b = ScheduleBuilder(topo)

    # ---- forward ----------------------------------------------------------
    # embedding lookup finishes at emb_lookup; fwd A2A starts then
    g_emb = b.new_group("emb_done")
    b.add_marker(g_emb, dep=-1, delay=prof.emb_lookup)
    a2a_f = _add_a2a(b, gpus, comm.alltoall_fwd_bytes, comm.n_chunks,
                     dep=g_emb, tag="a2a_fwd")
    # bottom MLP fwd runs concurrently; top MLP needs both
    g_bot = b.new_group("bot_fwd_done")
    b.add_marker(g_bot, dep=-1, delay=prof.bot_mlp_fwd)
    g_top = b.new_group("top_fwd_done")
    b.add_marker(g_top, dep=a2a_f, delay=prof.interact_top_fwd)

    # ---- backward ---------------------------------------------------------
    g_topb = b.new_group("top_bwd_done")
    b.add_marker(g_topb, dep=g_top, delay=prof.top_bwd)
    _add_a2a(b, gpus, comm.alltoall_bwd_bytes, comm.n_chunks,
             dep=g_topb, tag="a2a_bwd")
    g_botb = b.new_group("bot_bwd_done")
    b.add_marker(g_botb, dep=g_topb, delay=prof.bot_bwd)

    # ---- gradient all-reduce (per chunk, overlapping bwd) ------------------
    if comm.allreduce_algo == "2d":
        _add_ar2d(b, topo, gpus, comm.allreduce_bytes, comm.n_chunks, dep=g_topb)
    else:
        _add_ar1d(b, gpus, comm.allreduce_bytes, comm.n_chunks, dep=g_topb)
    return b.build()


def _add_a2a(b, gpus, total, n_chunks, dep, tag):
    P = len(gpus)
    per_pair = total / n_chunks / P
    # the ECMP salt is the tag's CRC-32, the same in every process
    # (``hash`` of a str is salted per process by PYTHONHASHSEED)
    base = zlib.crc32(tag.encode()) % 65536
    prev = dep
    for c in range(n_chunks):
        g = b.new_group(f"{tag}_c{c}")
        _direct_phase(b, gpus, per_pair, g, prev, 0.0,
                      salt=base + c * 104729)
        prev = g
    # umbrella group: completion of the last chunk == collective done
    return prev


def _add_ar1d(b, gpus, total, n_chunks, dep):
    P = len(gpus)
    seg = total / n_chunks / P
    prev_rs = dep
    for c in range(n_chunks):
        rs = b.new_group(f"ar_c{c}_rs")
        _direct_phase(b, gpus, seg, rs, prev_rs, 0.0, salt=c * 7919)
        ag = b.new_group(f"ar_c{c}_ag")
        _direct_phase(b, gpus, seg, ag, rs, 0.0, salt=c * 7919 + 31)
        prev_rs = rs
    return ag


def _add_ar2d(b, topo, gpus, total, n_chunks, dep):
    gpn = topo.meta.get("gpus_per_node", 8)
    nodes: dict = {}
    for g in gpus:
        nodes.setdefault(g // gpn, []).append(g)
    node_list = sorted(nodes)
    n_nodes = len(node_list)
    chunk = total / n_chunks
    prev1 = dep
    last = None
    for c in range(n_chunks):
        g1 = b.new_group(f"ar_c{c}_rs_local")
        for node in node_list:
            _direct_phase(b, nodes[node], chunk / gpn, g1, prev1, 0.0,
                          salt=c * 7919 + node)
        g2 = b.new_group(f"ar_c{c}_rs_xnode")
        for r in range(gpn):
            members = [nodes[n][r] for n in node_list]
            _direct_phase(b, members, chunk / (gpn * n_nodes), g2, g1, 0.0,
                          salt=c * 7919 + 101 + r)
        g3 = b.new_group(f"ar_c{c}_ag_xnode")
        for r in range(gpn):
            members = [nodes[n][r] for n in node_list]
            _direct_phase(b, members, chunk / (gpn * n_nodes), g3, g2, 0.0,
                          salt=c * 7919 + 211 + r)
        g4 = b.new_group(f"ar_c{c}_ag_local")
        for node in node_list:
            _direct_phase(b, nodes[node], chunk / gpn, g4, g3, 0.0,
                          salt=c * 7919 + 307 + node)
        prev1 = g1
        last = g4
    return last


@dataclasses.dataclass(frozen=True)
class DLRMIterationSpec:
    """Scenario workload: one DLRM training iteration (compute markers +
    A2A halves + per-chunk gradient All-Reduce)."""
    prof: DLRMComputeProfile = DLRMComputeProfile()
    comm: DLRMCommSpec = DLRMCommSpec()
    gpus: tuple | None = None      # None -> every fabric GPU

    def build_schedule(self, topo: Topology) -> Schedule:
        gpus = (list(self.gpus) if self.gpus is not None
                else list(range(topo.n_gpus)))
        return build_dlrm_iteration(topo, gpus, self.prof, self.comm)


@dataclasses.dataclass
class IterationReport:
    iteration_time: float
    total_compute: float
    exposed_comm: float
    pfc_pauses: int
    policy: str
    finished: bool


def simulate_dlrm_policies(topo: Topology, gpus: list, policies=None,
                           prof: DLRMComputeProfile = DLRMComputeProfile(),
                           comm: DLRMCommSpec = DLRMCommSpec(),
                           cfg: EngineConfig = EngineConfig(dt=2e-6),
                           runner=None,
                           batched: bool | None = None) -> list[IterationReport]:
    """The Fig-10 per-policy loop as ONE vmapped policy-axis dispatch:
    every CC policy simulates the same DLRM iteration in a single compiled
    call (``SweepRunner.run_policy_axis``).  ``batched=None`` defers to
    ``SweepRunner.policy_axis_pays_off`` (serial fallback on CPU, same
    reports either way)."""
    from repro.core import cc as cc_mod
    from repro.core.sweep import SweepRunner
    runner = runner or SweepRunner(cfg)
    sched = build_dlrm_iteration(topo, gpus, prof, comm)
    policies = tuple(policies or cc_mod.ALL_POLICIES)
    if batched is None:
        batched = runner.policy_axis_pays_off()
    if not batched:
        from repro.core.cc import get_policy
        return [simulate_dlrm_iteration(
                    topo, gpus, get_policy(p) if isinstance(p, str) else p,
                    prof, comm, cfg=cfg, runner=runner)
                for p in policies]
    batch = runner.run_policy_axis(topo, sched, policies, cfg=cfg)
    out = []
    for i in range(batch.n):
        iter_time = float(batch.completion_time[i]) + prof.opt_update
        out.append(IterationReport(
            iteration_time=iter_time,
            total_compute=prof.total,
            exposed_comm=max(iter_time - prof.total, 0.0),
            pfc_pauses=int(batch.pause_count[i].sum()),
            policy=batch.policy_of(i),
            finished=bool(batch.finished[i]),
        ))
    return out


def simulate_dlrm_iteration(topo: Topology, gpus: list, policy,
                            prof: DLRMComputeProfile = DLRMComputeProfile(),
                            comm: DLRMCommSpec = DLRMCommSpec(),
                            cfg: EngineConfig = EngineConfig(dt=2e-6),
                            runner=None) -> IterationReport:
    """Pass a ``repro.core.sweep.SweepRunner`` to reuse compiled engines
    across the per-policy / per-algo loops of Figs 10-11."""
    spec = ScenarioSpec(fabric=topo, policy=policy,
                        workload=DLRMIterationSpec(prof, comm, tuple(gpus)))
    if runner is not None:
        res = runner.run_spec(spec, cfg=cfg)
    else:
        topo, sched, policy = spec.build()
        res = simulate(topo, sched, policy, cfg)
    # iteration ends when every flow (incl. compute markers) is done, plus
    # the optimizer update after the last gradient arrives
    iter_time = res.completion_time + prof.opt_update
    total_compute = prof.total
    return IterationReport(
        iteration_time=iter_time,
        total_compute=total_compute,
        exposed_comm=max(iter_time - total_compute, 0.0),
        pfc_pauses=int(res.pause_count.sum()),
        policy=policy.name,
        finished=res.finished,
    )
