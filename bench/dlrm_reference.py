"""Plain reference for the DLRM training iteration (arXiv:2207.10898
Sec. IV-D, Fig 10 and Table II) on the paper's two-tier CLOS.

It takes from ``bench/reference.py`` the fabric, the routes (``route``,
its ECMP key and hash), the policies and ``MAXHOP``, and adds what the
iteration needs.

The iteration, written from the paper's description: the embedding
lookup, then the forward All-to-All in 4 chained chunks; the bottom MLP
beside it; interaction and top MLP after the All-to-All; top MLP
backprop; then at once the backward All-to-All (4 chunks), the bottom MLP
backprop and the MLP gradients' hierarchical (2D) All-Reduce.  Per
All-Reduce chunk: a reduce-scatter inside each node over NVLink, then per
rail (the GPUs of one local rank, one per node) a reduce-scatter and an
all-gather across nodes over the NICs, then an all-gather inside each
node; chunk c's first phase waits on chunk c-1's.  Every phase is a
direct exchange: each member sends its segment to every other member.
29 groups: five compute segments, 4 + 4 All-to-All chunks, 4 x 4
All-Reduce phases.  The optimizer step after the last gradient is not a
flow.

* A compute segment is a marker: a group of one flow with no path and no
  bytes, released a delay (its compute time) after its dependency.
* ECMP: a flow's key is ``src * 131071 + dst * 8191 + salt * 524287 +
  group`` (31 bits); a direct phase's salt is its phase salt plus ``i *
  1009 + j`` for the sender's and receiver's places among the phase's
  members.  Phase salts: an All-to-All half's is the CRC-32 of its tag
  (``a2a_fwd``, ``a2a_bwd``) mod 65536 plus ``c * 104729`` for chunk c;
  the All-Reduce's ``c * 7919`` plus ``node`` (NVLink reduce-scatter),
  ``101 + rank`` and ``211 + rank`` (the NIC phases), ``307 + node``
  (NVLink all-gather).

Its step departs from ``bench.reference.simulate`` in three places and
nowhere else:

* stage 3 releases a flow in the first step whose start ``t`` satisfies
  ``t >= t_dep + delay`` (``t_dep``: the end of the step its dependency
  group completed in, 0 where it has none), the sum and the comparison
  held in ``dtype``; a marker injects nothing;
* stage 8 completes a data flow once its delivered bytes reach its wire
  size less ``eps_done``, and a marker at the end of the step it is
  released in (the program's rule: ``marker_done = (n_hops == 0) &
  started``);
* stages 1, 5 and 6 run over every flow at every hop: a hop past a
  flow's last is the null link, which carries no backlog, marks nothing
  and adds exactly 0.

It imports nothing of the program and takes nothing the program built.
"""
from __future__ import annotations

import dataclasses
import time
import zlib

import numpy as np

from bench import reference as base

MAXHOP = base.MAXHOP
POLICIES = base.POLICIES


@dataclasses.dataclass
class Flows(base.Flows):
    delay: np.ndarray      # (F,) seconds to wait after the dependency


class _Job(base._FlowList):
    """``bench.reference``'s flow list (its ECMP key) with compute markers
    and each flow's delay."""

    def __init__(self, fab: base.Fabric):
        super().__init__(fab)
        self.delays = []

    def marker(self, dep: int, delay: float) -> int:
        """A compute segment: a group of one marker."""
        g = self.group()
        self.rows.append(([], 0.0, g, dep))
        self.delays.append(delay)
        return g

    def direct(self, members: list, seg: float, group: int, dep: int,
               salt: int):
        for i, u in enumerate(members):
            for j, v in enumerate(members):
                if u != v:
                    self.send(u, v, seg, group, dep, salt + i * 1009 + j)
                    self.delays.append(0.0)

    def build(self) -> Flows:
        return Flows(**vars(super().build()),
                     delay=np.array(self.delays, np.float64))


def dlrm_iteration(fab: base.Fabric, compute: dict, comm: dict) -> Flows:
    """The iteration's flows, group by group in the order named above."""
    if comm["allreduce_algo"] != "2d":
        raise ValueError("the reference writes the 2D All-Reduce only")
    job = _Job(fab)
    P, G = fab.n_gpus, fab.gpus_per_node
    gpus = list(range(P))
    n_chunks = int(comm["n_chunks"])

    def alltoall(total, dep, tag):
        per_pair = total / n_chunks / P
        salt = zlib.crc32(tag.encode()) % 65536
        for c in range(n_chunks):
            g = job.group()
            job.direct(gpus, per_pair, g, dep, salt + c * 104729)
            dep = g
        return dep

    emb = job.marker(-1, compute["emb_lookup"])
    a2a_fwd = alltoall(comm["alltoall_fwd_bytes"], emb, "a2a_fwd")
    job.marker(-1, compute["bot_mlp_fwd"])
    top = job.marker(a2a_fwd, compute["interact_top_fwd"])
    top_bwd = job.marker(top, compute["top_bwd"])
    alltoall(comm["alltoall_bwd_bytes"], top_bwd, "a2a_bwd")
    job.marker(top_bwd, compute["bot_bwd"])

    n_nodes = P // G
    nodes = [list(range(n * G, (n + 1) * G)) for n in range(n_nodes)]
    rails = [[nodes[n][r] for n in range(n_nodes)] for r in range(G)]
    chunk = comm["allreduce_bytes"] / n_chunks
    local, xnode = chunk / G, chunk / (G * n_nodes)
    prev = top_bwd
    for c in range(n_chunks):
        s = c * 7919
        rs_local = job.group()
        for n in range(n_nodes):
            job.direct(nodes[n], local, rs_local, prev, s + n)
        rs_x = job.group()
        for r in range(G):
            job.direct(rails[r], xnode, rs_x, rs_local, s + 101 + r)
        ag_x = job.group()
        for r in range(G):
            job.direct(rails[r], xnode, ag_x, rs_x, s + 211 + r)
        ag_local = job.group()
        for n in range(n_nodes):
            job.direct(nodes[n], local, ag_local, ag_x, s + 307 + n)
        prev = rs_local
    return job.build()


def build_scenario(config: dict) -> tuple:
    """(fabric, flows) of a configuration file's ``fabric`` and
    ``workload`` blocks."""
    fab = base.build_clos(config["fabric"])
    job = config["workload"]
    return fab, dlrm_iteration(fab, job["compute"], job["comm"])


# ---------------------------------------------------------------------------
# the fluid step: bench/reference.py's, with delays and markers
# ---------------------------------------------------------------------------

def simulate(fab: base.Fabric, flows: Flows, policy: str, params: dict,
             knobs: dict, engine: dict, dtype=np.float32) -> dict:
    """``bench.reference.simulate`` for flows that wait a delay after
    their dependency and for markers (the departures the module's
    docstring lists); the same arguments and the same result."""
    dt_ = np.dtype(dtype)
    one = dt_.type
    pol = POLICIES[policy]()
    p = dict(pol.defaults)
    p.update({k: float(v) for k, v in params.items()})
    p = {k: one(v) for k, v in p.items()}
    dt = float(engine["dt"])
    total = int(engine["max_steps"]) * (int(engine["max_extends"]) + 1)
    L, D = fab.n_links, fab.n_devices
    F = len(flows.size)
    Gn = flows.n_groups

    def arr(x):
        return np.asarray(x, dt_)

    valid = [flows.path[:, h] >= 0 for h in range(MAXHOP)]
    path = [np.where(valid[h], flows.path[:, h], L) for h in range(MAXHOP)]
    n_hops = sum(v.astype(np.int64) for v in valid)
    marker = n_hops == 0
    cap = arr(np.concatenate([fab.cap, [1e18]]))
    lat = arr(np.concatenate([fab.lat, [0.0]]))
    caps = [cap[path[h]] for h in range(MAXHOP)]
    ecn_l = np.concatenate([fab.ecn, [False]])
    ecn_mask = [arr(ecn_l[path[h]] & valid[h]) for h in range(MAXHOP)]
    vmask = [arr(valid[h]) for h in range(MAXHOP)]
    ingress = [np.full(F, L)] + [np.where(valid[h], path[h - 1], L)
                                 for h in range(1, MAXHOP)]
    can_pause = np.concatenate([fab.is_switch[fab.dst] & fab.fabric, [False]])
    sw_sw = fab.is_switch[fab.src] & fab.is_switch[fab.dst] & fab.fabric
    acc = np.zeros(F, dt_)
    for h in range(MAXHOP):
        acc = acc + lat[path[h]] * vmask[h]
    base_rtt = np.maximum(2.0 * acc, 1e-7).astype(dt_)
    delay = np.clip(np.round(base_rtt / dt), 1, 511).astype(np.int64)
    ring = 1 << int(delay.max()).bit_length()
    line = cap[path[0]]
    bdp = (line * base_rtt).astype(dt_)
    load = np.zeros(L + 1)
    for g in range(Gn):
        in_g = (flows.group == g) & (flows.size > 0)
        cnt = np.zeros(L + 1)
        for h in range(MAXHOP):
            cnt += np.bincount(path[h][in_g & valid[h]], minlength=L + 1)
        load = np.maximum(load, cnt)
    load[L] = 1.0
    fanin = np.ones(F)
    for h in range(MAXHOP):
        fanin = np.maximum(fanin, np.where(valid[h], load[path[h]], 1.0))
    fanin = arr(fanin)
    gsize = np.bincount(flows.group, minlength=Gn).astype(np.float64)
    dep = flows.dep
    dep0 = np.maximum(dep, 0)
    has_dep = dep >= 0
    wait = arr(flows.delay)
    ctx = {"F": F, "dtype": dt_, "line": line, "bdp": bdp, "fanin": fanin}
    cc = {k: arr(v) for k, v in pol.init(ctx).items()}
    size = arr(flows.size)
    wire_size = size * one(pol.wire)

    kmin, kmax, pmax = (one(knobs[k]) for k in ("kmin", "kmax", "pmax"))
    xoff, xon = one(knobs["xoff"]), one(knobs["xon"])
    ramp = np.maximum(kmax - kmin, one(1.0))
    tbu = one(engine["t_base_util"])
    eps = one(engine["eps_done"])
    resend = one(dt / float(engine["pause_resend"]))
    check_every = int(engine["deadlock_check_every"])
    dtd = one(dt)

    backlog = [np.zeros(F, dt_) for _ in range(MAXHOP)]
    remaining = wire_size.copy()
    injected = np.zeros(F, dt_)
    delivered = np.zeros(F, dt_)
    done = np.zeros(F, bool)
    t_finish = np.full(F, np.inf, dt_)
    g_count = np.zeros(Gn)
    g_time = np.full(Gn, np.inf, dt_)
    paused = np.zeros(L + 1, bool)
    pause_count = np.zeros(D, dt_)
    hist_q = np.zeros(ring * (L + 1), dt_)
    hist_tx = np.zeros(ring * (L + 1), dt_)
    deadlock_step = -1
    last = [n_hops == h + 1 for h in range(MAXHOP)]
    ing_rows = [np.nonzero(ingress[h] < L)[0] for h in range(MAXHOP)]

    it = 0
    while it < total and not done.all():
        t = one(it) * dtd
        t_end = one(it + 1) * dtd
        # 1. signals, delayed by each flow's base RTT
        base_i = (np.maximum(it - delay, 0) % ring) * (L + 1)
        qsum = np.zeros(F, dt_)
        keep = np.ones(F, dt_)
        util = np.zeros(F, dt_)
        for h in range(MAXHOP):
            q_d = hist_q.take(base_i + path[h])
            tx_d = hist_tx.take(base_i + path[h])
            qsum = qsum + q_d / caps[h]
            keep = keep * (1.0 - np.clip((q_d - kmin) / ramp, 0.0, 1.0)
                           * pmax * ecn_mask[h])
            util = np.maximum(util, tx_d / caps[h] + q_d / (caps[h] * tbu))
        rtt = (base_rtt + qsum).astype(dt_)
        ecn = (1.0 - keep).astype(dt_)
        sig = {"t": t, "dt": dtd, "line": line, "base_rtt": base_rtt,
               "ecn": ecn, "rtt": rtt, "util": util, "F": F, "dtype": dt_}
        # 2. congestion control
        cc, rate, win = pol.update(p, cc, sig)
        cc = {k: arr(v) for k, v in cc.items()}
        rate, win = arr(rate), arr(win)
        # 3. release once the dependency group has completed and the
        # flow's delay has passed since
        dep_ok = np.where(has_dep, g_count[dep0] >= gsize[dep0] - 0.5, True)
        dep_t = np.where(has_dep, g_time[dep0], 0.0).astype(dt_)
        started = dep_ok & (t >= (dep_t + wait).astype(dt_))
        room = np.maximum(win - (injected - delivered), 0.0)
        inj = np.minimum(np.minimum(rate * dtd, room), remaining)
        inj = np.where(started & ~marker, np.maximum(inj, 0.0),
                       0.0).astype(dt_)
        backlog[0] = backlog[0] + inj
        remaining = remaining - inj
        injected = injected + inj
        # 4. paused ports send nothing
        rem_cap = (cap * dtd * ~paused).astype(dt_)
        rem_cap[L] = 1e18
        # 5. hop-ordered forwarding
        tx = np.zeros(L + 1, dt_)
        for h in range(MAXHOP):
            dem = base._segsum(path[h], backlog[h], L + 1, dt_)
            frac = np.where(dem > 0, np.minimum(1.0, rem_cap / np.maximum(
                dem, one(1e-9))), 0.0).astype(dt_)
            moved = backlog[h] * frac.take(path[h])
            backlog[h] = backlog[h] - moved
            delivered = delivered + np.where(last[h], moved, 0.0).astype(dt_)
            if h + 1 < MAXHOP:
                backlog[h + 1] = backlog[h + 1] + np.where(
                    last[h], 0.0, moved).astype(dt_)
            sent = frac * dem
            rem_cap = np.maximum(rem_cap - sent, 0.0).astype(dt_)
            tx = tx + sent
        # 6. queues
        q_link = np.zeros(L + 1)
        q_port = np.zeros(L + 1)
        for h in range(MAXHOP):
            q_link += np.bincount(path[h], weights=backlog[h],
                                  minlength=L + 1)
            r = ing_rows[h]
            if r.size:
                q_port += np.bincount(ingress[h][r], weights=backlog[h][r],
                                      minlength=L + 1)
        q_link, q_port = q_link.astype(dt_), q_port.astype(dt_)
        # 7. PFC hysteresis
        old = paused
        paused = np.where((q_port > xoff) & can_pause, True,
                          np.where(q_port < xon, False, old))
        frames = ((paused & ~old)[:L].astype(dt_)
                  + paused[:L].astype(dt_) * resend)
        pause_count = pause_count + base._segsum(fab.dst, frames, D, dt_)
        # 8. completion at the end of this step: a data flow once its
        # bytes are delivered, a marker once released
        newly = ~done & np.where(marker, started,
                                 delivered >= wire_size - eps)
        done = done | newly
        t_finish = np.where(newly, t_end, t_finish)
        was = g_count >= gsize - 0.5
        g_count = g_count + np.bincount(flows.group, weights=newly,
                                        minlength=Gn)
        g_time = np.where((g_count >= gsize - 0.5) & ~was, t_end, g_time)
        # 9. signal history
        slot = (it % ring) * (L + 1)
        hist_q[slot:slot + L + 1] = q_link
        hist_tx[slot:slot + L + 1] = tx / dtd
        # 10. pause-cycle observer on switch-to-switch links
        if (deadlock_step < 0 and it % check_every == 0
                and (paused[:L] & sw_sw).any()):
            e = paused[:L] & sw_sw
            reach = np.zeros((D, D), np.int64)
            reach[fab.src[e], fab.dst[e]] = 1
            for _ in range(max(1, (max(D, 2) - 1).bit_length())):
                reach = np.minimum(reach + reach @ reach, 1)
            if np.diagonal(reach).any():
                deadlock_step = it
        it += 1
    return {"t_finish": t_finish.astype(np.float64),
            "pause_count": pause_count.astype(np.float64),
            "finished": bool(done.all()), "steps": it,
            "deadlock_step": deadlock_step}


def run_lane(config: dict, lane, dtype_name: str = "float32",
             max_steps: int | None = None) -> dict:
    """One lane (``bench.lanes.Lane``) of a cell's configuration through
    the reference, as ``bench.reference.run_lane`` runs it."""
    t0 = time.perf_counter()
    dtype = np.float32
    if dtype_name == "bfloat16":
        import ml_dtypes
        dtype = ml_dtypes.bfloat16
    fab, flows = build_scenario(config)
    engine = dict(config["engine"])
    if max_steps is not None:
        engine.update(max_steps=max_steps, max_extends=0)
    out = simulate(fab, flows, lane.policy, lane.params,
                   dict(config["fabric_knobs"], kmin=lane.kmin,
                        kmax=lane.kmax, xoff=lane.xoff), engine, dtype=dtype)
    out["deadlocked"] = out["deadlock_step"] >= 0
    out["seconds"] = time.perf_counter() - t0
    return out
