"""Plain reference for the benchmark's correctness check.

A straightforward NumPy implementation of the fluid RoCE fabric model the
program simulates: the two-tier CLOS of the paper's Section III-B, per-flow
ECMP, the topology-aware ring all-reduce and the direct all-to-all, the
classical congestion-control policies (pfc, dcqcn, dctcp, timely, hpcc,
hpcc_pint, static_window) and the learned one (mlp, on the weights the
lane gives), and the fixed-timestep fluid step
(delayed signals, CC update, paced injection, hop-ordered forwarding with
proportional drain, PFC per-port hysteresis with PAUSE frame counts,
dependency groups, the pause-cycle deadlock observer).  It imports nothing
of the program and takes nothing the program built: the fabric, the routes
and the flows are built here from the configuration file.

Every per-flow and per-link quantity is held in ``dtype`` (float32, as the
configuration states; bfloat16 for the control).  Segment sums use
``np.bincount``, accumulated in float64 and rounded once to ``dtype``.
Only the flows and links that exist are simulated: no padding, no gather
plans, no batching.
"""
from __future__ import annotations

import dataclasses

import numpy as np

MAXHOP = 4
LINK_CLASSES = ("nvlink", "host_nic", "tor_down", "tor_up", "spine_down")


# ---------------------------------------------------------------------------
# fabric
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Fabric:
    """Directed links and devices.  Devices are numbered GPUs first, then
    NVSwitches, ToRs and spines; the null link ``n_links`` closes every
    path shorter than ``MAXHOP``."""
    cap: np.ndarray          # (L,) bytes/s
    lat: np.ndarray          # (L,) s
    src: np.ndarray          # (L,) device owning the egress queue
    dst: np.ndarray          # (L,) device the link feeds
    ecn: np.ndarray          # (L,) bool: the egress queue marks ECN
    fabric: np.ndarray       # (L,) bool: RoCE link (PFC-capable)
    cls: np.ndarray          # (L,) index into LINK_CLASSES
    is_switch: np.ndarray    # (D,) bool
    n_gpus: int
    gpus_per_node: int
    nodes_per_rack: int
    n_spines: int
    nv_up: np.ndarray
    nv_down: np.ndarray
    host_up: np.ndarray
    tor_down: np.ndarray
    tor_up: np.ndarray       # (racks, spines)
    spine_down: np.ndarray   # (spines, racks)

    @property
    def n_links(self) -> int:
        return len(self.cap)

    @property
    def n_devices(self) -> int:
        return len(self.is_switch)


def build_clos(f: dict) -> Fabric:
    """The CLOS of ``f`` (a configuration's ``fabric`` block): GPUs with an
    NVSwitch per node, one NIC per GPU to its rack's ToR, every ToR to every
    spine."""
    R, N, G = f["n_racks"], f["nodes_per_rack"], f["gpus_per_node"]
    S = f["n_spines"]
    nic_bw, nic_lat = f["nic_gbit_s"] * 1e9 / 8, f["nic_latency_s"]
    nv_bw, nv_lat = f["nvlink_gbyte_s"] * 1e9, f["nvlink_latency_s"]
    n_nodes, n_gpus = R * N, R * N * G
    nvsw = [n_gpus + n for n in range(n_nodes)]
    tors = [n_gpus + n_nodes + r for r in range(R)]
    spines = [n_gpus + n_nodes + R + s for s in range(S)]
    is_switch = np.array([False] * n_gpus + [True] * (n_nodes + R + S))
    links = []

    def link(u, v, cap, lat, ecn, fabric, cls):
        links.append((cap, lat, u, v, ecn, fabric, LINK_CLASSES.index(cls)))
        return len(links) - 1

    nv_up, nv_down = np.zeros(n_gpus, int), np.zeros(n_gpus, int)
    host_up, tor_down = np.zeros(n_gpus, int), np.zeros(n_gpus, int)
    for g in range(n_gpus):
        node = g // G
        rack = node // N
        nv_up[g] = link(g, nvsw[node], nv_bw, nv_lat, False, False, "nvlink")
        nv_down[g] = link(nvsw[node], g, nv_bw, nv_lat, False, False,
                          "nvlink")
        host_up[g] = link(g, tors[rack], nic_bw, nic_lat, False, True,
                          "host_nic")
        tor_down[g] = link(tors[rack], g, nic_bw, nic_lat, True, True,
                           "tor_down")
    tor_up, spine_down = np.zeros((R, S), int), np.zeros((S, R), int)
    for r in range(R):
        for s in range(S):
            tor_up[r, s] = link(tors[r], spines[s], nic_bw, nic_lat, True,
                                True, "tor_up")
            spine_down[s, r] = link(spines[s], tors[r], nic_bw, nic_lat,
                                    True, True, "spine_down")
    cols = list(zip(*links))
    return Fabric(
        cap=np.array(cols[0], np.float64), lat=np.array(cols[1], np.float64),
        src=np.array(cols[2]), dst=np.array(cols[3]),
        ecn=np.array(cols[4], bool), fabric=np.array(cols[5], bool),
        cls=np.array(cols[6]), is_switch=is_switch, n_gpus=n_gpus,
        gpus_per_node=G, nodes_per_rack=N, n_spines=S, nv_up=nv_up,
        nv_down=nv_down, host_up=host_up, tor_down=tor_down, tor_up=tor_up,
        spine_down=spine_down)


def ecmp_hash(x: int) -> int:
    """The fabric's per-flow ECMP hash (an avalanche mix of the flow key)."""
    x = (x ^ 61) ^ (x >> 16)
    x = (x + (x << 3)) & 0xFFFFFFFF
    x = x ^ (x >> 4)
    x = (x * 0x27D4EB2D) & 0xFFFFFFFF
    return (x ^ (x >> 15)) & 0x7FFFFFFF


def route(fab: Fabric, src: int, dst: int, key: int) -> list:
    """Same node: NVLink up and down.  Same rack: NIC to the ToR and down.
    Otherwise NIC, ToR uplink to the spine the ECMP hash picks, spine
    downlink, ToR downlink."""
    s_node, d_node = src // fab.gpus_per_node, dst // fab.gpus_per_node
    s_rack, d_rack = s_node // fab.nodes_per_rack, d_node // fab.nodes_per_rack
    if s_node == d_node:
        return [fab.nv_up[src], fab.nv_down[dst]]
    if s_rack == d_rack:
        return [fab.host_up[src], fab.tor_down[dst]]
    spine = ecmp_hash(key) % fab.n_spines
    return [fab.host_up[src], fab.tor_up[s_rack, spine],
            fab.spine_down[spine, d_rack], fab.tor_down[dst]]


# ---------------------------------------------------------------------------
# collectives -> flows
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Flows:
    path: np.ndarray       # (F, MAXHOP) link ids, -1 beyond the last hop
    size: np.ndarray       # (F,) bytes
    group: np.ndarray      # (F,) completion group
    dep: np.ndarray        # (F,) group that must complete first, or -1
    n_groups: int


class _FlowList:
    def __init__(self, fab: Fabric):
        self.fab, self.rows, self.n_groups = fab, [], 0

    def group(self) -> int:
        self.n_groups += 1
        return self.n_groups - 1

    def send(self, src, dst, size, group, dep, salt):
        key = (src * 131071 + dst * 8191 + salt * 524287 + group) & 0x7FFFFFFF
        self.rows.append((route(self.fab, src, dst, key), size, group, dep))

    def build(self) -> Flows:
        F = len(self.rows)
        path = np.full((F, MAXHOP), -1, np.int64)
        for i, (p, *_) in enumerate(self.rows):
            path[i, :len(p)] = p
        return Flows(path=path,
                     size=np.array([r[1] for r in self.rows], np.float64),
                     group=np.array([r[2] for r in self.rows], np.int64),
                     dep=np.array([r[3] for r in self.rows], np.int64),
                     n_groups=self.n_groups)


def ring_allreduce(fab: Fabric, total_bytes: float, n_chunks: int) -> Flows:
    """Ring all-reduce over GPUs in id order (neighbours share a node where
    they can): per chunk, P-1 reduce-scatter steps then P-1 all-gather
    steps of chunk/P bytes, each step one group that waits on the one
    before; chunk c's first step waits on chunk c-1's first step."""
    out = _FlowList(fab)
    P = fab.n_gpus
    seg = total_bytes / n_chunks / P
    prev_first = -1
    for c in range(n_chunks):
        firsts = []
        dep = prev_first
        for phase_salt in (c * 7919, c * 7919 + 31):
            for s in range(P - 1):
                g = out.group()
                firsts.append(g)
                for i in range(P):
                    out.send(i, (i + 1) % P, seg, g, dep,
                             phase_salt + s * 1009 + i)
                dep = g
        prev_first = firsts[0]
    return out.build()


def alltoall(fab: Fabric, total_bytes: float, n_chunks: int) -> Flows:
    """Direct all-to-all: every GPU sends total/n_chunks/P bytes to every
    other GPU at once; chunk c waits on chunk c-1."""
    out = _FlowList(fab)
    P = fab.n_gpus
    per_pair = total_bytes / n_chunks / P
    for c in range(n_chunks):
        g = out.group()
        dep = -1 if c == 0 else g - 1
        for i in range(P):
            for j in range(P):
                if i != j:
                    out.send(i, j, per_pair, g, dep, c * 104729 + i * 1009 + j)
    return out.build()


COLLECTIVES = {"allreduce_ring": ring_allreduce, "alltoall": alltoall}


def build_scenario(config: dict) -> tuple:
    """(fabric, flows) of a configuration file's ``fabric`` and
    ``collective`` blocks."""
    fab = build_clos(config["fabric"])
    col = config["collective"]
    flows = COLLECTIVES[col["kind"]](fab, float(col["bytes"]),
                                     int(col["n_chunks"]))
    return fab, flows


# ---------------------------------------------------------------------------
# congestion control (per-flow update rules, on flat arrays)
# ---------------------------------------------------------------------------

INF = 1e18


class _Policy:
    """``init(ctx)`` -> state dict; ``update(p, st, sig)`` -> (state, rate,
    window).  ``p`` maps parameter -> python float."""
    wire = 1.0
    defaults: dict = {}


def _ones(ctx, v):
    return np.full(ctx["F"], v, ctx["dtype"])


class Pfc(_Policy):
    def init(self, ctx):
        return {}

    def update(self, p, st, sig):
        return st, sig["line"], _ones(sig, INF)


class Dcqcn(_Policy):
    defaults = dict(g=1 / 256, rai_frac=0.03, rhai_frac=0.05, timer=55e-6,
                    cut_gap=50e-6, fast_rounds=5, hai_after=5,
                    ecn_thresh=0.01, mss=1000.0)

    def init(self, ctx):
        dt_ = ctx["dtype"]
        # per-flow timer jitter in [0.9, 1.1): the float32 expression
        # 0.9 + 0.2 * ((k * 7919 % 97) / 97), the multiply-add rounded once
        k = np.arange(ctx["F"], dtype=np.float32)
        q = (k * np.float32(7919)) % np.float32(97) / np.float32(97.0)
        jit = (np.float64(np.float32(0.9))
               + np.float64(np.float32(0.2)) * q.astype(np.float64))
        line = ctx["line"]
        return {"rc": line.copy(), "rt": line.copy(),
                "alpha": _ones(ctx, 1.0), "jit": jit.astype(dt_),
                "t_cut": _ones(ctx, -1.0), "t_inc": _ones(ctx, 0.0),
                "t_alpha": _ones(ctx, 0.0), "inc_count": _ones(ctx, 0.0)}

    def update(self, p, st, sig):
        t, line, ecn = sig["t"], sig["line"], sig["ecn"]
        jit = st["jit"]
        pkts = st["rc"] * p["cut_gap"] / p["mss"]
        p_cnp = 1.0 - np.exp(-pkts * ecn)
        cong = p_cnp > p["ecn_thresh"]
        docut = cong & ((t - st["t_cut"]) >= p["cut_gap"] * jit)
        rt = np.where(docut, st["rc"], st["rt"])
        rc = np.where(docut, st["rc"] * (1 - st["alpha"] / 2 * p_cnp),
                      st["rc"])
        alpha = np.where(docut,
                         (1 - p["g"] * p_cnp) * st["alpha"] + p["g"] * p_cnp,
                         st["alpha"])
        t_cut = np.where(docut, t, st["t_cut"])
        inc_count = np.where(docut, 0.0, st["inc_count"]).astype(rc.dtype)
        t_inc = np.where(docut, t, st["t_inc"])
        dodec = (~cong) & ((t - st["t_alpha"]) >= p["timer"] * jit)
        alpha = np.where(dodec, (1 - p["g"]) * alpha, alpha)
        t_alpha = np.where(dodec | docut, t, st["t_alpha"])
        doinc = (t - t_inc) >= p["timer"] * jit
        inc_count = np.where(doinc, inc_count + 1, inc_count)
        additive = inc_count > p["fast_rounds"]
        hyper = inc_count > p["fast_rounds"] + p["hai_after"]
        bump = np.where(hyper, p["rhai_frac"], p["rai_frac"]).astype(
            rc.dtype) * line
        rt = np.where(doinc & additive, rt + bump, rt)
        rc = np.where(doinc, 0.5 * (rt + rc), rc)
        t_inc = np.where(doinc, t, t_inc)
        rc = np.clip(rc, 0.001 * line, line)
        rt = np.clip(rt, 0.001 * line, line)
        st2 = {"rc": rc, "rt": rt, "alpha": alpha, "jit": jit,
               "t_cut": t_cut, "t_inc": t_inc, "t_alpha": t_alpha,
               "inc_count": inc_count}
        return st2, rc, _ones(sig, INF)


class Dctcp(_Policy):
    defaults = dict(g=1 / 16, mss=1000.0, ecn_thresh=0.01, wmax_bdp=32.0)

    def init(self, ctx):
        return {"w": ctx["bdp"].copy(), "alpha": _ones(ctx, 0.0),
                "t_rtt": _ones(ctx, 0.0), "bdp": ctx["bdp"]}

    def update(self, p, st, sig):
        t, ecn = sig["t"], sig["ecn"]
        rtt = np.maximum(sig["rtt"], 1e-6)
        do = (t - st["t_rtt"]) >= rtt
        alpha = np.where(do, (1 - p["g"]) * st["alpha"] + p["g"] * ecn,
                         st["alpha"])
        marked = ecn > p["ecn_thresh"]
        w = np.where(do & marked, st["w"] * (1 - alpha / 2), st["w"])
        w = np.where(do & ~marked, w + p["mss"], w)
        t_rtt = np.where(do, t, st["t_rtt"])
        w = np.clip(w, p["mss"], p["wmax_bdp"] * st["bdp"])
        return ({"w": w, "alpha": alpha, "t_rtt": t_rtt, "bdp": st["bdp"]},
                sig["line"], w)


class Timely(_Policy):
    defaults = dict(tlow=30e-6, thigh=300e-6, beta=0.8, add_frac=0.002,
                    ewma=0.3, hai_thresh=5)

    def init(self, ctx):
        return {"rate": ctx["line"].copy(), "rtt_prev": _ones(ctx, 0.0),
                "grad": _ones(ctx, 0.0), "t_upd": _ones(ctx, 0.0),
                "neg_count": _ones(ctx, 0.0)}

    def update(self, p, st, sig):
        t, line, rtt = sig["t"], sig["line"], sig["rtt"]
        minrtt = np.maximum(sig["base_rtt"], 1e-6)
        period = np.maximum(minrtt, 20e-6)
        do = (t - st["t_upd"]) >= period
        grad_new = (rtt - st["rtt_prev"]) / minrtt
        grad = np.where(do, (1 - p["ewma"]) * st["grad"]
                        + p["ewma"] * grad_new, st["grad"])
        delta = p["add_frac"] * line
        neg = np.where(do & (grad <= 0), st["neg_count"] + 1,
                       0.0).astype(line.dtype)
        hai = neg >= p["hai_thresh"]
        r = st["rate"]
        r_low = r + np.where(hai, 5.0 * delta, delta)
        r_high = r * (1 - p["beta"] * (1 - p["thigh"]
                                       / np.maximum(rtt, p["thigh"])))
        gnorm = np.clip(grad, 0.0, 1.0)
        r_grad = np.where(grad <= 0,
                          r + np.where(hai, 5.0, 1.0).astype(line.dtype)
                          * delta,
                          r * (1 - p["beta"] * gnorm))
        r_new = np.where(rtt < p["tlow"], r_low,
                         np.where(rtt > p["thigh"], r_high, r_grad))
        rate = np.where(do, np.clip(r_new, 0.001 * line, line), r)
        st2 = {"rate": rate, "rtt_prev": np.where(do, rtt, st["rtt_prev"]),
               "grad": grad, "t_upd": np.where(do, t, st["t_upd"]),
               "neg_count": neg}
        return st2, rate, _ones(sig, INF)


class Hpcc(_Policy):
    defaults = dict(eta=0.95, wai_frac=0.001, max_stage=5)
    wire = 1.048
    rtt_scale = 1.0        # HPCC-PINT refreshes its reference window 2x slower

    def init(self, ctx):
        return {"w": ctx["bdp"].copy(), "wc": ctx["bdp"].copy(),
                "t_rtt": _ones(ctx, 0.0), "stage": _ones(ctx, 0.0),
                "bdp": ctx["bdp"]}

    def update(self, p, st, sig):
        t = sig["t"]
        u = np.maximum(sig["util"], 1e-3)
        wai = p["wai_frac"] * st["bdp"]
        mult = st["wc"] * (p["eta"] / u) + wai
        addv = st["wc"] + wai
        use_mult = (u >= p["eta"]) | (st["stage"] >= p["max_stage"])
        w = np.where(use_mult, mult, addv)
        w = np.clip(w, wai, 16.0 * st["bdp"])
        base = sig["base_rtt"] * self.rtt_scale if self.rtt_scale != 1.0 \
            else sig["base_rtt"]
        rtt = np.maximum(base, 1e-6)
        do = (t - st["t_rtt"]) >= rtt
        wc = np.where(do, w, st["wc"])
        stage = np.where(do, np.where(use_mult, 0.0, st["stage"] + 1),
                         st["stage"]).astype(w.dtype)
        t_rtt = np.where(do, t, st["t_rtt"])
        rate = w / rtt
        st2 = {"w": w, "wc": wc, "t_rtt": t_rtt, "stage": stage,
               "bdp": st["bdp"]}
        return st2, np.minimum(rate, sig["line"]), w


class HpccPint(Hpcc):
    wire = 1.001
    rtt_scale = 2.0


class StaticWindow(_Policy):
    defaults = dict(margin=2.0, headroom=0.5e6, min_w=4000.0)

    def init(self, ctx):
        d = self.defaults
        f = ctx["fanin"]
        w = d["margin"] * ctx["bdp"] / f + d["headroom"] / f
        return {"w": np.maximum(w, d["min_w"]).astype(ctx["dtype"])}

    def update(self, p, st, sig):
        return st, sig["line"], st["w"]


# the learned policy's net: 6 features, 4 hidden units, 2 heads; weights
# w1_{j}{i}, b1_{j} per hidden unit j, then w2_{o}{j}, b2_{o} per head o
# (0: rate, 1: window), 38 in all
MLP_FEATURES, MLP_HIDDEN = 6, 4
MLP_WEIGHTS = tuple(
    [k for j in range(MLP_HIDDEN)
     for k in [f"w1_{j}{i}" for i in range(MLP_FEATURES)] + [f"b1_{j}"]]
    + [k for o in range(2)
       for k in [f"w2_{o}{j}" for j in range(MLP_HIDDEN)] + [f"b2_{o}"]])


class Mlp(_Policy):
    """The learned policy: a per-flow net of one hidden layer (tanh) over
    six bounded features (ECN mark fraction, d/(1+d) of the queueing delay
    d over the base RTT, u/(1+u) of the INT utilisation, rate / line,
    window / (window + 4 BDP), 1 / fan-in) and two heads: a rate target
    line * sigmoid(s_r + 4) and a window target, the static window prior
    max(2 BDP / fan-in + 0.5 MB / fan-in, 4000) times exp(2.5 tanh(s_w)).
    Rate and window track their targets at RTT timescale, a = out_gain *
    dt / max(base RTT, dt) clipped to [0, 1], then clip to [1e-3 line,
    line] and [1000, 32 BDP].  Weights the lane leaves out are 0 (the net
    then gives the prior).  The loss cut is left out: the reference fabric
    loses nothing."""
    defaults = dict(out_gain=1.0, loss_cut=1.0,
                    **dict.fromkeys(MLP_WEIGHTS, 0.0))

    def init(self, ctx):
        f = np.maximum(ctx["fanin"], 1.0)
        win0 = np.maximum(2.0 * ctx["bdp"] / f + 0.5e6 / f, 4000.0)
        return {"rate": ctx["line"].copy(), "win": win0,
                "bdp": ctx["bdp"].copy(), "fanin": f}

    def update(self, p, st, sig):
        line = np.maximum(sig["line"], 1.0)
        base = np.maximum(sig["base_rtt"], 1e-7)
        bdp = np.maximum(st["bdp"], 1.0)
        qd = np.maximum(sig["rtt"] - sig["base_rtt"], 0.0) / base
        u = np.maximum(sig["util"], 0.0)
        f = np.maximum(st["fanin"], 1.0)
        x = (sig["ecn"], qd / (1.0 + qd), u / (1.0 + u), st["rate"] / line,
             st["win"] / (st["win"] + 4.0 * bdp), 1.0 / f)
        s_r, s_w = 0, 0
        for j in range(MLP_HIDDEN):
            z = 0
            for i in range(MLP_FEATURES):
                z = z + p[f"w1_{j}{i}"] * x[i]
            h = np.tanh(z + p[f"b1_{j}"])
            s_r = s_r + p[f"w2_0{j}"] * h
            s_w = s_w + p[f"w2_1{j}"] * h
        s_r, s_w = s_r + p["b2_0"], s_w + p["b2_1"]
        win_prior = np.maximum(2.0 * bdp / f + 0.5e6 / f, 4000.0)
        rate_tgt = line * (1.0 / (1.0 + np.exp(-(s_r + 4.0))))
        win_tgt = win_prior * np.exp(2.5 * np.tanh(s_w))
        a = np.clip(p["out_gain"] * sig["dt"]
                    / np.maximum(base, sig["dt"]), 0.0, 1.0)
        rate = np.clip(st["rate"] + a * (rate_tgt - st["rate"]),
                       1e-3 * line, line)
        win = np.clip(st["win"] + a * (win_tgt - st["win"]), 1000.0,
                      32.0 * bdp)
        return ({"rate": rate, "win": win, "bdp": st["bdp"],
                 "fanin": st["fanin"]}, rate, win)


POLICIES = {"pfc": Pfc, "dcqcn": Dcqcn, "dctcp": Dctcp, "timely": Timely,
            "hpcc": Hpcc, "hpcc_pint": HpccPint,
            "static_window": StaticWindow, "mlp": Mlp}


def policy_defaults(name: str) -> dict:
    return dict(POLICIES[name]().defaults)


# ---------------------------------------------------------------------------
# the fluid step
# ---------------------------------------------------------------------------

def _segsum(ids, vals, n, dtype):
    return np.bincount(ids, weights=vals, minlength=n)[:n].astype(dtype)


def simulate(fab: Fabric, flows: Flows, policy: str, params: dict,
             knobs: dict, engine: dict, dtype=np.float32) -> dict:
    """Run one lane to completion or to the step budget.

    ``params``: the policy's parameters (defaults fill in the rest).
    ``knobs``: ECN and PFC thresholds (kmin, kmax, pmax, xoff, xon), the
    same on every link.  ``engine``: dt, max_steps, max_extends,
    t_base_util, eps_done, pause_resend, deadlock_check_every.

    Returns per-flow finish times (inf where a flow never finished), PAUSE
    frames per device, whether every flow finished, the steps run and the
    first step a pause cycle was seen (-1: never)."""
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

    # per hop h: the link each flow crosses (null link L past its last hop)
    valid = [flows.path[:, h] >= 0 for h in range(MAXHOP)]
    path = [np.where(valid[h], flows.path[:, h], L) for h in range(MAXHOP)]
    n_hops = sum(v.astype(np.int64) for v in valid)
    cap = arr(np.concatenate([fab.cap, [1e18]]))
    lat = arr(np.concatenate([fab.lat, [0.0]]))
    caps = [cap[path[h]] for h in range(MAXHOP)]
    ecn_l = np.concatenate([fab.ecn, [False]])
    ecn_mask = [arr(ecn_l[path[h]] & valid[h]) for h in range(MAXHOP)]
    vmask = [arr(valid[h]) for h in range(MAXHOP)]
    # the ingress port of hop h's queue is the link of hop h-1 (hop 0 is the
    # host's own send queue, which PFC never pauses)
    ingress = [np.full(F, L)] + [np.where(valid[h], path[h - 1], L)
                                 for h in range(1, MAXHOP)]
    can_pause = np.concatenate([fab.is_switch[fab.dst] & fab.fabric, [False]])
    sw_sw = fab.is_switch[fab.src] & fab.is_switch[fab.dst] & fab.fabric
    acc = np.zeros(F, dt_)
    for h in range(MAXHOP):
        acc = acc + lat[path[h]] * vmask[h]
    base_rtt = np.maximum(2.0 * acc, 1e-7).astype(dt_)
    delay = np.clip(np.round(base_rtt / dt), 1, 511).astype(np.int64)
    ring = 1 << int(delay.max()).bit_length()          # > max delay
    line = cap[path[0]]
    bdp = (line * base_rtt).astype(dt_)
    # concurrent flows on each flow's busiest link (flows of one group)
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
    # per hop: the flows that have it, and their link, capacity, ECN mask
    rows = [np.nonzero(valid[h])[0] for h in range(MAXHOP)]
    hop_path = [path[h][rows[h]] for h in range(MAXHOP)]
    hop_caps = [caps[h][rows[h]] for h in range(MAXHOP)]
    hop_ecn = [ecn_mask[h][rows[h]] for h in range(MAXHOP)]
    hop_last = [n_hops[rows[h]] == h + 1 for h in range(MAXHOP)]
    ing_rows = [np.nonzero(ingress[h] < L)[0] for h in range(MAXHOP)]

    it = 0
    while it < total and not done.all():
        t = one(it) * dtd
        t_end = one(it + 1) * dtd
        # 1. signals, delayed by each flow's base RTT (a hop past a
        # flow's last adds nothing: only the flows that have it are read)
        base = (np.maximum(it - delay, 0) % ring) * (L + 1)
        qsum = np.zeros(F, dt_)
        keep = np.ones(F, dt_)
        util = np.zeros(F, dt_)
        for h in range(MAXHOP):
            r = rows[h]
            if r.size == 0:
                continue
            b = base if r.size == F else base[r]
            q_d = hist_q.take(b + hop_path[h])
            tx_d = hist_tx.take(b + hop_path[h])
            rtt_h = q_d / hop_caps[h]
            mark = np.clip((q_d - kmin) / ramp, 0.0, 1.0) * pmax * hop_ecn[h]
            u = tx_d / hop_caps[h] + q_d / (hop_caps[h] * tbu)
            if r.size == F:
                qsum = qsum + rtt_h
                keep = keep * (1.0 - mark)
                util = np.maximum(util, u)
            else:
                qsum[r] += rtt_h
                keep[r] *= 1.0 - mark
                util[r] = np.maximum(util[r], u)
        rtt = (base_rtt + qsum).astype(dt_)
        ecn = (1.0 - keep).astype(dt_)
        sig = {"t": t, "dt": dtd, "line": line, "base_rtt": base_rtt,
               "ecn": ecn, "rtt": rtt, "util": util, "F": F, "dtype": dt_}
        # 2. congestion control
        cc, rate, win = pol.update(p, cc, sig)
        cc = {k: arr(v) for k, v in cc.items()}
        rate, win = arr(rate), arr(win)
        # 3. injection once the dependency group has completed
        dep_ok = np.where(has_dep, g_count[dep0] >= gsize[dep0] - 0.5, True)
        dep_t = np.where(has_dep, g_time[dep0], 0.0).astype(dt_)
        started = dep_ok & (t >= dep_t)
        room = np.maximum(win - (injected - delivered), 0.0)
        inj = np.minimum(np.minimum(rate * dtd, room), remaining)
        inj = np.where(started & (n_hops > 0), np.maximum(inj, 0.0),
                       0.0).astype(dt_)
        backlog[0] = backlog[0] + inj
        remaining = remaining - inj
        injected = injected + inj
        # 4. paused ports send nothing
        rem_cap = (cap * dtd * ~paused).astype(dt_)
        rem_cap[L] = 1e18
        # 5. hop-ordered forwarding: each link's capacity is shared in
        # proportion to the backlog waiting for it
        tx = np.zeros(L + 1, dt_)
        for h in range(MAXHOP):
            r = rows[h]
            if r.size == 0:
                continue
            dem = _segsum(hop_path[h], backlog[h][r], L + 1, dt_)
            frac = np.where(dem > 0, np.minimum(1.0, rem_cap / np.maximum(
                dem, one(1e-9))), 0.0).astype(dt_)
            moved = backlog[h][r] * frac.take(hop_path[h])
            backlog[h][r] -= moved
            end = hop_last[h]
            delivered[r] += np.where(end, moved, 0.0).astype(dt_)
            if h + 1 < MAXHOP:
                backlog[h + 1][r] += np.where(end, 0.0, moved).astype(dt_)
            sent = frac * dem
            rem_cap = np.maximum(rem_cap - sent, 0.0).astype(dt_)
            tx = tx + sent
        # 6. queues: per egress link, and per ingress port of the receiver
        q_link = np.zeros(L + 1)
        q_port = np.zeros(L + 1)
        for h in range(MAXHOP):
            q_link += np.bincount(hop_path[h], weights=backlog[h][rows[h]],
                                  minlength=L + 1)
            r = ing_rows[h]
            if r.size:
                q_port += np.bincount(ingress[h][r], weights=backlog[h][r],
                                      minlength=L + 1)
        q_link, q_port = q_link.astype(dt_), q_port.astype(dt_)
        # 7. PFC hysteresis; a PAUSE frame on each pause and every
        # pause_resend while paused
        old = paused
        paused = np.where((q_port > xoff) & can_pause, True,
                          np.where(q_port < xon, False, old))
        frames = ((paused & ~old)[:L].astype(dt_)
                  + paused[:L].astype(dt_) * resend)
        pause_count = pause_count + _segsum(fab.dst, frames, D, dt_)
        # 8. completion at the end of this step
        newly = ~done & (delivered >= wire_size - eps)
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
    the reference, as a worker process runs it.  ``dtype_name``:
    ``float32``, or ``bfloat16`` for the control; ``max_steps`` cuts the
    step budget to that many steps."""
    import time
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
