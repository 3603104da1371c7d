"""RoCE congestion-control policies (paper §II-D) as vectorized fluid
update rules — Policy API v2.

Each policy is a pair of pure functions over flat per-flow arrays:

    init(ctx: FlowCtx)                       -> state pytree
    update(params, state, sig: Signals)      -> (state, rate, window)

``Signals`` is the frozen per-step feedback struct produced by the engine
(all path signals delayed by the flow's base RTT); ``FlowCtx`` is the
frozen per-flow context the engine hands to ``init`` (line rate, BDP and
the deterministic schedule's static fan-in).  Both are registered pytrees,
so they ride through jit/vmap/grad untouched.

Policies are rate-based (DCQCN, TIMELY; window = inf) or window-based
(DCTCP, HPCC, StaticWindow; the engine gates injection on in-flight bytes).

Parameters are declared, not ad hoc: every policy carries a ``ParamSpec``
table (default, lo/hi bounds, log/linear tuning scale, integer flag,
init-baked flag).  The table drives

* validation  — ``Policy.check_tunable`` rejects unknown and init-baked
  names (``SweepRunner`` and ``autotune`` call it);
* autotuning  — ``repro.core.autotune`` reads scale + bounds from the spec
  (log-space descent only where declared, projection onto bounds each
  step, loud rejection of integer-valued params);
* sweeps      — ``repro.core.sweep.grid_from_spec`` generates grid axes
  from the declared ranges;
* docs        — ``policy_table_markdown`` renders the registry table
  embedded in README.md (regenerated by scripts/gen_policy_table.py).

``stack_policies([...])`` builds a *product* policy over several members:
its state is the tuple of member states and its ``update`` is a
``lax.switch`` on the traced ``_which`` selector, so a whole policy
comparison (the paper's Figs 3-11 x-axis) batches through ONE vmapped
dispatch via ``SweepRunner.run_policy_axis`` — see ``repro.core.sweep``.

StaticWindowCC is the paper's §IV-E *proposed* scheme (future work there,
implemented here): a per-flow static window sized from the deterministic
schedule's BDP, no feedback at all.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# python float, not a jnp scalar: policy updates run inside Pallas kernel
# bodies (repro.kernels.engine_step), which reject captured array constants
INF = 1e18


# ---------------------------------------------------------------------------
# typed engine<->policy contract
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Signals:
    """Per-step feedback the engine feeds every policy (paper §II-D).

    All path signals are delayed by the flow's base RTT (read from the
    engine's per-link history ring).  Registered pytree: every field is a
    traced array (scalars arrive as 0-d arrays), so Signals flow through
    jit/vmap/grad and ``lax.switch`` branches unchanged.
    """
    ecn: jnp.ndarray       # marked-traffic fraction seen along the path (F,)
    rtt: jnp.ndarray       # base RTT + queueing delay along the path    (F,)
    util: jnp.ndarray      # max_l (tx_l/cap_l + q_l/(cap_l*T)), HPCC INT (F,)
    t: jnp.ndarray         # sim time (scalar)
    dt: jnp.ndarray        # step size (scalar)
    line: jnp.ndarray      # line rate bytes/s                           (F,)
    base_rtt: jnp.ndarray  # propagation-only RTT                        (F,)
    # recent loss fraction (RTT-scale EWMA of dropped/sent, from the
    # fault layer's lossy-RoCE mode; 0 on a lossless fabric — policies
    # MUST be bitwise no-ops then, so the engine goldens hold)
    loss: object = 0.0     # (F,) or scalar 0

    def replace(self, **kw) -> "Signals":
        return dataclasses.replace(self, **kw)


jax.tree_util.register_dataclass(
    Signals,
    data_fields=("ecn", "rtt", "util", "t", "dt", "line", "base_rtt",
                 "loss"),
    meta_fields=())


@dataclasses.dataclass(frozen=True)
class FlowCtx:
    """Per-flow context for ``Policy.init``: everything the deterministic
    schedule knows ahead of time.  ``fanin`` is the number of concurrent
    flows sharing the flow's most-contended link (exactly the knowledge
    the paper says an optimized CC should exploit, §IV-E)."""
    line: jnp.ndarray      # first-hop line rate bytes/s (F,)
    bdp: jnp.ndarray       # line * base_rtt bytes       (F,)
    fanin: jnp.ndarray     # static schedule fan-in      (F,)
    n_flows: int = 0       # static flow count (== F, incl. padding)

    @classmethod
    def make(cls, line, bdp, fanin=None) -> "FlowCtx":
        line = jnp.asarray(line, jnp.float32)
        fanin = (jnp.ones_like(line) if fanin is None
                 else jnp.maximum(jnp.asarray(fanin, jnp.float32), 1.0))
        return cls(line=line, bdp=jnp.asarray(bdp, jnp.float32),
                   fanin=fanin, n_flows=int(line.shape[0]))


jax.tree_util.register_dataclass(
    FlowCtx, data_fields=("line", "bdp", "fanin"), meta_fields=("n_flows",))


# ---------------------------------------------------------------------------
# declarative parameter spaces
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One CC/fabric parameter: default value + search-space metadata.

    ``scale`` declares how tuners should move the value ("log" for
    positive scale-free knobs, "linear" for bounded fractions); ``lo/hi``
    are the physical bounds tuned values are projected onto; ``integer``
    marks count-valued params (swept, never gradient-tuned);
    ``init_baked`` marks params consumed by ``init`` (baked into the
    starting state, so runtime ``cc_params`` overrides cannot reach them).
    """
    default: float
    lo: float | None = None
    hi: float | None = None
    scale: str = "linear"          # "linear" | "log"
    integer: bool = False
    init_baked: bool = False

    def __post_init__(self):
        if self.scale not in ("linear", "log"):
            raise ValueError(f"scale must be 'linear' or 'log', "
                             f"got {self.scale!r}")
        if self.scale == "log" and self.lo is not None and self.lo <= 0:
            raise ValueError("log-scale params need a positive lo bound")

    @property
    def bounded(self) -> bool:
        return self.lo is not None and self.hi is not None

    def clip(self, v: float) -> float:
        if self.lo is not None:
            v = max(v, self.lo)
        if self.hi is not None:
            v = min(v, self.hi)
        return v


def _specs(meta: dict, **defaults) -> dict:
    """Instantiate a static spec table with this factory call's defaults."""
    return {k: dataclasses.replace(meta[k], default=float(v))
            for k, v in defaults.items()}


@dataclasses.dataclass(frozen=True)
class Policy:
    name: str
    spec: dict                     # {param name: ParamSpec}
    init: Callable                 # FlowCtx -> state pytree
    update: Callable               # (params, state, Signals) -> (state, rate, win)
    wire_factor: float = 1.0       # per-packet header overhead (HPCC INT)
    kind: str = "rate"             # "rate" | "window" | "mixed" (stacked)
    members: tuple = ()            # stacked policies: member labels
    key_extra: tuple = ()          # extra compile-cache identity (stacked)
    loss_aware: bool = False       # reacts to Signals.loss (lossy RoCE)

    @property
    def params(self) -> dict:
        """Default parameter values (the traced ``cc_params`` pytree)."""
        return {k: s.default for k, s in self.spec.items()}

    @property
    def init_params(self) -> tuple:
        """Names consumed by ``init`` (baked into the starting state)."""
        return tuple(k for k, s in self.spec.items() if s.init_baked)

    @property
    def tunable(self) -> tuple:
        """Names overridable per run via ``cc_params`` (sweeps/tuning)."""
        return tuple(k for k, s in self.spec.items() if not s.init_baked)

    def param_spec(self, key: str) -> ParamSpec:
        try:
            return self.spec[key]
        except KeyError:
            raise KeyError(f"unknown {self.name} param {key!r}; known: "
                           f"{sorted(self.spec)}") from None

    def check_tunable(self, keys):
        """Reject params that cc_params cannot actually influence."""
        unknown = set(keys) - set(self.spec)
        if unknown:
            raise ValueError(
                f"unknown {self.name} params {sorted(unknown)}; tunable: "
                f"{sorted(self.tunable)}")
        baked = [k for k in keys if self.spec[k].init_baked]
        if baked:
            raise ValueError(
                f"{self.name} params {sorted(baked)} are consumed by init "
                "(baked into the starting state) and cannot be overridden "
                "via cc_params; rebuild the policy via its factory instead")


def _full(F, v):
    return jnp.full((F,), v, jnp.float32)


# ---------------------------------------------------------------------------
# Baseline: PFC only (no end-to-end CC; link-layer pauses do the work)
# ---------------------------------------------------------------------------

def make_pfc_only() -> Policy:
    def init(ctx):
        return {}

    def update(params, st, sig):
        return st, sig.line, _full(sig.line.shape[0], INF)

    return Policy("pfc", {}, init, update, kind="rate")


# ---------------------------------------------------------------------------
# DCQCN (Zhu et al., SIGCOMM'15)
# ---------------------------------------------------------------------------

_DCQCN_SPECS = {
    "g": ParamSpec(1 / 256, lo=1e-4, hi=1.0, scale="log"),
    "rai_frac": ParamSpec(0.03, lo=1e-4, hi=0.5, scale="log"),
    "rhai_frac": ParamSpec(0.05, lo=1e-4, hi=1.0, scale="log"),
    "timer": ParamSpec(55e-6, lo=1e-6, hi=5e-3, scale="log"),
    "cut_gap": ParamSpec(50e-6, lo=1e-6, hi=5e-3, scale="log"),
    "fast_rounds": ParamSpec(5, lo=0, hi=20, integer=True),
    "hai_after": ParamSpec(5, lo=0, hi=20, integer=True),
    "ecn_thresh": ParamSpec(0.01, lo=1e-4, hi=1.0, scale="log"),
    "mss": ParamSpec(1000.0, lo=256.0, hi=9000.0, scale="log"),
}


def _dcqcn_jitter(n_flows: int) -> jnp.ndarray:
    """A fixed per-flow hash in [0.9, 1.1), computed on the host so that
    every backend gets the same values: in-graph, XLA:TPU's f32 remainder
    is inexact once ``k * 7919`` passes 2**24.  The values are those of
    the f32 expression ``0.9 + 0.2 * ((k * 7919 % 97) / 97)`` as XLA:CPU
    evaluates it, with the final multiply-add fused (rounded once)."""
    k = np.arange(n_flows, dtype=np.float32)
    q = (k * np.float32(7919)) % np.float32(97) / np.float32(97.0)
    fused = (np.float64(np.float32(0.9))
             + np.float64(np.float32(0.2)) * q.astype(np.float64))
    return jnp.asarray(fused.astype(np.float32))


def make_dcqcn(g: float = 1 / 256, rai_frac: float = 0.03,
               rhai_frac: float = 0.05, timer: float = 55e-6,
               cut_gap: float = 50e-6, fast_rounds: int = 5,
               hai_after: int = 5, ecn_thresh: float = 0.01,
               mss: float = 1000.0) -> Policy:
    spec = _specs(_DCQCN_SPECS, g=g, rai_frac=rai_frac, rhai_frac=rhai_frac,
                  timer=timer, cut_gap=cut_gap, fast_rounds=fast_rounds,
                  hai_after=hai_after, ecn_thresh=ecn_thresh, mss=mss)

    def init(ctx):
        F, line = ctx.n_flows, ctx.line
        # +-10% per-flow timer jitter: desynchronizes the post-cut sawtooth
        # (packet simulators get this for free from stochastic marking).
        jit = _dcqcn_jitter(F)
        return {
            "rc": _full(F, 0) + line, "rt": _full(F, 0) + line,
            "alpha": _full(F, 1.0), "jit": jit,
            "t_cut": _full(F, -1.0), "t_inc": _full(F, 0.0),
            "t_alpha": _full(F, 0.0), "inc_count": _full(F, 0.0),
        }

    def update(p, st, sig):
        t, line = sig.t, sig.line
        # CNPs are generated by the receiver from packets marked at ENQUEUE:
        # a flow sending few packets receives few CNPs.  Fluidization:
        # P(>=1 CNP per window) = 1 - exp(-pkts_in_window * mark_prob).
        jit = st["jit"]
        pkts = st["rc"] * p["cut_gap"] / p["mss"]
        # lossy RoCE: NACK-driven cuts — treat loss like extra marking
        # (guarded where so loss==0 stays bitwise-identical to lossless)
        ecn_eff = jnp.where(sig.loss > 0,
                            jnp.minimum(sig.ecn + 2.0 * sig.loss, 1.0),
                            sig.ecn)
        p_cnp = 1.0 - jnp.exp(-pkts * ecn_eff)
        cong = p_cnp > p["ecn_thresh"]
        docut = cong & ((t - st["t_cut"]) >= p["cut_gap"] * jit)
        rt = jnp.where(docut, st["rc"], st["rt"])
        rc = jnp.where(docut, st["rc"] * (1 - st["alpha"] / 2 * p_cnp), st["rc"])
        alpha = jnp.where(docut,
                          (1 - p["g"] * p_cnp) * st["alpha"] + p["g"] * p_cnp,
                          st["alpha"])
        t_cut = jnp.where(docut, t, st["t_cut"])
        inc_count = jnp.where(docut, 0.0, st["inc_count"])
        t_inc = jnp.where(docut, t, st["t_inc"])

        # alpha decay when no CNP for `timer`
        dodec = (~cong) & ((t - st["t_alpha"]) >= p["timer"] * jit)
        alpha = jnp.where(dodec, (1 - p["g"]) * alpha, alpha)
        t_alpha = jnp.where(dodec | docut, t, st["t_alpha"])

        # rate increase every `timer`: fast recovery -> additive -> hyper
        doinc = (t - t_inc) >= p["timer"] * jit
        inc_count = jnp.where(doinc, inc_count + 1, inc_count)
        additive = inc_count > p["fast_rounds"]
        hyper = inc_count > p["fast_rounds"] + p["hai_after"]
        bump = jnp.where(hyper, p["rhai_frac"], p["rai_frac"]) * line
        rt = jnp.where(doinc & additive, rt + bump, rt)
        rc = jnp.where(doinc, 0.5 * (rt + rc), rc)
        t_inc = jnp.where(doinc, t, t_inc)

        rc = jnp.clip(rc, 0.001 * line, line)
        rt = jnp.clip(rt, 0.001 * line, line)
        st2 = {"rc": rc, "rt": rt, "alpha": alpha, "jit": jit, "t_cut": t_cut,
               "t_inc": t_inc, "t_alpha": t_alpha, "inc_count": inc_count}
        return st2, rc, _full(rc.shape[0], INF)

    return Policy("dcqcn", spec, init, update, kind="rate", loss_aware=True)


# ---------------------------------------------------------------------------
# DCTCP (Alizadeh et al., SIGCOMM'10), line-rate start as in HPCC's port
# ---------------------------------------------------------------------------

_DCTCP_SPECS = {
    "g": ParamSpec(1 / 16, lo=1e-3, hi=1.0, scale="log"),
    "mss": ParamSpec(1000.0, lo=256.0, hi=9000.0, scale="log"),
    "ecn_thresh": ParamSpec(0.01, lo=1e-4, hi=1.0, scale="log"),
    "wmax_bdp": ParamSpec(32.0, lo=1.0, hi=128.0, scale="log"),
}


def make_dctcp(g: float = 1 / 16, mss: float = 1000.0,
               ecn_thresh: float = 0.01, wmax_bdp: float = 32.0) -> Policy:
    spec = _specs(_DCTCP_SPECS, g=g, mss=mss, ecn_thresh=ecn_thresh,
                  wmax_bdp=wmax_bdp)

    def init(ctx):
        F = ctx.n_flows
        return {"w": ctx.bdp * 1.0, "alpha": _full(F, 0.0),
                "t_rtt": _full(F, 0.0), "bdp": ctx.bdp}

    def update(p, st, sig):
        t = sig.t
        rtt = jnp.maximum(sig.rtt, 1e-6)
        do = (t - st["t_rtt"]) >= rtt
        # lossy RoCE: dropped packets feed the congestion estimator just
        # like marks (DCTCP's alpha is a fraction-of-congested-traffic
        # EWMA); guarded where keeps loss==0 bitwise-lossless
        ecn_eff = jnp.where(sig.loss > 0,
                            jnp.minimum(sig.ecn + 2.0 * sig.loss, 1.0),
                            sig.ecn)
        alpha = jnp.where(do, (1 - p["g"]) * st["alpha"] + p["g"] * ecn_eff,
                          st["alpha"])
        marked = ecn_eff > p["ecn_thresh"]
        w = jnp.where(do & marked, st["w"] * (1 - alpha / 2), st["w"])
        w = jnp.where(do & ~marked, w + p["mss"], w)
        t_rtt = jnp.where(do, t, st["t_rtt"])
        w = jnp.clip(w, p["mss"], p["wmax_bdp"] * st["bdp"])
        rate = sig.line
        return {"w": w, "alpha": alpha, "t_rtt": t_rtt, "bdp": st["bdp"]}, rate, w

    return Policy("dctcp", spec, init, update, kind="window",
                  loss_aware=True)


# ---------------------------------------------------------------------------
# TIMELY (Mittal et al., SIGCOMM'15)
# ---------------------------------------------------------------------------

_TIMELY_SPECS = {
    "tlow": ParamSpec(30e-6, lo=1e-6, hi=1e-3, scale="log"),
    "thigh": ParamSpec(300e-6, lo=1e-5, hi=1e-2, scale="log"),
    "beta": ParamSpec(0.8, lo=0.01, hi=1.0, scale="linear"),
    "add_frac": ParamSpec(0.002, lo=1e-5, hi=0.1, scale="log"),
    "ewma": ParamSpec(0.3, lo=0.01, hi=1.0, scale="linear"),
    "hai_thresh": ParamSpec(5, lo=1, hi=20, integer=True),
}


def make_timely(tlow: float = 30e-6, thigh: float = 300e-6, beta: float = 0.8,
                add_frac: float = 0.002, ewma: float = 0.3,
                hai_thresh: int = 5) -> Policy:
    spec = _specs(_TIMELY_SPECS, tlow=tlow, thigh=thigh, beta=beta,
                  add_frac=add_frac, ewma=ewma, hai_thresh=hai_thresh)

    def init(ctx):
        F = ctx.n_flows
        return {"rate": _full(F, 0) + ctx.line, "rtt_prev": _full(F, 0.0),
                "grad": _full(F, 0.0), "t_upd": _full(F, 0.0),
                "neg_count": _full(F, 0.0)}

    def update(p, st, sig):
        t = sig.t
        line = sig.line
        rtt = sig.rtt
        minrtt = jnp.maximum(sig.base_rtt, 1e-6)
        period = jnp.maximum(minrtt, 20e-6)
        do = (t - st["t_upd"]) >= period

        grad_new = (rtt - st["rtt_prev"]) / minrtt
        grad = jnp.where(do, (1 - p["ewma"]) * st["grad"] + p["ewma"] * grad_new,
                         st["grad"])
        delta = p["add_frac"] * line
        neg = jnp.where(do & (grad <= 0), st["neg_count"] + 1, 0.0)
        hai = neg >= p["hai_thresh"]

        r = st["rate"]
        r_low = r + jnp.where(hai, 5.0 * delta, delta)
        r_high = r * (1 - p["beta"] * (1 - p["thigh"] / jnp.maximum(rtt, p["thigh"])))
        gnorm = jnp.clip(grad, 0.0, 1.0)
        r_grad = jnp.where(grad <= 0, r + jnp.where(hai, 5.0, 1.0) * delta,
                           r * (1 - p["beta"] * gnorm))
        r_new = jnp.where(rtt < p["tlow"], r_low,
                          jnp.where(rtt > p["thigh"], r_high, r_grad))
        rate = jnp.where(do, jnp.clip(r_new, 0.001 * line, line), r)
        # lossy RoCE: TIMELY sees loss as retransmission timeouts — a
        # multiplicative cut on update ticks, scaled by the loss fraction
        # (guarded where: loss==0 is bitwise-lossless)
        rate = jnp.where((sig.loss > 0) & do,
                         jnp.clip(rate * (1.0 - p["beta"]
                                          * jnp.minimum(2.0 * sig.loss, 1.0)),
                                  0.001 * line, line),
                         rate)
        rtt_prev = jnp.where(do, rtt, st["rtt_prev"])
        t_upd = jnp.where(do, t, st["t_upd"])
        st2 = {"rate": rate, "rtt_prev": rtt_prev, "grad": grad, "t_upd": t_upd,
               "neg_count": neg}
        return st2, rate, _full(rate.shape[0], INF)

    return Policy("timely", spec, init, update, kind="rate",
                  loss_aware=True)


# ---------------------------------------------------------------------------
# HPCC (Li et al., SIGCOMM'19) — INT-based; +4.8% wire overhead
# ---------------------------------------------------------------------------

_HPCC_SPECS = {
    "eta": ParamSpec(0.95, lo=0.5, hi=1.0, scale="linear"),
    "wai_frac": ParamSpec(0.001, lo=1e-5, hi=0.1, scale="log"),
    "max_stage": ParamSpec(5, lo=0, hi=20, integer=True),
}


def make_hpcc(eta: float = 0.95, wai_frac: float = 0.001, max_stage: int = 5,
              wire_factor: float = 1.048) -> Policy:
    spec = _specs(_HPCC_SPECS, eta=eta, wai_frac=wai_frac,
                  max_stage=max_stage)

    def init(ctx):
        F = ctx.n_flows
        return {"w": ctx.bdp * 1.0, "wc": ctx.bdp * 1.0,
                "t_rtt": _full(F, 0.0), "stage": _full(F, 0.0),
                "bdp": ctx.bdp}

    def update(p, st, sig):
        t = sig.t
        u = jnp.maximum(sig.util, 1e-3)
        # lossy RoCE: a dropped packet means the INT-observed path was
        # oversubscribed somewhere invisible to util — force u above eta
        # so the window takes the multiplicative branch (guarded where:
        # loss==0 is bitwise-lossless)
        u = jnp.where(sig.loss > 0,
                      jnp.maximum(u, 1.0 + 2.0 * sig.loss), u)
        wai = p["wai_frac"] * st["bdp"]
        mult = st["wc"] * (p["eta"] / u) + wai
        addv = st["wc"] + wai
        use_mult = (u >= p["eta"]) | (st["stage"] >= p["max_stage"])
        w = jnp.where(use_mult, mult, addv)
        w = jnp.clip(w, wai, 16.0 * st["bdp"])
        rtt = jnp.maximum(sig.base_rtt, 1e-6)
        do = (t - st["t_rtt"]) >= rtt
        wc = jnp.where(do, w, st["wc"])
        stage = jnp.where(do, jnp.where(use_mult, 0.0, st["stage"] + 1), st["stage"])
        t_rtt = jnp.where(do, t, st["t_rtt"])
        rate = w / rtt
        st2 = {"w": w, "wc": wc, "t_rtt": t_rtt, "stage": stage, "bdp": st["bdp"]}
        return st2, jnp.minimum(rate, sig.line), w

    return Policy("hpcc", spec, init, update, wire_factor=wire_factor,
                  kind="window", loss_aware=True)


def make_hpcc_pint(eta: float = 0.95, wai_frac: float = 0.001,
                   max_stage: int = 5) -> Policy:
    """HPCC with probabilistic INT: ~1 byte overhead, coarser feedback
    (modelled as 2x slower reference-window refresh)."""
    base = make_hpcc(eta, wai_frac, max_stage, wire_factor=1.001)

    def update(p, st, sig):
        sig = sig.replace(base_rtt=sig.base_rtt * 2.0)  # delayed feedback
        return base.update(p, st, sig)

    return Policy("hpcc_pint", base.spec, base.init, update,
                  wire_factor=1.001, kind="window", loss_aware=True)


# ---------------------------------------------------------------------------
# StaticWindow — the paper's §IV-E proposal (our beyond-paper contribution)
# ---------------------------------------------------------------------------

_STATIC_WINDOW_SPECS = {
    "margin": ParamSpec(2.0, lo=0.1, hi=32.0, scale="log", init_baked=True),
    "headroom": ParamSpec(0.5e6, lo=1e3, hi=64e6, scale="log",
                          init_baked=True),
    "min_w": ParamSpec(4000.0, lo=100.0, hi=1e6, scale="log",
                       init_baked=True),
}


def make_static_window(margin: float = 2.0, headroom: float = 0.5e6,
                       min_w: float = 4000.0) -> Policy:
    """The paper's §IV-E proposal: windows set *statically from the
    deterministic schedule*.  Each flow gets its fair share of path
    bandwidth-delay product plus its fair share of a per-port headroom
    budget: W = margin*BDP/fanin + headroom/fanin, where ``fanin`` is the
    number of flows sharing the flow's most-contended link — known ahead
    of time because training communication is deterministic.  Zero per-ack
    computation; buffer use bounded below the PFC threshold by
    construction => (near-)zero PAUSE frames."""
    spec = _specs(_STATIC_WINDOW_SPECS, margin=margin, headroom=headroom,
                  min_w=min_w)

    def init(ctx):
        f = ctx.fanin
        w = margin * ctx.bdp / f + headroom / f
        return {"w": jnp.maximum(w, min_w)}

    def update(p, st, sig):
        return st, sig.line, st["w"]

    return Policy("static_window", spec, init, update, kind="window")


# ---------------------------------------------------------------------------
# stacked policies: the batched policy axis
# ---------------------------------------------------------------------------

def _member_token(p: Policy) -> tuple:
    """Hashable identity of one member's logic for the compile cache."""
    return (p.name, float(p.wire_factor),
            getattr(p.init, "__code__", p.init),
            getattr(p.update, "__code__", p.update),
            tuple(sorted((k, float(v)) for k, v in p.params.items())),
            p.key_extra)


def stack_labels(policies) -> list:
    """Unique member labels for a policy stack (name, or name<i> on
    duplicates) — the namespace prefix of the stacked param table."""
    names = [p if isinstance(p, str) else p.name for p in policies]
    labels = []
    for i, n in enumerate(names):
        labels.append(n if names.count(n) == 1 else f"{n}{i}")
    return labels


def stack_policies(policies) -> Policy:
    """Product policy over ``policies`` (names or Policy objects).

    State is the tuple of every member's state; ``update`` is a
    ``lax.switch`` over member updates on the traced integer selector
    ``_which`` — so a vmap over ``_which`` runs the whole policy
    comparison in one compiled dispatch (``SweepRunner.run_policy_axis``).
    Member params are namespaced ``"<label>.<param>"``; ``_wire`` carries
    the selected member's wire factor (HPCC INT overhead) as a traced
    input, since it differs across members.

    Under vmap, ``lax.switch`` evaluates every branch and selects per
    lane; the CC update is O(F) arithmetic while the engine step is
    dominated by the per-link gather reductions, so the product costs
    little — and the fluid forwarding itself batches perfectly.
    """
    members = [get_policy(p) if isinstance(p, str) else p for p in policies]
    if len(members) < 2:
        raise ValueError("stack_policies needs at least two policies")
    labels = stack_labels(members)
    n = len(members)

    spec = {
        "_which": ParamSpec(0, lo=0, hi=n - 1, integer=True),
        "_wire": ParamSpec(float(members[0].wire_factor),
                           lo=1.0, hi=1.1, scale="linear"),
    }
    for lab, m in zip(labels, members):
        for k, s in m.spec.items():
            spec[f"{lab}.{k}"] = s

    def init(ctx):
        return tuple(m.init(ctx) for m in members)

    def update(p, st, sig):
        which = jnp.asarray(p["_which"], jnp.int32)

        def make_branch(i):
            lab, m = labels[i], members[i]

            def branch(operand):
                st, sig = operand
                mp = {k: p[f"{lab}.{k}"] for k in m.spec}
                sub, rate, win = m.update(mp, st[i], sig)
                new_st = tuple(sub if j == i else st[j] for j in range(n))
                return new_st, rate, win

            return branch

        return lax.switch(which, [make_branch(i) for i in range(n)],
                          (st, sig))

    return Policy(name="stack(" + "+".join(labels) + ")",
                  spec=spec, init=init, update=update,
                  kind="mixed", members=tuple(labels),
                  key_extra=tuple(_member_token(m) for m in members),
                  loss_aware=any(m.loss_aware for m in members))


def make_mlp(**kw) -> Policy:
    """The learned policy (repro.learn.net) — deferred import so the
    registry stays importable without the learn package in the loop (and
    repro.learn.net can import this module's Policy/ParamSpec types)."""
    from repro.learn.net import make_mlp as _mk
    return _mk(**kw)


REGISTRY = {
    "pfc": make_pfc_only,
    "dcqcn": make_dcqcn,
    "dctcp": make_dctcp,
    "timely": make_timely,
    "hpcc": make_hpcc,
    "hpcc_pint": make_hpcc_pint,
    "static_window": make_static_window,
    "mlp": make_mlp,
}


# ---------------------------------------------------------------------------
# kernel-friendly flat-array form (consumed by repro.kernels.engine_step)
#
# Every registered (non-stacked) policy keeps its state as a dict of (F,)
# float32 arrays and its params as a flat dict of scalars, so both pack
# into dense arrays ordered by sorted key: state -> (K, F), params -> (P,).
# That is exactly the layout a tiled Pallas kernel wants — one stacked
# state tensor indexed by a static key order instead of a pytree.
# ---------------------------------------------------------------------------

_KERNEL_KEYS_CACHE: dict = {}


def kernel_state_keys(policy: Policy):
    """Sorted state-key order of a kernel-eligible policy, else ``None``.

    Eligible = non-stacked with a dict-of-(F,)-float32 state (all eight
    registered policies qualify, the learned ``mlp`` included;
    ``stack_policies`` products, whose state is a tuple of member states,
    do not).
    """
    ck = (policy.name, getattr(policy.init, "__code__", policy.init),
          tuple(sorted((k, float(v)) for k, v in policy.params.items())))
    if ck in _KERNEL_KEYS_CACHE:
        return _KERNEL_KEYS_CACHE[ck]
    keys = None
    if not policy.members:
        probe = jax.eval_shape(
            policy.init,
            FlowCtx(line=jax.ShapeDtypeStruct((8,), jnp.float32),
                    bdp=jax.ShapeDtypeStruct((8,), jnp.float32),
                    fanin=jax.ShapeDtypeStruct((8,), jnp.float32),
                    n_flows=8))
        if (isinstance(probe, dict)
                and all(v.shape == (8,) and v.dtype == jnp.float32
                        for v in probe.values())):
            keys = tuple(sorted(probe))
    _KERNEL_KEYS_CACHE[ck] = keys
    return keys


def kernel_eligible(policy: Policy) -> bool:
    """True if the fused engine_step kernel can run this policy's update."""
    return kernel_state_keys(policy) is not None


def kernel_param_keys(policy: Policy) -> tuple:
    """Sorted param order for the packed (P,) kernel param vector."""
    return tuple(sorted(policy.spec))


def pack_state(policy: Policy, state: dict,
               n_flows: int | None = None) -> jnp.ndarray:
    """dict of (F,) float32 -> (K, F) stacked in ``kernel_state_keys``
    order; K >= 1 (a stateless policy gets one dummy zero row, sized by
    ``n_flows``, so block shapes never degenerate)."""
    keys = kernel_state_keys(policy)
    if keys is None:
        raise ValueError(f"policy {policy.name!r} is not kernel-eligible")
    if not keys:
        if n_flows is None:
            raise ValueError("pack_state needs n_flows for a stateless "
                             "policy")
        return jnp.zeros((1, n_flows), jnp.float32)
    return jnp.stack([jnp.asarray(state[k], jnp.float32) for k in keys])


def unpack_state(policy: Policy, packed: jnp.ndarray) -> dict:
    """(K, F) -> dict in ``kernel_state_keys`` order (inverse of pack)."""
    keys = kernel_state_keys(policy)
    if keys is None:
        raise ValueError(f"policy {policy.name!r} is not kernel-eligible")
    return {k: packed[j] for j, k in enumerate(keys)}


def pack_params(policy: Policy, params: dict | None = None) -> jnp.ndarray:
    """Flat params dict -> (P,) float32 in ``kernel_param_keys`` order;
    P >= 1 (param-free policies get one dummy zero)."""
    params = dict(policy.params, **(params or {}))
    keys = kernel_param_keys(policy)
    if not keys:
        return jnp.zeros((1,), jnp.float32)
    return jnp.stack([jnp.asarray(params[k], jnp.float32) for k in keys])


def flat_update(policy: Policy) -> Callable:
    """``policy.update`` adapted to tiled-array calling conventions.

    Returns ``fn(params_dict, state_dict, sig) -> (state_dict', rate, win)``
    where every per-flow array (state values, Signals fields, rate, win)
    may be any shape — in the Pallas kernel they are (sublane, lane) tiles
    rather than flat (F,) vectors.  The only policy idiom that breaks under
    that generalization is ``jnp.full((x.shape[0],), v)`` for constant
    rate/window outputs; those are constant per flow, so the adapter
    rebroadcasts them to the tile shape.
    """
    keys = kernel_state_keys(policy)
    if keys is None:
        raise ValueError(f"policy {policy.name!r} is not kernel-eligible")

    def _tileize(a, shape):
        a = jnp.asarray(a, jnp.float32)
        if a.shape == shape:
            return a
        if a.ndim == 1 and len(shape) == 2 and a.shape[0] == shape[0]:
            return jnp.broadcast_to(a[:, None], shape)
        return jnp.broadcast_to(a, shape)

    def update(params, state, sig):
        shape = jnp.shape(sig.line)
        st2, rate, win = policy.update(params, state, sig)
        st2 = {k: _tileize(v, shape) for k, v in st2.items()}
        return st2, _tileize(rate, shape), _tileize(win, shape)

    return update

ALL_POLICIES = tuple(REGISTRY)


def get_policy(name: str, **kw) -> Policy:
    return REGISTRY[name](**kw)


# ---------------------------------------------------------------------------
# registry-driven docs (README table; see scripts/gen_policy_table.py)
# ---------------------------------------------------------------------------

def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e6:
        return str(int(v))
    e = math.floor(math.log10(abs(v))) if v else 0
    if -3 <= e <= 5:
        return f"{v:g}"
    return f"{v:.3g}"


def _is_weight_key(k: str) -> bool:
    """Learned-policy weight params (``w1_00``, ``b2_1``, ...): collapsed
    in the rendered table — 38 near-arbitrary floats are not docs."""
    return len(k) > 1 and k[0] in "wb" and k[1].isdigit()


def policy_table_markdown() -> str:
    """The registry policy table embedded in README.md — generated, so it
    cannot go stale (a test asserts the README copy matches)."""
    lines = ["| policy | kind | wire | loss | "
             "tunable params (default, bounds, scale) |",
             "|---|---|---|---|---|"]
    for name in ALL_POLICIES:
        p = REGISTRY[name]()
        weight_keys = [k for k in p.spec if _is_weight_key(k)]
        collapse = len(weight_keys) > 8
        cells = []
        for k, s in p.spec.items():
            if collapse and _is_weight_key(k):
                continue
            tag = "int" if s.integer else s.scale
            if s.init_baked:
                tag += ", init-baked"
            bounds = (f"{_fmt(s.lo)}..{_fmt(s.hi)}" if s.bounded else "-")
            cells.append(f"`{k}`={_fmt(s.default)} [{bounds}, {tag}]")
        if collapse:
            s = p.spec[weight_keys[0]]
            cells.append(f"`w*`/`b*`: {len(weight_keys)} trained weights "
                         f"[{_fmt(s.lo)}..{_fmt(s.hi)}, {s.scale}]")
        loss = "yes" if p.loss_aware else "—"
        lines.append(f"| `{name}` | {p.kind} | {p.wire_factor:g} | {loss} | "
                     + ("; ".join(cells) if cells else "—") + " |")
    return "\n".join(lines)
