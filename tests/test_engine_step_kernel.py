"""Fused engine-step kernel correctness (repro.kernels.engine_step).

Runs the Pallas kernels in interpret mode (the CI configuration on CPU;
``repro.kernels.default_interpret``) against the pure-jnp oracle in
``engine_step.ref`` and against the engine's jnp path end to end:

* ``fused_step`` (stages 1-2: signals + policy update) must be allclose
  (rtol 1e-5) to the reference for EVERY kernel-eligible registered
  policy, lossless and lossy, and for every flow when the flow count ends
  in a partial block of tiles;
* a full engine run with ``step_impl="pallas"`` must be allclose to
  ``step_impl="jnp"``;
* the default path (``step_impl="auto"`` -> "jnp" off-accelerator) must
  stay bitwise on the PR-2 goldens (it shares the executable with an
  explicit ``step_impl="jnp"`` by construction — asserted here), and
  ``effective_step_impl`` must report the step each policy really runs;
* ``SweepRunner`` batching decisions must follow the measured crossover
  table once ``calibrate_backend`` has cached one.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cc, sweep
from repro.core.engine import (EngineConfig, _cfg_static,
                               effective_step_impl, resolve_step_impl,
                               simulate)
from repro.kernels.engine_step import ops as es_ops
from repro.kernels.engine_step import ref as es_ref

pytestmark = pytest.mark.kernel

MAXHOP = 4
F = 200          # deliberately not a multiple of 128: exercises padding


def _rand_case(rng, n_flows=F, lossy=False):
    """Random-but-plausible stage-1 inputs for one flow population."""
    H = MAXHOP
    hopmask = (rng.random((n_flows, H)) < 0.7).astype(np.float32)
    hopmask[:, 0] = 1.0
    caps = rng.uniform(10e9, 50e9, (n_flows, H)).astype(np.float32)
    kw = dict(
        q_d=(rng.uniform(0, 3e6, (n_flows, H)) * hopmask).astype(np.float32),
        tx_d=(rng.uniform(0, 50e9, (n_flows, H)) * hopmask).astype(
            np.float32),
        caps=caps,
        ecn_mask=(rng.random((n_flows, H)) < 0.8).astype(np.float32)
        * hopmask,
        hopmask=hopmask,
        kmin_h=np.full((n_flows, H), 400e3, np.float32),
        kmax_h=np.full((n_flows, H), 1600e3, np.float32),
        pmax_h=np.full((n_flows, H), 0.2, np.float32),
        base_rtt=rng.uniform(2e-6, 20e-6, n_flows).astype(np.float32),
        line=np.full(n_flows, 25e9, np.float32),
        loss=(rng.uniform(0, 2e3, n_flows).astype(np.float32) if lossy
              else np.zeros(n_flows, np.float32)),
        t=np.float32(3.3e-4),
        dt=1e-6,
        t_base_util=1e-5,
    )
    return {k: jnp.asarray(v) if isinstance(v, np.ndarray) else v
            for k, v in kw.items()}


def _rand_state(policy, rng, n_flows=F):
    keys = cc.kernel_state_keys(policy)
    line = jnp.full((n_flows,), 25e9, jnp.float32)
    ctx = cc.FlowCtx(line=line, bdp=line * 5e-6,
                     fanin=jnp.full((n_flows,), 4.0, jnp.float32),
                     n_flows=n_flows)
    st = policy.init(ctx)
    # perturb so the update sees non-initial state
    return {k: v * jnp.asarray(rng.uniform(0.5, 1.5, n_flows), jnp.float32)
            for k, v in st.items()}, keys


ALL = list(cc.REGISTRY)


@pytest.mark.parametrize("lossy", [False, True], ids=["lossless", "lossy"])
@pytest.mark.parametrize("pol", ALL)
def test_fused_step_matches_ref(pol, lossy):
    """Kernel (interpret) vs pure-jnp oracle for every registered policy."""
    policy = cc.get_policy(pol)
    assert cc.kernel_eligible(policy)
    rng = np.random.default_rng(hash(pol) % 2**31 + lossy)
    case = _rand_case(rng, lossy=lossy)
    state, _ = _rand_state(policy, rng)
    st_k, rate_k, win_k = es_ops.fused_step(
        policy, state=state, params=None, interpret=True, **case)
    st_r, rate_r, win_r = es_ref.fused_step_ref(
        policy, state=state, params=None,
        **{k: v for k, v in case.items()})
    np.testing.assert_allclose(rate_k, rate_r, rtol=1e-5)
    np.testing.assert_allclose(win_k, win_r, rtol=1e-5)
    for k in st_r:
        np.testing.assert_allclose(st_k[k], np.broadcast_to(st_r[k], (F,)),
                                   rtol=1e-5, err_msg=f"state[{k!r}]")


@pytest.mark.parametrize("n_flows", [1100, 7936])
@pytest.mark.parametrize("pol", ["dcqcn", "hpcc"])
def test_fused_step_partial_last_block(pol, n_flows):
    """More than 8 rows of 128 flows, not a multiple of 8: the kernel's
    last block is partial and its flows must still be computed (7936 is
    the clos32 headline width)."""
    policy = cc.get_policy(pol)
    rng = np.random.default_rng(n_flows)
    case = _rand_case(rng, n_flows=n_flows)
    state, _ = _rand_state(policy, rng, n_flows=n_flows)
    st_k, rate_k, win_k = es_ops.fused_step(
        policy, state=state, params=None, interpret=True, **case)
    st_r, rate_r, win_r = es_ref.fused_step_ref(
        policy, state=state, params=None, **case)
    np.testing.assert_allclose(rate_k, rate_r, rtol=1e-5)
    np.testing.assert_allclose(win_k, win_r, rtol=1e-5)
    for k in st_r:
        np.testing.assert_allclose(
            st_k[k], np.broadcast_to(st_r[k], (n_flows,)), rtol=1e-5,
            err_msg=f"state[{k!r}]")


def test_fused_step_param_overrides_ride_smem():
    """Non-default CC params must reach the kernel (packed SMEM row)."""
    policy = cc.get_policy("dcqcn")
    rng = np.random.default_rng(7)
    case = _rand_case(rng)
    state, _ = _rand_state(policy, rng)
    # ecn_thresh=2.0 disables rate cuts entirely — guaranteed to differ
    # from the defaults on marked flows
    over = {"ecn_thresh": 2.0, "g": 0.3}
    st_k, rate_k, _ = es_ops.fused_step(policy, state=state, params=over,
                                        interpret=True, **case)
    st_r, rate_r, _ = es_ref.fused_step_ref(policy, state=state,
                                            params=over, **case)
    np.testing.assert_allclose(rate_k, rate_r, rtol=1e-5)
    # and the override actually changed the result vs defaults
    _, rate_d, _ = es_ops.fused_step(policy, state=state, params=None,
                                     interpret=True, **case)
    assert not np.allclose(rate_k, rate_d, rtol=1e-5)


def test_batched_tiles_match_per_lane():
    """B sweep lanes folded into the kernel grid == B separate calls."""
    from repro.kernels.engine_step.engine_step import (
        fused_signals_policy_tiled)
    policy = cc.get_policy("dcqcn")
    rng = np.random.default_rng(11)
    B = 3
    cases = [_rand_case(np.random.default_rng(100 + b)) for b in range(B)]
    states = [_rand_state(policy, np.random.default_rng(200 + b))[0]
              for b in range(B)]
    n_pad = (-F) % 128
    from repro.kernels.engine_step.ops import _tile_flat, _tile_hop
    hop_keys = ("q_d", "tx_d", "caps", "ecn_mask", "hopmask", "kmin_h",
                "kmax_h", "pmax_h")
    hop = tuple(jnp.concatenate([_tile_hop(c[k], n_pad, fill=1.0)
                                 for c in cases]) for k in hop_keys)
    flat = tuple(jnp.concatenate([_tile_flat(c[k], n_pad, fill=1.0)
                                  for c in cases])
                 for k in ("base_rtt", "line", "loss"))
    st4d = jnp.concatenate([
        jnp.pad(cc.pack_state(policy, s, n_flows=F), ((0, 0), (0, n_pad)),
                constant_values=1.0).reshape(1, -1, (F + n_pad) // 128, 128)
        for s in states])
    p2d = jnp.tile(cc.pack_params(policy, None)[None], (B, 1))
    outs = fused_signals_policy_tiled(
        policy, hop, flat, st4d, p2d, cases[0]["t"], dt=1e-6,
        t_base_util=1e-5, interpret=True)
    keys = cc.kernel_state_keys(policy)
    for b in range(B):
        st_r, rate_r, win_r = es_ref.fused_step_ref(
            policy, state=states[b], params=None, **cases[b])
        np.testing.assert_allclose(outs[1][b].reshape(-1)[:F],
                                   np.broadcast_to(rate_r, (F,)), rtol=1e-5)
        np.testing.assert_allclose(outs[2][b].reshape(-1)[:F],
                                   np.broadcast_to(win_r, (F,)), rtol=1e-5)
        for j, k in enumerate(keys):
            np.testing.assert_allclose(
                outs[0][b, j].reshape(-1)[:F],
                np.broadcast_to(st_r[k], (F,)), rtol=1e-5,
                err_msg=f"lane {b} state[{k!r}]")


# -- engine dispatch ---------------------------------------------------------

def _scenario():
    from repro.core.collectives import incast
    from repro.core.topology import single_switch
    topo = single_switch(8)
    return topo, incast(topo, list(range(1, 8)), 0, 5e6)


@pytest.mark.parametrize("pol", ["dcqcn", "hpcc", "pfc"])
def test_engine_pallas_matches_jnp(pol):
    """Full run: fused-kernel step vs the jnp step, same physics."""
    topo, sched = _scenario()
    cfg = EngineConfig(dt=1e-6, max_steps=1200, max_extends=2,
                       queue_stride=0)
    outs = {}
    for impl in ("jnp", "pallas"):
        outs[impl] = simulate(topo, sched, cc.get_policy(pol),
                              dataclasses.replace(cfg, step_impl=impl))
    a, b = outs["jnp"], outs["pallas"]
    assert a.finished == b.finished
    np.testing.assert_allclose(a.completion_time, b.completion_time,
                               rtol=1e-4)
    np.testing.assert_allclose(a.t_finish, b.t_finish, rtol=1e-4)
    np.testing.assert_allclose(a.delivered, b.delivered, rtol=1e-4)
    np.testing.assert_allclose(a.pause_count, b.pause_count,
                               rtol=1e-3, atol=1.0)


def test_default_impl_is_jnp_off_accelerator_and_bitwise_golden():
    """``step_impl="auto"`` resolves to the jnp step off-accelerator and
    shares its compiled executable (identical static config), so the
    default path reproduces the PR-2 goldens bitwise; one golden scenario
    is re-checked here under an explicit ``step_impl="jnp"``."""
    cfg = EngineConfig()
    expect = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert resolve_step_impl(cfg) == expect
    assert _cfg_static(cfg) == _cfg_static(
        dataclasses.replace(cfg, step_impl=resolve_step_impl(cfg)))

    from _engine_scenarios import scenarios
    gold = json.load(open(os.path.join(os.path.dirname(__file__), "golden",
                                       "engine_seed.json")))
    tag, topo, sched, pols, cfg = next(iter(scenarios()))
    g = gold[f"{tag}/{pols[0]}"]
    r = simulate(topo, sched, cc.get_policy(pols[0]),
                 dataclasses.replace(cfg, step_impl="jnp"))
    np.testing.assert_allclose(r.completion_time, g["completion_time"],
                               rtol=1e-5)


def test_effective_step_impl_reports_the_step_that_runs():
    """A kernel-eligible policy runs the kernel under "pallas"; a stacked
    product policy runs the jnp step whatever the config asks."""
    pallas = EngineConfig(step_impl="pallas")
    assert effective_step_impl(cc.get_policy("dcqcn"), pallas) == "pallas"
    assert effective_step_impl(cc.get_policy("dcqcn"),
                               EngineConfig(step_impl="jnp")) == "jnp"
    stacked = cc.stack_policies([cc.get_policy(p) for p in ALL])
    assert effective_step_impl(stacked, pallas) == "jnp"


def test_resolve_step_impl_rejects_unknown():
    with pytest.raises(ValueError):
        resolve_step_impl(EngineConfig(step_impl="vulkan"))


# -- calibration-driven batching decisions -----------------------------------

def test_pays_off_follows_measured_crossover():
    """batch/policy-axis decisions come from the cached measured table."""
    def fake(kind, n, B, cfg):
        # batched wins below 1000 flows for sweeps, never for the axis
        if kind == "sweep":
            return n, 1.0, (0.5 if n < 1000 else 2.0)
        return n, 1.0, 2.0

    sweep.reset_calibration()
    try:
        cal = sweep.calibrate_backend(probe_flows=(100, 1600), B=4,
                                      _measure=fake)
        assert cal.source == "measured"
        assert 100 < cal.crossover["sweep"] < 1600
        assert cal.crossover["policy_axis"] == 0.0
        runner = sweep.SweepRunner()
        small = type("S", (), {"n_flows": 64})()
        big = type("S", (), {"n_flows": 4096})()
        assert runner.batch_pays_off(small)
        assert not runner.batch_pays_off(big)
        assert not runner.policy_axis_pays_off()
        assert not runner.policy_axis_pays_off(small)

        # all probes winning -> batching always on, n_flows-independent
        cal = sweep.calibrate_backend(probe_flows=(100, 1600), B=4,
                                      _measure=lambda k, n, B, c:
                                      (n, 2.0, 1.0))
        assert cal.crossover["sweep"] == float("inf")
        assert runner.batch_pays_off(big)
        assert runner.policy_axis_pays_off()

        # records are JSON-serializable (inf encoded)
        rec = cal.record()
        json.dumps(rec)
        assert rec["crossover"]["sweep"] == "inf"
    finally:
        sweep.reset_calibration()


def test_calibration_defaults_match_bench_measurements():
    """Uncalibrated CPU falls back to the BENCH_engine-derived defaults."""
    sweep.reset_calibration()
    cal = sweep.get_calibration("cpu")
    assert cal.source == "default"
    assert cal.crossover == {"sweep": 2048.0, "policy_axis": 0.0}
    assert sweep.get_calibration("tpu").pays_off("sweep", 10**9)
