"""Engine rewrite safety net: the scatter-free / early-exit / batched
engine must reproduce the PR-1 seed engine's physics.

Golden data (tests/golden/engine_seed.json) was generated by the seed
engine — per-hop dense scatter-adds, fixed 512-slot history ring, host-
synced segment extends — via scripts/gen_engine_goldens.py.
"""
import json
import os

import numpy as np
import pytest

from _engine_scenarios import scenarios
from repro.core.cc import get_policy
from repro.core.collectives import ScheduleBuilder, incast
from repro.core.engine import EngineConfig, Simulator, simulate
from repro.core.sweep import SweepRunner
from repro.core.topology import single_switch

GOLD = json.load(open(os.path.join(os.path.dirname(__file__), "golden",
                                   "engine_seed.json")))

CASES = [(tag, topo, sched, pol, cfg)
         for tag, topo, sched, pols, cfg in scenarios() for pol in pols]


@pytest.mark.parametrize("tag,topo,sched,pol,cfg",
                         CASES, ids=[f"{t}-{p}" for t, _, _, p, _ in CASES])
def test_matches_seed_engine(tag, topo, sched, pol, cfg):
    g = GOLD[f"{tag}/{pol}"]
    r = simulate(topo, sched, get_policy(pol), cfg)
    assert r.finished == g["finished"]
    np.testing.assert_allclose(r.completion_time, g["completion_time"],
                               rtol=1e-5)
    t_gold = np.array([np.inf if v is None else v for v in g["t_finish"]])
    # completion is quantized to steps, like the PAUSE counts below: a
    # rounding change (another XLA version) can flip the step in which a
    # flow's last bytes clear eps_done, so each t_finish may move by one dt
    np.testing.assert_allclose(r.t_finish, t_gold, rtol=1e-5, atol=cfg.dt)
    # delivered carries the eps_done completion slack (512 B/flow), so the
    # aggregate gets a correspondingly looser tolerance than t_finish
    np.testing.assert_allclose(r.delivered.sum(), g["delivered_sum"],
                               rtol=1e-4)
    # PAUSE frames are step-quantized events behind an X_OFF/X_ON
    # hysteresis: a 1e-7 rounding difference in a port's queue right at the
    # threshold shifts an un/pause by a step, so event counts get a looser
    # tolerance than the continuous metrics above.
    np.testing.assert_allclose(r.pause_count, np.asarray(g["pause_count"]),
                               rtol=1e-3, atol=1.0)


def _tiny_case():
    topo = single_switch(8)
    sched = incast(topo, list(range(1, 8)), 0, 5e6)
    return topo, sched


def test_early_exit_bitwise_equals_monolithic():
    """Chunked while_loop stepping must be *bitwise* identical to one
    monolithic scan: finished steps are exact no-ops, so results can never
    depend on chunk_steps."""
    topo, sched = _tiny_case()
    # deliberately awkward chunk size: boundaries never align with done
    cfg = EngineConfig(dt=1e-6, max_steps=1500, max_extends=2, chunk_steps=177)
    sim = Simulator(topo, sched, get_policy("dcqcn"), cfg)
    r_fast = sim.run(early_exit=True)
    r_full = sim.run(early_exit=False)
    assert r_fast.finished and r_full.finished
    assert r_fast.meta["steps_run"] < r_full.meta["steps_run"]
    assert np.array_equal(r_fast.t_finish, r_full.t_finish)
    assert np.array_equal(r_fast.pause_count, r_full.pause_count)
    assert np.array_equal(r_fast.delivered, r_full.delivered)


def test_padding_is_inert():
    """Shape-bucket padding (inert flows/groups) must not change physics."""
    topo, sched = _tiny_case()
    cfg = EngineConfig(dt=1e-6, max_steps=1500, max_extends=2)
    base = Simulator(topo, sched, get_policy("dctcp"), cfg).run()
    padded = Simulator(topo, sched, get_policy("dctcp"), cfg,
                       pad_flows=sched.n_flows + 17,
                       pad_groups=sched.n_groups + 3).run()
    assert padded.t_finish.shape == base.t_finish.shape
    assert np.array_equal(base.t_finish, padded.t_finish)
    assert np.array_equal(base.pause_count, padded.pause_count)
    np.testing.assert_allclose(base.delivered, padded.delivered, rtol=1e-6)


def test_queue_stride_subsamples_timeline():
    topo, sched = _tiny_case()
    cfg1 = EngineConfig(dt=1e-6, max_steps=1200, max_extends=1, queue_stride=1)
    cfg4 = EngineConfig(dt=1e-6, max_steps=1200, max_extends=1, queue_stride=4)
    r1 = Simulator(topo, sched, get_policy("pfc"), cfg1).run()
    r4 = Simulator(topo, sched, get_policy("pfc"), cfg4).run()
    n = len(r4.dev_queue)
    assert n > 0
    assert np.array_equal(r4.dev_queue, r1.dev_queue[::4][:n])
    # stride 0 disables recording entirely
    cfg0 = EngineConfig(dt=1e-6, max_steps=1200, max_extends=1, queue_stride=0)
    r0 = Simulator(topo, sched, get_policy("pfc"), cfg0).run()
    assert r0.dev_queue.size == 0
    assert np.array_equal(r0.t_finish, r1.t_finish)


@pytest.mark.parametrize("pol", ["dcqcn", "dctcp", "hpcc"])
def test_sweep_batch_matches_serial(pol):
    """vmap-batched parameter sweeps must match per-params serial runs."""
    topo, sched = _tiny_case()
    cfg = EngineConfig(dt=1e-6, max_steps=1500, max_extends=2, queue_stride=0)
    runner = SweepRunner(cfg)
    policy = get_policy(pol)
    # member 0 = defaults, member 1 = every tunable scaled by 1.3
    stacked = {k: np.asarray([float(v), float(v) * 1.3], np.float32)
               for k, v in policy.params.items()}
    batch = runner.run_batch(topo, sched, policy, stacked)
    assert batch.n == 2
    for i in range(2):
        serial = runner.simulator(topo, sched, policy, cfg).run(
            cc_params=batch.param_set(i))
        assert bool(batch.finished[i]) == serial.finished
        np.testing.assert_allclose(batch.t_finish[i], serial.t_finish,
                                   rtol=1e-5)
        np.testing.assert_allclose(batch.completion_time[i],
                                   serial.completion_time, rtol=1e-5)
        np.testing.assert_allclose(batch.pause_count[i], serial.pause_count,
                                   rtol=1e-3, atol=1.0)


def test_batch_byte_conservation():
    topo, sched = _tiny_case()
    cfg = EngineConfig(dt=1e-6, max_steps=1500, max_extends=3, queue_stride=0)
    runner = SweepRunner(cfg)
    batch = runner.grid(topo, sched, "dctcp", {"g": [1 / 16, 1 / 8]})
    assert batch.finished.all()
    for i in range(batch.n):
        np.testing.assert_allclose(batch.delivered[i].sum(),
                                   sched.size.sum(), rtol=2e-3)


def test_grid_sweep_shapes_and_best():
    topo, sched = _tiny_case()
    cfg = EngineConfig(dt=1e-6, max_steps=1500, max_extends=3, queue_stride=0)
    runner = SweepRunner(cfg)
    batch = runner.grid(topo, sched, "dcqcn",
                        {"rai_frac": [0.01, 0.03], "timer": [25e-6, 55e-6]})
    assert batch.n == 4
    b = batch.best()
    assert batch.finished[b]
    assert batch.completion_time[b] == min(
        batch.completion_time[batch.finished])


def test_population_autotune_improves_or_matches():
    from repro.core.autotune import autotune
    topo = single_switch(5)
    b = ScheduleBuilder(topo)
    g = b.new_group("x")
    for s in range(1, 5):
        b.add_flow(s, 0, 2e6, g)
    sched = b.build()
    cfg = EngineConfig(dt=2e-6, max_steps=400, max_extends=0, queue_stride=0)
    res = autotune(topo, sched, get_policy("dcqcn"), ["rai_frac", "timer"],
                   steps=3, cfg=cfg, population=3)
    assert len(res.history) == 3
    assert len(res.history[0]["population_costs"]) == 3
    # best-of-population can only improve on (or match) the default member
    assert res.tuned_cost <= res.baseline_cost + 1e-6


def test_sweep_rejects_bad_and_init_baked_params():
    """Typos and init-consumed params must fail loudly, not sweep nothing."""
    topo, sched = _tiny_case()
    cfg = EngineConfig(dt=1e-6, max_steps=200, max_extends=0, queue_stride=0)
    runner = SweepRunner(cfg)
    with pytest.raises(ValueError, match="unknown"):
        runner.run_batch(topo, sched, "dcqcn",
                         {"rai_frac_typo": np.array([0.1, 0.2])})
    # static_window consumes its params in init — sweeping them via
    # cc_params would silently produce B identical members
    with pytest.raises(ValueError, match="consumed by init"):
        runner.run_batch(topo, sched, "static_window",
                         {"margin": np.array([0.5, 8.0])})
    from repro.core.autotune import autotune
    with pytest.raises(ValueError, match="consumed by init"):
        autotune(topo, sched, get_policy("static_window"), ["margin"],
                 steps=1, cfg=cfg)


def test_batch_best_requires_a_finished_member():
    topo, sched = _tiny_case()
    # 10-step budget: nothing can finish
    cfg = EngineConfig(dt=1e-6, max_steps=10, max_extends=0, queue_stride=0)
    runner = SweepRunner(cfg)
    batch = runner.grid(topo, sched, "dctcp", {"g": [1 / 16, 1 / 8]})
    assert not batch.finished.any()
    with pytest.raises(ValueError, match="no sweep member finished"):
        batch.best()


def test_scenario_cache_distinguishes_fabric_flags():
    """Topologies differing only in ECN/PFC capability must not collide in
    the SweepRunner scenario cache — they simulate differently."""
    import dataclasses as dc
    topo, sched = _tiny_case()
    no_ecn = dc.replace(topo, ecn_on=np.zeros_like(topo.ecn_on))
    cfg = EngineConfig(dt=1e-6, max_steps=1500, max_extends=3, queue_stride=0)
    runner = SweepRunner(cfg)
    r1 = runner.run(topo, sched, "dcqcn")
    r2 = runner.run(no_ecn, sched, "dcqcn")
    ref = SweepRunner(cfg).run(no_ecn, sched, "dcqcn")
    assert r2.completion_time == ref.completion_time
    assert r1.completion_time != r2.completion_time


def test_sweeprunner_compile_cache_reuse():
    """Two same-shaped schedules must share one compiled engine."""
    from repro.core import engine as engine_mod
    topo = single_switch(8)
    s1 = incast(topo, list(range(1, 5)), 0, 1e6)
    s2 = incast(topo, list(range(2, 6)), 7, 1e6)   # same shapes, new flows
    cfg = EngineConfig(dt=1e-6, max_steps=300, max_extends=0, queue_stride=0)
    runner = SweepRunner(cfg)
    runner.run(topo, s1, "pfc")
    n_compiled = len(engine_mod._RUN_CACHE)
    runner.run(topo, s2, "pfc")
    assert len(engine_mod._RUN_CACHE) == n_compiled
