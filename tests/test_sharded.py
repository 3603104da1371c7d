"""Sharded sweep execution: shard_map grid scale-out vs the vmap path.

The multi-device equivalence tests need >1 JAX device and auto-skip on
the plain single-CPU tier-1 run; CI runs them (marker ``sharded``) under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  The chunking,
padding-arithmetic, mesh-resolution and calibration-persistence tests are
single-device-safe and always run.
"""
import dataclasses
import json
import os
import warnings

import jax
import numpy as np
import pytest

from repro.common import cache as cache_mod
from repro.common.cache import backend_compiles
from repro.common.sharding import GRID_AXIS, grid_mesh, resolve_grid_mesh
from repro.core import sweep as sweep_mod
from repro.core.cc import get_policy
from repro.core.collectives import allreduce_1d, incast
from repro.core.engine import EngineConfig
from repro.core.faults import FaultSpec
from repro.core.scenario import CollectiveSpec, scenario_matrix
from repro.core.sweep import BackendCalibration, SweepRunner
from repro.core.topology import single_switch

pytestmark = pytest.mark.sharded

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >1 JAX device "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

CFG = EngineConfig(dt=2e-6, max_steps=600, max_extends=1, queue_stride=0)


def scenario(n=4, mb=4e6):
    topo = single_switch(n)
    return topo, allreduce_1d(topo, list(range(n)), mb)


# -- mesh resolution / chunk arithmetic (single-device-safe) ----------------

def test_resolve_grid_mesh_modes():
    assert resolve_grid_mesh(None) is None
    if N_DEV < 2:
        assert resolve_grid_mesh("auto") is None   # 1 device -> vmap path
    else:
        m = resolve_grid_mesh("auto")
        assert m.axis_names == (GRID_AXIS,)
        assert resolve_grid_mesh(m) is m
        assert resolve_grid_mesh(2).devices.size == 2
    with pytest.raises(TypeError):
        resolve_grid_mesh(3.5)
    with pytest.raises(ValueError):
        grid_mesh(N_DEV + 1)


def test_runner_defaults_unchanged():
    """mesh=None is the historical single-dispatch path."""
    r = SweepRunner(CFG)
    assert r.mesh is None
    assert r.n_mesh_devices == 1
    assert not r.sharded_pays_off()
    # one chunk covers any grid up to the auto limit
    assert r._chunk_size(7) == 7
    assert r._chunk_size(SweepRunner.AUTO_CHUNK_PER_DEVICE) == \
        SweepRunner.AUTO_CHUNK_PER_DEVICE
    assert r._chunk_size(SweepRunner.AUTO_CHUNK_PER_DEVICE + 1) == \
        SweepRunner.AUTO_CHUNK_PER_DEVICE


def test_chunk_size_is_mesh_multiple():
    r = SweepRunner(CFG, chunk_lanes=10)
    assert r._chunk_size(100) == 10
    assert r._chunk_size(4) == 4          # padded up only to B
    if N_DEV > 1:
        rs = SweepRunner(CFG, mesh="auto", chunk_lanes=10)
        c = rs._chunk_size(100)
        assert c % rs.n_mesh_devices == 0 and c >= 10
        assert rs._chunk_size(3) == rs.n_mesh_devices   # pad 3 -> mesh


def test_unsharded_chunked_streaming_matches_single_dispatch():
    """Chunked streaming (mesh=None) returns exactly B lanes in input
    order, trailing-pad dropped, allclose with the one-dispatch path."""
    topo, sched = scenario()
    B = 11                                 # 3 chunks of 4, last padded
    scale = np.linspace(0.5, 2.0, B).astype(np.float32)
    stacked = {"rai_frac": 0.03 * scale}
    one = SweepRunner(CFG).run_batch(topo, sched, "dcqcn", stacked)
    chunked = SweepRunner(CFG, chunk_lanes=4).run_batch(
        topo, sched, "dcqcn", stacked)
    assert chunked.n == B
    np.testing.assert_allclose(chunked.completion_time,
                               one.completion_time, rtol=1e-5)
    np.testing.assert_allclose(chunked.t_finish, one.t_finish, rtol=1e-5)
    assert chunked.lane_status() == one.lane_status()
    # per-lane params survive the chunk round-trip in order
    np.testing.assert_allclose(chunked.params["rai_frac"],
                               stacked["rai_frac"])


def test_compile_batch_is_the_executable_run_batch_dispatches():
    """After ``compile_batch`` (and ``Simulator.compile``) the matching
    run compiles nothing, and the results equal an uncompiled runner's."""
    topo, sched = scenario(n=5)
    stacked = {"rai_frac": np.asarray([0.02, 0.03, 0.05], np.float32)}
    runner = SweepRunner(CFG)
    compiled = runner.compile_batch(topo, sched, "dcqcn", stacked)
    assert "while" in compiled.as_text()
    with backend_compiles() as got:
        batch = runner.run_batch(topo, sched, "dcqcn", stacked)
    assert got == []
    want = SweepRunner(CFG).run_batch(topo, sched, "dcqcn", stacked)
    np.testing.assert_array_equal(batch.t_finish, want.t_finish)

    sim = runner.simulator(topo, sched, get_policy("hpcc"))
    sim.compile()
    with backend_compiles() as got:
        r = runner.run(topo, sched, "hpcc")
    assert got == [] and r.finished


def test_lane_state_bytes_positive_and_faulty_larger():
    topo, sched = scenario()
    r = SweepRunner(CFG)
    base = r.lane_state_bytes(topo, sched, "dcqcn")
    assert base > 0
    assert r.lane_state_bytes(topo, sched, "dcqcn", faulty=True) > base


# -- calibration persistence (single-device-safe) ---------------------------

def test_calibration_save_load_roundtrip(tmp_path):
    cal = BackendCalibration(
        backend=jax.default_backend(), source="measured",
        crossover={"sweep": 123.0, "policy_axis": 0.0,
                   "sharded": float("inf")},
        probes=(("sweep", 90, 0.5, 0.2),))
    path = str(tmp_path / "cal.json")
    assert sweep_mod.save_calibration(cal, path) == path
    got = sweep_mod.load_calibration(path=path)
    assert got is not None
    assert got.crossover == cal.crossover
    assert got.probes == cal.probes
    assert got.source == "measured"


def test_calibration_load_rejects_mismatch(tmp_path):
    cal = BackendCalibration(backend=jax.default_backend(),
                             source="measured", crossover={"sweep": 1.0})
    path = str(tmp_path / "cal.json")
    sweep_mod.save_calibration(cal, path)
    rec = json.load(open(path))
    # wrong backend
    rec2 = dict(rec, backend="not-a-backend")
    json.dump(rec2, open(path, "w"))
    assert sweep_mod.load_calibration(path=path) is None
    # wrong jax version
    rec2 = dict(rec, jax="0.0.0")
    json.dump(rec2, open(path, "w"))
    assert sweep_mod.load_calibration(path=path) is None
    # stale
    rec2 = dict(rec, saved_at=0.0)
    json.dump(rec2, open(path, "w"))
    assert sweep_mod.load_calibration(path=path, max_age_days=1.0) is None
    json.dump(rec, open(path, "w"))
    assert sweep_mod.load_calibration(path=path) is not None


def test_get_calibration_warm_starts_from_disk(tmp_path, monkeypatch):
    """A fresh process (simulated: cleared in-memory table + _NO_DISK)
    picks up the persisted measurement; reset_calibration pins back to
    the defaults without reconsulting the file."""
    monkeypatch.setattr(cache_mod, "CACHE_ROOT", str(tmp_path))
    backend = jax.default_backend()
    cal = BackendCalibration(backend=backend, source="measured",
                             crossover={"sweep": 777.0})
    sweep_mod.save_calibration(cal)
    saved_mem = dict(sweep_mod._CALIBRATION)
    saved_nodisk = set(sweep_mod._NO_DISK)
    try:
        sweep_mod._CALIBRATION.clear()
        sweep_mod._NO_DISK.clear()
        got = sweep_mod.get_calibration()
        assert got.source == "measured"
        assert got.crossover["sweep"] == 777.0
        sweep_mod.reset_calibration()
        assert sweep_mod.get_calibration().source == "default"
    finally:
        sweep_mod._CALIBRATION.clear()
        sweep_mod._CALIBRATION.update(saved_mem)
        sweep_mod._NO_DISK.clear()
        sweep_mod._NO_DISK.update(saved_nodisk)


def test_cache_paths_are_fixed_whatever_the_cwd(tmp_path, monkeypatch):
    """The compilation cache and calibrations sit under
    ``<checkout>/.cache`` from any cwd; with ``JAX_COMPILATION_CACHE_DIR``
    set, JAX keeps its own directory and no other is configured."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("REPRO_COMPILATION_CACHE", raising=False)
    monkeypatch.chdir(tmp_path)
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".cache")
    want = os.path.join(root, "jax_compilation")
    assert cache_mod.enable_compilation_cache() == want
    assert calls["jax_compilation_cache_dir"] == want
    assert sweep_mod.calibration_cache_path("cpu") == os.path.join(
        root, "repro_calibration_cpu.json")
    calls.clear()
    own = str(tmp_path / "jax_own")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", own)
    assert cache_mod.enable_compilation_cache() == own
    assert "jax_compilation_cache_dir" not in calls


def test_get_calibration_env_gate(tmp_path, monkeypatch):
    monkeypatch.setattr(cache_mod, "CACHE_ROOT", str(tmp_path))
    monkeypatch.setenv("REPRO_CALIBRATION_CACHE", "0")
    backend = jax.default_backend()
    sweep_mod.save_calibration(BackendCalibration(
        backend=backend, source="measured", crossover={"sweep": 777.0}))
    saved_mem = dict(sweep_mod._CALIBRATION)
    saved_nodisk = set(sweep_mod._NO_DISK)
    try:
        sweep_mod._CALIBRATION.clear()
        sweep_mod._NO_DISK.clear()
        assert sweep_mod.get_calibration().source == "default"
    finally:
        sweep_mod._CALIBRATION.clear()
        sweep_mod._CALIBRATION.update(saved_mem)
        sweep_mod._NO_DISK.clear()
        sweep_mod._NO_DISK.update(saved_nodisk)


# -- sharded-vs-vmap equivalence (multi-device) -----------------------------

@multi_device
def test_sharded_grid_matches_vmap():
    """Divisible and non-divisible grids through shard_map match the
    single-device vmap at rtol 1e-5, padded remainder lanes masked out."""
    topo, sched = scenario()
    vm = SweepRunner(CFG)
    sh = SweepRunner(CFG, mesh="auto")
    assert sh.n_mesh_devices == N_DEV
    for B in (N_DEV, 2 * N_DEV, N_DEV + 3, 2 * N_DEV - 1):
        scale = np.linspace(0.5, 2.0, B).astype(np.float32)
        grid = {"rai_frac": [0.01, 0.05], "timer": [40e-6, 70e-6]}
        a = vm.run_batch(topo, sched, "dcqcn", {"rai_frac": 0.03 * scale})
        b = sh.run_batch(topo, sched, "dcqcn", {"rai_frac": 0.03 * scale})
        assert b.n == B
        np.testing.assert_allclose(b.completion_time, a.completion_time,
                                   rtol=1e-5)
        np.testing.assert_allclose(b.t_finish, a.t_finish, rtol=1e-5)
        assert a.lane_status() == b.lane_status()
    ga = vm.grid(topo, sched, "dcqcn", grid)
    gb = sh.grid(topo, sched, "dcqcn", grid)
    np.testing.assert_allclose(gb.completion_time, ga.completion_time,
                               rtol=1e-5)


@multi_device
def test_sharded_chunked_streaming_matches():
    """Streamed chunks (3 chunks, trailing pad) through the mesh match
    the one-dispatch vmap; round-robin permutation restores lane order."""
    topo, sched = scenario()
    B = 3 * N_DEV - 2
    scale = np.linspace(0.5, 2.0, B).astype(np.float32)
    stacked = {"rai_frac": 0.03 * scale}
    a = SweepRunner(CFG).run_batch(topo, sched, "dcqcn", stacked)
    b = SweepRunner(CFG, mesh="auto", chunk_lanes=N_DEV).run_batch(
        topo, sched, "dcqcn", stacked)
    assert b.n == B
    np.testing.assert_allclose(b.completion_time, a.completion_time,
                               rtol=1e-5)
    np.testing.assert_allclose(b.params["rai_frac"], stacked["rai_frac"])


@multi_device
def test_sharded_policy_axis_matches():
    topo, sched = scenario()
    pols = ["dcqcn", "timely", "hpcc", "dctcp", "pfc"]
    a = SweepRunner(CFG).run_policy_axis(topo, sched, pols)
    b = SweepRunner(CFG, mesh="auto").run_policy_axis(topo, sched, pols)
    np.testing.assert_allclose(b.completion_time, a.completion_time,
                               rtol=1e-5)
    assert a.lane_status() == b.lane_status()
    assert [b.policy_of(i) for i in range(b.n)] == pols


@multi_device
@pytest.mark.fault
def test_sharded_fault_grid_lane_isolation():
    """A fault grid with unhealthy lanes shards like it vmaps: per-lane
    status (incl. isolation of non-finishing lanes) is identical and
    healthy-lane results are allclose."""
    topo = single_switch(8)
    sched = incast(topo, list(range(1, 8)), 0, 5e6)
    cfg = EngineConfig(dt=1e-6, max_steps=400, max_extends=0,
                       queue_stride=0)
    fault_grid = {"loss_rate": [0.0, 1e-4, 3e-3], "gbn": [0.0, 1.0]}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        a = SweepRunner(cfg).grid(topo, sched, "dcqcn",
                                  {"rai_frac": [0.03]},
                                  fault_grid=fault_grid,
                                  fault_spec=FaultSpec(pfc_on=0.0))
        b = SweepRunner(cfg, mesh="auto").grid(
            topo, sched, "dcqcn", {"rai_frac": [0.03]},
            fault_grid=fault_grid, fault_spec=FaultSpec(pfc_on=0.0))
    assert a.lane_status() == b.lane_status()
    ok = np.asarray([s == "ok" for s in a.lane_status()])
    np.testing.assert_allclose(b.completion_time[ok],
                               a.completion_time[ok], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(b.fault["loss_rate"]),
                               np.asarray(a.fault["loss_rate"]))


@multi_device
def test_sharded_spec_pipeline():
    """scenario_matrix(stacked=True) -> run_specs runs the policy axis
    through the sharded dispatch and returns BatchResults."""
    from repro.core.scenario import FabricSpec
    fab = FabricSpec(family="single", n_racks=1, nodes_per_rack=1,
                     gpus_per_node=4)
    wl = CollectiveSpec(kind="1d", total_bytes=4e6)
    specs = scenario_matrix([fab], [wl], ["dcqcn", "timely"], stacked=True)
    assert len(specs) == 1 and isinstance(specs[0].policy, tuple)
    sh = SweepRunner(CFG, mesh="auto")
    out = sh.run_specs(specs)
    assert len(out) == 1 and out[0].n == 2
    assert out[0].policy_of(0) == "dcqcn"
    vm_out = SweepRunner(CFG).run_specs(specs)
    np.testing.assert_allclose(out[0].completion_time,
                               vm_out[0].completion_time, rtol=1e-5)
    # ScenarioSpec.run routes tuple policies through the batched path too
    direct = specs[0].run(runner=sh)
    np.testing.assert_allclose(direct.completion_time,
                               out[0].completion_time, rtol=1e-5)


@multi_device
def test_sharded_calibration_kind():
    cfg = dataclasses.replace(CFG, max_steps=200)
    cal = sweep_mod.calibrate_backend(probe_flows=(24,), B=4, cfg=cfg,
                                      persist=False)
    try:
        assert "sharded" in cal.crossover
        assert any(p[0] == "sharded" for p in cal.probes)
    finally:
        sweep_mod.reset_calibration()
