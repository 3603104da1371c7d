"""A configuration brings its scenario and its reference as modules of
its own, found by the names in its file: every committed configuration's
modules expose what the harness calls, a configuration added as new files
alone (under a temporary root) runs through the whole harness, and a mix
names an entry the harness has."""
import json
import os
import textwrap

import jax
import pytest

from bench import modules
from bench.lanes import make_lanes
from bench.run import ENTRIES, Program, load_cell, load_json, run_cell
from bench.tests.cells import ROOT, small_pair
from repro.common.cache import backend_compiles


def _spec():
    return load_json(ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("config", sorted(
    c["name"] for c in load_json(ROOT, "BENCHMARK.json")["configs"]))
def test_every_configuration_names_modules_that_expose_the_calls(config):
    spec = _spec()
    cfg = load_json(ROOT, "bench", "configs", f"{config}.json")
    scen = modules.scenario(cfg)
    ref = modules.reference(cfg)
    assert callable(scen.build)
    for name in ("run_lane", "build_scenario"):
        assert callable(getattr(ref, name))
    assert isinstance(ref.MAXHOP, int) and ref.MAXHOP > 0
    for cell in spec["workloads"]:
        if cell["config"] == config:
            mix = load_json(ROOT, "bench", "traffic",
                            f"{cell['traffic']}.json")
            assert mix["entry"] in ENTRIES
            assert set(mix["policies"]) <= set(ref.POLICIES)
    # the reference imports nothing of the program
    with open(ref.__file__) as f:
        assert "repro" not in f.read()


def test_unknown_entry_fails_before_any_compile():
    cell = small_pair("a2a128", "atlas_dcqcn")
    mix = dict(cell["mix"], entry="run_everything")
    with backend_compiles() as compiles, pytest.raises(ValueError,
                                                       match="entry"):
        Program(cell["config"], mix, make_lanes(mix, 5))
    assert compiles == []


# A configuration a later change could add, as new files only: the paper's
# single-switch incast, with a scenario module built from the program's
# public API and a reference that reuses the shared reference's step.
SCENARIO = '''
from repro.core import incast, single_switch


def build(config):
    f, job = config["fabric"], config["incast"]
    topo = single_switch(f["n_gpus"], bw=f["nic_gbit_s"] * 1e9 / 8,
                         lat=f["nic_latency_s"])
    sched = incast(topo, list(range(1, 1 + job["senders"])), 0,
                   float(job["bytes_each"]))
    return topo, sched
'''

REFERENCE = '''
import numpy as np

from bench import reference as base

MAXHOP, POLICIES = base.MAXHOP, base.POLICIES


def build_scenario(config):
    """GPUs 0..n-1 and the switch n; each GPU's NIC link up to the switch
    (links 0..n-1), then the switch's egress down to each (n..2n-1)."""
    f, job = config["fabric"], config["incast"]
    n = f["n_gpus"]
    bw, lat = f["nic_gbit_s"] * 1e9 / 8, f["nic_latency_s"]
    none = np.zeros(0, int)
    fab = base.Fabric(
        cap=np.full(2 * n, bw), lat=np.full(2 * n, lat),
        src=np.r_[np.arange(n), np.full(n, n)],
        dst=np.r_[np.full(n, n), np.arange(n)],
        ecn=np.r_[np.zeros(n, bool), np.ones(n, bool)],
        fabric=np.ones(2 * n, bool), cls=np.zeros(2 * n, int),
        is_switch=np.r_[np.zeros(n, bool), True], n_gpus=n,
        gpus_per_node=1, nodes_per_rack=n, n_spines=0, nv_up=none,
        nv_down=none, host_up=np.arange(n), tor_down=np.arange(n, 2 * n),
        tor_up=none, spine_down=none)
    senders = np.arange(1, 1 + job["senders"])
    path = np.full((len(senders), MAXHOP), -1)
    path[:, 0], path[:, 1] = senders, n
    k = len(senders)
    flows = base.Flows(path=path, size=np.full(k, float(job["bytes_each"])),
                       group=np.zeros(k, int), dep=np.full(k, -1),
                       n_groups=1)
    return fab, flows


def run_lane(config, lane, dtype_name="float32", max_steps=None):
    assert dtype_name == "float32" and max_steps is None
    fab, flows = build_scenario(config)
    out = base.simulate(fab, flows, lane.policy, lane.params,
                        dict(config["fabric_knobs"], kmin=lane.kmin,
                             kmax=lane.kmax, xoff=lane.xoff),
                        config["engine"])
    out["deadlocked"] = out["deadlock_step"] >= 0
    return out
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_a_configuration_of_new_files_runs_through_the_harness(tmp_path):
    """Under a root of its own, a new configuration (its file, scenario
    and reference modules), traffic mix, limits and cell: the harness
    builds the program's lanes through the scenario and checks every lane
    against the reference, with no file of the harness changed."""
    spec = _spec()
    spec["workloads"] = [{"name": "incast8.incast_dcqcn",
                          "config": "incast8", "traffic": "incast_dcqcn",
                          "chips": 1, "why": "7-to-1 incast"}]
    spec["per_layer"] = []
    bench = tmp_path / "bench"
    _write(str(tmp_path / "BENCHMARK.json"), json.dumps(spec))
    a2a = load_json(ROOT, "bench", "configs", "a2a128.json")
    config = {"name": "incast8", "precision": "float32",
              "scenario": "incast", "reference": "reference_incast",
              "fabric": {"n_gpus": 8, "nic_gbit_s": 200,
                         "nic_latency_s": 5e-7},
              "incast": {"senders": 7, "bytes_each": 2e6},
              "engine": dict(a2a["engine"], max_steps=600, max_extends=1),
              "fabric_knobs": a2a["fabric_knobs"]}
    mix = dict(load_json(ROOT, "bench", "traffic", "atlas_dcqcn.json"),
               param_span=[1.0])
    _write(str(bench / "configs" / "incast8.json"), json.dumps(config))
    _write(str(bench / "traffic" / "incast_dcqcn.json"), json.dumps(mix))
    _write(str(bench / "limits" / "incast8.incast_dcqcn.json"),
           json.dumps({"finish_gap_steps": 1.0, "pause_gap": 1e-5,
                       "status_mismatch": 0}))
    _write(str(bench / "scenarios" / "incast.py"), textwrap.dedent(SCENARIO))
    _write(str(bench / "reference_incast.py"), textwrap.dedent(REFERENCE))

    cell = load_cell("incast8.incast_dcqcn", root=str(tmp_path))
    assert cell["bench"] == str(bench)
    res = run_cell(cell, 2**31 + 3, 1e-3, False, jax.devices(), workers=2)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4                # 4 fabric corners, 1 dispatch
    prog = Program(cell["config"], cell["mix"],
                   make_lanes(cell["mix"], 1), cell["bench"])
    assert prog.sched.n_flows == 7 and prog.topo.n_gpus == 8
    batch = prog.dispatch()
    assert batch.finished.all() and (batch.pause_count.sum(axis=1) > 0).any()


def _fake_trace(monkeypatch):
    """The profiler, its reading and the peak table replaced: the CPU has
    no device trace.  The dispatch itself runs."""
    from bench import peaks, trace
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS[
        "TPU v5 lite"])
    kernel = ('%engine_step_signals_cc.1 = f32[8] custom-call(x), '
              'custom_call_target="tpu_custom_call"')
    monkeypatch.setattr(trace, "capture", lambda fn: (fn(), {}))
    monkeypatch.setattr(trace, "reduce", lambda events: {
        "busy_s": 0.5, "window_s": 0.52, "lead_s": 0.004, "tail_s": 0.006,
        "device_ops": [("%fusion.1 = f32[8] fusion(x)", 0.4),
                       (kernel, 0.1)],
        "op_counts": {kernel: 100.0, "%fusion.1 = f32[8] fusion(x)": 1.0},
        "idle_gaps": [["bench.dispatch:_after_last_op", 0.006]]})


@pytest.mark.parametrize("name,pallas,kernel", [
    ("a2a128.policy_axis", True, None),
    ("a2a128.atlas_dcqcn", False, None),
    ("a2a128.atlas_dcqcn", True, "dcqcn"),
])
def test_stage12_work_only_where_one_policy_runs_the_kernel(
        monkeypatch, name, pallas, kernel):
    """The stacked policy axis always runs the jnp step, so its ``--trace
    1`` run counts no stages 1-2 work and reports no reading of the
    kernel; a cell of one kernel-eligible policy does, where the step is
    the kernel's."""
    from bench.tests.cells import small_cell
    from repro.core import engine
    cell = small_cell(name)
    prog = Program(cell["config"], cell["mix"],
                   make_lanes(cell["mix"], 3), cell["bench"])
    if pallas:
        monkeypatch.setattr(engine, "resolve_step_impl", lambda cfg: "pallas")
    assert prog.kernel_policy() == kernel
    if kernel is None and name.endswith("policy_axis"):
        monkeypatch.undo()
        _fake_trace(monkeypatch)
        res = run_cell(cell, 3, 1e-3, True, jax.devices(), workers=2)
        assert res["correct"] is True
        assert res["attempted"] == 2 * 8      # the window's and the traced
        assert set(res["metrics"]) == {"device_idle_share",
                                       "dispatch_gap_ms", "batch_step_ms"}
        assert res["device"]["busy_s"] == 0.5
