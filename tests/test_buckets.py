"""The sweep's shape buckets (``sweep._bucket``): the padded flow count
every engine stage runs over.

Up to ``_FINE_BUCKETS_ABOVE`` flows a bucket is the next power of two;
above it, the next multiple of that power's eighth.  The padding is inert
at either size: a run padded to a multiple of 1,024 that is no power of two
gives the unpadded run's results.
"""
import json
import os

import numpy as np
import pytest
from _engine_scenarios import scenarios

from repro.core.cc import get_policy
from repro.core.engine import EngineConfig, Simulator, _next_pow2
from repro.core.sweep import _FINE_BUCKETS_ABOVE, SweepRunner, _bucket
from repro.core.topology import clos
from repro.core.workload import (DLRMCommSpec, DLRMComputeProfile,
                                 build_dlrm_iteration)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("n,expected", [
    (152581, 163840),      # the DLRM iteration on the 128-GPU CLOS
    (32512, 32768),        # the 128-GPU ring all-reduce
    (32768, 32768),
    (32769, 40960),
    (16256, 16384),        # the 128-GPU all-to-all
    (448, 512),            # the goldens' 8-GPU ring
])
def test_bucket(n, expected):
    assert _bucket(n) == expected


@pytest.mark.parametrize("n", [32769, 40960, 40961, 65535, 65536, 65537,
                               98304, 131073, 152581, 262143, 262145,
                               1000003])
def test_fine_buckets_bound_the_padding(n):
    b = _bucket(n)
    assert n > _FINE_BUCKETS_ABOVE
    assert n <= b < n + _next_pow2(n) // 8
    assert b % 1024 == 0


def test_group_axis_keeps_powers_of_two():
    for n in (1, 5, 8, 9, 300, 4096):
        b = _bucket(n, lo=8)
        assert b == max(8, _next_pow2(n))


# (scenario, policy): one PFC-paused incast and one multi-group ring
EQUIV = [("incast_ss8", "pfc"), ("ar1d_clos8", "hpcc")]
PAD = 6144                 # a multiple of 1,024, no power of two


@pytest.mark.parametrize("tag,pol", EQUIV, ids=[f"{t}-{p}" for t, p in EQUIV])
def test_padding_to_a_fine_bucket_is_inert(tag, pol):
    topo, sched, cfg = {n: (t, s, c) for n, t, s, _, c in scenarios()}[tag]
    assert sched.n_flows <= PAD and PAD != _next_pow2(PAD)
    policy = get_policy(pol)
    base = Simulator(topo, sched, policy, cfg).run()
    pow2 = Simulator(topo, sched, policy, cfg,
                     pad_flows=_next_pow2(PAD)).run()
    fine = Simulator(topo, sched, policy, cfg, pad_flows=PAD)
    assert fine.plan.n_flows_pad == PAD
    got = fine.run()
    for ref in (base, pow2):
        assert got.status == ref.status
        assert got.finished == ref.finished
        np.testing.assert_allclose(got.completion_time, ref.completion_time,
                                   rtol=1e-5)
        np.testing.assert_allclose(got.t_finish, ref.t_finish, rtol=1e-5,
                                   atol=cfg.dt)
        np.testing.assert_allclose(got.pause_count, ref.pause_count,
                                   rtol=1e-3, atol=1.0)
    assert got.finished


def test_dlrm_iteration_prepares_at_a_fine_bucket():
    """The DLRM configuration's iteration pads to 163,840 flows, not
    262,144 (prepared on the host only, never run)."""
    with open(os.path.join(ROOT, "bench", "configs", "dlrm128.json")) as fh:
        c = json.load(fh)
    f, job = c["fabric"], c["workload"]
    topo = clos(n_racks=f["n_racks"], nodes_per_rack=f["nodes_per_rack"],
                gpus_per_node=f["gpus_per_node"], n_spines=f["n_spines"],
                nic_bw=f["nic_gbit_s"] * 1e9 / 8, nic_lat=f["nic_latency_s"],
                nv_bw=f["nvlink_gbyte_s"] * 1e9, nv_lat=f["nvlink_latency_s"])
    sched = build_dlrm_iteration(
        topo, list(range(topo.n_gpus)), DLRMComputeProfile(**job["compute"]),
        DLRMCommSpec(**job["comm"]))
    cfg = EngineConfig(**c["engine"])
    plan = SweepRunner(cfg).simulator(topo, sched, get_policy("pfc")).plan
    assert plan.n_flows == 152581
    assert plan.n_flows_pad == 163840
    assert plan.n_groups_pad == _bucket(sched.n_groups, lo=8)
