"""Stage 6's queue reductions (``engine._queues``) against a NumPy
``bincount`` over the schedule's paths, and the choice of gather plan
(``engine._reduce_plan``).

Plans only, no run: each scenario is prepared with padded flows, given a
random ``(Fp, MAXHOP)`` backlog, and its per-link and per-ingress-port sums
compared with the reference.  The scenarios are the engine goldens'
topologies and the benchmark cells' configurations (``bench/configs``).
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from _engine_scenarios import scenarios

from repro.core.collectives import get_collective
from repro.core.engine import (_SPLIT_C, MAXHOP, EngineConfig, _prep,
                               _queues, _reduce, _reduce_plan)
from repro.core.sweep import _bucket
from repro.core.topology import clos

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIGS = ("ring128_ar", "a2a128")


def _cell(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as fh:
        c = json.load(fh)
    f, col, e = c["fabric"], c["collective"], c["engine"]
    topo = clos(n_racks=f["n_racks"], nodes_per_rack=f["nodes_per_rack"],
                gpus_per_node=f["gpus_per_node"], n_spines=f["n_spines"],
                nic_bw=f["nic_gbit_s"] * 1e9 / 8, nic_lat=f["nic_latency_s"],
                nv_bw=f["nvlink_gbyte_s"] * 1e9, nv_lat=f["nvlink_latency_s"])
    sched = get_collective(col["kind"])(
        topo, list(range(topo.n_gpus)), float(col["bytes"]),
        n_chunks=int(col["n_chunks"]))
    return topo, sched, EngineConfig(dt=e["dt"], max_steps=e["max_steps"])


def _scenario(name):
    if name in CELL_CONFIGS:
        return _cell(name)
    sc = {n: (topo, sched, cfg) for n, topo, sched, _, cfg in scenarios()}
    return sc[name]


NAMES = [n for n, *_ in scenarios()] + list(CELL_CONFIGS)


@pytest.fixture(scope="module", params=NAMES)
def prepared(request):
    """``(sched, pp, plan)`` with at least one padded flow."""
    topo, sched, cfg = _scenario(request.param)
    pp, plan = _prep(topo, sched, cfg, pad_flows=_bucket(sched.n_flows + 1))
    assert plan.n_flows_pad > sched.n_flows
    return sched, pp, plan


def _backlog(sched, Fp, seed, integer):
    """Random backlog on the schedule's hop slots, zero everywhere else."""
    rng = np.random.default_rng(seed)
    b = np.zeros((Fp, MAXHOP), np.float32)
    valid = sched.path >= 0
    vals = (rng.integers(0, 1024, valid.sum()) if integer
            else rng.uniform(0.0, 4e6, valid.sum()))
    b[:sched.n_flows][valid] = vals
    return b


def _reference(sched, backlog, n_out):
    """Per-link and per-ingress-port sums in float64."""
    b = backlog[:sched.n_flows].astype(np.float64)
    q_link = np.zeros(n_out)
    q_port = np.zeros(n_out)
    for h in range(MAXHOP):
        v = sched.path[:, h] >= 0
        q_link += np.bincount(sched.path[v, h], b[v, h], minlength=n_out)
        if h >= 1:
            q_port += np.bincount(sched.path[v, h - 1], b[v, h],
                                  minlength=n_out)
    return q_link, q_port


def _sums(pp, plan, backlog):
    q_link, q_port = _queues(plan, pp, jnp.asarray(backlog))
    return np.asarray(q_link), np.asarray(q_port)


@pytest.mark.parametrize("integer", [True, False], ids=["exact", "float"])
def test_queue_sums_match_bincount(prepared, integer):
    sched, pp, plan = prepared
    b = _backlog(sched, plan.n_flows_pad, 7, integer)
    q_link, q_port = _sums(pp, plan, b)
    ref_link, ref_port = _reference(sched, b, plan.n_links + 1)
    assert q_link.dtype == q_port.dtype == np.float32
    if integer:   # integer-valued f32 sums are exact in any order
        np.testing.assert_array_equal(q_link, ref_link)
        np.testing.assert_array_equal(q_port, ref_port)
    else:
        np.testing.assert_allclose(q_link, ref_link, rtol=1e-6)
        np.testing.assert_allclose(q_port, ref_port, rtol=1e-6)
    assert q_port.sum() > 0 or not (sched.path[:, 1] >= 0).any()


def test_hop0_backlog_never_counts_toward_q_port(prepared):
    sched, pp, plan = prepared
    b = _backlog(sched, plan.n_flows_pad, 11, False)
    b[:, 1:] = 0.0
    q_link, q_port = _sums(pp, plan, b)
    np.testing.assert_array_equal(q_port, 0.0)
    assert q_link.sum() > 0


def test_padded_flows_and_unused_slots_contribute_nothing(prepared):
    sched, pp, plan = prepared
    b = _backlog(sched, plan.n_flows_pad, 13, False)
    want = _sums(pp, plan, b)
    poisoned = b.copy()
    poisoned[sched.n_flows:] = np.nan            # padded flows
    unused = np.zeros_like(b, bool)
    unused[:sched.n_flows] = sched.path < 0      # slots past n_hops
    poisoned[unused] = np.nan
    got = _sums(pp, plan, poisoned)
    for g, w in zip(got, want):
        assert np.isfinite(g).all()
        np.testing.assert_array_equal(g, w)


def _rows(n_out, counts):
    """Rows gathered by the single-level and by the two-level plan."""
    c = max(counts)
    single = n_out * (1 << max(c - 1, 0).bit_length())
    nblk = [-(-k // _SPLIT_C) for k in counts]
    two = sum(nblk) * _SPLIT_C + n_out * (1 << max(max(nblk) - 1, 0).bit_length())
    return single, two


@pytest.mark.parametrize("counts, kind", [
    ([40] * 16, "gather"),                     # dense: every segment full
    ([100], "gather"),                         # one segment past _SPLIT_C
    ([500] + [1] * 7, "gather2"),              # one hot port
    ([32] * 64 + [0] * 577, "gather2"),        # few busy links among many
], ids=["dense", "one_wide", "hot_port", "sparse"])
def test_reduce_plan_gathers_fewer_rows(counts, kind):
    rng = np.random.default_rng(3)
    n_out = len(counts)
    ids = rng.permutation(np.repeat(np.arange(n_out), counts))
    vals = rng.integers(0, 1024, ids.size).astype(np.float32)
    arrs, strategy = _reduce_plan(ids, ids.size, n_out)
    assert strategy[0] == kind
    single, two = _rows(n_out, counts)
    rows = (strategy[1] * strategy[2] if kind == "gather"
            else strategy[2] * _SPLIT_C + strategy[1] * strategy[3])
    assert rows == min(single, two)
    got = np.asarray(_reduce(strategy, arrs, jnp.asarray(vals)))
    np.testing.assert_array_equal(got, np.bincount(ids, vals, minlength=n_out))
