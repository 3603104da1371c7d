"""The stage and host-span readers (``bench/stages.py``) on a small recorded
chip trace (``data/trace_excerpt_scoped.pbtxt``: a traced
``ring128_ar.atlas_dcqcn`` dispatch cut to its first and last operations
and one loop step from the middle, with the harness's and the sweep's
host spans; recorded by ``scripts/record_trace_excerpt.py``), with the
op -> stage map of the executable that ran beside it
(``trace_excerpt_scoped.op_stage.json``)."""
import json
import os

import pytest
from jax.profiler import ProfileData

from bench import stages, trace
from bench.roofline import is_stage12, stage12_time
from bench.run import read_metric

DATA = os.path.join(os.path.dirname(__file__), "data")
SWEEP = ("sweep.inputs", "sweep.dispatch", "sweep.pull", "sweep.results")


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(DATA, "trace_excerpt_scoped.pbtxt")) as f:
        profile = ProfileData.from_text_proto(f.read())
    events = trace.load(profile)
    events["spans"] = stages.host_spans(profile)
    return events


@pytest.fixture(scope="module")
def op_stage():
    with open(os.path.join(DATA, "trace_excerpt_scoped.op_stage.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced(events):
    return trace.reduce(events)


def test_sweep_spans_in_order_inside_the_dispatch(events):
    (lo, hi), = [(s, e) for n, s, e in events["spans"]
                 if n == "bench.dispatch"]
    inner = [(n, s, e) for n, s, e in events["spans"] if n != "bench.dispatch"]
    assert [n for n, _, _ in inner] == list(SWEEP)
    assert all(lo <= s <= e <= hi for _, s, e in inner)


def test_the_seven_shares_partition_the_device_time(reduced, op_stage):
    got = stages.shares(reduced, op_stage)
    assert set(got) == set(stages.SHARES)
    assert all(0.0 <= v <= 100.0 for v in got.values())
    assert sum(got.values()) == pytest.approx(100.0, abs=0.01)
    # each engine stage group ran in the kept loop step
    assert all(got[m] > 0 for m in ("signals_share", "forward_share",
                                    "pfc_share", "completion_share",
                                    "health_share"))
    # the map names every kept operation
    assert stages.UNMAPPED not in stages.stage_seconds(reduced, op_stage)


def test_idle_by_span_adds_up_to_the_idle_time(events, reduced):
    idle, edge = stages.idle_by_span(events)
    (ops,) = events["devices"].values()
    (lo, hi), = [(s, e) for n, s, e in events["spans"]
                 if n == "bench.dispatch"]
    busy = trace._union(ops, lo, hi)
    between = sum(b[0] - a[1] for a, b in zip(busy, busy[1:])) / 1e9
    assert set(idle) == set(edge) == {"bench.dispatch", *SWEEP}
    assert sum(idle.values()) == pytest.approx(
        reduced["lead_s"] + reduced["tail_s"] + between, rel=1e-9)
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    assert sum(edge.values()) == pytest.approx(
        reduced["lead_s"] + reduced["tail_s"], rel=1e-9)
    assert all(edge[n] <= idle[n] + 1e-15 for n in idle)


def test_host_idle_is_named_by_the_sweep_spans(events, reduced):
    host = stages.host_idle_ms(stages.idle_by_span(events)[1])
    gap = read_metric("dispatch_gap_ms", {"trace": reduced})
    assert host["host_prep_ms"] > 0 and host["host_pull_ms"] > 0
    assert host["host_prep_ms"] + host["host_pull_ms"] <= gap + 1e-9
    # the dispatch's lead and tail, its two longest idle gaps, are named
    # by the sweep's spans
    lead, tail = reduced["idle_gaps"][0][0], reduced["idle_gaps"][1][0]
    assert {lead.split(":")[0], tail.split(":")[0]} <= set(SWEEP)


def test_the_kernel_is_found_by_name(reduced, op_stage):
    (name,) = {n for n, _ in reduced["device_ops"] if is_stage12(n)}
    assert stages.op_key(name).startswith("engine_step_signals_cc")
    assert op_stage[stages.op_key(name)] == "s12_fused"
    secs, calls = stage12_time(reduced)
    assert secs > 0 and calls >= 1


def test_no_tags_no_readings(events, reduced, op_stage):
    """A program without the tags reads no stage share and no host span."""
    assert stages.shares(reduced, dict.fromkeys(op_stage,
                                                stages.UNSCOPED)) is None
    bare = dict(events, spans=[s for s in events["spans"]
                               if s[0] == "bench.dispatch"])
    assert stages.host_idle_ms(stages.idle_by_span(bare)[1]) == {}
