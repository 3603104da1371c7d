"""The trace reduction on a small recorded trace (``data/trace_excerpt.pbtxt``:
the harness's dispatch span and device operations of a traced dispatch),
against numbers worked out here from the same events by brute force."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace
from bench.roofline import is_stage12, stage12_time

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_excerpt.pbtxt")


@pytest.fixture(scope="module")
def events():
    with open(DATA) as f:
        return trace.load(ProfileData.from_text_proto(f.read()))


def _busy_brute(ops, lo, hi):
    """Busy time by walking every boundary point in order."""
    pts = sorted({lo, hi} | {max(min(t, hi), lo) for s, e, _ in ops
                             for t in (s, e)})
    busy = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e, _ in ops):
            busy += b - a
    return busy


def test_load_finds_devices_and_the_span(events):
    assert events["devices"], "no device plane"
    names = [n for n, _, _ in events["spans"]]
    assert names.count("bench.dispatch") == 1


def test_busy_union_gaps_and_ops(events):
    red = trace.reduce(events)
    (name, lo, hi), = [s for s in events["spans"] if s[0] == "bench.dispatch"]
    n = len(events["devices"])
    busy = sum(_busy_brute(ops, lo, hi) for ops in events["devices"].values())
    assert red["busy_s"] == pytest.approx(busy / n / 1e9, rel=1e-9)
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9, rel=1e-12)
    first = min(s for ops in events["devices"].values() for s, _, _ in ops)
    last = max(e for ops in events["devices"].values() for _, e, _ in ops)
    if n == 1:
        assert red["lead_s"] == pytest.approx((first - lo) / 1e9)
        assert red["tail_s"] == pytest.approx((hi - last) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    # every idle gap lies inside the dispatch span, longest first
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert all(n.startswith("bench.dispatch: ") for n, _ in red["idle_gaps"])
    # per-op seconds add up to the summed durations inside the span
    total = sum(min(e, hi) - max(s, lo) for ops in events["devices"].values()
                for s, e, _ in ops if e > lo and s < hi)
    assert sum(v for _, v in red["device_ops"]) == pytest.approx(
        total / n / 1e9)
    assert sum(red["op_counts"].values()) == pytest.approx(
        sum(len(ops) for ops in events["devices"].values()) / n)


def test_stage12_ops_are_named(events):
    red = trace.reduce(events)
    assert any(is_stage12(n) for n, _ in red["device_ops"])


def test_stage12_time_is_the_one_kernels(events):
    red = trace.reduce(events)
    secs, calls = stage12_time(red)
    (name,) = {n for n, _ in red["device_ops"] if is_stage12(n)}
    assert secs == dict((n, v) for n, v in red["device_ops"])[name] > 0
    assert calls == red["op_counts"][name] >= 1


def test_a_second_kernel_is_refused(events):
    """A second Mosaic kernel in the trace (one that takes over another
    stage, say) makes the stages 1-2 reading fail, not grow."""
    red = trace.reduce(events)
    (name, secs), = [(n, v) for n, v in red["device_ops"] if is_stage12(n)]
    other = name.replace(name.split(" = ")[0], "%closed_call.99")
    red["device_ops"].append([other, secs])
    red["op_counts"][other] = 1
    with pytest.raises(RuntimeError, match="2 distinct Mosaic kernels"):
        stage12_time(red)
    red["device_ops"] = [[n, v] for n, v in red["device_ops"]
                         if not is_stage12(n)]
    assert stage12_time(red) == (0.0, 0.0)


def test_no_span_no_numbers(events):
    assert trace.reduce(events, span="bench.absent") == {}
