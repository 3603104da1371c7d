"""The engine's trace tags: the named scopes of the step's stages and of the
loop's lane gate in the compiled executable, the Pallas kernel's stable
name, ``BatchResults.steps_run``, and the flow counts on the sweep's
``sweep.inputs`` span.

The benchmark reads the scopes from the executable's op metadata
(``bench/trace.py`` ``op_stages``) and the loop count from
``steps_run``; these tests pin what it reads on the CPU, on both step
paths (the Pallas one in interpret mode), lossless and lossy.
"""
import re

import jax
import numpy as np
import pytest

from repro.core import EngineConfig, SweepRunner, incast, single_switch
from repro.core.cc import get_policy
from repro.core.engine import Simulator
from repro.core.faults import FaultSpec

# the scopes each path builds: stages 1-2 are one kernel on the Pallas path
COMMON = ("engine_step", "s1_signals", "s3_inject", "s4_gates",
          "s5_forward", "s6_queues", "s7_pfc", "s8_completion",
          "s9_history", "s10_health", "engine_loop/lane_gate")
PATH_SCOPES = {"jnp": ("s2_cc",), "pallas": ("s12_fused",)}
ALL_STAGES = ("s1_signals", "s2_cc", "s12_fused", "s3_inject", "s4_gates",
              "s5_forward", "s5b_loss", "s6_queues", "s7_pfc",
              "s8_completion", "s9_history", "s10_health")
RAI = np.array([0.5, 1.0, 2.0], np.float32)


def _scenario():
    topo = single_switch(8)
    return topo, incast(topo, list(range(1, 8)), 0, 5e6)


def _cfg(impl="jnp", **kw):
    return EngineConfig(dt=1e-6, max_steps=1500, max_extends=3,
                        queue_stride=0, step_impl=impl, **kw)


def _op_names(hlo_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _has_scope(names: set, scope: str) -> bool:
    return any(re.search(rf"(^|[/;]){re.escape(scope)}/", n) for n in names)


@pytest.mark.parametrize("lossy", [False, True], ids=["lossless", "lossy"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_stage_scopes_in_the_executable(impl, lossy):
    topo, sched = _scenario()
    kw = {"fault_spec": FaultSpec.lossy_roce(1e-3)} if lossy else {}
    text = SweepRunner(_cfg(impl)).compile_batch(
        topo, sched, "dcqcn", {"rai_frac": RAI}, **kw).as_text()
    names = _op_names(text)
    built = COMMON + PATH_SCOPES[impl] + (("s5b_loss",) if lossy else ())
    for scope in built:
        assert _has_scope(names, scope), scope
    for scope in set(ALL_STAGES) - set(built):
        assert not _has_scope(names, scope), scope
    # every stage sits under the step's outer scope
    for scope in built:
        if scope.startswith("s"):
            assert _has_scope(names, f"engine_step/{scope}"), scope
    if impl == "pallas":
        assert _has_scope(names, "s12_fused/engine_step_signals_cc")


def test_unbatched_run_gates_under_the_lane_gate_scope():
    topo, sched = _scenario()
    sim = Simulator(topo, sched, get_policy("dcqcn"), _cfg())
    names = _op_names(sim.compile().as_text())
    assert _has_scope(names, "engine_loop/lane_gate")
    assert _has_scope(names, "engine_step/s5_forward")


def test_steps_run_is_the_loop_count():
    topo, sched = _scenario()
    cfg = _cfg()
    batch = SweepRunner(cfg).run_batch(topo, sched, "dcqcn",
                                       {"rai_frac": RAI})
    assert batch.steps_run.shape == (3,)
    assert len(set(batch.steps_run.tolist())) == 1   # one shared loop
    own = np.round(batch.completion_time / cfg.dt).astype(int)
    chunk = cfg.chunk_steps
    assert batch.steps_run[0] == -(-own.max() // chunk) * chunk
    assert (own < batch.steps_run).all()
    serial = [Simulator(topo, sched, get_policy("dcqcn"), cfg).run(
        {**get_policy("dcqcn").params, "rai_frac": float(r)}
    ).meta["steps_run"] for r in RAI]
    assert batch.steps_run[0] == max(serial)
    one = SweepRunner(cfg).run_batch(topo, sched, "dcqcn",
                                     {"rai_frac": RAI[:1]})
    assert one.steps_run.tolist() == [serial[0]]


class _Recorded:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span's
    name and its arguments, given at the start or set inside it."""
    spans: list = []

    def __init__(self, name, **kwargs):
        self.kwargs = dict(kwargs)
        _Recorded.spans.append((name, self.kwargs))

    def set_metadata(self, **kwargs):
        self.kwargs.update(kwargs)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_inputs_span_carries_the_flow_counts(monkeypatch):
    topo, sched = _scenario()
    monkeypatch.setattr(_Recorded, "spans", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorded)
    SweepRunner(_cfg()).run_batch(topo, sched, "dcqcn", {"rai_frac": RAI})
    inputs = [kw for name, kw in _Recorded.spans if name == "sweep.inputs"]
    assert inputs == [{"batch": 1, "flows": sched.n_flows,
                       "flows_padded": 32}]
    # the other spans keep their arguments
    assert {name for name, _ in _Recorded.spans} >= {
        "sweep.dispatch", "sweep.pull", "sweep.results"}
    for name, kw in _Recorded.spans:
        if name != "sweep.inputs":
            assert "flows" not in kw
