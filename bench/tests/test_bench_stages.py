"""The readers of the program's trace tags (``bench/stages.py``), on the
CPU: the op -> stage map of an executable's HLO text and the sweep's host
spans in a profile."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import stages
from repro.core import EngineConfig, SweepRunner, incast, single_switch

RAI = np.array([0.5, 1.0, 2.0], np.float32)

HLO = """HloModule m

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/engine_step/s3_inject/add"}
  ROOT %mul.2 = f32[4]{0} multiply(%add.1, %add.1), metadata={op_name="jit(f)/while/body/engine_step/s6_queues/mul"}
}

%fused_computation.2 (param_0.1: f32[4]) -> (f32[4], f32[4]) {
  %param_0.1 = f32[4]{0} parameter(0)
  %neg.3 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(f)/engine_step/s7_pfc/neg"}
  ROOT %tuple.4 = (f32[4]{0}, f32[4]{0}) tuple(%neg.3, %neg.3)
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.5 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/engine_step/s3_inject/add"}
  %fusion.6 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/engine_loop/lane_gate/select_n;jit(f)/engine_step/s7_pfc/neg"}
  %copy.7 = f32[4]{0} copy(%p)
  %sel.8 = f32[4]{0} select(%p, %p, %p), metadata={op_name="jit(f)/engine_loop/lane_gate/cond/branch_1_fun/engine_step/s1_signals/gather"}
  ROOT %x.10 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/while/body/add"}
}
"""


def test_op_stages_reads_scopes():
    m = stages.op_stages(HLO)
    assert m["fusion.5"] == "s6_queues"        # a fusion: its root's stage
    assert m["fusion.6"] == "lane_gate"        # tuple root: its own, first
    assert m["sel.8"] == "s1_signals"          # the innermost scope
    assert m["copy.7"] == m["x.10"] == m["p"] == stages.UNSCOPED
    assert m["add.1"] == "s3_inject" and m["tuple.4"] == stages.UNSCOPED
    assert stages.op_key("%fusion.5 = f32[4]{0} fusion(%p)") == "fusion.5"


def _scenario():
    topo = single_switch(8)
    return topo, incast(topo, list(range(1, 8)), 0, 5e6)


@pytest.mark.parametrize("impl,fused", [("jnp", "s2_cc"),
                                        ("pallas", "s12_fused")])
def test_op_stage_map_covers_the_executable(impl, fused):
    topo, sched = _scenario()
    runner = SweepRunner(EngineConfig(dt=1e-6, max_steps=1500,
                                      max_extends=3, step_impl=impl))
    text = runner.compile_batch(topo, sched, "dcqcn",
                                {"rai_frac": RAI}).as_text()
    m = stages.op_stages(text)
    names = set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", text, re.M))
    assert set(m) == names
    assert set(m.values()) <= set(stages.STAGES) | {stages.UNSCOPED}
    built = {"s1_signals", fused, "s3_inject", "s4_gates", "s5_forward",
             "s6_queues", "s7_pfc", "s8_completion", "s9_history",
             "s10_health", "lane_gate"}
    assert set(m.values()) - {stages.UNSCOPED} == built
    # the stepping loop's own fusions are, nearly all, charged to a stage
    body = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]* fusion\([^\n]*"
                      r"op_name=\"[^\"]*while/body/while/body/", text, re.M)
    scoped = [n for n in body if m[n] != stages.UNSCOPED]
    assert body and len(scoped) >= 0.9 * len(body)


def test_sweep_spans_nest_in_the_dispatch(tmp_path):
    topo, sched = _scenario()
    runner = SweepRunner(EngineConfig(dt=1e-6, max_steps=1500,
                                      max_extends=3, queue_stride=0))
    args = (topo, sched, "dcqcn", {"rai_frac": RAI})
    runner.run_batch(*args)                         # compiled outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            batch = runner.run_batch(*args)
    finally:
        jax.profiler.stop_trace()
    assert batch.n == 3
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = stages.host_spans(ProfileData.from_file(path))
    (lo, hi), = [(s, e) for n, s, e in spans if n == "bench.dispatch"]
    inner = [(n, s, e) for n, s, e in spans if n.startswith("sweep.")]
    assert [n for n, _, _ in inner] == ["sweep.inputs", "sweep.dispatch",
                                        "sweep.pull", "sweep.results"]
    assert all(lo <= s <= e <= hi for _, s, e in inner)
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
