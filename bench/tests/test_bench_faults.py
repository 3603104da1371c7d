"""The harness, driven on the CPU past its look for a chip, with the timed
path broken underneath: each fault the cells can have makes ``correct``
false (no cell runs on several chips, so none can lose the exchange
between chips).  And the look for a chip itself: no TPU, no result."""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench.run import run_cell
from bench.tests.cells import ROOT, small_cell
from repro.core import engine, sweep

CELL = "a2a128.atlas_dcqcn"
POLICY_AXIS = "a2a128.policy_axis"
SEED = 3


def _run(cell, seed=SEED):
    return run_cell(cell, seed, 1e-3, False, jax.devices(), workers=2)


@pytest.fixture
def fresh_compiles():
    sweep._BATCH_CACHE.clear()
    engine._RUN_CACHE.clear()
    yield
    sweep._BATCH_CACHE.clear()
    engine._RUN_CACHE.clear()


def test_sound_run_is_correct(fresh_compiles):
    res = _run(small_cell(CELL))
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["metrics"]["lane_steps_per_s"]["value"] > 0


def _frozen_step(monkeypatch, cell):
    real = engine._make_step

    def frozen(*a, **k):
        real(*a, **k)
        return lambda carry, *args: carry

    monkeypatch.setattr(engine, "_make_step", frozen)
    res = _run(small_cell(cell))
    assert res["correct"] is False
    assert res["check"]["status_mismatch"]["value"] == 1


def _half_batch(monkeypatch, cell, seed):
    real = sweep.SweepRunner._dispatch_lanes

    def half(self, policy, cfg, sim, full, fab, flt, faulty, B):
        # only the first half of the lanes is computed; the rest of the
        # result stays zero
        out = real(self, policy, cfg, sim, full, fab, flt, faulty, B)

        def first_half(a):
            a = np.array(a)
            a[(B + 1) // 2:] = 0
            return a
        return jax.tree.map(first_half, out)

    monkeypatch.setattr(sweep.SweepRunner, "_dispatch_lanes", half)
    res = _run(small_cell(cell), seed)
    assert res["correct"] is False
    # every dispatch's left-out lanes fail, and only those
    assert res["failed"] == res["attempted"] // 2


def test_step_that_returns_its_state_unchanged(fresh_compiles, monkeypatch):
    _frozen_step(monkeypatch, CELL)


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 11])
def test_half_of_the_batch_left_out(fresh_compiles, monkeypatch, seed):
    _half_batch(monkeypatch, CELL, seed)


@pytest.mark.parametrize("cell,field,alter", [
    # finish times reported 100 steps late
    ("ring128_ar.atlas_dcqcn", "t_finish", lambda a, dt: a + 100 * dt),
    # PAUSE frames miscounted by a tenth
    (CELL, "pause_count", lambda a, dt: a * 1.1),
    # finish times reported 1000 steps late (past the policy axis's limit)
    (POLICY_AXIS, "t_finish", lambda a, dt: a + 1000 * dt),
])
def test_answer_altered_where_produced(fresh_compiles, monkeypatch, cell,
                                       field, alter):
    real = sweep.SweepRunner.run_batch

    def altered(self, *a, **k):
        out = real(self, *a, **k)
        setattr(out, field, alter(getattr(out, field), self.cfg.dt))
        return out

    monkeypatch.setattr(sweep.SweepRunner, "run_batch", altered)
    res = _run(small_cell(cell))
    assert res["correct"] is False


@pytest.mark.parametrize("fault", ["frozen_step", "half_batch"])
def test_policy_axis_faults(fresh_compiles, monkeypatch, fault):
    """The same faults under the stacked policy axis (its dispatch is
    ``run_policy_axis``, which runs the batch through the same path)."""
    if fault == "frozen_step":
        _frozen_step(monkeypatch, POLICY_AXIS)
    else:
        _half_batch(monkeypatch, POLICY_AXIS, 2**31 + 12)


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""


def test_bench_files_alone_give_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
