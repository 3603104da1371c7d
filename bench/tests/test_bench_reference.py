"""The plain reference against the program on the CPU, at small sizes:
same routes, and lanes that agree flow by flow."""
import jax
import numpy as np
import pytest

from bench import check, modules, reference
from bench.lanes import make_lanes
from bench.run import Program
from bench.tests.cells import small_pair


def test_routes_match_the_programs_schedule():
    for config in ("ring128_ar", "a2a128"):
        cell = small_pair(config, "atlas_dcqcn")
        lanes = make_lanes(cell["mix"], 0)
        prog = Program(cell["config"], cell["mix"], lanes)
        _, flows = modules.reference(cell["config"]).build_scenario(
            cell["config"])
        np.testing.assert_array_equal(flows.path, prog.sched.path)
        np.testing.assert_array_equal(flows.size, prog.sched.size)
        np.testing.assert_array_equal(flows.dep, prog.sched.dep)


def _one_policy(policy: str, seeded: dict) -> dict:
    """A mix of one lane: ``policy`` at its defaults (the learned one on
    seeded weights), at the fabric's."""
    return {"entry": "run_batch", "mesh": None, "policies": [policy],
            "key_param": {}, "param_span": [1.0],
            "fabric_points": [[400e3, 1.6e6, 1e6]],
            "seed_factor": [0.95, 1.05], "seeded_params": seeded}


@pytest.mark.parametrize("config,traffic,policy", [
    ("ring128_ar", "atlas_dcqcn", None),
    ("a2a128", "atlas_dcqcn", None),
    ("a2a128", "policy_axis", None),
] + [("a2a128", None, p) for p in sorted(reference.POLICIES)
     if p != "dcqcn"])
def test_every_lane_agrees_with_the_reference(config, traffic, policy):
    """Every lane of the mix, and every other policy the reference has
    (``policy_axis``: all 8 in one stacked dispatch, mlp on seeded
    weights)."""
    cell = small_pair(config, traffic or "atlas_dcqcn")
    if policy:
        cell["mix"] = _one_policy(policy, small_pair(
            config, "policy_axis")["mix"]["seeded_params"])
    lanes = make_lanes(cell["mix"], 987654321)
    prog = Program(cell["config"], cell["mix"], lanes)
    batch = prog.dispatch()
    e = cell["config"]["engine"]
    budget = e["max_steps"] * (e["max_extends"] + 1)
    ref = modules.reference(cell["config"])
    readings = []
    for i, ln in enumerate(lanes):
        want = ref.run_lane(cell["config"], ln)
        assert want["finished"]
        readings.append(check.lane_numbers(Program.lane(batch, i), want,
                                           e["dt"], budget))
    worst = check.worst(readings)
    assert worst["status_mismatch"] == 0
    assert worst["finish_gap_steps"] <= 1.0 + 1e-3
    assert worst["pause_gap"] <= 1e-5
    assert jax.devices()[0].platform == "cpu"
