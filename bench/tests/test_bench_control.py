"""The control: the reference computed in bfloat16, the precision below
the configurations' float32, put in the program's place.  Each cell's
limits must fail it (``bench/control.py`` reads it at the cells' own
size on the chip; here at a size a test run holds)."""
import numpy as np
import pytest

from bench import check, modules
from bench.lanes import make_lanes
from bench.tests.cells import small_cell

SIZES = {"ring128_ar": 32e6, "a2a128": 8e6}


@pytest.mark.parametrize("name", ["ring128_ar.atlas_dcqcn",
                                  "a2a128.atlas_dcqcn"])
@pytest.mark.parametrize("lane", [0, 11])
def test_control_fails_the_limits(name, lane):
    """One lane of the control already fails the limits, so the whole
    batch of it (whose numbers are the worst over its lanes) does."""
    cell = small_cell(name, nbytes=SIZES[name.split(".")[0]])
    config = cell["config"]
    ln = make_lanes(cell["mix"], 2**31 + 5)[lane]
    ref = modules.reference(config)
    want = ref.run_lane(config, ln)
    got = ref.run_lane(config, ln, "bfloat16", 2 * want["steps"])
    nums = check.lane_numbers(got, want, config["engine"]["dt"],
                              2 * want["steps"])
    assert not check.verdict(nums, cell["limits"]), nums
    assert np.isfinite(nums["finish_gap_steps"])


@pytest.mark.parametrize("lane", range(8))
def test_control_fails_the_limits_on_every_policy_lane(lane):
    """``a2a128.policy_axis``: the control of each policy's lane (the
    learned one on seeded weights) fails the cell's limits."""
    test_control_fails_the_limits("a2a128.policy_axis", lane)
