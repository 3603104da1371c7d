"""The traffic generator, the stages 1-2 byte count and the peak table."""
import csv
import json
import os

import numpy as np
import pytest

from bench import reference
from bench.lanes import make_lanes
from bench.peaks import peaks
from bench.roofline import stage12_bytes, stage12_flops
from bench.tests.cells import ROOT


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_seed_zero_is_the_committed_atlas_grid():
    """Seed 0 of ring128_ar.atlas_dcqcn gives exactly the lanes of the
    committed paper-scale atlas slice, in its order."""
    path = os.path.join(ROOT, "experiments", "atlas",
                        "atlas_paper_ring128.csv")
    with open(path, newline="") as f:
        rows = [r for r in csv.DictReader(f) if r["policy"] == "dcqcn"]
    lanes = make_lanes(_mix("atlas_dcqcn"), 0)
    assert len(lanes) == len(rows) == 12
    for ln, r in zip(lanes, rows):
        assert ln.policy == "dcqcn"
        assert ln.params == {"rai_frac": float(r["param_value"])}
        assert (ln.kmin, ln.kmax, ln.xoff) == (float(r["kmin"]),
                                               float(r["kmax"]),
                                               float(r["xoff"]))


@pytest.mark.parametrize("mix", ["atlas_dcqcn", "policy_axis"])
def test_seed_draws_repeat_and_stay_in_range(mix):
    m = _mix(mix)
    grid = make_lanes(m, 0)
    seed = 2**31 + 12345
    a, b = make_lanes(m, seed), make_lanes(m, seed)
    assert a == b and a != make_lanes(m, seed + 1)
    lo, hi = m["seed_factor"]
    seeded = m.get("seeded_params", {})
    for g, ln in zip(grid, a):
        assert ln.policy == g.policy
        for k in ("kmin", "kmax", "xoff"):
            assert lo * 0.999 <= getattr(ln, k) / getattr(g, k) <= hi * 1.001
        for k, v in ln.params.items():
            if ln.policy in seeded:
                assert abs(v) <= seeded[ln.policy]["scale"]
            else:
                key = m["key_param"][ln.policy]
                assert key["lo"] <= v <= key["hi"]
    assert [ln.policy for ln in a] == [ln.policy for ln in grid]


def test_atlas_lanes_are_what_they_were():
    """The seeded-parameter stream leaves a mix without one exactly as the
    generator gave it before (lanes recorded from that generator)."""
    with open(os.path.join(ROOT, "bench", "tests", "data",
                           "atlas_dcqcn_lanes.json")) as f:
        recorded = json.load(f)["lanes"]
    m = _mix("atlas_dcqcn")
    for seed, rows in recorded.items():
        got = [[ln.policy, ln.params, ln.kmin, ln.kmax, ln.xoff]
               for ln in make_lanes(m, int(seed))]
        assert got == rows, seed


def test_policy_axis_lanes():
    """Every registered policy once, at its defaults, on the simulator's
    fabric defaults; the mlp lane carries exactly the 38 weights of the
    program's net, drawn from the seed inside the spec's bounds."""
    from repro.core.cc import ALL_POLICIES, get_policy
    spec = get_policy("mlp").spec
    weight_keys = tuple(k for k in spec if k not in ("out_gain", "loss_cut"))
    m = _mix("policy_axis")
    draw = m["seeded_params"]["mlp"]
    assert tuple(draw["keys"]) == weight_keys == reference.MLP_WEIGHTS
    assert all(spec[k].lo <= -draw["scale"] and draw["scale"] <= spec[k].hi
               for k in weight_keys)
    seeds = (0, 7, 2**31 + 99)
    runs = [make_lanes(m, s) for s in seeds]
    for lanes in runs:
        assert [ln.policy for ln in lanes] == list(ALL_POLICIES)
        for ln in lanes:
            assert ln.params == {} or ln.policy == "mlp"
            for k, grid in zip(("kmin", "kmax", "xoff"), (400e3, 1.6e6, 1e6)):
                assert 0.95 * 0.999 <= getattr(ln, k) / grid <= 1.05 * 1.001
        w = lanes[-1].params
        assert tuple(w) == weight_keys
        assert all(np.float32(v) == v for v in w.values())
    weights = [np.array(list(r[-1].params.values())) for r in runs]
    assert not np.array_equal(weights[0], weights[1])
    assert not np.array_equal(weights[1], weights[2])


def test_stage12_bytes_at_the_first_cells_shapes():
    """12 dcqcn lanes of 32,512 flows on one chip: per lane, 5 per-hop
    arrays x 4 hops, 8 state arrays in and out and 5 outputs; once, 3
    per-hop and 3 per-flow arrays of the fabric; all float32."""
    ones = np.ones(4, np.float32)
    ctx = {"F": 4, "dtype": np.dtype(np.float32), "line": ones,
           "bdp": ones, "fanin": ones}
    n_state = len(reference.POLICIES["dcqcn"]().init(ctx))
    assert n_state == 8
    got = stage12_bytes(32512, reference.MAXHOP, n_state, 12)
    assert got == 4 * 32512 * (12 * (5 * 4 + 2 * 8 + 5) + 3 * 4 + 3)
    assert got == 65934336
    assert stage12_flops(32512, 4, "dcqcn", 12) == 12 * 32512 * (17 * 4
                                                                  + 1 + 45)


def test_peaks_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
