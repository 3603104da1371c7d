"""The DLRM iteration (``dlrm128``) against its own reference on the CPU, at
2 racks x 2 nodes x 8 GPUs: the reference builds the program's schedule
row for row (and at the paper's size); every lane of ``pfc_xoff``, and two
dcqcn lanes, agree with it within the cell's limits on three seeds; a
program that drops the compute delays fails them; the scenario refuses an
iteration that another process builds otherwise; and the dependency
gate's ops are charged to ``s3_inject``."""
import re

import numpy as np
import pytest

import repro.core.engine as engine_mod
from bench import check, modules, stages
from bench.lanes import make_lanes
from bench.run import ENTRIES, Program, load_cell, reference_lanes

CELL = "dlrm128.pfc_xoff"
SEEDS = (3, 1717000101, 2**31 + 17)


def _small() -> dict:
    """The cell with the fabric cut to 2 racks (32 GPUs); the compute
    delays, the collectives' bytes and the step budget stay."""
    cell = load_cell(CELL)
    cell["config"]["fabric"]["n_racks"] = 2
    return cell


def _dcqcn(mix: dict) -> dict:
    """Two dcqcn lanes at the policy's defaults, on the mix's first and
    last fabric points."""
    return dict(mix, policies=["dcqcn"], key_param={}, param_span=[1.0],
                fabric_points=[mix["fabric_points"][0],
                               mix["fabric_points"][-1]])


@pytest.mark.parametrize("size", ["small", "paper"])
def test_reference_builds_the_programs_schedule(size):
    cell = _small() if size == "small" else load_cell(CELL)
    config = cell["config"]
    _, sched = modules.scenario(config).build(config)
    _, flows = modules.reference(config).build_scenario(config)
    for key in ("path", "size", "group", "dep", "delay"):
        np.testing.assert_array_equal(getattr(flows, key),
                                      getattr(sched, key), err_msg=key)
    assert flows.n_groups == sched.n_groups == 29
    if size == "paper":
        assert sched.n_flows == 152_581
        assert int((sched.n_hops == 0).sum()) == 5
        # bytes per flow -> flows: the markers; 4 MiB / 4 chunks / 128
        # GPUs per All-to-All pair; 109.5 MiB / 4 chunks / 8 ranks per
        # NVLink flow, / 8 / 16 nodes per NIC flow
        sizes, counts = np.unique(sched.size, return_counts=True)
        assert dict(zip(sizes.tolist(), counts.tolist())) == {
            0.0: 5, 8192.0: 130_048, 224_256.0: 15_360,
            3_588_096.0: 7_168}


def test_scenario_refuses_an_iteration_another_process_builds_otherwise(
        monkeypatch):
    config = _small()["config"]
    scen = modules.scenario(config)
    monkeypatch.setattr(scen, "_digest_elsewhere", lambda cfg: "0" * 64)
    with pytest.raises(RuntimeError, match="another process"):
        scen.build(config)


@pytest.fixture(scope="module")
def small():
    """The small cell, a program for each mix (built once), and a cache of
    each seed's lanes and reference answers."""
    cell = _small()
    mixes = {"pfc": cell["mix"], "dcqcn": _dcqcn(cell["mix"])}
    progs = {k: Program(cell["config"], m, make_lanes(m, SEEDS[0]))
             for k, m in mixes.items()}
    return cell, mixes, progs, {}


def _dispatch(prog: Program, mix: dict, lanes: list):
    fab = {k: np.asarray([getattr(ln, k) for ln in lanes], np.float32)
           for k in ("kmin", "kmax", "xoff")}
    _, _, dispatch = ENTRIES[mix["entry"]](prog.runner, prog.topo,
                                           prog.sched, lanes, fab)
    return dispatch()


def _answers(small, kind: str, seed: int):
    cell, mixes, _, cache = small
    if (kind, seed) not in cache:
        lanes = make_lanes(mixes[kind], seed)
        cache[kind, seed] = lanes, reference_lanes(cell["config"], lanes,
                                                   workers=4)
    return cache[kind, seed]


def _readings(cell, batch, wants) -> list:
    e = cell["config"]["engine"]
    budget = e["max_steps"] * (e["max_extends"] + 1)
    return [check.lane_numbers(Program.lane(batch, i), w, e["dt"], budget)
            for i, w in enumerate(wants)]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_lane_agrees_with_the_reference(small, seed):
    """The 12 PFC lanes of ``pfc_xoff`` and two dcqcn lanes (the delay
    rows under a CC policy too)."""
    cell, mixes, progs, _ = small
    for kind in ("pfc", "dcqcn"):
        lanes, wants = _answers(small, kind, seed)
        assert [ln.policy for ln in lanes] == [kind] * len(lanes)
        assert all(w["finished"] for w in wants)
        readings = _readings(cell, _dispatch(progs[kind], mixes[kind],
                                             lanes), wants)
        assert all(check.verdict(r, cell["limits"]) for r in readings)
        assert check.worst(readings)["status_mismatch"] == 0
        if kind == "pfc":
            # the thresholds matter: the lowest xoff pauses most
            assert len(lanes) == 12
            pauses = [w["pause_count"].sum() for w in wants]
            assert pauses[0] > pauses[-1] and pauses[0] > 0


def test_compute_delays_dropped_fail_the_check(small):
    """The program's schedule with every delay set to 0 (the reference's
    kept): the compute markers release at once and the iteration ends
    hundreds of steps early."""
    cell, mixes, progs, _ = small
    lanes, wants = _answers(small, "pfc", SEEDS[0])
    prog = Program(cell["config"], mixes["pfc"], lanes[:2])
    prog.sched.delay[:] = 0.0
    readings = _readings(cell, prog.dispatch(), wants[:2])
    assert not any(check.verdict(r, cell["limits"]) for r in readings)
    assert check.worst(readings)["finish_gap_steps"] > \
        cell["limits"]["finish_gap_steps"]


def _frames(text: str) -> dict:
    """``{stack frame id: (file, line)}`` of an executable's HLO text,
    from its debug tables (the innermost call site of each frame)."""
    def table(name):
        body = text.split(f"\n{name}\n", 1)[1].split("\n\n", 1)[0]
        return dict(re.findall(r"^(\d+) (.*)$", body, re.M))

    files = {k: v.strip('"') for k, v in table("FileNames").items()}
    locs = {k: (files[re.search(r"file_name_id=(\d+)", v).group(1)],
                int(re.search(r" line=(\d+)", v).group(1)))
            for k, v in table("FileLocations").items()}
    return {int(k): locs[re.search(r"file_location_id=(\d+)", v).group(1)]
            for k, v in table("StackFrames").items()}


def test_dependency_gate_is_charged_to_s3_inject(small):
    """The instructions the engine emits for the dependency gate (its
    group lookups and the release test ``t >= t_dep + delay``) are charged
    to ``s3_inject``, none left unscoped.  Found by their call site in
    ``engine.py``; an instruction traced inside a nested ``jit`` (such as
    ``jnp.where``) is left out, as its call site is that of its first
    trace."""
    _, _, progs, _ = small
    text = progs["pfc"].compile().as_text()
    with open(engine_mod.__file__) as f:
        src = f.read().splitlines()

    def line_of(code):
        return next(i for i, ln in enumerate(src, 1) if code in ln)

    first = line_of('g_done = carry["g_count"]')
    lookups = line_of("dep_ok = jnp.where"), line_of("dep_t = jnp.where")
    last = line_of('started = dep_ok & (t >= dep_t + pp["sdelay"])')
    frames = _frames(text)
    charged = stages.op_stages(text)
    gate = []
    for m in re.finditer(r'^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\('
                         r'[^\n]*op_name="([^"]*)" stack_frame_id=(\d+)',
                         text, re.M):
        name, opcode, op_name, frame = m.groups()
        where, line = frames[int(frame)]
        if (where == engine_mod.__file__ and first <= line <= last
                and "jit(" not in op_name.split("engine_step/")[-1]):
            gate.append((line, opcode, charged[name]))
    ops = {(line, opcode) for line, opcode, _ in gate}
    assert {(lookups[0], "gather"), (lookups[1], "gather"), (last, "add"),
            (last, "compare")} <= ops
    assert {stage for _, _, stage in gate} == {"s3_inject"}
