"""BENCHMARK.json against the benchmark's rules of form, and every name it
uses found as a file under bench/."""
import json
import os
import re

import pytest

from bench.tests.cells import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert len(spec["command"]) <= 32 and all(_line(w) for w in
                                              spec["command"])
    assert spec["command"][1].startswith(spec["paths"][0] + "/")
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    # a full check of 24 cells must fit its 43,200 s
    cells = 24
    need = (2 + 14 * cells) * (spec["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200
    assert need <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_names_units_and_lines(spec):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
    assert len({n for _, n in names if True}) == len(names)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_cells_and_files_found_by_name(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    pairs = set()
    for w in spec["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        c = configs[w["config"]]
        assert c["file"] == f"bench/configs/{w['config']}.json"
        for path in (c["file"], f"bench/traffic/{w['traffic']}.json",
                     f"bench/limits/{w['name']}.json"):
            assert os.path.isfile(os.path.join(ROOT, path)), path
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["assumed"] and cfg["precision"] == "float32"
    assert {w["config"] for w in spec["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_every_cell_reports_what_its_metrics_move(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = [w["name"] for w in spec["workloads"]]

    def reports(metric, cell):
        return cell in e2e[metric].get("workloads", cells)

    for cell in cells:
        assert reports("setup_s", cell)
        assert any(reports(m, cell) for m in e2e if m != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(m["moves"], cell)
    layers = {}
    for m in spec["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
