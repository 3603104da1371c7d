"""Small cells for the CPU tests: the committed files with the fabric cut
to 2 racks (32 GPUs), 8 MB collectives and a short budget."""
import os

from bench.run import load_cell, load_json

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _shrink(config: dict, racks: int, nbytes: float) -> dict:
    config["fabric"]["n_racks"] = racks
    config["collective"]["bytes"] = nbytes
    config["engine"].update(max_steps=600, max_extends=1)
    return config


def small_cell(name: str, racks: int = 2, nbytes: float = 8e6) -> dict:
    """A cell of BENCHMARK.json, with its limits."""
    cell = load_cell(name)
    _shrink(cell["config"], racks, nbytes)
    return cell


def small_pair(config: str, traffic: str, racks: int = 2,
               nbytes: float = 8e6) -> dict:
    """A configuration file under a traffic mix, whether or not a cell of
    BENCHMARK.json pairs them."""
    bench = os.path.join(ROOT, "bench")
    return {"config": _shrink(load_json(bench, "configs", f"{config}.json"),
                              racks, nbytes),
            "mix": load_json(bench, "traffic", f"{traffic}.json")}
