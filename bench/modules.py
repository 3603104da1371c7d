"""A configuration's own modules, found by the names its file gives.

* ``"scenario"``: ``bench/scenarios/<name>.py``, exposing ``build(config)
  -> (topo, sched)``, built only through the program's public API;
* ``"reference"``: ``bench/<name>.py``, the plain reference, exposing
  ``run_lane(config, lane, dtype_name, max_steps)``,
  ``build_scenario(config)``, ``POLICIES`` and ``MAXHOP``, and importing
  nothing of the program.

Both are loaded by path, so that a configuration brings its own as new
files; a reference may import ``bench.reference`` and extend it.
"""
from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))

_LOADED: dict = {}          # absolute path -> module


def load(path: str):
    """The module at ``path``, executed once per process."""
    path = os.path.abspath(path)
    mod = _LOADED.get(path)
    if mod is None:
        stem = os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(
            f"bench_module{len(_LOADED)}_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod          # dataclasses look it up
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return mod


def scenario(config: dict, bench: str = BENCH):
    return load(os.path.join(bench, "scenarios",
                             f"{config['scenario']}.py"))


def reference(config: dict, bench: str = BENCH):
    return load(os.path.join(bench, f"{config['reference']}.py"))


def reference_lane(bench: str, config: dict, lane,
                   dtype_name: str = "float32",
                   max_steps: int | None = None) -> dict:
    """One lane through the configuration's reference: what a worker
    process runs (a module loaded by path cannot be pickled, its
    directory and name can)."""
    return reference(config, bench).run_lane(config, lane, dtype_name,
                                             max_steps)
