"""Sharded sweep execution: the same grid through the single-device vmap
and through a device mesh, equivalence-checked, plus streamed chunking.

On a machine with one device this demo emulates 8 before importing jax
(the `XLA_FLAGS=--xla_force_host_platform_device_count=8` testing recipe
from README "Scaling sweeps across devices").  Emulated devices share the
host's cores — the point here is placement and equivalence, not speed,
which only real chips can show (``bench/run.py``, ``PERF.md``).

Run:  PYTHONPATH=src python examples/sharded_sweep.py
"""
import os
import time

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import jax                                            # noqa: E402
import numpy as np                                    # noqa: E402

from repro.core import EngineConfig, SweepRunner      # noqa: E402
from repro.core.collectives import allreduce_1d       # noqa: E402
from repro.core.topology import single_switch         # noqa: E402


def main():
    print(f"devices: {len(jax.devices())} ({jax.default_backend()})")
    topo = single_switch(8)
    sched = allreduce_1d(topo, list(range(8)), 8e6)
    cfg = EngineConfig(dt=1e-6, max_steps=2500, max_extends=0,
                       queue_stride=0)

    vm = SweepRunner(cfg)                 # mesh=None: historical vmap path
    sh = SweepRunner(cfg, mesh="auto")    # grid axis over all devices
    print(f"mesh: {sh.mesh}  lanes/device state: "
          f"{sh.lane_state_bytes(topo, sched, 'dcqcn')} B/lane")

    # a 22-lane CC x fabric grid (non-divisible: 8-device mesh pads to 24
    # by edge-repeating, then masks the padding back out)
    grid = {"rai_frac": list(np.geomspace(0.005, 0.1, 11))}
    fabric_grid = {"kmin": [200e3, 400e3]}
    for name, runner in (("vmap", vm), ("sharded", sh)):
        runner.grid(topo, sched, "dcqcn", grid, fabric_grid)   # warm up
        t0 = time.time()
        batch = runner.grid(topo, sched, "dcqcn", grid, fabric_grid)
        print(f"  {name:8s} B={batch.n:3d} warm {time.time()-t0:6.3f}s "
              f"best lane #{batch.best()} "
              f"ct={batch.completion_time[batch.best()]*1e3:.3f}ms")
    a = vm.grid(topo, sched, "dcqcn", grid, fabric_grid)
    b = sh.grid(topo, sched, "dcqcn", grid, fabric_grid)
    print("  equivalent (rtol 1e-5):",
          np.allclose(a.completion_time, b.completion_time, rtol=1e-5))

    # streamed chunking: the same grid in chunks of one mesh-width — the
    # per-device working set is chunk/n_dev lanes regardless of grid size
    shc = SweepRunner(cfg, mesh="auto", chunk_lanes=sh.n_mesh_devices)
    c = shc.grid(topo, sched, "dcqcn", grid, fabric_grid)
    print(f"  chunked  B={c.n:3d} chunks of {shc._chunk_size(c.n)}: "
          "equivalent",
          np.allclose(a.completion_time, c.completion_time, rtol=1e-5))


if __name__ == "__main__":
    main()
