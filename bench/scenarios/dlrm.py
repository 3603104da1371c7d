"""One DLRM training iteration on the paper's two-tier CLOS: the
configuration's ``fabric`` block as ``repro.core.topology.clos`` and its
``workload`` block (the compute profile and the communication sizes) as
``repro.core.workload.build_dlrm_iteration`` over every GPU.

The reference rebuilds the iteration's routes, and a seed has to give the
same flows in every run, so ``build`` also builds the iteration in a
child process under another string-hash seed and refuses a schedule that
differs there (a program whose ECMP salts follow Python's per-process
string hashing).

    python3 bench/scenarios/dlrm.py '<configuration as JSON>'

prints the digest of the iteration the configuration gives (what the
child runs)."""
import hashlib
import json
import os
import subprocess
import sys

from repro.core import topology
from repro.core.topology import clos
from repro.core.workload import (DLRMCommSpec, DLRMComputeProfile,
                                 build_dlrm_iteration)


def _build(config: dict) -> tuple:
    f, job = config["fabric"], config["workload"]
    topo = clos(
        n_racks=f["n_racks"], nodes_per_rack=f["nodes_per_rack"],
        gpus_per_node=f["gpus_per_node"], n_spines=f["n_spines"],
        nic_bw=f["nic_gbit_s"] * 1e9 / 8, nic_lat=f["nic_latency_s"],
        nv_bw=f["nvlink_gbyte_s"] * 1e9, nv_lat=f["nvlink_latency_s"])
    sched = build_dlrm_iteration(
        topo, list(range(topo.n_gpus)), DLRMComputeProfile(**job["compute"]),
        DLRMCommSpec(**job["comm"]))
    return topo, sched


def digest(sched) -> str:
    """SHA-256 of the schedule's paths, sizes, groups, dependencies and
    delays."""
    h = hashlib.sha256()
    for a in (sched.path, sched.size, sched.group, sched.dep, sched.delay):
        h.update(a.tobytes())
    return h.hexdigest()


def _digest_elsewhere(config: dict) -> str:
    """``digest`` of the iteration built in a child process, on the CPU,
    under another ``PYTHONHASHSEED``."""
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(topology.__file__))))
    seed = "2" if os.environ.get("PYTHONHASHSEED") == "1" else "1"
    env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
               PYTHONPATH=src)
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          json.dumps(config)], env=env, capture_output=True,
                         text=True, timeout=600, check=True)
    return out.stdout.split()[-1]


def build(config: dict) -> tuple:
    topo, sched = _build(config)
    if digest(sched) != _digest_elsewhere(config):
        raise RuntimeError(
            "the program builds another DLRM iteration in another process "
            "(routes that follow the process's string hashing): no run of "
            "it can be repeated or checked")
    return topo, sched


if __name__ == "__main__":
    print(digest(_build(json.loads(sys.argv[1]))[1]))
