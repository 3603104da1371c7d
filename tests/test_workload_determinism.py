"""The DLRM iteration's schedule is the same in every process: its
All-to-All halves' ECMP salts do not follow Python's per-process string
hashing (``PYTHONHASHSEED``)."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUILD = """
import hashlib
from repro.core.topology import clos
from repro.core.workload import build_dlrm_iteration
topo = clos(n_racks=2)
sched = build_dlrm_iteration(topo, list(range(topo.n_gpus)))
print(hashlib.sha256(sched.path.tobytes()).hexdigest())
"""


def _paths_digest(hash_seed: str) -> str:
    env = dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", BUILD], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return out.stdout.split()[-1]


def test_dlrm_routes_are_the_same_under_any_hash_seed():
    assert _paths_digest("0") == _paths_digest("4242")
