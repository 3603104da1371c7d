"""One collective on the paper's two-tier CLOS: the configuration's
``fabric`` block as ``repro.core.topology.clos`` and its ``collective``
block as a registered collective over every GPU."""
from repro.core.collectives import get_collective
from repro.core.topology import clos


def build(config: dict) -> tuple:
    f, col = config["fabric"], config["collective"]
    topo = clos(
        n_racks=f["n_racks"], nodes_per_rack=f["nodes_per_rack"],
        gpus_per_node=f["gpus_per_node"], n_spines=f["n_spines"],
        nic_bw=f["nic_gbit_s"] * 1e9 / 8, nic_lat=f["nic_latency_s"],
        nv_bw=f["nvlink_gbyte_s"] * 1e9, nv_lat=f["nvlink_latency_s"])
    sched = get_collective(col["kind"])(
        topo, list(range(topo.n_gpus)), float(col["bytes"]),
        n_chunks=int(col["n_chunks"]))
    return topo, sched
