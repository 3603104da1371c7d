"""Work of the engine step's stages 1-2 (delayed signals and the CC
update), counted from the scenario's shapes.

Each call must read and write, once, in float32:

* per lane: the delayed queue and rate history on each hop, and the
  lane's ECN ramp (kmin, kmax, pmax) on each hop; the policy state in and
  out (K arrays); 5 per-flow outputs (rate, window, ECN, RTT, utilisation);
* once for all lanes: the fabric's per-hop capacities, ECN mask and hop
  mask, and the per-flow base RTT, line rate and loss signal.

The counts use the real F, the fabric's hop limit, the policy's state size
and the lanes one call serves (those on one chip), never padded operand
shapes, so a change of layout cannot move them.  The elementwise work is
about 0.3 FLOP per byte, so HBM bandwidth bounds it.

``stage12_time`` takes the device time of this work from a reduced trace:
today the Mosaic kernel's (a ``tpu_custom_call``).  It raises where the
trace holds more than one distinct Mosaic kernel, so that a second kernel
(one that takes over another stage, say) cannot be folded into stages
1-2 unseen: the reading then waits for the stages to be named in the
trace.
"""
from __future__ import annotations

F32 = 4
LANE_HOP, SHARED_HOP = 5, 3          # per-hop arrays: per lane, shared
SHARED_FLOW, FLOW_OUTPUTS = 3, 5
# per flow and step: per hop, RTT (3), ECN mark (6), its product (2) and
# INT utilisation (6) terms; then the CC update's own arithmetic
FLOPS_PER_HOP = 17
UPDATE_FLOPS = {"pfc": 0, "dcqcn": 45, "dctcp": 14, "timely": 30,
                "hpcc": 20, "hpcc_pint": 21, "static_window": 0}


def stage12_bytes(n_flows: int, max_hop: int, n_state: int,
                  lanes: int) -> int:
    per_lane = LANE_HOP * max_hop + 2 * n_state + FLOW_OUTPUTS
    shared = SHARED_HOP * max_hop + SHARED_FLOW
    return F32 * n_flows * (lanes * per_lane + shared)


def stage12_flops(n_flows: int, max_hop: int, policy: str,
                  lanes: int) -> int:
    return lanes * n_flows * (FLOPS_PER_HOP * max_hop + 1
                              + UPDATE_FLOPS[policy])


def is_stage12(op_name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op_name


def stage12_time(trace: dict) -> tuple[float, float]:
    """``(seconds, calls)`` of stages 1-2 in a reduced trace (``bench.trace
    .reduce``), per device; ``(0.0, 0.0)`` where no Mosaic kernel ran."""
    names = sorted({n for n, _ in trace["device_ops"] if is_stage12(n)})
    if len(names) > 1:
        raise RuntimeError(
            f"{len(names)} distinct Mosaic kernels in the trace, not one: "
            "stages 1-2 cannot be told apart from the rest ("
            + "; ".join(n.split(" = ")[0] for n in names) + ")")
    if not names:
        return 0.0, 0.0
    secs = dict((n, v) for n, v in trace["device_ops"])[names[0]]
    return secs, trace["op_counts"][names[0]]
