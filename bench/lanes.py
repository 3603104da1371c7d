"""The traffic generator: a traffic mix's parameters and a seed -> sweep lanes.

A mix (``bench/traffic/<mix>.json``) is the shape of a sweep users submit:
the policies, each policy's key parameter and the span around its default,
and the fabric corners (kmin, kmax, xoff).  Lanes are the cross product
policy x key-parameter point x fabric corner, in that order.  The seed
multiplies each lane's key parameter, kmin, kmax and xoff by factors drawn
log-uniform in the mix's ``seed_factor`` range, then clips the key
parameter to its bounds; seed 0 draws no factors, so it gives the grid
itself.

A policy named in the mix's ``seeded_params`` (``{policy: {"keys": [...],
"scale": s}}``) gets each listed parameter drawn uniform in [-s, s] from
the seed, on every seed (seed 0 too), from a stream of its own: the
factors above, and so every lane of a mix without ``seeded_params``, are
what they were before such draws existed.  The learned ``mlp`` policy's
weights come from the seed this way.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Lane:
    policy: str
    params: dict          # key parameter -> float32 value ({} at defaults)
    kmin: float
    kmax: float
    xoff: float


def make_lanes(mix: dict, seed: int) -> list[Lane]:
    rows = []
    for pol in mix["policies"]:
        key = mix["key_param"].get(pol)
        span = mix["param_span"] if key else [None]
        for s in span:
            for kmin, kmax, xoff in mix["fabric_points"]:
                rows.append((pol, key, s, kmin, kmax, xoff))
    lo, hi = mix["seed_factor"]
    if seed == 0:
        fac = np.ones((len(rows), 4))
    else:
        rng = np.random.default_rng(seed)
        fac = np.exp(rng.uniform(np.log(lo), np.log(hi), (len(rows), 4)))
    seeded = mix.get("seeded_params", {})
    wrng = np.random.default_rng([seed, 1]) if seeded else None
    lanes = []
    for (pol, key, s, kmin, kmax, xoff), f in zip(rows, fac):
        params = {}
        if key:
            d, klo, khi = key["default"], key["lo"], key["hi"]
            v = min(max(d * s, klo), khi)
            v = min(max(v * f[0], klo), khi)
            params = {key["name"]: float(np.float32(v))}
        if pol in seeded:
            draw = seeded[pol]
            vals = wrng.uniform(-draw["scale"], draw["scale"],
                                len(draw["keys"]))
            params.update({k: float(np.float32(v))
                           for k, v in zip(draw["keys"], vals)})
        lanes.append(Lane(pol, params, float(np.float32(kmin * f[1])),
                          float(np.float32(kmax * f[2])),
                          float(np.float32(xoff * f[3]))))
    return lanes
