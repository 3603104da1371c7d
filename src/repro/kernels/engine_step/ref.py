"""Oracle: engine stages 1-2 in pure jnp.

Mirrors ``repro.core.engine._make_step``'s signal formulas exactly, so the
kernel allclose tests pin the fused Pallas path to the engine's jnp
semantics.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.cc import Signals


def fused_step_ref(policy, *, q_d, tx_d, caps, ecn_mask, hopmask,
                   kmin_h, kmax_h, pmax_h, base_rtt, line, loss,
                   state: dict, params: dict, t, dt: float,
                   t_base_util: float):
    """Flat-array reference for ``ops.fused_step`` (same signature minus
    ``interpret``): returns ``(state', rate, win)``."""
    hopmask = hopmask.astype(bool)
    rtt = base_rtt + (q_d / caps * hopmask).sum(1)
    mark = jnp.clip((q_d - kmin_h) / jnp.maximum(kmax_h - kmin_h, 1.0),
                    0.0, 1.0) * pmax_h
    mark = mark * ecn_mask
    ecn = 1.0 - jnp.prod(1.0 - mark, axis=1)
    util_l = tx_d / caps + q_d / (caps * t_base_util)
    util = jnp.max(jnp.where(hopmask, util_l, 0.0), axis=1)
    sig = Signals(ecn=ecn, rtt=rtt, util=util,
                  t=jnp.asarray(t, jnp.float32), dt=jnp.float32(dt),
                  line=line, base_rtt=base_rtt, loss=loss)
    st2, rate, win = policy.update(dict(policy.params, **(params or {})),
                                   state, sig)
    F = line.shape[0]
    return (st2, jnp.broadcast_to(rate, (F,)), jnp.broadcast_to(win, (F,)))
