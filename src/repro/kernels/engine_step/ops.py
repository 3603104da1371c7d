"""Wrapper: the engine's flat per-flow arrays -> tiled Pallas engine-step
kernel -> flat.

``fused_step`` is the entry point ``repro.core.engine`` dispatches to when
``step_impl`` resolves to "pallas" (see ``engine.resolve_step_impl``):
it pads the (F, MAXHOP) hop arrays and (F,) flow arrays to 128-lane rows,
packs the policy state/params via the ``cc`` flat-array tables, runs the
fused signals+policy kernel and unpacks.

Padding is inert by construction: padded lanes get neutral values (caps 1,
kmax > kmin, masks 0) so no NaN/Inf can leak out of discarded lanes, and
outputs are sliced back to the live prefix.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import cc as cc_mod
from repro.kernels import default_interpret
from repro.kernels.engine_step.engine_step import fused_signals_policy_tiled


def _tile_flat(x, n_pad, fill=0.0):
    """(F,) -> (1, N8, 128)."""
    return jnp.pad(x.astype(jnp.float32), (0, n_pad),
                   constant_values=fill).reshape(1, -1, 128)


def _tile_hop(x, n_pad, fill=0.0):
    """(F, H) -> (1, H, N8, 128)."""
    xt = jnp.pad(x.astype(jnp.float32).T, ((0, 0), (0, n_pad)),
                 constant_values=fill)
    return xt.reshape(1, xt.shape[0], -1, 128)


def fused_step(policy, *, q_d, tx_d, caps, ecn_mask, hopmask,
               kmin_h, kmax_h, pmax_h, base_rtt, line, loss,
               state: dict, params: dict, t, dt: float, t_base_util: float,
               interpret: bool | None = None):
    """Engine stages 1-2 in one fused kernel call.

    Hop-shaped inputs are (F, MAXHOP); flow-shaped inputs are (F,);
    ``state``/``params`` are the policy's dict forms (packed internally
    via ``cc.pack_state``/``cc.pack_params``).  Returns ``(state', rate,
    win)`` matching ``policy.update``'s contract on flat (F,) arrays.
    """
    interpret = default_interpret(interpret)
    F = line.shape[0]
    n_pad = (-F) % 128
    Fp = F + n_pad
    hop_inputs = (
        _tile_hop(q_d, n_pad),
        _tile_hop(tx_d, n_pad),
        _tile_hop(caps, n_pad, fill=1.0),
        _tile_hop(ecn_mask, n_pad),
        _tile_hop(hopmask.astype(jnp.float32), n_pad),
        _tile_hop(kmin_h, n_pad, fill=1.0),
        _tile_hop(kmax_h, n_pad, fill=2.0),
        _tile_hop(pmax_h, n_pad),
    )
    flat_inputs = (
        _tile_flat(base_rtt, n_pad, fill=1.0),
        _tile_flat(line, n_pad, fill=1.0),
        _tile_flat(loss, n_pad),
    )
    packed = cc_mod.pack_state(policy, state, n_flows=F)
    st4d = jnp.pad(packed, ((0, 0), (0, n_pad)),
                   constant_values=1.0).reshape(1, packed.shape[0], -1, 128)
    p2d = cc_mod.pack_params(policy, params).reshape(1, -1)
    st_out, rate, win, _, _, _ = fused_signals_policy_tiled(
        policy, hop_inputs, flat_inputs, st4d, p2d, t,
        dt=dt, t_base_util=t_base_util, interpret=interpret)
    keys = cc_mod.kernel_state_keys(policy)
    new_state = {k: st_out[0, j].reshape(Fp)[:F]
                 for j, k in enumerate(keys)}
    return (new_state,
            rate[0].reshape(Fp)[:F],
            win[0].reshape(Fp)[:F])
