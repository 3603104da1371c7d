"""Pallas TPU kernel for the fluid engine's hot inner loop.

``fused_signals_policy_tiled``
    Engine stages 1-2 (see ``repro.core.engine``) fused into one VPU
    pass: ECN-mark product, queueing-delay RTT and HPCC INT utilisation
    across the flow's MAXHOP path slots,
    feeding directly into the *generic* per-flow policy state update — any
    kernel-eligible registered policy (all eight, the learned ``mlp``
    included: the ``Signals``-driven update is pure elementwise jnp, so
    the same tiled body runs DCQCN and HPCC alike; cf. the DCQCN-only
    ``kernels/cc_update``).  Flows tile
    (8, 128) (sublane x lane); the sweep batch axis is folded into the
    leading grid dimension, so a B-lane vmapped sweep is one grid of
    B x ceil(N8/8) tiles instead of B separate dispatches.

The engine's segment reductions and PFC hysteresis stay XLA on every
backend: their padded gather (``engine._reduce``) needs a 2-D vector
index that Mosaic cannot lower.

Params ride in SMEM as a packed (1, P) row per batch lane (sorted-key
order from ``cc.kernel_param_keys``), so CC-parameter sweeps stay traced —
no recompile per parameter point, matching the engine contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import cc as cc_mod


# ---------------------------------------------------------------------------
# kernel A: fused delayed-signal computation + generic policy update
# ---------------------------------------------------------------------------

def _signals_policy_kernel(qd_ref, txd_ref, caps_ref, emask_ref, hmask_ref,
                           kmin_ref, kmax_ref, pmax_ref,
                           brtt_ref, line_ref, loss_ref,
                           state_ref, params_ref, t_ref,
                           o_state, o_rate, o_win, o_ecn, o_rtt, o_util,
                           *, update, state_keys, param_keys, dt,
                           t_base_util, maxhop):
    t = t_ref[0, 0]
    base_rtt = brtt_ref[0]                       # (bs, 128)
    line = line_ref[0]
    loss = loss_ref[0]
    shape = line.shape

    # stage 1: ECN-mark product, queueing RTT, INT utilisation over hops
    rtt = base_rtt
    unmarked = jnp.ones(shape, jnp.float32)
    util = jnp.zeros(shape, jnp.float32)
    for h in range(maxhop):
        q_d = qd_ref[0, h]
        tx_d = txd_ref[0, h]
        caps = caps_ref[0, h]
        hm = hmask_ref[0, h]
        mark = jnp.clip((q_d - kmin_ref[0, h])
                        / jnp.maximum(kmax_ref[0, h] - kmin_ref[0, h], 1.0),
                        0.0, 1.0) * pmax_ref[0, h] * emask_ref[0, h]
        unmarked = unmarked * (1.0 - mark)
        rtt = rtt + q_d / caps * hm
        util_l = tx_d / caps + q_d / (caps * t_base_util)
        util = jnp.maximum(util, jnp.where(hm > 0, util_l, 0.0))
    ecn = 1.0 - unmarked

    # stage 2: the policy's Signals-driven state update (elementwise jnp,
    # so the registered updates run on (bs, 128) tiles unchanged)
    sig = cc_mod.Signals(ecn=ecn, rtt=rtt, util=util, t=t,
                         dt=jnp.float32(dt), line=line, base_rtt=base_rtt,
                         loss=loss)
    params = {k: params_ref[0, 0, j] for j, k in enumerate(param_keys)}
    state = {k: state_ref[0, j] for j, k in enumerate(state_keys)}
    st2, rate, win = update(params, state, sig)
    for j, k in enumerate(state_keys):
        o_state[0, j] = st2[k]
    if not state_keys:                           # stateless: dummy row
        o_state[0, 0] = jnp.zeros(shape, jnp.float32)
    o_rate[0] = rate
    o_win[0] = win
    o_ecn[0] = ecn
    o_rtt[0] = rtt
    o_util[0] = util


def fused_signals_policy_tiled(policy, hop_inputs: tuple, flat_inputs: tuple,
                               state4d: jax.Array, params2d: jax.Array,
                               t: jax.Array, *, dt: float,
                               t_base_util: float, interpret: bool):
    """Run the fused stage-1/2 kernel on tiled inputs.

    ``hop_inputs``: 8-tuple (q_d, tx_d, caps, ecn_mask, hopmask, kmin,
    kmax, pmax), each (B, H, N8, 128) float32; ``flat_inputs``: 3-tuple
    (base_rtt, line, loss), each (B, N8, 128); ``state4d``: (B, K, N8,
    128) packed in ``cc.kernel_state_keys`` order (K >= 1); ``params2d``:
    (B, P) packed in ``cc.kernel_param_keys`` order (P >= 1); ``t``:
    scalar sim time.  Returns (state', rate, win, ecn, rtt, util) with the
    input shapes.  The batch axis B is the leading grid dimension; the
    flow axis is cut into blocks of 8 rows, the last one partial when 8
    does not divide N8 (its out-of-range rows are never written).
    """
    state_keys = cc_mod.kernel_state_keys(policy)
    if state_keys is None:
        raise ValueError(f"policy {policy.name!r} is not kernel-eligible")
    param_keys = cc_mod.kernel_param_keys(policy)
    update = cc_mod.flat_update(policy)

    B, H, N8, _ = hop_inputs[0].shape
    K = state4d.shape[1]
    P = params2d.shape[1]
    bs = min(8, N8)
    hop_spec = pl.BlockSpec((1, H, bs, 128), lambda b, i: (b, 0, i, 0))
    flat_spec = pl.BlockSpec((1, bs, 128), lambda b, i: (b, i, 0))
    st_spec = pl.BlockSpec((1, K, bs, 128), lambda b, i: (b, 0, i, 0))
    # one (1, P) params row per lane: the block's last two dims equal the
    # array's, which Mosaic requires of an SMEM block at any B
    p_spec = pl.BlockSpec((1, 1, P), lambda b, i: (b, 0, 0),
                          memory_space=pltpu.SMEM)
    t_spec = pl.BlockSpec((1, 1), lambda b, i: (0, 0),
                          memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((B, K, N8, 128), jnp.float32)] \
        + [jax.ShapeDtypeStruct((B, N8, 128), jnp.float32)] * 5
    kernel = functools.partial(
        _signals_policy_kernel, update=update, state_keys=state_keys,
        param_keys=param_keys, dt=float(dt),
        t_base_util=float(t_base_util), maxhop=H)
    return pl.pallas_call(
        kernel,
        grid=(B, pl.cdiv(N8, bs)),
        in_specs=[hop_spec] * 8 + [flat_spec] * 3 + [st_spec, p_spec,
                                                     t_spec],
        out_specs=[st_spec] + [flat_spec] * 5,
        out_shape=out_shape,
        interpret=interpret,
    )(*hop_inputs, *flat_inputs, state4d, params2d.reshape(B, 1, P),
      jnp.asarray(t, jnp.float32).reshape(1, 1))
