"""Pallas TPU kernel: fused DCQCN per-flow state update.

The fluid simulator's arithmetic hot-spot when sweeping CC configurations
on-TPU: 8 state arrays + 1 signal array -> 8 outputs, all elementwise over
flows.  Flows are tiled (8, 128) (sublane x lane) so a 65k-flow schedule is
64 grid steps of one fused VPU pass each — one HBM round-trip instead of
the ~30 XLA would need for the unfused update chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _kernel(rc_ref, rt_ref, alpha_ref, tcut_ref, tinc_ref, talpha_ref,
            cnt_ref, jit_ref, ecn_ref, line_ref, t_ref,
            o_rc, o_rt, o_alpha, o_tcut, o_tinc, o_talpha, o_cnt,
            *, g, rai_frac, rhai_frac, timer, cut_gap, fast_rounds,
            hai_after, ecn_thresh, mss):
    t = t_ref[0, 0]
    rc, rt, alpha = rc_ref[...], rt_ref[...], alpha_ref[...]
    t_cut, t_inc, t_alpha = tcut_ref[...], tinc_ref[...], talpha_ref[...]
    inc_count, jit, ecn, line = cnt_ref[...], jit_ref[...], ecn_ref[...], line_ref[...]

    pkts = rc * cut_gap / mss
    p_cnp = 1.0 - jnp.exp(-pkts * ecn)
    cong = p_cnp > ecn_thresh
    docut = cong & ((t - t_cut) >= cut_gap * jit)
    rt = jnp.where(docut, rc, rt)
    rc = jnp.where(docut, rc * (1 - alpha / 2 * p_cnp), rc)
    alpha = jnp.where(docut, (1 - g * p_cnp) * alpha + g * p_cnp, alpha)
    t_cut = jnp.where(docut, t, t_cut)
    inc_count = jnp.where(docut, 0.0, inc_count)
    t_inc = jnp.where(docut, t, t_inc)

    dodec = (~cong) & ((t - t_alpha) >= timer * jit)
    alpha = jnp.where(dodec, (1 - g) * alpha, alpha)
    t_alpha = jnp.where(dodec | docut, t, t_alpha)

    doinc = (t - t_inc) >= timer * jit
    inc_count = jnp.where(doinc, inc_count + 1, inc_count)
    additive = inc_count > fast_rounds
    hyper = inc_count > fast_rounds + hai_after
    bump = jnp.where(hyper, rhai_frac, rai_frac) * line
    rt = jnp.where(doinc & additive, rt + bump, rt)
    rc = jnp.where(doinc, 0.5 * (rt + rc), rc)
    t_inc = jnp.where(doinc, t, t_inc)

    rc = jnp.clip(rc, 0.001 * line, line)
    rt = jnp.clip(rt, 0.001 * line, line)

    o_rc[...], o_rt[...], o_alpha[...] = rc, rt, alpha
    o_tcut[...], o_tinc[...], o_talpha[...], o_cnt[...] = t_cut, t_inc, t_alpha, inc_count


@functools.partial(jax.jit, static_argnames=("params", "interpret"))
def dcqcn_update_tiled(state2d: tuple, ecn2d: jax.Array, line2d: jax.Array,
                       t: jax.Array, params: tuple,
                       interpret: bool | None = None):
    """state2d: 8-tuple of (N8, 128) float32 arrays
    (rc, rt, alpha, t_cut, t_inc, t_alpha, inc_count, jit); returns the
    7 updated state arrays (jit is static).  The last block of 8 rows is
    partial when 8 does not divide N8.  ``interpret=None`` resolves via
    ``repro.kernels.default_interpret``."""
    pk = dict(params)
    N8 = ecn2d.shape[0]
    bs = min(8, N8)
    spec = pl.BlockSpec((bs, 128), lambda i: (i, 0))
    tspec = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((N8, 128), jnp.float32)] * 7
    outs = pl.pallas_call(
        functools.partial(_kernel, **pk),
        grid=(pl.cdiv(N8, bs),),
        in_specs=[spec] * 10 + [tspec],
        out_specs=[spec] * 7,
        out_shape=out_shape,
        interpret=default_interpret(interpret),
    )(*state2d, ecn2d, line2d, t.reshape(1, 1))
    return outs
