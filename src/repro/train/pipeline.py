"""Pipeline parallelism (GPipe-style) over a "stage" mesh axis.

Optional at the production mesh (the dry-run brief fixes the mesh to
(pod, data, model)), but provided as a first-class primitive for clusters
that want PP instead of deeper DP: stages hold disjoint layer slices and
microbatches stream through `lax.ppermute` inside one shard_map — the
collective-permute traffic pattern the network simulator models.

``gpipe(fn, stage_params, x, mesh, ...)`` where
  fn(params_slice, x) -> x          one stage's computation
  stage_params: leaves (n_stages, ...) sharded over "stage"
  x: (n_micro, micro_batch, ...)    microbatched input

Returns the stacked outputs of the LAST stage, in microbatch order.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def gpipe(fn, stage_params, x, mesh, stage_axis: str = "stage"):
    n_stages = int(mesh.shape[stage_axis])
    n_micro = x.shape[0]

    def local(params_loc, x_loc):
        # params_loc: (1, ...) slice for my stage; x_loc: full microbatches
        # (replicated input: stage 0 reads them, others ignore)
        params_my = jax.tree.map(lambda p: p[0], params_loc)
        sid = lax.axis_index(stage_axis)
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_loc[0])
        outs = jnp.zeros((n_micro,) + x_loc.shape[1:], x_loc.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when available)
            take = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(sid == 0,
                               jnp.where(t < n_micro, 1.0, 0.0), 0.0)
            cur = jnp.where(inject > 0, x_loc[take], buf)
            y = fn(params_my, cur)
            # last stage commits its result for microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - n_stages + 1, 0, n_micro - 1)
            commit = (sid == n_stages - 1) & (t - n_stages + 1 >= 0)
            outs = lax.cond(
                commit,
                lambda o: lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
                lambda o: o, outs)
            # shift activations downstream
            nxt = lax.ppermute(y, stage_axis,
                               [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (_, outs), _ = lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage holds valid outs; broadcast via masked psum
        outs = lax.psum(jnp.where(sid == n_stages - 1, outs, 0.0), stage_axis)
        return outs

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(pspec, P()), out_specs=P(),
                         check_vma=False)(stage_params, x)
