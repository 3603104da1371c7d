"""Pallas TPU kernel: multi-hot embedding-bag sum-pooling (DLRM hot-spot).

TPU adaptation (DESIGN.md §6): the GPU version is a warp-per-bag gather;
on TPU the idiom is *scalar-prefetch-driven DMA* — the multi-hot indices
are prefetched as scalars, and each grid step's BlockSpec index_map selects
the (1, D) table row to DMA from HBM into VMEM, accumulating into the
revisited output block.  grid = (bags, pooling); rows land MXU-aligned by
padding D to a lane multiple (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _kernel(idx_ref, table_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += table_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_rows(table2d: jax.Array, rows: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """table2d: (R, Dp) with Dp % 128 == 0; rows: (NB, P) int32.

    Returns (NB, Dp) float32 sum-pooled bags.  ``interpret=None``
    resolves via ``repro.kernels.default_interpret``."""
    NB, P = rows.shape
    _, Dp = table2d.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NB, P),
        in_specs=[
            pl.BlockSpec((1, Dp), lambda i, j, idx_ref: (idx_ref[i, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, Dp), lambda i, j, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NB, Dp), jnp.float32),
        interpret=default_interpret(interpret),
    )(rows, table2d)
