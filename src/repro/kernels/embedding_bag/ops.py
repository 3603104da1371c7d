"""jit'd public wrapper: stacked DLRM tables -> pooled bags via Pallas."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.embedding_bag import embedding_bag_rows


def embedding_bag_stacked(tables: jax.Array, idx: jax.Array,
                          interpret: bool | None = None) -> jax.Array:
    """tables (T, R, D), idx (B, T, P) int32 -> (B, T, D) in tables.dtype.

    Flattens the stacked tables to one (T*R, Dp) row space (row id =
    t*R + idx), pads D to a 128-lane multiple, and runs the
    scalar-prefetch gather-accumulate kernel over (B*T, P).
    ``interpret=None`` resolves via ``repro.kernels.default_interpret``."""
    T, R, D = tables.shape
    B = idx.shape[0]
    P = idx.shape[2]
    Dp = max(128, ((D + 127) // 128) * 128)
    tab2d = tables.reshape(T * R, D)
    if Dp != D:
        tab2d = jnp.pad(tab2d, ((0, 0), (0, Dp - D)))
    # bag (b, t) -> rows t*R + idx[b, t, :]
    rows = (idx + (jnp.arange(T, dtype=idx.dtype) * R)[None, :, None])
    rows = rows.reshape(B * T, P).astype(jnp.int32)
    out = embedding_bag_rows(tab2d, rows, interpret=interpret)
    return out[:, :D].reshape(B, T, D).astype(tables.dtype)
