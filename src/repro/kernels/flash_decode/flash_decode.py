"""Pallas TPU kernel: single-token (decode) attention over a long KV cache.

Split-K/flash-decoding style: grid = (batch, kv_heads, S/block_s); each
step loads a (block_s, D) KV tile into VMEM, updates the online-softmax
running (m, l, acc) scratch, and the final step normalizes into the output
block.  ``length`` is scalar-prefetched to mask the tail.  Block sizes are
MXU-aligned: D padded to 128 lanes, block_s a multiple of 8 sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (block_s, D)
    v = v_ref[0, :, 0].astype(jnp.float32)

    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(pos < len_ref[b], logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_new = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(s == ns - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, length: jax.Array,
                 block_s: int = 256,
                 interpret: bool | None = None) -> jax.Array:
    """q: (B, Hkv, G, D); k/v: (B, S, Hkv, D); length: (B,) int32.

    Returns (B, Hkv, G, D) attention output in q.dtype.
    ``interpret=None`` resolves via ``repro.kernels.default_interpret``."""
    B, Hkv, G, D = q.shape
    S = k.shape[1]
    assert S % block_s == 0, (S, block_s)
    scale = 1.0 / (D ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, S // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s, L: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, D), lambda b, h, s, L: (b, s, h, 0)),
            pl.BlockSpec((1, block_s, 1, D), lambda b, h, s, L: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s, L: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=default_interpret(interpret),
    )(length.astype(jnp.int32), q, k, v)
