"""jit'd wrapper: GQA decode attention with the Pallas flash-decode kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.flash_decode import flash_decode


def gqa_decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         length: jax.Array, block_s: int = 256,
                         interpret: bool | None = None) -> jax.Array:
    """q: (B, 1, Hq, D) over cache (B, S, Hkv, D); length () or (B,).

    Drop-in for models.layers.decode_attention on TPU."""
    B, _, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    S = k_cache.shape[1]
    bs = min(block_s, S)
    while S % bs:
        bs //= 2
    qr = q.reshape(B, Hkv, G, D)
    lng = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    out = flash_decode(qr, k_cache, v_cache, lng, block_s=max(bs, 1),
                       interpret=interpret)
    return out.reshape(B, 1, Hq, D)
