"""Per-kernel allclose vs ref.py oracles over shape/dtype sweeps
(interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.cc import FlowCtx, Signals, make_dcqcn
from repro.kernels.cc_update.ops import dcqcn_update
from repro.kernels.embedding_bag.ops import embedding_bag_stacked
from repro.kernels.embedding_bag.ref import embedding_bag_stacked_ref
from repro.kernels.flash_decode.ops import gqa_decode_attention
from repro.kernels.flash_decode.ref import flash_decode_ref

pytestmark = pytest.mark.kernel


# ---------------------------------------------------------------- embedding
@pytest.mark.parametrize("T,R,D,B,P", [(2, 16, 64, 2, 3), (4, 64, 64, 3, 60),
                                       (1, 8, 128, 2, 5), (3, 32, 96, 2, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_shapes(T, R, D, B, P, dtype, key):
    tables = jax.random.normal(key, (T, R, D), dtype)
    idx = jax.random.randint(key, (B, T, P), 0, R)
    out = embedding_bag_stacked(tables, idx)
    ref = embedding_bag_stacked_ref(tables, idx)
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@given(st.integers(1, 4), st.integers(1, 16), st.integers(1, 3))
@settings(max_examples=10, deadline=None)
def test_embedding_bag_property(T, P, B):
    key = jax.random.PRNGKey(T * 100 + P * 10 + B)
    tables = jax.random.normal(key, (T, 32, 64), jnp.float32)
    idx = jax.random.randint(key, (B, T, P), 0, 32)
    out = embedding_bag_stacked(tables, idx)
    ref = embedding_bag_stacked_ref(tables, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- flash decode
@pytest.mark.parametrize("B,S,Hkv,G,D,bs", [
    (1, 256, 1, 1, 128, 128), (2, 512, 2, 4, 128, 256),
    (2, 384, 4, 2, 64, 128), (1, 1024, 2, 8, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_shapes(B, S, Hkv, G, D, bs, dtype, key):
    q = jax.random.normal(key, (B, 1, Hkv * G, D), dtype)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), dtype)
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), dtype)
    lng = jnp.asarray([S - 17] + [S] * (B - 1), jnp.int32)
    out = gqa_decode_attention(q, kc, vc, lng, block_s=bs)
    ref = flash_decode_ref(q.reshape(B, Hkv, G, D), kc, vc, lng).reshape(B, 1, Hkv * G, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@given(st.integers(1, 3), st.sampled_from([128, 256, 512]), st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_flash_decode_property(B, S, cut):
    key = jax.random.PRNGKey(B * 1000 + S + cut)
    Hkv, G, D = 2, 2, 64
    q = jax.random.normal(key, (B, 1, Hkv * G, D), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D), jnp.float32)
    lng = jnp.full((B,), max(1, S - cut), jnp.int32)
    out = gqa_decode_attention(q, kc, vc, lng, block_s=128)
    ref = flash_decode_ref(q.reshape(B, Hkv, G, D), kc, vc, lng).reshape(B, 1, Hkv * G, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------- cc update
# 1100 and 7936 flows: more than 8 rows of 128, not a multiple of 8, so
# the kernel's last block is partial
@pytest.mark.parametrize("F", [7, 128, 300, 1000, 1100, 7936])
def test_cc_update_matches_policy(F, key):
    pol = make_dcqcn()
    line = jnp.full((F,), 25e9, jnp.float32)
    st_ = pol.init(FlowCtx.make(line, line * 2e-6))
    st_ = dict(st_, rc=st_["rc"] * jax.random.uniform(key, (F,), minval=0.05, maxval=1.0),
               alpha=jax.random.uniform(key, (F,), minval=0.1, maxval=1.0))
    ecn = jax.random.uniform(jax.random.PRNGKey(9), (F,), maxval=0.4)
    got = dcqcn_update(st_, ecn, line, 2e-3, pol.params)
    sig = Signals(ecn=ecn, rtt=jnp.zeros(F), util=jnp.zeros(F),
                  t=jnp.asarray(2e-3, jnp.float32), dt=jnp.float32(1e-6),
                  line=line, base_rtt=jnp.zeros(F))
    want, _, _ = pol.update(pol.params, st_, sig)
    for k in ("rc", "rt", "alpha", "t_cut", "t_inc", "t_alpha", "inc_count"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
