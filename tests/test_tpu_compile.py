"""The engine-step kernels compile for a TPU v5e at the paper's width.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned tiles, too much fast memory, vector gathers
Mosaic cannot lower.  These tests compile the Mosaic path for a
*described* v5e — no chip attached — at the 128-GPU ring all-reduce
width (32,512 flows: 254 rows of 128, so the last block of 8 rows is
partial), for one lane and for a 12-lane sweep batch.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports every
test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cc
from repro.core.topology import MAXHOP
from repro.kernels.cc_update.cc_update import dcqcn_update_tiled
from repro.kernels.engine_step.engine_step import fused_signals_policy_tiled

pytestmark = pytest.mark.kernel

PAPER_RING_FLOWS = 32_512
N8 = -(-PAPER_RING_FLOWS // 128)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [1, 12])
@pytest.mark.parametrize("pol", ["dcqcn", "hpcc", "mlp"])
def test_fused_signals_policy_compiles_for_v5e(one_chip, pol, B):
    policy = cc.get_policy(pol)
    K = max(len(cc.kernel_state_keys(policy)), 1)
    P = cc.pack_params(policy, None).shape[0]
    hop = tuple(_f32((B, MAXHOP, N8, 128), one_chip) for _ in range(8))
    flat = tuple(_f32((B, N8, 128), one_chip) for _ in range(3))

    def step(hop, flat, state, params, t):
        return fused_signals_policy_tiled(
            policy, hop, flat, state, params, t, dt=4e-6,
            t_base_util=1e-5, interpret=False)

    compiled = jax.jit(step).lower(
        hop, flat, _f32((B, K, N8, 128), one_chip), _f32((B, P), one_chip),
        _f32((), one_chip)).compile()
    _assert_mosaic(compiled)


def test_dcqcn_update_compiles_for_v5e(one_chip):
    params = tuple(sorted(cc.get_policy("dcqcn").params.items()))
    tile = _f32((N8, 128), one_chip)
    compiled = dcqcn_update_tiled.lower(
        (tile,) * 8, tile, tile, _f32((), one_chip), params,
        interpret=False).compile()
    _assert_mosaic(compiled)
