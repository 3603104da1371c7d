# TPU Pallas kernels for the compute hot-spots of this system:
#   embedding_bag — DLRM multi-hot pooled lookup (the paper's workload)
#   flash_decode  — chunked-KV decode attention (serving shape cells)
#   cc_update     — fused DCQCN per-flow state update (the simulator's
#                   inner loop when sweeping CC configs on-TPU)
#   engine_step   — fused engine signals + generic policy update (the
#                   simulator's stage-1/2 hot loop; see repro.core.engine
#                   step_impl)
# Each has ops.py (jit wrapper) + ref.py (pure-jnp oracle) + allclose tests.
from __future__ import annotations

import jax


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve the kernel ``interpret`` convention.

    ``None`` (the default everywhere) auto-detects: compiled Mosaic on TPU,
    interpret mode elsewhere (the CPU test runs).  Pass an explicit bool
    to force either path.
    """
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
