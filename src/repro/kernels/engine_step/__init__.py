# Fused engine-step kernel: signals + policy update.  ops.py (the flat
# wrapper the engine dispatches to), engine_step.py (the tiled
# pallas_call), ref.py (pure-jnp oracle).
from repro.kernels.engine_step.ops import fused_step  # noqa: F401
