"""THE question the engine asks the device, asked in one place.

Every trace-time choice of a TPU-only form -- the scatter-free small-G
kernels, bf16 MXU operands and the Pallas kernel they reach
(ops/aggregation.py, ops/pallas_kernels.py) -- reads `on_tpu()`, so two
call sites can never disagree about the device. Call it as
`device.on_tpu()` (module attribute), so a test that steers a CPU trace
down the TPU branch patches one name.
"""

import jax

__all__ = ["on_tpu"]


def on_tpu() -> bool:
    """Is this program being traced for a TPU?"""
    return jax.default_backend() == "tpu"
