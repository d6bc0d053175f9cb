"""Pallas TPU kernels of the serving hot path, one package each:
`kernel.py` (the pallas_call), `ops.py` (the dispatch the model and the
engine import) and `ref.py` (the jnp reference the kernel is held
against). `masked_logits` keeps only its reference, which the fused
mask+select reference composes."""
from __future__ import annotations

import jax

from ..distributed.api import sharding_active


def use_kernel(backend: str) -> bool:
    """The backend rule every kernel op shares: "auto" is the compiled
    kernel on a TPU and the jnp reference elsewhere, "pallas" is the
    kernel (interpret mode off the TPU), "jnp" the reference. An active
    sharding context always takes the reference: GSPMD cannot partition
    a pallas_call (docs/sharding.md)."""
    if backend == "jnp" or sharding_active():
        return False
    return backend == "pallas" or jax.default_backend() == "tpu"
