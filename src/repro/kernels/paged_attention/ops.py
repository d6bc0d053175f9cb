"""Public op: page-table attention on device.

`paged_attention` dispatches between the Pallas kernel and the pure-jnp
gather-based reference by `kernels.use_kernel`: the compiled kernel on
a TPU, the reference on the CPU and under a serving mesh.

The kernel, its reference and the dense decode attention in
`models/layers.py` share one head-major attention math (`ref.attend`):
on the CPU the interpreted kernel and the reference reproduce the dense
path's attention bit for bit (tests/test_paged_attention.py). On a TPU
the compiled kernel and XLA round differently; `chip_smoke.py` holds
the paged engine to the dense one by teacher-forced logits within a
bound.
"""
from __future__ import annotations

import jax

from .kernel import paged_attention_decode, paged_attention_span
from .ref import paged_attention_ref
from .. import use_kernel


def paged_attention(q, k_pool, v_pool, page_table, pos, *,
                    backend: str = "auto"):
    """q [B,S,H,Dh] (roped, unscaled); k_pool/v_pool [P,ps,K,Dh];
    page_table [B,nP] int32 (-1 = unmapped); pos [B] int32 absolute
    start positions -> [B,S,H,Dh].

    backend: 'pallas' | 'jnp' | 'auto'. 'auto' picks the compiled
    kernel on TPU and the jnp reference elsewhere; 'pallas' off the TPU
    runs the kernel in interpret mode (parity tests). Under an active
    serving sharding context the jnp reference is used regardless:
    GSPMD cannot partition a pallas_call (docs/sharding.md)."""
    if not use_kernel(backend):
        return paged_attention_ref(q, k_pool, v_pool, page_table, pos)
    return paged_attention_span(q, k_pool, v_pool, page_table, pos,
                                interpret=jax.default_backend() != "tpu")
