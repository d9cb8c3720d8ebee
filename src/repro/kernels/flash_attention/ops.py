"""Public attention op with kernel/oracle dispatch.

Models call ``attention`` — on TPU targets this is the Pallas flash
kernel; under the CPU dry-run/compile path it lowers the jnp oracle
(whose HLO cost model is what the roofline reads; the kernel's FLOPs
match it modulo the causal-skip factor recorded in EXPERIMENTS.md).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.kernels.flash_attention import kernel, ref


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    use_kernel: bool = False,
) -> jnp.ndarray:
    if use_kernel:
        return kernel.flash_attention(
            q, k, v, causal=causal, interpret=kernels_lib.interpret()
        )
    return ref.mha(q, k, v, causal=causal)
