"""jit'd wrapper for the fused dense transform."""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.kernels.dense_xform import kernel


def dense_transform(dense: jnp.ndarray) -> jnp.ndarray:
    return kernel.dense_transform(dense, interpret=kernels_lib.interpret())
