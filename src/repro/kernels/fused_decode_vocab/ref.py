"""Differential oracle for the bytes-in loop-① kernel.

The oracle is the *composition the kernel replaces*: the reference
segmented-scan decode (``decode_utf8/ref.py``) followed by the unfused
uint32 Modulus → XLA scatter-min state update. The kernel must be
**bit-identical** to this on every input — scatter-min is order-
independent, padding/truncated rows carry ``NEVER`` positions (the min
identity), and ``rows_seen`` advances by exactly the valid-row count.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core import vocab as vocab_lib
from repro.kernels.decode_utf8 import ref as decode_ref


def _hex_table(n_fields: int, hex_start: int) -> jnp.ndarray:
    """The contiguous decimal-then-hex layout the fused kernels assume."""
    return jnp.arange(n_fields) >= hex_start


def fused_decode_genvocab(
    state: vocab_lib.VocabState,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    max_rows: int,
) -> vocab_lib.VocabState:
    """Reference bytes-in loop ① step: decode → Modulus → scatter-min."""
    n_dense = hex_start - 1
    n_sparse = n_fields - hex_start
    _, _, sparse, valid = decode_ref.decode_bytes(
        byte_buf,
        _hex_table(n_fields, hex_start),
        n_fields=n_fields,
        max_rows=max_rows,
        n_dense=n_dense,
        n_sparse=n_sparse,
    )
    modded = core_ops.positive_modulus(sparse, state.layout.ranges)
    return vocab_lib.update(state, modded, valid)
