"""Pure-jnp oracle for the fused loop-② transform.

Exactly the unfused op chain the fused kernel replaces:
``positive_modulus`` → table gather (``vocab.lookup`` semantics) →
``dense_transform``. The differential tests (tests/test_fused_xform.py)
hold the kernel to this oracle bit-for-bit on the sparse ids and to
rtol 1e-6 on the dense floats.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core import vocab as vocab_lib


def fused_transform(
    vocab: vocab_lib.Vocabulary,
    sparse: jnp.ndarray,
    dense: jnp.ndarray,
    columns: tuple[int, ...] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """vocab: a finalized Vocabulary; sparse int32 [rows, k] (column
    ``j`` through vocabulary column ``columns[j]``, default ``j``);
    dense [rows, n_dense] → (ids int32 [rows, k], dense float32
    [rows, n_dense])."""
    modded = core_ops.positive_modulus(
        sparse, core_ops.column_ranges(vocab.ranges, columns)
    )
    ids = vocab_lib.lookup(vocab, modded, columns)
    dense_out = jnp.log1p(jnp.maximum(dense.astype(jnp.float32), 0.0))
    return ids, dense_out
