"""Differential oracle for the bytes-in loop-② kernel.

The oracle is the composition the kernel replaces: the reference
segmented-scan decode (``decode_utf8/ref.py``) followed by the unfused
loop-② chain — uint32 Modulus → table gather → Neg2Zero + Logarithm.
Sparse ids and labels must be **bit-identical** (integer ops only) and
dense floats identical-formula (same f32 op sequence) on every input,
padding rows included: the kernel seeds never-written cells with the
transform of a zero field, exactly what this composition leaves there.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import ops as core_ops
from repro.core import vocab as vocab_lib
from repro.kernels.decode_utf8 import ref as decode_ref


def _hex_table(n_fields: int, hex_start: int) -> jnp.ndarray:
    return jnp.arange(n_fields) >= hex_start


def fused_decode_transform(
    vocab: vocab_lib.Vocabulary,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    max_rows: int,
):
    """Reference bytes-in loop ② step.

    → (label int32 [max_rows], dense f32 [max_rows, hex_start - 1],
       ids int32 [max_rows, n_sparse], valid bool [max_rows]).
    """
    n_dense = hex_start - 1
    n_sparse = n_fields - hex_start
    label, dense, sparse, valid = decode_ref.decode_bytes(
        byte_buf,
        _hex_table(n_fields, hex_start),
        n_fields=n_fields,
        max_rows=max_rows,
        n_dense=n_dense,
        n_sparse=n_sparse,
    )
    modded = core_ops.positive_modulus(sparse, vocab.ranges)
    ids = vocab_lib.lookup(vocab, modded)
    dfx = core_ops.dense_transform(dense)
    return label, dfx, ids, valid
