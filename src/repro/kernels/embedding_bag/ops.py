"""jit'd wrapper + tier dispatch for the DLRM embedding gather."""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.kernels.embedding_bag import kernel, ref

# One column's table must fit VMEM alongside the batch block.
VMEM_TABLE_BYTES = 8 * 1024 * 1024


def embedding_gather(
    tables: jnp.ndarray, ids: jnp.ndarray, use_kernel: bool = False
) -> jnp.ndarray:
    """tables [n_cols, vocab, dim]; ids [batch, n_cols] → [batch, n_cols, dim]."""
    n_cols, vocab, dim = tables.shape
    table_bytes = vocab * dim * tables.dtype.itemsize
    if use_kernel and table_bytes <= VMEM_TABLE_BYTES:
        batch = ids.shape[0]
        bb = min(512, batch)
        pad = (-batch) % bb
        ids_t = jnp.pad(ids, ((0, pad), (0, 0))).T
        out = kernel.embedding_gather(
            tables, ids_t, batch_block=bb, interpret=kernels_lib.interpret()
        )
        return out.transpose(1, 0, 2)[:batch]
    return ref.embedding_gather(tables, ids)
