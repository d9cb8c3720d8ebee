"""jit'd wrappers + memory-tier dispatch for the vocabulary kernels.

The tier policy follows the paper (§3.2, §4.4.6): tables that fit the
on-chip tier route through the Pallas VMEM kernels; larger tables use the
HBM-resident XLA gather/scatter path (where the paper hides HBM latency
by interleaving columns across channels — XLA's batched gather issues the
same many-outstanding-reads pattern).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.core import vocab as vocab_lib
from repro.kernels.vocab import kernel, ref


def apply_vocab_vmem(table: jnp.ndarray, modded: jnp.ndarray) -> jnp.ndarray:
    """ApplyVocab-2 through the VMEM kernel.

    table [n_cols, vocab_range]; modded [rows, n_cols] (row-major pipeline
    layout). Transposes to the PE-per-column layout, pads rows to the
    kernel's block, gathers, transposes back.
    """
    rows, n_cols = modded.shape
    blk = min(1024, max(128, rows))
    pad = (-rows) % blk
    vals_t = jnp.pad(modded, ((0, pad), (0, 0))).T
    ids_t = kernel.apply_vocab(
        table, vals_t, row_block=blk, interpret=kernels_lib.interpret()
    )
    return ids_t.T[:rows]


def genvocab_update(
    state: vocab_lib.VocabState, modded: jnp.ndarray, valid: jnp.ndarray
) -> vocab_lib.VocabState:
    """Chunk update of the first-occurrence state through the Pallas kernel.

    Only the VMEM tier routes to the kernel; the HBM tier uses the
    vectorized scatter-min oracle (identical results — property-tested).
    """
    rows = modded.shape[0]
    vocab_lib.check_row_ceiling(state.rows_seen, rows)
    # overflow-safe positions: saturate at NEVER past the int32 ceiling
    pos = vocab_lib.positions(state.rows_seen, rows, valid)
    vals_t = modded.T
    if state.first_pos.shape[1] <= vocab_lib.VMEM_TIER_MAX:
        first_pos = kernel.genvocab(
            state.first_pos, vals_t, pos, interpret=kernels_lib.interpret()
        )
    else:
        first_pos = ref.genvocab(state.first_pos, vals_t, pos)
    rows_seen = vocab_lib.advance_rows_seen(
        state.rows_seen, jnp.sum(valid.astype(jnp.int32))
    )
    counts = state.counts
    if counts is not None:
        # the per-column kernel carries no count plane — accumulate via
        # the same scatter-add the oracle uses (bit-identical)
        cols = jnp.arange(modded.shape[1], dtype=jnp.int32)[None, :]
        bcols = jnp.broadcast_to(cols, modded.shape)
        inc = (pos < vocab_lib.NEVER).astype(jnp.int32)
        counts = counts.at[bcols, modded].add(
            jnp.broadcast_to(inc[:, None], modded.shape)
        )
    return vocab_lib.VocabState(
        first_pos=first_pos, rows_seen=rows_seen, counts=counts
    )
