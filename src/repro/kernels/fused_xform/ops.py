"""jit'd wrapper + memory-tier dispatch for the fused loop-② kernel.

Tier policy (paper §3.2, §4.4.6, same cutoff as ``core.ops.apply_vocab``):

  * **VMEM tier** — every range ``≤ vocab.VMEM_TIER_MAX`` *and* the whole
    flat table fits the fused kernel's residency budget
    (:data:`FUSED_TABLE_VMEM_BYTES`): one Pallas kernel does modulus +
    table gather + dense transform per row tile, every column table
    resident in VMEM for the whole call. The extra bytes condition is
    what distinguishes this kernel from the per-column vocab kernel:
    that one holds *one* ≤2 MiB table at a time, this one holds all
    ``n_sparse`` of them simultaneously.

  * **HBM tier** — otherwise: the modulus and the dense transform still
    fuse into one Pallas pass (``fused_mod_dense``); the table lookup is
    an XLA gather against the HBM-resident table, the same
    many-outstanding-reads pattern ``apply_vocab`` uses there.

Both tiers return outputs bit-identical (ids) / identical-formula
(dense) to the unfused chain — the padding rows the wrapper adds to
reach the row block are sliced back off before returning.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.core import vocab as vocab_lib
from repro.kernels.fused_xform import kernel, ref

# VMEM budget for the resident table stack (all columns at once). 8 MiB
# leaves half of a 16 MiB/core VMEM for the row tiles + double buffering.
# Worked numbers live in ``vmem_accounting`` (the one structured place
# repro.analysis.kernelcheck audits): Criteo's 5K point keeps the stack
# well inside; the same stack at VMEM_TIER_MAX widths blows the budget
# and routes to the HBM tier.
FUSED_TABLE_VMEM_BYTES = 8 * 1024 * 1024


def vmem_accounting(
    layout: vocab_lib.VocabLayout,
    n_sparse: int | None = None,
    *,
    n_dense: int = 0,
    row_block: int = 256,
) -> dict[str, int]:
    """Bytes of each VMEM-resident buffer the fused kernel carries.

    ``table_stack`` is the grid-carried block (constant index map — the
    whole flat vocabulary resident for the call) and is the only entry
    charged against :data:`FUSED_TABLE_VMEM_BYTES`; the tiles stream per
    grid step and live in the budget's other half. This dict is the
    kernel package's declared footprint — ``fused_tier`` derives its
    decision from it, and ``repro.analysis.kernelcheck`` asserts the two
    never disagree.
    """
    n_sparse = layout.n_columns if n_sparse is None else n_sparse
    return {
        "table_stack": layout.entries * 4,
        "sparse_tile": row_block * n_sparse * 4,
        "dense_tile": row_block * n_dense * 4,
        "ids_tile": row_block * n_sparse * 4,
        "dense_out_tile": row_block * n_dense * 4,
    }


def fused_tier(layout: vocab_lib.VocabLayout) -> str:
    """Which tier the fused dispatch picks: ``"vmem"`` or ``"hbm"``."""
    table_bytes = vmem_accounting(layout)["table_stack"]
    if (
        max(layout.ranges, default=0) <= vocab_lib.VMEM_TIER_MAX
        and table_bytes <= FUSED_TABLE_VMEM_BYTES
    ):
        return "vmem"
    return "hbm"


def _row_block(rows: int) -> int:
    return min(256, max(8, rows))


def fused_transform(
    vocab: vocab_lib.Vocabulary,
    sparse: jnp.ndarray,
    dense: jnp.ndarray,
    columns: tuple[int, ...] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Loop ②'s per-chunk chain in one dispatch, tier-routed.

    sparse int32 [rows, k] (raw hash bitcasts), column ``j`` mapped
    through the vocabulary's column ``columns[j]`` (default ``j``);
    dense int/float [rows, n_dense] (raw decoded values)
    → (ids int32 [rows, k], dense float32 [rows, n_dense]).
    """
    rows, n_sparse = sparse.shape
    n_dense = dense.shape[1]
    lay = vocab.layout
    if rows == 0 or n_sparse == 0 or n_dense == 0:
        # Degenerate tiles have no Pallas grid; the oracle is exact.
        return ref.fused_transform(vocab, sparse, dense, columns)
    blk = _row_block(rows)
    pad = (-rows) % blk
    sparse_p = jnp.pad(sparse, ((0, pad), (0, 0)))
    dense_p = jnp.pad(dense, ((0, pad), (0, 0)))
    cols = columns if columns is not None else tuple(range(lay.n_columns))
    cols = dict(
        ranges=tuple(lay.ranges[c] for c in cols),
        offsets=tuple(lay.offsets[c] for c in cols),
        row_block=blk,
    )
    if fused_tier(lay) == "vmem":
        ids, dense_out = kernel.fused_transform(
            vocab.table, sparse_p, dense_p, interpret=kernels_lib.interpret(), **cols
        )
    else:
        keys, dense_out = kernel.fused_mod_dense(
            sparse_p, dense_p, interpret=kernels_lib.interpret(), **cols
        )
        ids = vocab.table[keys]
    return ids[:rows], dense_out[:rows]
