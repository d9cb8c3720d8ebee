"""Pallas TPU kernel: the whole loop-② operator chain in one VMEM pass.

Piper's central dataflow claim (paper §3.2, §4.4) is that a row streams
through the *entire* operator graph on-chip — no operator ever
materializes its output to off-chip memory. Our loop ② previously ran
``positive_modulus`` → ``apply_vocab`` → ``dense_transform`` as three
dispatches with an HBM round-trip between each (the per-op
materialization overhead tf.data identifies as the dominant cost of
composed input pipelines). These kernels collapse the chain:

``fused_transform_kernel`` (VMEM tier)
    One grid step per row tile. The sparse tile is bitcast to uint32,
    reduced modulo each column's range, gathered through the flat
    vocabulary table at the column's offset, while the dense tile is
    clamped (Neg2Zero) and log1p'd — all inside VMEM, one HBM read and
    one HBM write per tile. The
    tables use a **constant index map**, so Pallas DMAs them into VMEM
    once at the first grid step and keeps every per-column table
    resident for the rest of the call (the FPGA's on-chip-SRAM
    dictionaries). This is why the tier guard is stricter than the
    standalone vocab kernel's: *all* column tables are resident at
    once, not one per grid row (see ops.FUSED_TABLE_VMEM_BYTES).

``fused_mod_dense_kernel`` (HBM tier)
    The table no longer fits on-chip, so the lookup falls back to an
    XLA gather against the HBM-resident table (ops.py) — but the
    modulus (with each column's offset added) and the dense transform
    still fuse into one pass, so the only extra materialization vs. the
    VMEM tier is the flat keys the gather consumes. This mirrors the
    FPGA's HBM mode, where only the dictionary access leaves the chip.

Both kernels run ``interpret=True`` on CPU (the repo-wide convention —
tier-1 CI exercises the kernel logic without accelerator hardware).
ops.py switches to compiled Mosaic on a TPU backend. There Mosaic
refuses ``fused_transform_kernel``: its in-kernel table gather fails
inside the lowering (tests/test_tpu_compile.py), so the plan compiler
keeps the VMEM tier off the TPU route and it runs the
unfused XLA chain. ``fused_mod_dense_kernel`` compiles and is the HBM
tier's front half on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _keys(sparse_tile: jnp.ndarray, ranges_ref, offsets_ref) -> jnp.ndarray:
    """Flat table index of every entry: each column's uint32 modulus
    (sparse hashes are always positive — paper §3.2 — so the modulus is
    defined on the uint32 view of the int32 bitcast) plus the column's
    offset into the table. ``ranges_ref``/``offsets_ref``: [1, n_sparse]."""
    u = jax.lax.bitcast_convert_type(sparse_tile, jnp.uint32)
    r = jax.lax.bitcast_convert_type(ranges_ref[...], jnp.uint32)
    return (u % r).astype(jnp.int32) + offsets_ref[...]


def _column_operands(ranges, offsets):
    """Each column's range and offset as [1, n_sparse] int32 operands."""
    return (
        jnp.asarray(ranges, jnp.int32)[None, :],
        jnp.asarray(offsets, jnp.int32)[None, :],
    )


def _dense_xform(dense_tile: jnp.ndarray) -> jnp.ndarray:
    """Neg2Zero + Logarithm, one VPU pass."""
    x = dense_tile.astype(jnp.float32)
    return jnp.log1p(jnp.maximum(x, 0.0))


# ---------------------------------------------------------------------- #
# VMEM tier: modulus → table gather → dense transform, single kernel
# ---------------------------------------------------------------------- #
def _fused_transform_kernel(
    table_ref, ranges_ref, offsets_ref, sparse_ref, dense_ref, ids_ref,
    dense_out_ref,
):
    # table_ref:  int32 [entries] — the flat table, VMEM-resident
    #             (constant index map: fetched once, reused every step)
    # sparse_ref: int32 [R_BLK, n_sparse]; dense_ref: [R_BLK, n_dense]
    keys = _keys(sparse_ref[...], ranges_ref, offsets_ref)
    # ids[r, c] = table[offsets[c] + modded[r, c]] — VMEM gather, the
    # FPGA's II=2 SRAM read as a vectorized lane gather.
    ids_ref[...] = jnp.take(table_ref[...], keys, axis=0)
    dense_out_ref[...] = _dense_xform(dense_ref[...])


@functools.partial(
    jax.jit, static_argnames=("ranges", "offsets", "row_block", "interpret")
)
def fused_transform(
    table: jnp.ndarray,
    sparse: jnp.ndarray,
    dense: jnp.ndarray,
    *,
    ranges: tuple[int, ...],
    offsets: tuple[int, ...],
    row_block: int = 256,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Whole loop-② chain per row tile, the table resident in VMEM.

    table  int32 [entries] — flat, offset-indexed (``vocab.VocabLayout``)
    sparse int32 [rows, n_sparse] (raw hash bitcasts, pre-modulus)
    dense  int/float [rows, n_dense] (raw decoded values)
    ranges, offsets — each column's modulus range and its first entry
    → (ids int32 [rows, n_sparse], dense float32 [rows, n_dense])

    ``rows`` must divide by ``row_block`` (ops.py pads); callers slice
    the padding rows back off.
    """
    (entries,) = table.shape
    rows, n_sparse = sparse.shape
    n_dense = dense.shape[1]
    if rows % row_block:
        raise ValueError(f"rows ({rows}) must divide by row_block ({row_block})")
    whole = pl.BlockSpec((1, n_sparse), lambda r: (0, 0))
    return pl.pallas_call(
        _fused_transform_kernel,
        grid=(rows // row_block,),
        in_specs=[
            pl.BlockSpec((entries,), lambda r: (0,)),
            whole,
            whole,
            pl.BlockSpec((row_block, n_sparse), lambda r: (r, 0)),
            pl.BlockSpec((row_block, n_dense), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_block, n_sparse), lambda r: (r, 0)),
            pl.BlockSpec((row_block, n_dense), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n_sparse), jnp.int32),
            jax.ShapeDtypeStruct((rows, n_dense), jnp.float32),
        ],
        interpret=interpret,
    )(table, *_column_operands(ranges, offsets), sparse, dense)


# ---------------------------------------------------------------------- #
# HBM tier: modulus + dense transform fused; the gather stays in XLA
# ---------------------------------------------------------------------- #
def _fused_mod_dense_kernel(
    ranges_ref, offsets_ref, sparse_ref, dense_ref, keys_ref, dense_out_ref
):
    keys_ref[...] = _keys(sparse_ref[...], ranges_ref, offsets_ref)
    dense_out_ref[...] = _dense_xform(dense_ref[...])


@functools.partial(
    jax.jit, static_argnames=("ranges", "offsets", "row_block", "interpret")
)
def fused_mod_dense(
    sparse: jnp.ndarray,
    dense: jnp.ndarray,
    *,
    ranges: tuple[int, ...],
    offsets: tuple[int, ...],
    row_block: int = 256,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Modulus ∥ Neg2Zero+Logarithm in one pass (HBM-tier front half).

    → (keys int32 [rows, n_sparse], dense float32 [rows, n_dense]): each
    entry's flat table index (its column's modulus plus offset); the
    caller gathers ``keys`` from the HBM-resident table.
    """
    rows, n_sparse = sparse.shape
    n_dense = dense.shape[1]
    if rows % row_block:
        raise ValueError(f"rows ({rows}) must divide by row_block ({row_block})")
    whole = pl.BlockSpec((1, n_sparse), lambda r: (0, 0))
    return pl.pallas_call(
        _fused_mod_dense_kernel,
        grid=(rows // row_block,),
        in_specs=[
            whole,
            whole,
            pl.BlockSpec((row_block, n_sparse), lambda r: (r, 0)),
            pl.BlockSpec((row_block, n_dense), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_block, n_sparse), lambda r: (r, 0)),
            pl.BlockSpec((row_block, n_dense), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n_sparse), jnp.int32),
            jax.ShapeDtypeStruct((rows, n_dense), jnp.float32),
        ],
        interpret=interpret,
    )(*_column_operands(ranges, offsets), sparse, dense)
