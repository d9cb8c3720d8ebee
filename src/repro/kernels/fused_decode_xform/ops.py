"""jit'd wrapper + memory-tier dispatch for the bytes-in loop-② kernel.

Tier policy — the fused loop-② residency budget
(:data:`~repro.kernels.fused_xform.ops.FUSED_TABLE_VMEM_BYTES`, 8 MiB),
tightened for what this kernel actually keeps on-chip: the vocabulary
stack **plus** the accumulated ``[max_rows + 1, n_fields]`` output table
are both VMEM-resident for the whole call, so their bytes share the
budget. ``max_rows`` is per-engine (stream buckets shrink it), so the
tier is decided at dispatch time, not plan-compile time.

  * **VMEM tier** — ONE Pallas dispatch from raw UTF-8 bytes to the
    final features: decode (shared ``decode_block`` scan) → uint32
    Modulus → vocabulary gather ∥ Neg2Zero + Logarithm, byte tile,
    tables, and output all on-chip.

  * **HBM tier / degenerate shapes** — reference decode + the existing
    tier-routed ``fused_xform`` chain (which itself degrades to an XLA
    gather there) — shared implementations, not copies; ``ref.py`` stays
    the standalone oracle.

Both tiers are bit-identical (ids/label) / identical-formula (dense f32)
to decode → ``fused_transform``, padding rows included.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.core import vocab as vocab_lib
from repro.kernels.fused_decode_xform import kernel
from repro.kernels.fused_xform import ops as fx_ops


def vmem_accounting(
    n_dense: int,
    n_sparse: int,
    vocab_range: int,
    max_rows: int,
    *,
    block: int = 0,
) -> dict[str, int]:
    """Bytes of each VMEM-resident buffer the bytes-in loop-② kernel
    carries: the grid-carried vocabulary ``table_stack`` AND the
    accumulated ``[max_rows + 1, n_fields]`` ``out_table`` (both
    constant-index-map blocks, resident for the whole call — they share
    the budget, which is why the tier depends on ``max_rows``), the
    streamed byte tile, and the SMEM decode carry. Audited by
    ``repro.analysis.kernelcheck`` against :func:`fused_decode_tier`,
    which derives its decision from this dict."""
    n_fields = 1 + n_dense + n_sparse
    return {
        "table_stack": n_sparse * vocab_range * 4,
        "out_table": (max_rows + 1) * n_fields * 4,
        "byte_tile": block or kernel.BLOCK,
        "decode_carry": 4 * 4,
    }


def fused_decode_tier(
    n_dense: int, n_sparse: int, vocab_range: int, max_rows: int
) -> str:
    """Which tier the bytes-in loop-② dispatch picks: ``"vmem"`` or
    ``"hbm"`` — vocabulary stack + output table share the 8 MiB budget."""
    acct = vmem_accounting(n_dense, n_sparse, vocab_range, max_rows)
    if (
        vocab_range <= vocab_lib.VMEM_TIER_MAX
        and acct["table_stack"] + acct["out_table"]
        <= fx_ops.FUSED_TABLE_VMEM_BYTES
    ):
        return "vmem"
    return "hbm"


def fused_decode_transform(
    vocab: vocab_lib.Vocabulary,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    max_rows: int,
    block: int = kernel.BLOCK,
):
    """Loop ② straight from a raw UTF-8 chunk, tier-routed.

    byte_buf uint8 [B] — whole ``\\n``-terminated rows + zero padding
    (any length; the wrapper pads to the byte-tile multiple).
    → (label int32 [max_rows], dense f32 [max_rows, n_dense],
       ids int32 [max_rows, n_sparse], valid bool [max_rows]) — exactly
    what decode + ``fused_transform`` produce, padding rows included.
    """
    n_dense = hex_start - 1
    n_sparse = n_fields - hex_start
    n = int(byte_buf.shape[0])
    if (
        n_sparse == 0
        or n_dense == 0
        or n == 0
        or fused_decode_tier(n_dense, n_sparse, vocab.vocab_range, max_rows)
        == "hbm"
    ):
        # HBM tier / degenerate widths: reference decode + the tier-routed
        # decoded-input chain (itself the XLA gather on HBM).
        from repro.kernels.decode_utf8 import ref as decode_ref

        label, dense, sparse, valid = decode_ref.decode_bytes(
            byte_buf,
            jnp.arange(n_fields) >= hex_start,
            n_fields=n_fields,
            max_rows=max_rows,
            n_dense=n_dense,
            n_sparse=n_sparse,
        )
        ids, dfx = fx_ops.fused_transform(vocab, sparse, dense)
        return label, dfx, ids, valid
    pad = (-n) % block
    if pad:
        byte_buf = jnp.pad(byte_buf, (0, pad))
    return kernel.fused_decode_transform(
        vocab.table,
        byte_buf,
        n_fields=n_fields,
        hex_start=hex_start,
        max_rows=max_rows,
        interpret=kernels_lib.interpret(),
        block=block,
    )
