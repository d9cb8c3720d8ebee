"""jit'd wrapper + memory-tier dispatch for the bytes-in loop-① kernel.

Tier policy — exactly the fused loop-① guard (``kernels/fused_vocab``):
the bytes-in kernel carries the same VMEM-resident ``first_pos`` stack,
so it is admissible iff ``fused_vocab_tier`` says ``"vmem"`` (range
within the per-column cutoff AND the whole stack within the shared
8 MiB :data:`~repro.kernels.fused_vocab.ops.FUSED_STATE_VMEM_BYTES`
residency budget) and every column shares one range (the kernel takes
the state's rectangular ``[n_cols, range]`` view).

  * **VMEM tier** — ONE Pallas dispatch from raw UTF-8 bytes to the
    updated state: decode (shared ``decode_block`` scan) → uint32
    Modulus → scatter-min, the byte tile and the state both on-chip.
    The only HBM traffic is the byte read.

  * **hbm_slab tier, ranges that differ by column, tracked counts,
    degenerate shapes** — no bytes-in kernel: the chunk decodes through
    the reference scan and the decoded matrix takes the existing
    tier-routed ``fused_vocab`` chain — a shared implementation, not a
    copy; ``ref.py`` stays the standalone oracle.

Both tiers are **bit-identical** to decode → ``positive_modulus`` →
``vocab.update``: the kernel's dead lanes scatter ``NEVER`` (the min
identity) and ``rows_seen`` advances by exactly the valid-row count.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib
from repro.kernels.fused_decode_vocab import kernel
from repro.kernels.fused_vocab import ops as fv_ops


def vmem_accounting(
    n_cols: int, vocab_range: int, *, block: int = 0
) -> dict[str, int]:
    """Bytes of each VMEM-resident buffer the bytes-in loop-① kernel
    carries: the grid-carried ``state_stack`` (identical to the
    decoded-input kernel's — same budget, same tier decision), the
    streamed byte tile, and the SMEM decode carry ``(m, a, neg,
    ndelim)``. ``block`` defaults to the kernel's byte-tile size.
    Audited by ``repro.analysis.kernelcheck`` against
    :func:`fused_decode_vocab_tier`."""
    return {
        "state_stack": n_cols * vocab_range * 4,
        "byte_tile": block or kernel.BLOCK,
        "decode_carry": 4 * 4,
    }


def fused_decode_vocab_tier(ranges: tuple[int, ...]) -> str:
    """Which tier the bytes-in loop-① dispatch picks — the state residency
    condition is identical to the decoded-input fused kernel's. Only the
    ``"vmem"`` tier has a bytes-in kernel; ``"hbm_slab"`` routes through
    the reference decode + the tier-routed decoded-input chain."""
    return fv_ops.fused_vocab_tier(ranges)


def fused_decode_update(
    state: vocab_lib.VocabState,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    max_rows: int,
    block: int = kernel.BLOCK,
) -> vocab_lib.VocabState:
    """Loop ① straight from a raw UTF-8 chunk, tier-routed.

    byte_buf uint8 [B] — whole ``\\n``-terminated rows + zero padding
    (any length; the wrapper pads to the byte-tile multiple — zero bytes
    are inert to the decode). → the updated
    :class:`~repro.core.vocab.VocabState`, bit-identical to
    ``decode → positive_modulus → vocab.update`` with row positions
    seeded from ``state.rows_seen``.

    **Consumes** ``state`` on the VMEM tier (``first_pos`` is donated to
    the kernel for in-place accumulation) — thread the returned state
    through, as every engine's loop ① does.
    """
    n_cols = n_fields - hex_start
    lay = state.layout
    n = int(byte_buf.shape[0])
    # conservative host-side ceiling guard (rows ≤ max_rows per chunk);
    # traced offsets rely on the kernel's saturating position arithmetic
    vocab_lib.check_row_ceiling(state.rows_seen, max_rows)
    if (
        n_cols <= 0
        or n == 0
        or state.counts is not None
        or not lay.uniform
        or fused_decode_vocab_tier(lay.ranges) != "vmem"
    ):
        # Over-budget state / tracked counts (the bytes-in kernel carries
        # no count plane) / no vocab columns: reference decode + the
        # tier-routed decoded-input chain (the slab kernel on hbm_slab,
        # the XLA oracle on the fallback tier).
        from repro.kernels.decode_utf8 import ref as decode_ref

        _, _, sparse, valid = decode_ref.decode_bytes(
            byte_buf,
            jnp.arange(n_fields) >= hex_start,
            n_fields=n_fields,
            max_rows=max_rows,
            n_dense=hex_start - 1,
            n_sparse=n_cols,
        )
        return fv_ops.fused_update(state, sparse, valid)
    pad = (-n) % block
    if pad:
        byte_buf = jnp.pad(byte_buf, (0, pad))
    n_rows = jnp.sum((byte_buf == schema_lib.NEWLINE).astype(jnp.int32))
    n_cap = jnp.minimum(n_rows, jnp.int32(max_rows))
    offset = state.rows_seen.astype(jnp.int32)
    limits = jnp.stack([n_cap, offset])
    first_pos = kernel.fused_decode_genvocab(
        lay.rect(state.first_pos),
        byte_buf,
        limits,
        n_fields=n_fields,
        hex_start=hex_start,
        vocab_range=lay.ranges[0],
        interpret=kernels_lib.interpret(),
        block=block,
    )
    # Structurally short rows (fewer delimiters than fields — malformed,
    # but the oracle is defined on them): the decoded matrix keeps its
    # 0-defaults in the never-written cells and `vocab.update` scatters
    # those too. The unwritten cells are exactly the consecutive ordinal
    # suffix [n_delims, n_cap·n_fields), so the equivalent contribution
    # is one value-0 scatter per column at its first unwritten row.
    n_delims = jnp.sum(
        ((byte_buf == schema_lib.TAB) | (byte_buf == schema_lib.NEWLINE)).astype(
            jnp.int32
        )
    )
    field_col = hex_start + jnp.arange(n_cols, dtype=jnp.int32)
    r_miss = jnp.maximum((n_delims - field_col + n_fields - 1) // n_fields, 0)
    fill_sat = jnp.minimum(
        offset.astype(jnp.uint32) + r_miss.astype(jnp.uint32),
        jnp.uint32(vocab_lib.NEVER),
    ).astype(jnp.int32)
    fill = jnp.where(r_miss < n_cap, fill_sat, vocab_lib.NEVER)
    first_pos = first_pos.at[:, 0].min(fill)
    return vocab_lib.VocabState(
        first_pos=lay.pack(first_pos, vocab_lib.NEVER),
        layout=lay,
        rows_seen=vocab_lib.advance_rows_seen(state.rows_seen, n_cap),
    )
