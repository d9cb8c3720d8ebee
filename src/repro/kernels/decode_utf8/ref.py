"""Pure-jnp oracle for the parallel UTF-8 tabular decoder.

This is the TPU-native reformulation of PIPER's parallel decoding unit
(paper §3.3, Script 1). The FPGA consumes a W-byte window per cycle,
counts delimiters, and extracts 0..W/8 completed field values; the
running value register ``v`` carries across windows. On TPU we observe
that the per-byte update

    dense (decimal) digit:  v ← v*10 + d
    sparse (hex)    digit:  v ← v*16 + d

is composition of affine maps ``x ↦ m*x + a`` — an **associative**
operation — so the entire decode becomes one *segmented* associative
scan over bytes, with segment resets at delimiters. Delimiter counting
(for field indexing) and the minus-sign flag are folded into the same
scan element, giving a single O(log n)-depth, fully-vectorized decode.

Semantics reproduced from the paper:
  * ``\t`` and ``\n`` both delimit; ``\n`` additionally ends a row.
  * empty fields decode to 0 (FillMissing folded into Decode).
  * dense fields are signed decimal; sparse fields unsigned hex
    (``0-9a-f``); the minus sign sets a flag, two's complement applied
    at extraction.
  * any other byte (e.g. zero padding after the last row) is inert.

Integer overflow wraps in 32-bit two's complement — identical bit
behaviour to the FPGA's 32-bit register.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import schema as schema_lib


class ScanElem(NamedTuple):
    """Element of the fused segmented scan.

    ``m``/``a``: affine map (value' = value*m + a) for the digit value.
    ``neg``: minus-sign seen within the current segment.
    ``reset``: 1 if this element starts a fresh segment (delimiters).
    ``ndelim``: delimiter count (plain cumsum, never reset).
    """

    m: jnp.ndarray
    a: jnp.ndarray
    neg: jnp.ndarray
    reset: jnp.ndarray
    ndelim: jnp.ndarray


def combine(l: ScanElem, r: ScanElem) -> ScanElem:
    """Associative combine for the fused segmented scan."""
    keep = 1 - r.reset  # 0 when the right element resets the segment
    return ScanElem(
        m=jnp.where(keep, l.m * r.m, r.m),
        a=jnp.where(keep, l.a * r.m + r.a, r.a),
        neg=jnp.where(keep, l.neg | r.neg, r.neg),
        reset=l.reset | r.reset,
        ndelim=l.ndelim + r.ndelim,
    )


IDENTITY = ScanElem(m=1, a=0, neg=0, reset=0, ndelim=0)


def shift_scan(op, elems, identity):
    """Inclusive scan of ``op`` along axis 0 by log-step shifts: step ``k``
    combines every element with the one ``2**k`` before it (``identity``
    shifted in at the front). Same result as ``lax.associative_scan`` for
    an associative ``op`` — int32 arithmetic wraps, so every bracketing is
    bit-identical — but built from static slices and elementwise ops only.
    The TPU compiler takes about 100 s on ``associative_scan``'s strided
    slices and interior pads (and up to 30 s on ``jnp.cumsum``) over a
    1 MiB chunk; this form compiles in seconds at every chunk size."""
    n = jax.tree.leaves(elems)[0].shape[0]
    k = 1
    while k < n:
        shifted = jax.tree.map(
            lambda x, e: jnp.concatenate([jnp.full((k,), e, x.dtype), x[:-k]]),
            elems,
            identity,
        )
        elems = op(shifted, elems)
        k *= 2
    return elems


def classify(
    byte: jnp.ndarray, delims_before: jnp.ndarray, hex_field_table: jnp.ndarray,
    n_fields: int,
) -> ScanElem:
    """Map raw bytes to scan elements.

    ``delims_before``: exclusive delimiter count per byte — determines which
    field each byte belongs to and therefore its base (10 vs 16).
    ``hex_field_table``: bool[n_fields] marking hexadecimal columns.
    """
    b = byte.astype(jnp.int32)
    is_delim = (b == schema_lib.TAB) | (b == schema_lib.NEWLINE)
    is_minus = b == schema_lib.MINUS
    is_dec = (b >= schema_lib.BYTE_0) & (b <= schema_lib.BYTE_9)
    is_hexa = (b >= schema_lib.BYTE_A_LOWER) & (b <= schema_lib.BYTE_F_LOWER)
    digit = jnp.where(is_dec, b - schema_lib.BYTE_0, 0) + jnp.where(
        is_hexa, b - schema_lib.BYTE_A_LOWER + 10, 0
    )
    is_digit = is_dec | is_hexa

    field_idx = delims_before % n_fields
    in_hex_field = hex_field_table[field_idx]
    base = jnp.where(in_hex_field, 16, 10)

    one = jnp.ones_like(b)
    zero = jnp.zeros_like(b)
    return ScanElem(
        m=jnp.where(is_digit, base, one),
        a=jnp.where(is_digit, digit, zero),
        neg=is_minus.astype(jnp.int32),
        reset=is_delim.astype(jnp.int32),
        ndelim=is_delim.astype(jnp.int32),
    )


@functools.partial(
    jax.jit, static_argnames=("n_fields", "max_rows", "n_dense", "n_sparse")
)
def decode_bytes(
    byte_buf: jnp.ndarray,
    hex_field_table: jnp.ndarray,
    *,
    n_fields: int,
    max_rows: int,
    n_dense: int,
    n_sparse: int,
):
    """Decode a padded byte buffer into a field table.

    Args:
      byte_buf: uint8[B] — whole rows (each ``\\n``-terminated) + zero padding.
      hex_field_table: bool[n_fields] — which columns are hexadecimal.
      max_rows: static output row capacity.

    Returns:
      (label int32[max_rows], dense int32[max_rows, n_dense],
       sparse int32[max_rows, n_sparse], valid bool[max_rows])
    """
    b = byte_buf.astype(jnp.int32)
    is_delim = (b == schema_lib.TAB) | (b == schema_lib.NEWLINE)
    # Exclusive cumsum of delimiters gives each byte its field ordinal.
    delims_incl = shift_scan(jnp.add, is_delim.astype(jnp.int32), 0)
    delims_before = delims_incl - is_delim.astype(jnp.int32)

    elems = classify(byte_buf, delims_before, hex_field_table, n_fields)
    acc = shift_scan(combine, elems, IDENTITY)

    # Completed value for delimiter k is the scan value just before it.
    prev_a = jnp.concatenate([jnp.zeros((1,), jnp.int32), acc.a[:-1]])
    prev_neg = jnp.concatenate([jnp.zeros((1,), jnp.int32), acc.neg[:-1]])
    # A delimiter at position 0 (or right after another delimiter) closes an
    # empty field: the reset flag of the *previous* element being set means
    # prev_a already restarted — but prev value belongs to the field only if
    # no delimiter sat between; the segmented scan guarantees exactly that.
    value = jnp.where(prev_neg == 1, -prev_a, prev_a)

    ordinal = delims_before  # k-th delimiter closes field k (0-based, global)
    row = ordinal // n_fields
    col = ordinal % n_fields
    # Scatter completed fields; non-delimiter lanes are dropped via an
    # out-of-range row index.
    row = jnp.where(is_delim, row, max_rows)
    out = jnp.zeros((max_rows, n_fields), jnp.int32)
    out = out.at[row, col].set(value, mode="drop")

    n_rows = jnp.sum((b == schema_lib.NEWLINE).astype(jnp.int32))
    valid = jnp.arange(max_rows) < n_rows

    label = out[:, 0]
    dense = out[:, 1 : 1 + n_dense]
    sparse = out[:, 1 + n_dense : 1 + n_dense + n_sparse]
    return label, dense, sparse, valid


def decode(byte_buf, schema: schema_lib.TableSchema, max_rows: int):
    """Schema-typed convenience wrapper returning a TabularBatch."""
    hex_table = jnp.asarray(schema.field_is_hex())
    label, dense, sparse, valid = decode_bytes(
        byte_buf,
        hex_table,
        n_fields=schema.n_fields,
        max_rows=max_rows,
        n_dense=schema.n_dense,
        n_sparse=schema.n_sparse,
    )
    return schema_lib.TabularBatch(label=label, dense=dense, sparse=sparse, valid=valid)
