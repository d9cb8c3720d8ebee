"""jit'd public wrapper for the parallel decode kernel.

``decode`` mirrors the signature of ``ref.decode_bytes`` so the pipeline
can swap implementations; the kernel emits per-byte (value, ordinal,
is_delim) and this wrapper performs the StoreData scatter + row-validity
bookkeeping. The kernel's byte classifier is hard-wired to the
contiguous decimal-then-hex column layout (label + dense decimal fields
first, hex fields from ``1 + n_dense`` on), so the wrapper **validates**
``hex_field_table`` against that implied layout and raises instead of
decoding garbage for a permuted schema — the ref decoder handles
arbitrary layouts; this kernel deliberately does not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels as kernels_lib
from repro.core import schema as schema_lib
from repro.kernels.decode_utf8 import kernel


def _check_layout(hex_field_table, n_fields: int, n_dense: int) -> None:
    """Raise unless the table is the contiguous decimal-then-hex layout.

    The check needs concrete values; a traced table (the pipeline closes
    over a constant array, so in practice this only happens if a caller
    threads the table through as a jit argument) cannot be inspected and
    is let through — the layout assumption is then on the caller, as the
    docstring of :func:`decode` states.
    """
    if isinstance(hex_field_table, jax.core.Tracer):
        return
    table = np.asarray(hex_field_table).astype(bool)
    expected = np.zeros(n_fields, dtype=bool)
    expected[1 + n_dense :] = True
    if table.shape != (n_fields,) or not np.array_equal(table, expected):
        raise ValueError(
            "decode kernel requires the contiguous decimal-then-hex layout "
            f"(hex fields exactly at [{1 + n_dense}, {n_fields})); got "
            f"hex_field_table with hex columns at "
            f"{np.flatnonzero(table).tolist()} — use the ref decoder "
            "(kernels/decode_utf8/ref.py) for permuted schemas"
        )


@functools.partial(
    jax.jit,
    static_argnames=("n_fields", "max_rows", "n_dense", "n_sparse", "interpret"),
)
def _decode(
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    max_rows: int,
    n_dense: int,
    n_sparse: int,
    interpret: bool = True,
):
    hex_start = 1 + n_dense
    value, ordinal, isdelim = kernel.decode_scan(
        byte_buf, n_fields=n_fields, hex_start=hex_start, interpret=interpret
    )

    row = ordinal // n_fields
    col = ordinal - row * n_fields
    row = jnp.where(isdelim == 1, row, max_rows)  # drop non-delim lanes
    out = jnp.zeros((max_rows, n_fields), jnp.int32)
    out = out.at[row, col].set(value, mode="drop")

    n_rows = jnp.sum((byte_buf == schema_lib.NEWLINE).astype(jnp.int32))
    valid = jnp.arange(max_rows) < n_rows

    label = out[:, 0]
    dense = out[:, 1 : 1 + n_dense]
    sparse = out[:, 1 + n_dense : 1 + n_dense + n_sparse]
    return label, dense, sparse, valid


def decode(
    byte_buf: jnp.ndarray,
    hex_field_table: jnp.ndarray,
    *,
    n_fields: int,
    max_rows: int,
    n_dense: int,
    n_sparse: int,
):
    """Kernel decode with the layout contract made explicit.

    ``hex_field_table`` exists for signature parity with
    ``ref.decode_bytes``; the kernel implies the contiguous layout, so
    the table is validated against it (clear ``ValueError`` on mismatch)
    rather than silently ignored.
    """
    _check_layout(hex_field_table, n_fields, n_dense)
    return _decode(
        byte_buf,
        n_fields=n_fields,
        max_rows=max_rows,
        n_dense=n_dense,
        n_sparse=n_sparse,
        interpret=kernels_lib.interpret(),
    )
