"""Tabular schema for the PIPER preprocessing pipeline.

The paper's workload (Criteo Kaggle / Meta DLRM) is a fixed-width-schema,
variable-width-encoding table: every row is

    label \t d1 \t ... \t d13 \t s1 \t ... \t s26 \n

where ``label``/``d*`` are signed decimal integers (dense features) and
``s*`` are unsigned hexadecimal hash strings (sparse features). Empty
fields decode to 0 (the paper folds ``FillMissing`` into ``Decode`` on
the FPGA; we do the same).

A :class:`TableSchema` generalizes this to any (n_dense, n_sparse) layout
so PIPER-JAX can "cater to tabular datasets" (paper §5) beyond Criteo.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# --- byte constants -------------------------------------------------------
TAB = 0x09        # field delimiter
NEWLINE = 0x0A    # row delimiter
MINUS = 0x2D      # sign for dense (decimal) fields
BYTE_0, BYTE_9 = 0x30, 0x39
BYTE_A_LOWER, BYTE_F_LOWER = 0x61, 0x66


@dataclasses.dataclass(frozen=True)
class TableSchema:
    """Column layout of a PIPER table.

    Field order on the wire is: 1 label, then ``n_dense`` decimal columns,
    then ``n_sparse`` hexadecimal columns — exactly the Criteo layout when
    ``n_dense=13, n_sparse=26``.
    """

    n_dense: int = 13
    n_sparse: int = 26
    # Modulus range for sparse features == embedding-table row count: one
    # int for every sparse column, or one range per sparse column (kept
    # as a tuple so the schema stays hashable). The paper evaluates 5K
    # ("SRAM/VMEM" tier) and 1M ("HBM" tier); MLPerf's DLRM sizes each
    # Criteo 1TB column on its own (up to 40M).
    vocab_range: int | tuple[int, ...] = 5000
    # Maximum encoded width of one row in bytes (used to size decode buffers:
    # label ≤2B + 13 dense ≤12B each + 26 sparse ≤17B each + 40 delimiters).
    max_row_bytes: int = 640

    def __post_init__(self):
        if not isinstance(self.vocab_range, int):
            ranges = tuple(int(r) for r in self.vocab_range)
            if len(ranges) != self.n_sparse:
                raise ValueError(
                    f"vocab_range lists {len(ranges)} ranges for "
                    f"{self.n_sparse} sparse columns"
                )
            object.__setattr__(self, "vocab_range", ranges)

    @property
    def ranges(self) -> tuple[int, ...]:
        """The modulus range of each sparse column."""
        if isinstance(self.vocab_range, int):
            return (self.vocab_range,) * self.n_sparse
        return self.vocab_range

    @property
    def uniform(self) -> bool:
        """True when every sparse column shares one modulus range."""
        return len(set(self.ranges)) <= 1

    @property
    def n_fields(self) -> int:
        """Fields per row, label included."""
        return 1 + self.n_dense + self.n_sparse

    @property
    def dense_slice(self) -> slice:
        return slice(1, 1 + self.n_dense)

    @property
    def sparse_slice(self) -> slice:
        return slice(1 + self.n_dense, self.n_fields)

    def field_is_hex(self) -> np.ndarray:
        """Bool[n_fields]: True for hexadecimal (sparse) columns."""
        flags = np.zeros(self.n_fields, dtype=bool)
        flags[self.sparse_slice] = True
        return flags


# The paper's exact evaluation schema (Criteo Kaggle).
CRITEO = TableSchema(n_dense=13, n_sparse=26, vocab_range=5000)
CRITEO_1M = TableSchema(n_dense=13, n_sparse=26, vocab_range=1_000_000)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TabularBatch:
    """Decoded (binary) representation of a chunk of rows.

    ``label``  int32 [rows]
    ``dense``  int32 [rows, n_dense]      (raw decoded integers, pre-transform)
    ``sparse`` int32 [rows, n_sparse]     (raw hashed ids, pre-modulus)
    ``valid``  bool  [rows]               (False for padding rows)
    """

    label: jnp.ndarray
    dense: jnp.ndarray
    sparse: jnp.ndarray
    valid: jnp.ndarray

    @property
    def rows(self) -> int:
        return int(self.label.shape[0])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ProcessedBatch:
    """Output of the full pipeline — what the trainer consumes.

    ``dense``  float32 [rows, n_dense]    (Neg2Zero + log1p applied)
    ``sparse`` int32   [rows, n_sparse]   (vocabulary-encoded ordinals)
    """

    label: jnp.ndarray
    dense: jnp.ndarray
    sparse: jnp.ndarray
    valid: jnp.ndarray
