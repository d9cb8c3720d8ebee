"""The least HBM bytes each loop's algorithm needs, from data and shapes.

Nothing here looks at what an implementation touches: padded row slots,
slab counts and HLO byte counts never enter, so a change of kernel cannot
move the yardstick, and a share of the roofline cannot pass 100% for any
implementation that does the same work.
"""

from __future__ import annotations

import numpy as np

STATE_ENTRY_BYTES = 4  # one int32 first-occurrence position
ORDINAL_BYTES = 4  # one int32 vocabulary ordinal read in loop 2
OUTPUT_ROW_BYTES = 40 * 4  # label + 13 dense + 26 sparse, 4 B each


def distinct_keys(sparse: np.ndarray, vocab_range: int) -> int:
    """Distinct (column, value mod range) keys among ``sparse`` rows."""
    u = sparse.view(np.uint32).astype(np.int64) % vocab_range
    return int(sum(np.unique(u[:, c]).size for c in range(u.shape[1])))


def loop1_bytes(framed_bytes: int, n_distinct_keys: int) -> int:
    """Loop 1 on one chunk: read the chunk's framed bytes once, and read
    and write the state entry of each distinct key once."""
    return int(framed_bytes) + 2 * STATE_ENTRY_BYTES * int(n_distinct_keys)


def loop2_bytes(framed_bytes: int, valid_rows: int, n_sparse: int) -> int:
    """Loop 2 on one chunk: read the framed bytes, read one ordinal per
    valid row and sparse column, write each valid row's 40 outputs."""
    rows = int(valid_rows)
    return int(framed_bytes) + ORDINAL_BYTES * rows * int(n_sparse) + OUTPUT_ROW_BYTES * rows


def job_bytes(table: dict, rows_per_chunk, bytes_per_chunk, vocab_range: int) -> dict:
    """Needed bytes of a whole job over its chunks, per loop."""
    l1 = l2 = 0
    lo = 0
    n_sparse = table["sparse"].shape[1]
    for rows, nbytes in zip(rows_per_chunk, bytes_per_chunk):
        sp = table["sparse"][lo : lo + int(rows)]
        l1 += loop1_bytes(nbytes, distinct_keys(sp, vocab_range))
        l2 += loop2_bytes(nbytes, rows, n_sparse)
        lo += int(rows)
    return {"loop1": l1, "loop2": l2}
