"""The plain reference and the comparison that decides ``correct``.

The reference is numpy alone and shares no code with the program: the
generator's ground-truth table, a first-occurrence vocabulary built with
``np.unique``, and Neg2Zero + log1p in float64 (copied from
``chip_smoke.py``). :class:`Checks` holds each number compared with its
limit; every limit is set in ``PERF.md`` from the readings named there.
"""

from __future__ import annotations

import numpy as np

# Largest |got - want| / max(want, DENSE_FLOOR) over every dense value.
# Sound runs on a TPU v5e read up to 5.7e-5 (f32 log1p, 523 ulp at x = 2)
# on every seed and the bfloat16 control 3.7e-3 to 4.1e-3: see PERF.md.
DENSE_REL_LIMIT = 2e-3
# Every nonzero reference value is log1p(n) >= log1p(1) > 0.69, so the
# floor only keeps exact zeros from dividing by zero.
DENSE_FLOOR = 0.5


def first_occurrence_ids(sparse: np.ndarray, vocab_range: int) -> np.ndarray:
    """Per column: each row's value ``uint32 % vocab_range`` replaced by
    its ordinal in order of first appearance."""
    u = sparse.view(np.uint32).astype(np.int64) % vocab_range
    out = np.empty(u.shape, np.int32)
    for c in range(u.shape[1]):
        uniq, first, inv = np.unique(u[:, c], return_index=True, return_inverse=True)
        rank = np.empty(len(uniq), np.int64)
        rank[np.argsort(first)] = np.arange(len(uniq))
        out[:, c] = rank[inv.reshape(-1)]
    return out


def dense_reference(dense: np.ndarray) -> np.ndarray:
    return np.log1p(np.maximum(dense.astype(np.float64), 0.0))


def dense_control(dense: np.ndarray) -> np.ndarray:
    """The reference one precision below float32: Neg2Zero + log1p in
    bfloat16 on the device (the step a later change might take)."""
    import jax.numpy as jnp

    x = jnp.asarray(dense).astype(jnp.bfloat16)
    return np.asarray(jnp.log1p(jnp.maximum(x, 0)).astype(jnp.float32))


def dense_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    if got.size == 0:
        return 0.0
    err = np.abs(got.astype(np.float64) - want) / np.maximum(want, DENSE_FLOOR)
    return float(err.max())


class Checks:
    """Numbers compared, each with its limit (a run is correct when every
    number is at or under its limit)."""

    def __init__(self):
        self.items: dict[str, tuple[float, float]] = {}

    def add(self, name: str, value, limit) -> None:
        """Record ``value`` against ``limit``; a repeated name keeps the
        worst value."""
        old = self.items.get(name)
        value = float(value)
        if old is not None:
            value = max(value, old[0])
        self.items[name] = (value, float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(v <= lim for v, lim in self.items.values())

    def as_dict(self) -> dict:
        return {k: {"value": v, "limit": lim} for k, (v, lim) in self.items.items()}


def compare_rows(checks: Checks, got: dict, table: dict, ids: np.ndarray, lo: int) -> None:
    """Compare one run of rows ``[lo, lo + n)`` of the table: labels and
    sparse ordinals exactly, dense by relative error."""
    n = got["label"].shape[0]
    hi = lo + n
    checks.add("label_mismatches", np.count_nonzero(got["label"] != table["label"][lo:hi]), 0)
    checks.add("sparse_mismatches", np.count_nonzero(got["sparse"] != ids[lo:hi]), 0)
    want = dense_reference(table["dense"][lo:hi])
    checks.add("dense_max_rel_err", dense_rel_err(got["dense"], want), DENSE_REL_LIMIT)


def compare_job(checks: Checks, got: dict, sizes: np.ndarray, table: dict, ids: np.ndarray) -> None:
    """One whole offline job: every row, and the vocabulary sizes."""
    rows = table["label"].shape[0]
    n = got["label"].shape[0]
    checks.add("rows_missing", abs(rows - n), 0)
    checks.add("vocab_size_mismatches", np.count_nonzero(np.asarray(sizes) != ids.max(axis=0) + 1), 0)
    if n == rows:
        compare_rows(checks, got, table, ids, 0)
    else:
        checks.add("sparse_mismatches", rows, 0)
