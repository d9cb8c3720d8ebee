"""The vocabulary state's size: what a configuration asks for, and what
the program reports it allocated.

``range_entries`` is Σ of the sparse columns' modulus ranges, from the
configuration alone: ``vocab_ranges`` (one range per column) where the
configuration lists them, else ``vocab_range`` for every column.
``pad_share`` reads the program's exact counters ``vocab.state_entries``
(entries allocated, read off the array) and ``vocab.range_entries``
(Σ ranges) from its process-wide registry; a program without them
reports nothing.
"""

from __future__ import annotations


def range_entries(cfg: dict) -> int:
    ranges = cfg.get("vocab_ranges", cfg["vocab_range"])
    if isinstance(ranges, int):
        return ranges * int(cfg["schema"]["n_sparse"])
    return int(sum(ranges))


def pad_share(registry) -> float | None:
    """100 × (1 − range entries / state entries) over every state the
    process initialized, in percent; None without the counters."""
    state = registry.get("vocab.state_entries")
    asked = registry.get("vocab.range_entries")
    if state is None or asked is None or state.value <= 0:
        return None
    return 100.0 * (1.0 - asked.value / state.value)
