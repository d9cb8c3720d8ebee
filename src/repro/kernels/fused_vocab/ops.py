"""jit'd wrapper + memory-tier dispatch for the fused loop-① kernels.

Tier policy (paper §3.2, §4.4.6) — two tiers, graded by where the flat,
offset-indexed ``first_pos`` state (``vocab.VocabLayout``, plus the
optional occurrence-count plane) can live. The same policy sizes the
layout (:func:`state_layout`), so every engine builds states the kernels
take as they are, with no pad or slice per chunk:

  * **vmem** — every column's range ``≤ vocab.VMEM_TIER_MAX`` *and* the
    whole state fits the fused residency budget
    (:data:`FUSED_STATE_VMEM_BYTES`): one Pallas kernel bitcasts,
    reduces each column modulo its range, and scatter-mins
    first-occurrence positions per row tile, with the *entire* state
    resident in VMEM for the whole call and carried across grid steps.

  * **hbm_slab** — otherwise: ``first_pos`` stays HBM-resident as runs
    of ``layout.slab`` entries (sized so one slab fits
    :data:`SLAB_VMEM_BYTES`, even over the state, a whole number of
    tiles). ONE Pallas dispatch per chunk streams every slab through
    VMEM — grid ``(n_slabs, row_tiles)``, the slab block carried across
    the inner row-tile dim and written back when the slab advances — so
    loop ① keeps the single-fused-dispatch property at ANY ranges. A
    pre-pass in the same program sorts each row tile's entries by flat
    key, so grid step ``(s, t)`` walks only tile ``t``'s run of slab
    ``s``: every entry is visited once a chunk, not once per slab (with
    a single slab nothing is sorted). Slabs are contiguous runs of the
    flat state, so a slab may hold several small columns or part of a
    large one, and any number of columns fits.

Both tiers are **bit-identical** to the unfused ``positive_modulus`` →
``vocab.update`` chain: scatter-min (and the count's sum) is order-
independent, so bucketing entries by slab changes no result; padding
rows carry ``NEVER`` positions (the min identity), every entry updates
exactly the one slab that holds its key, and the valid-row count
advances exactly as ``vocab.update`` advances it (saturating at the
int32 position ceiling — see ``vocab.positions``). When the state tracks
occurrence counts, the vmem tier runs the slab kernel with a single
resident slab so the counts ride the same dispatch. Degenerate chunks
(no rows or no columns) have no Pallas grid and take the unfused chain.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.core import vocab as vocab_lib
from repro.kernels.fused_vocab import kernel

# VMEM budget for the resident first_pos state (all columns at once) —
# the same 8 MiB residency budget as the fused loop-② table stack
# (kernels/fused_xform/ops.py): half of a 16 MiB/core VMEM, leaving room
# for the row tiles + double buffering. Worked numbers live in
# ``vmem_accounting`` (audited by repro.analysis.kernelcheck): Criteo's
# 5K point keeps the state well inside; the same state at VMEM_TIER_MAX
# widths blows the budget and routes to the HBM-slab tier.
FUSED_STATE_VMEM_BYTES = 8 * 1024 * 1024
# Budget for ONE resident slab on the hbm_slab tier: half the stack
# budget, so the Pallas pipeline can double-buffer the next slab's DMA
# against the current slab's RMW loop.
SLAB_VMEM_BYTES = 4 * 1024 * 1024
# Slabs are whole (8, 128) tiles of the flat state.
SLAB_GRAIN = kernel.TILE


def _entry_bytes(track_counts: bool) -> int:
    # int32 first_pos, plus an int32 count plane when tracked.
    return 8 if track_counts else 4


def _tile_round(n: int) -> int:
    return max(-(-n // SLAB_GRAIN) * SLAB_GRAIN, SLAB_GRAIN)


def fused_vocab_tier(
    ranges: tuple[int, ...],
    *,
    slab_range: int | None = None,
    track_counts: bool = False,
) -> str:
    """Which tier the fused loop-① dispatch picks for columns of these
    ``ranges``: ``"vmem"`` or ``"hbm_slab"``.

    ``slab_range`` forces the slab tier with slabs of that many entries
    (the ``PipelineConfig.vocab_slab_range`` expert/test knob — it lets
    tests pin slab/VMEM bit-identity on ranges that fit both tiers);
    ``track_counts`` doubles the per-entry footprint, so it tightens
    both the residency cutoff and the slab size."""
    if slab_range is not None:
        return "hbm_slab"
    used = vocab_lib.VocabLayout(tuple(ranges)).used
    if (
        max(ranges, default=0) <= vocab_lib.VMEM_TIER_MAX
        and _tile_round(used) * _entry_bytes(track_counts)
        <= FUSED_STATE_VMEM_BYTES
    ):
        return "vmem"
    return "hbm_slab"


def state_layout(
    ranges: tuple[int, ...],
    *,
    track_counts: bool = False,
    slab_range: int | None = None,
) -> vocab_lib.VocabLayout:
    """The layout of a loop-① state for columns of these ``ranges``.

    On the vmem tier one run holds every entry. On the hbm_slab tier
    the slab is the largest whole number of tiles that fits
    :data:`SLAB_VMEM_BYTES` (state + optional counts), evened out over
    the state so that no slab is a sliver; ``slab_range`` forces slabs
    of that many entries (rounded up to a tile)."""
    ranges = tuple(int(r) for r in ranges)
    tier = fused_vocab_tier(
        ranges, slab_range=slab_range, track_counts=track_counts
    )
    used = vocab_lib.VocabLayout(ranges).used
    if tier == "vmem":
        return vocab_lib.VocabLayout(ranges)
    if slab_range is not None:
        return vocab_lib.VocabLayout(ranges, slab=_tile_round(int(slab_range)))
    cap = SLAB_VMEM_BYTES // _entry_bytes(track_counts)
    n_slabs = -(-used // cap)
    return vocab_lib.VocabLayout(ranges, slab=_tile_round(-(-used // n_slabs)))


def vmem_accounting(
    layout: vocab_lib.VocabLayout,
    *,
    row_block: int = 1024,
    track_counts: bool = False,
) -> dict[str, int]:
    """Bytes of each VMEM-resident buffer the fused loop-① kernel carries.

    ``state_stack`` (plus ``counts_stack`` when tracked) is the
    grid-carried accumulator block: the whole state on the vmem tier, or
    one slab on the hbm_slab tier. The carried entries are what the tier
    guards charge against :data:`FUSED_STATE_VMEM_BYTES` /
    :data:`SLAB_VMEM_BYTES`; the row tiles stream per grid step through
    SMEM (``sparse_tile`` and ``pos_tile``; wherever the slab kernel
    runs, the tile's entries arrive bucketed by slab with
    ``offsets_tile``, the start of each slab's run). This dict is the
    package's declared footprint — :func:`fused_vocab_tier` derives its
    decision from the same numbers, and ``repro.analysis.kernelcheck``
    asserts the two never disagree.
    """
    n_cols = layout.n_columns
    acct = {
        "state_stack": layout.slab_entries * 4,
        "sparse_tile": row_block * n_cols * 4,
        "pos_tile": row_block * 4,
    }
    if track_counts:
        acct["counts_stack"] = layout.slab_entries * 4
    if layout.n_slabs > 1 or layout.slab or track_counts:  # the slab kernel runs
        acct["offsets_tile"] = kernel.offsets_width(layout.n_slabs) * 4
    return acct


def _row_block(rows: int) -> int:
    # The SMEM blocks of the row tile must be the whole array or a
    # multiple of 1024 entries.
    return min(1024, rows)


def fused_update(
    state: vocab_lib.VocabState,
    sparse: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    slab_range: int | None = None,
) -> vocab_lib.VocabState:
    """Loop ①'s per-chunk chain in one dispatch, tier-routed.

    sparse int32 [rows, n_cols] (raw hash bitcasts, pre-modulus);
    valid bool [rows] → the updated :class:`~repro.core.vocab.VocabState`
    (bit-identical to ``vocab.update(state, positive_modulus(sparse,
    ranges), valid)``). ``slab_range`` forces the hbm_slab tier with
    slabs of that many entries (None = tier policy decides); a state
    laid out for other slabs is padded to them for the call.

    **Consumes** ``state``: the kernel tiers donate ``state.first_pos``
    (and ``counts``) to the kernel (in-place accumulation, the same
    convention as ``kernels/vocab``'s ``genvocab``), so on backends that
    honor donation (TPU) the caller must not read the old state
    afterwards — thread the returned state through, as every engine's
    loop ① does.
    """
    rows, n_cols = sparse.shape
    lay = state.layout
    vocab_lib.check_row_ceiling(state.rows_seen, rows)
    track_counts = state.counts is not None
    if rows == 0 or n_cols == 0:
        # Degenerate tiles (no Pallas grid): the unfused chain itself.
        from repro.core import ops as core_ops

        return vocab_lib.update(
            state, core_ops.positive_modulus(sparse, lay.ranges), valid
        )
    tier = fused_vocab_tier(
        lay.ranges, slab_range=slab_range, track_counts=track_counts
    )
    pos = vocab_lib.positions(state.rows_seen, rows, valid)
    rows_seen = vocab_lib.advance_rows_seen(
        state.rows_seen, jnp.sum(valid.astype(jnp.int32))
    )
    blk = _row_block(rows)
    pad = (-rows) % blk
    # Padding rows scatter NEVER at value 0 % V — a min() no-op.
    sparse_p = jnp.pad(sparse, ((0, pad), (0, 0)))
    pos_p = jnp.pad(pos, (0, pad), constant_values=vocab_lib.NEVER)
    interpret = kernels_lib.interpret()
    if tier == "vmem" and not track_counts:
        first_pos = kernel.fused_genvocab(
            state.first_pos,
            sparse_p,
            pos_p,
            ranges=lay.ranges,
            offsets=lay.offsets,
            row_block=blk,
            interpret=interpret,
        )
        return vocab_lib.VocabState(
            first_pos=first_pos, rows_seen=rows_seen, layout=lay
        )
    # hbm_slab — or vmem with tracked counts, which runs the slab kernel
    # with a single resident slab so the count plane rides the same
    # dispatch. The engines' states are laid out for exactly these slabs.
    slab = state_layout(
        lay.ranges, track_counts=track_counts, slab_range=slab_range
    ).slab_entries
    entries = lay.entries
    spad = (-entries) % slab
    first_pos, counts = state.first_pos, state.counts
    if spad:
        first_pos = jnp.pad(first_pos, (0, spad), constant_values=vocab_lib.NEVER)
        if track_counts:
            counts = jnp.pad(counts, (0, spad))
    first_pos, counts = kernel.fused_genvocab_slabs(
        first_pos,
        counts,
        sparse_p,
        pos_p,
        ranges=lay.ranges,
        offsets=lay.offsets,
        slab=slab,
        row_block=blk,
        interpret=interpret,
    )
    if spad:
        first_pos = first_pos[:entries]
        if track_counts:
            counts = counts[:entries]
    return vocab_lib.VocabState(
        first_pos=first_pos, rows_seen=rows_seen, counts=counts, layout=lay
    )
