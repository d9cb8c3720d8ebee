"""jit'd wrapper + memory-tier dispatch for the fused loop-① kernels.

Tier policy (paper §3.2, §4.4.6) — THREE tiers, graded by where the
``first_pos`` stack (plus the optional occurrence-count plane) can live:

  * **vmem** — ``vocab_range ≤ vocab.VMEM_TIER_MAX`` *and* the whole
    state stack fits the fused residency budget
    (:data:`FUSED_STATE_VMEM_BYTES`): one Pallas kernel bitcasts,
    reduces modulo ``vocab_range``, and scatter-mins first-occurrence
    positions per row tile, with the *entire* per-column state resident
    in VMEM for the whole call and carried across grid steps. The extra
    bytes condition is what distinguishes this dispatch from the
    per-column vocab kernel (kernels/vocab): that one holds *one*
    ≤2 MiB state row at a time, this one holds all ``n_cols`` of them
    simultaneously.

  * **hbm_slab** — the state stack exceeds the budget: ``first_pos``
    stays HBM-resident, partitioned into ``[n_cols, slab_range]`` slabs
    (``slab_range`` sized so one slab fits :data:`SLAB_VMEM_BYTES`,
    rounded to the 128-lane grain). ONE Pallas dispatch per chunk
    streams every slab through VMEM — grid ``(n_slabs, row_tiles)``,
    the slab block carried across the inner row-tile dim and written
    back when the slab advances — so loop ① keeps the single-fused-
    dispatch property at ANY ``vocab_range`` instead of dropping to the
    unfused XLA oracle. A pre-pass in the same program sorts each row
    tile's entries by slab, so grid step ``(s, t)`` walks only tile
    ``t``'s entries of slab ``s``: every entry is visited once a chunk,
    not once per slab (with a single slab nothing is sorted).

  * **xla_fallback** — degenerate widths where not even one 128-lane
    slab per column fits the slab budget (thousands of vocab columns):
    the chunk falls back to the unfused chain itself
    (``core.ops.positive_modulus`` → ``vocab.update``'s vectorized XLA
    scatter-min against the HBM-resident state) — one shared
    implementation, not a copy; ``ref.py`` remains the standalone
    differential-test oracle.

All tiers are **bit-identical** to the unfused ``positive_modulus`` →
``vocab.update`` chain: scatter-min (and the count's sum) is order-
independent, so bucketing entries by slab changes no result; padding
rows carry ``NEVER`` positions (the min identity), every entry updates
exactly the one slab that holds its value, and the valid-row count
advances exactly as ``vocab.update`` advances it (saturating at the
int32 position ceiling — see ``vocab.positions``). When the state tracks occurrence
counts, the vmem tier runs the slab kernel with a single resident slab
so the counts ride the same dispatch.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro import kernels as kernels_lib
from repro.core import vocab as vocab_lib
from repro.kernels.fused_vocab import kernel

# VMEM budget for the resident first_pos stack (all columns at once) —
# the same 8 MiB residency budget as the fused loop-② table stack
# (kernels/fused_xform/ops.py): half of a 16 MiB/core VMEM, leaving room
# for the row tiles + double buffering. Worked numbers live in
# ``vmem_accounting`` (audited by repro.analysis.kernelcheck): Criteo's
# 5K point keeps the stack well inside; the same stack at VMEM_TIER_MAX
# widths blows the budget and routes to the HBM-slab tier.
FUSED_STATE_VMEM_BYTES = 8 * 1024 * 1024
# Budget for ONE resident slab on the hbm_slab tier: half the stack
# budget, so the Pallas pipeline can double-buffer the next slab's DMA
# against the current slab's RMW loop.
SLAB_VMEM_BYTES = 4 * 1024 * 1024
# Slab widths snap to the TPU lane grain.
SLAB_LANE = 128


def _entry_bytes(track_counts: bool) -> int:
    # int32 first_pos, plus an int32 count plane when tracked.
    return 8 if track_counts else 4


def vmem_accounting(
    n_cols: int,
    vocab_range: int,
    *,
    row_block: int = 1024,
    track_counts: bool = False,
    slab_range: int | None = None,
) -> dict[str, int]:
    """Bytes of each VMEM-resident buffer the fused loop-① kernel carries.

    ``state_stack`` (plus ``counts_stack`` when tracked) is the
    grid-carried accumulator block: the whole ``[n_cols, vocab_range]``
    stack on the vmem tier, or one ``[n_cols, slab_range]`` slab on the
    hbm_slab tier (pass ``slab_range``). The carried entries are what
    the tier guards charge against :data:`FUSED_STATE_VMEM_BYTES` /
    :data:`SLAB_VMEM_BYTES`; the row tiles stream per grid step through
    SMEM (``sparse_tile`` and ``pos_tile``; wherever the slab kernel
    runs, the tile's entries arrive bucketed by slab with
    ``offsets_tile``, the start of each column's run of each slab, and
    the positions laid out beside the entries where a packed key would
    not fit). This
    dict is the package's declared footprint — ``fused_vocab_tier``
    derives its decision from it, and ``repro.analysis.kernelcheck``
    asserts the two never disagree.
    """
    width = slab_range if slab_range else vocab_range
    acct = {
        "state_stack": n_cols * width * 4,
        "sparse_tile": row_block * n_cols * 4,
        "pos_tile": row_block * 4,
    }
    if track_counts:
        acct["counts_stack"] = n_cols * width * 4
    if slab_range or track_counts:  # the slab kernel runs
        sr = -(-min(width, vocab_range) // kernel.LANES) * kernel.LANES
        n_slabs = -(-vocab_range // sr)
        acct["offsets_tile"] = kernel.offsets_width(n_cols, n_slabs) * 4
        if kernel.key_row_bits(n_slabs * sr, row_block) is None:
            acct["pos_tile"] = row_block * n_cols * 4
    return acct


def default_slab_range(
    n_cols: int, vocab_range: int, track_counts: bool = False
) -> int:
    """Per-column slab width the hbm_slab tier picks: the largest
    128-lane multiple whose ``[n_cols, slab_range]`` slab (state +
    optional counts) fits :data:`SLAB_VMEM_BYTES`, shrunk to an even
    partition of ``vocab_range`` so no slab is a sliver. Returns 0 when
    not even one 128-lane slab per column fits (→ xla_fallback)."""
    if n_cols <= 0 or vocab_range <= 0:
        return 0
    cap = SLAB_VMEM_BYTES // (_entry_bytes(track_counts) * n_cols)
    cap = (cap // SLAB_LANE) * SLAB_LANE
    if cap <= 0:
        return 0
    if vocab_range <= cap:
        return vocab_range  # single resident slab
    n_slabs = -(-vocab_range // cap)
    even = -(-vocab_range // n_slabs)
    return min(cap, -(-even // SLAB_LANE) * SLAB_LANE)


def fused_vocab_tier(
    n_cols: int,
    vocab_range: int,
    *,
    slab_range: int | None = None,
    track_counts: bool = False,
) -> str:
    """Which tier the fused loop-① dispatch picks: ``"vmem"``,
    ``"hbm_slab"``, or ``"xla_fallback"``.

    ``slab_range`` forces the slab tier with that per-column slab width
    (the ``PipelineConfig.vocab_slab_range`` expert/test knob — it lets
    tests pin slab/VMEM bit-identity on ranges that fit both tiers);
    ``track_counts`` doubles the per-entry footprint, so it tightens
    both the residency cutoff and the slab width."""
    if slab_range is not None:
        return "hbm_slab" if slab_range > 0 else "xla_fallback"
    acct = vmem_accounting(n_cols, vocab_range, track_counts=track_counts)
    state_bytes = acct["state_stack"] + acct.get("counts_stack", 0)
    if (
        vocab_range <= vocab_lib.VMEM_TIER_MAX
        and state_bytes <= FUSED_STATE_VMEM_BYTES
    ):
        return "vmem"
    if default_slab_range(n_cols, vocab_range, track_counts) > 0:
        return "hbm_slab"
    return "xla_fallback"


def vocab_slab_count(
    n_cols: int,
    vocab_range: int,
    *,
    slab_range: int | None = None,
    track_counts: bool = False,
) -> int:
    """How many slabs the chosen tier streams per chunk (1 = resident /
    single-slab; also 1 on the fallback, which has no slabs at all)."""
    tier = fused_vocab_tier(
        n_cols, vocab_range, slab_range=slab_range, track_counts=track_counts
    )
    if tier != "hbm_slab":
        return 1
    sr = (
        slab_range
        if slab_range is not None
        else default_slab_range(n_cols, vocab_range, track_counts)
    )
    return max(1, -(-vocab_range // sr))


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
    (bit-identical to ``vocab.update(state, positive_modulus(sparse, V),
    valid)``). ``slab_range`` forces the hbm_slab tier with that slab
    width (None = tier policy decides).

    **Consumes** ``state``: the kernel tiers donate ``state.first_pos``
    (and ``counts``) to the kernel (in-place accumulation, the same
    convention as ``kernels/vocab``'s ``genvocab``), so on backends that
    honor donation (TPU) the caller must not read the old state
    afterwards — thread the returned state through, as every engine's
    loop ① does.
    """
    rows, n_cols = sparse.shape
    vocab_range = int(state.first_pos.shape[1])
    vocab_lib.check_row_ceiling(state.rows_seen, rows)
    track_counts = state.counts is not None
    tier = fused_vocab_tier(
        n_cols, vocab_range, slab_range=slab_range, track_counts=track_counts
    )
    if rows == 0 or n_cols == 0 or tier == "xla_fallback":
        # Fallback tier + degenerate tiles (no Pallas grid): the XLA
        # oracle IS the unfused chain — route through the one shared
        # implementation instead of a copy of its scatter-min.
        from repro.core import ops as core_ops

        return vocab_lib.update(
            state, core_ops.positive_modulus(sparse, vocab_range), valid
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
            row_block=blk,
            interpret=interpret,
        )
        return vocab_lib.VocabState(first_pos=first_pos, rows_seen=rows_seen)
    # hbm_slab — or vmem with tracked counts, which runs the slab kernel
    # with a single resident slab so the count plane rides the same
    # dispatch. Pad the state width to a slab multiple (pad entries are
    # NEVER / 0 — scatter targets only reach [0, vocab_range)).
    if tier == "vmem":
        sr = vocab_range
    elif slab_range is not None:
        sr = int(slab_range)
    else:
        sr = default_slab_range(n_cols, vocab_range, track_counts)
    # whole 128-lane windows for the kernel's RMW
    sr = -(-min(sr, vocab_range) // kernel.LANES) * kernel.LANES
    vpad = (-vocab_range) % sr
    first_pos, counts = state.first_pos, state.counts
    if vpad:
        first_pos = jnp.pad(
            first_pos, ((0, 0), (0, vpad)), constant_values=vocab_lib.NEVER
        )
        if track_counts:
            counts = jnp.pad(counts, ((0, 0), (0, vpad)))
    first_pos, counts = kernel.fused_genvocab_slabs(
        first_pos,
        counts,
        sparse_p,
        pos_p,
        slab_range=sr,
        vocab_range=vocab_range,
        row_block=blk,
        interpret=interpret,
    )
    if vpad:
        first_pos = first_pos[:, :vocab_range]
        if track_counts:
            counts = counts[:, :vocab_range]
    return vocab_lib.VocabState(
        first_pos=first_pos, rows_seen=rows_seen, counts=counts
    )
