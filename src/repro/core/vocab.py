"""Two-loop columnar vocabulary engine (GenVocab / ApplyVocab).

PIPER's stateful core: loop 1 streams the dataset and builds, per sparse
column, the table mapping hashed value → *appearing-sequence* ordinal
(GenVocab-1 + ApplyVocab-1 in the paper); loop 2 re-streams the dataset
and maps every feature through the table (GenVocab-2 + ApplyVocab-2).

TPU-native formulation
----------------------
The FPGA builds the table serially (II=2) with a BRAM bitmap + counter.
That algorithm is order-dependent; a parallel device needs an
order-independent equivalent. We use **first-occurrence positions**:

  loop 1:   first_pos[c, v] = min over rows r of (global position of r)
                              where modded[r, c] == v          (scatter-min)
  finalize: ordinal[c, v]   = rank of first_pos[c, v] among column c's
                              present values (argsort — stable, so ties
                              impossible: positions are unique)

Each column keeps its own modulus range: the state is one flat array in
which column ``c``'s entries start at a static offset
(:class:`VocabLayout`), so ``first_pos[c, v]`` is
``first_pos[offsets[c] + v]`` and a schema's small columns cost what
they hold, not the largest column's range.

``ordinal`` is bit-identical to the serial appearing-sequence counter, but
every step is a parallel primitive, and the state is **per-column** — the
paper's synchronization-free property. When rows are additionally sharded
over the ``data`` mesh axis, merging shards is a single elementwise
``min`` reduction (vs. the CPU's sequential sub-dictionary merge).

Memory tiers (paper §3.2, §4.4.6): the finalized table for vocab ≤
``VMEM_TIER_MAX`` entries is gathered through the Pallas VMEM kernel
("SRAM mode"); larger tables stay HBM-resident and use a plain XLA gather
("HBM mode"). ``ops.apply_vocab`` makes the choice.

Position arithmetic and the stream-length ceiling
-------------------------------------------------
Row positions are int32 and ``NEVER = int32.max`` is reserved as the
absent sentinel, so the largest representable position is ``NEVER - 1``
and a stream tops out at :data:`MAX_ROWS` (= 2³¹ − 1) rows. All position
arithmetic goes through :func:`positions` / :func:`advance_rows_seen`,
which compute in uint32 and **saturate at NEVER**: rows past the ceiling
scatter the min identity (i.e. are dropped from the state, never wrapped
into negative positions or aliased onto the sentinel). Host-driven entry
points additionally raise ``OverflowError`` via :func:`check_row_ceiling`
so the truncation is loud, not silent.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import obs

# Sentinel for "value never seen". Must exceed any real position.
NEVER = jnp.iinfo(jnp.int32).max
# Hard stream-length ceiling: row i carries position i, positions are
# int32, and NEVER is reserved — so at most NEVER (= 2³¹ − 1) rows carry
# representable positions. See the module docstring.
MAX_ROWS = int(NEVER)
# Per-column entries that still fit the on-chip ("SRAM") tier for a
# *single-column* table. The fused loop-① dispatch grades two tiers
# from this cutoff plus its residency budgets (kernels/fused_vocab/ops.py
# — the authoritative policy, which also sizes the layout's slabs):
#   vmem     — every range ≤ VMEM_TIER_MAX and the whole flat state
#              fits FUSED_STATE_VMEM_BYTES: state resident on-chip for
#              the entire call;
#   hbm_slab — larger: the flat state lives in HBM as runs of
#              ``layout.slab`` entries, each streamed through VMEM
#              (double-buffered by the Pallas pipeline).
VMEM_TIER_MAX = 512 * 1024


def positions(rows_seen: jnp.ndarray, rows: int, valid: jnp.ndarray) -> jnp.ndarray:
    """Global int32 positions for one chunk's rows, overflow-safe.

    Arithmetic runs in uint32 (headroom to 2³² − 1, so ``rows_seen`` near
    ``NEVER`` plus any realistic chunk length cannot wrap) and saturates
    at ``NEVER``: a row past :data:`MAX_ROWS` scatters the min identity
    instead of a wrapped negative position or an alias of the sentinel.
    Invalid (padding) rows scatter ``NEVER`` too.
    """
    pos = rows_seen.astype(jnp.uint32) + jnp.arange(rows, dtype=jnp.uint32)
    pos = jnp.minimum(pos, jnp.uint32(NEVER)).astype(jnp.int32)
    return jnp.where(valid, pos, NEVER)


def advance_rows_seen(rows_seen: jnp.ndarray, n_new: jnp.ndarray) -> jnp.ndarray:
    """``rows_seen + n_new`` in uint32, saturated at ``NEVER`` (int32).

    Keeps the stream counter from wrapping negative past the ceiling —
    once saturated, every later position saturates too, so overflow rows
    are dropped consistently rather than corrupting the scatter-min.
    """
    total = rows_seen.astype(jnp.uint32) + n_new.astype(jnp.uint32)
    return jnp.minimum(total, jnp.uint32(NEVER)).astype(jnp.int32)


def check_row_ceiling(rows_seen, rows: int) -> None:
    """Raise ``OverflowError`` if absorbing ``rows`` more rows would pass
    :data:`MAX_ROWS`. Host-side guard only: a no-op under tracing (jitted
    paths rely on the saturating arithmetic above), loud in eager use and
    in the host-driven engines' per-chunk checks."""
    if isinstance(rows_seen, jax.core.Tracer):
        return
    seen = int(rows_seen)
    if seen + int(rows) > MAX_ROWS:
        raise OverflowError(
            f"loop ① would absorb {rows} rows at offset {seen}, past the "
            f"int32 position ceiling of {MAX_ROWS} total rows (positions "
            "are int32 with NEVER reserved as the absent sentinel); split "
            "the stream or re-key it before the ceiling"
        )


# A column's entries start on a whole lane row of the state, and the
# state is a whole number of (8, 128) tiles: the kernels address it as
# ``[entries // TILE, 8, 128]`` (kernels/fused_vocab).
LANE = 128
TILE = 8 * LANE


# Finalize ranks columns of one range as a batch of at most this many
# entries: the Criteo 1M stack (26 × 1M) is one batch; 40M columns rank
# one at a time, which bounds the sorts' scratch.
FINALIZE_BATCH_ENTRIES = 1 << 26
# Columns of one range rank as one [k, r] batch only from this many up:
# a TPU tiles the batch's rows by 8 sublanes, so a [1, 40M] batch sorted
# eight times the entries (0.85 s a sort against 0.13 s for the 1-D
# column on a TPU v5e; PERF.md).
FINALIZE_MIN_BATCH = 8


def _round_up(n: int, grain: int) -> int:
    return -(-n // grain) * grain


@dataclasses.dataclass(frozen=True)
class VocabLayout:
    """Where each column's entries live in the flat, offset-indexed state.

    Column ``c`` keeps ``ranges[c]`` entries starting at the static offset
    ``offsets[c]`` (each column rounded up to a lane row), so the entry of
    value ``v`` is ``offsets[c] + (uint32(v) mod ranges[c])``. A schema
    whose columns share one range ``V`` is the special case
    ``offsets[c] = c × round_up(V, 128)``. The state holds ``n_slabs``
    runs of ``slab`` entries: the hbm_slab tier streams one run through
    VMEM at a time; ``slab == 0`` means one run of every used entry
    rounded to a tile. Hashable: states carry it as static metadata.
    """

    ranges: tuple[int, ...]
    slab: int = 0

    def __post_init__(self):
        object.__setattr__(self, "ranges", tuple(int(r) for r in self.ranges))
        if any(r <= 0 for r in self.ranges):
            raise ValueError(f"vocab ranges must be positive, got {self.ranges}")
        if self.slab < 0 or self.slab % TILE:
            raise ValueError(f"slab ({self.slab}) must be a multiple of {TILE}")

    @property
    def n_columns(self) -> int:
        return len(self.ranges)

    @property
    def offsets(self) -> tuple[int, ...]:
        out, off = [], 0
        for r in self.ranges:
            out.append(off)
            off += _round_up(r, LANE)
        return tuple(out)

    @property
    def used(self) -> int:
        """Entries up to the end of the last column (lane-rounded)."""
        return sum(_round_up(r, LANE) for r in self.ranges)

    @property
    def slab_entries(self) -> int:
        return self.slab or max(_round_up(self.used, TILE), TILE)

    @property
    def n_slabs(self) -> int:
        return max(1, -(-self.used // self.slab_entries))

    @property
    def entries(self) -> int:
        """Entries allocated: a whole number of slabs."""
        return self.n_slabs * self.slab_entries

    @property
    def range_entries(self) -> int:
        """Σ ranges: the entries the schema asks for."""
        return sum(self.ranges)

    @property
    def uniform(self) -> bool:
        return len(set(self.ranges)) <= 1

    def column(self, x: jnp.ndarray, c: int) -> jnp.ndarray:
        """Column ``c``'s entries of a flat state or table."""
        off = self.offsets[c]
        return x[off : off + self.ranges[c]]

    def rect(self, x: jnp.ndarray) -> jnp.ndarray:
        """A uniform layout's flat array as ``[n_columns, padded range]``
        (the rectangular kernels' view; pad lanes ride along)."""
        if not self.uniform:
            raise ValueError("only a uniform layout has a rectangular view")
        width = _round_up(self.ranges[0], LANE) if self.ranges else LANE
        return x[: self.n_columns * width].reshape(self.n_columns, width)

    def pack(self, columns, fill) -> jnp.ndarray:
        """Lay per-column arrays (a sequence, or the rows of a 2-D array,
        each at least its column's range wide) out flat; pad lanes and
        the tail are ``fill``. Inverse of :meth:`rect` and of
        :meth:`column`."""
        pieces = []
        for r, col in zip(self.ranges, columns, strict=True):
            col = jnp.asarray(col, jnp.int32)[: _round_up(r, LANE)]
            pieces.append(
                jnp.pad(col, (0, _round_up(r, LANE) - col.shape[0]), constant_values=fill)
            )
        pieces.append(jnp.full((self.entries - self.used,), fill, jnp.int32))
        return jnp.concatenate(pieces)

    def groups(self) -> list[tuple[int, tuple[int, ...]]]:
        """Columns grouped by range, ``(range, columns)``, in order of
        first appearance, each group cut to at most
        :data:`FINALIZE_BATCH_ENTRIES` entries (one column at least):
        finalize ranks each group as one batch."""
        by: dict[int, list[int]] = {}
        for c, r in enumerate(self.ranges):
            by.setdefault(r, []).append(c)
        out = []
        for r, cs in by.items():
            k = max(1, FINALIZE_BATCH_ENTRIES // r)
            out.extend((r, tuple(cs[i : i + k])) for i in range(0, len(cs), k))
        return out


def layout(ranges, *, track_counts: bool = False, slab_range: int | None = None) -> VocabLayout:
    """The state layout loop ① uses for ``ranges`` (one range per vocab
    column): the tier policy of kernels/fused_vocab sizes the slabs, so
    that every engine and path builds states that merge."""
    from repro.kernels.fused_vocab import ops as fv_ops

    return fv_ops.state_layout(
        tuple(ranges), track_counts=track_counts, slab_range=slab_range
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class VocabState:
    """Loop-1 accumulator: first-occurrence position per (column, value).

    ``first_pos`` is one flat int32 array laid out by ``layout``
    (:class:`VocabLayout`): column ``c``'s value ``v`` lives at
    ``layout.offsets[c] + v``. ``counts`` is optional (``None`` =
    untracked): when present it carries per-(column, value) occurrence
    counts in the same layout — the ingredient for the frequency-capped
    finalizers (:func:`finalize_topk` / :func:`finalize_min_count`).
    Count-tracking states and untracked states do not merge
    (:func:`check_compatible`).
    """

    first_pos: jnp.ndarray  # int32 [layout.entries], NEVER = absent
    rows_seen: jnp.ndarray  # int32 [] — global row counter (stream offset)
    counts: jnp.ndarray | None = None  # int32 [layout.entries] | None
    layout: VocabLayout = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @classmethod
    def init(
        cls,
        columns,
        vocab_range: int | None = None,
        track_counts: bool = False,
    ) -> "VocabState":
        """An empty state. ``columns`` is a :class:`VocabLayout`, a
        sequence of per-column ranges, or a column count with one shared
        ``vocab_range``."""
        if isinstance(columns, VocabLayout):
            lay = columns
        else:
            ranges = (
                (int(vocab_range),) * int(columns)
                if vocab_range is not None
                else tuple(columns)
            )
            lay = layout(ranges, track_counts=track_counts)
        first_pos = jnp.full((lay.entries,), NEVER, jnp.int32)
        _count_entries(lay, first_pos)
        return cls(
            first_pos=first_pos,
            rows_seen=jnp.zeros((), jnp.int32),
            counts=jnp.zeros((lay.entries,), jnp.int32) if track_counts else None,
            layout=lay,
        )

    def column(self, c: int) -> jnp.ndarray:
        """Column ``c``'s first positions."""
        return self.layout.column(self.first_pos, c)


def _count_entries(lay: VocabLayout, first_pos: jnp.ndarray) -> None:
    """``vocab.state_entries`` (read off the array) and
    ``vocab.range_entries`` (Σ ranges) for the state just allocated."""
    m = obs.metrics()
    m.counter("vocab.state_entries").add(int(first_pos.size))
    m.counter("vocab.range_entries").add(lay.range_entries)


def check_compatible(a: VocabState, b: VocabState) -> None:
    """Raise a clear ``ValueError`` unless ``a`` and ``b`` can merge.

    Layout/dtype mismatches (different ranges or column count, or a
    count-tracking state against an untracked one) previously surfaced
    as opaque broadcast errors deep inside jnp; this names the mismatch.
    Layouts are static under tracing, so the check also fires inside jit.
    """
    if a.layout != b.layout or a.first_pos.shape != b.first_pos.shape:
        raise ValueError(
            "cannot merge VocabStates with different vocab layouts: "
            f"{a.layout} {tuple(a.first_pos.shape)} vs {b.layout} "
            f"{tuple(b.first_pos.shape)} — loop ① states merge only when "
            "built with the same per-column ranges"
        )
    if a.first_pos.dtype != b.first_pos.dtype:
        raise ValueError(
            "cannot merge VocabStates with different first_pos dtypes: "
            f"{a.first_pos.dtype} vs {b.first_pos.dtype}"
        )
    if (a.counts is None) != (b.counts is None):
        raise ValueError(
            "cannot merge a count-tracking VocabState with an untracked "
            "one — build every loop ① shard with the same track_counts "
            "setting (PipelineConfig.track_vocab_counts)"
        )


def keys(
    lay: VocabLayout, modded: jnp.ndarray, columns: tuple[int, ...] | None = None
) -> jnp.ndarray:
    """Flat state index of every entry: ``offsets[c] + modded[r, j]``,
    where ``c`` is ``columns[j]`` (default: column ``j``)."""
    offsets = lay.offsets
    if columns is not None:
        offsets = tuple(offsets[c] for c in columns)
    if not any(offsets):
        return modded
    return modded + jnp.asarray(offsets, jnp.int32)[None, :]


def update(state: VocabState, modded: jnp.ndarray, valid: jnp.ndarray) -> VocabState:
    """Absorb one chunk (loop-1 step).

    modded: int32 [rows, n_columns], column c already in [0, ranges[c])
    valid:  bool  [rows]

    Positions saturate at ``NEVER`` past :data:`MAX_ROWS` (see
    :func:`positions`); in eager use the ceiling additionally raises.
    When ``state.counts`` is tracked, every valid row below the ceiling
    increments its (column, value) count — rows dropped by saturation are
    dropped from the counts too, so the fused kernels match bit-for-bit.
    """
    rows = modded.shape[0]
    check_row_ceiling(state.rows_seen, rows)
    pos = positions(state.rows_seen, rows, valid)
    k = keys(state.layout, modded).reshape(-1)
    pos_e = jnp.broadcast_to(pos[:, None], modded.shape).reshape(-1)
    first_pos = state.first_pos.at[k].min(pos_e)
    counts = state.counts
    if counts is not None:
        inc = (pos_e < NEVER).astype(jnp.int32)  # valid AND below the ceiling
        counts = counts.at[k].add(inc)
    rows_seen = advance_rows_seen(
        state.rows_seen, jnp.sum(valid.astype(jnp.int32))
    )
    return dataclasses.replace(
        state, first_pos=first_pos, rows_seen=rows_seen, counts=counts
    )


def merge(a: VocabState, b: VocabState) -> VocabState:
    """Merge loop-1 states from disjoint row shards (Piper's multi-instance
    sub-dictionary merge, reduced to one elementwise ``min``).

    ``(VocabState, merge)`` is a commutative monoid:

      * associative:  ``merge(merge(a, b), c) == merge(a, merge(b, c))``
      * commutative:  ``merge(a, b) == merge(b, a)``
      * identity:     ``VocabState.init(...)`` (all-NEVER, zero rows)

    because elementwise ``min`` and ``+`` are each associative/commutative
    and ``NEVER``/``0`` are their identities. That is what lets a
    multi-instance deployment reduce per-shard states in any order and in
    log-depth trees (:func:`merge_tree`) — the paper's "cheap merge" that
    replaces the CPU baseline's serial sub-dictionary merge. Tracked
    ``counts`` merge by elementwise ``+`` (identity: all-zero), so the
    frequency-capped finalizers stay bit-deterministic under resharding.
    Both states share one layout, so the merge is elementwise whatever
    the columns' ranges.

    Shards may also merge element-wise when states carry a leading stack
    axis (``first_pos [n, entries]``); :func:`merge_tree` relies on this.
    Incompatible layouts raise a clear ``ValueError``
    (:func:`check_compatible`) instead of an opaque broadcast error.
    """
    check_compatible(a, b)
    return VocabState(
        first_pos=jnp.minimum(a.first_pos, b.first_pos),
        rows_seen=advance_rows_seen(a.rows_seen, b.rows_seen),
        counts=None if a.counts is None else a.counts + b.counts,
        layout=a.layout,
    )


def merge_tree(states: VocabState) -> VocabState:
    """Tree-reduce a stack of per-shard loop-1 states into one state.

    Args:
      states: a :class:`VocabState` whose leaves carry a leading shard
        axis — ``first_pos int32 [n_shards, entries]``,
        ``rows_seen int32 [n_shards]`` (and ``counts`` alike when
        tracked) — as produced by running loop ① under ``shard_map``
        over the ``data`` mesh axis.

    Returns:
      The single merged :class:`VocabState` (no leading axis), equal to
      ``functools.reduce(merge, shards)`` in any shard order (merge is a
      commutative monoid), but evaluated as a log2-depth halving tree so
      a large shard count reduces in O(log n) dependent steps.

    The stack is padded to a power of two with the monoid identity
    (all-``NEVER`` positions, zero row count, zero counts), which leaves
    the result unchanged.
    """
    n = int(states.first_pos.shape[0])
    pow2 = 1 << max(n - 1, 0).bit_length()  # next power of two ≥ n
    if pow2 != n:
        pad = pow2 - n

        def ident(x, fill):
            return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])

        states = dataclasses.replace(
            states,
            first_pos=ident(states.first_pos, NEVER),
            rows_seen=ident(states.rows_seen, 0),
            counts=None if states.counts is None else ident(states.counts, 0),
        )
    while pow2 > 1:
        half = pow2 // 2
        states = merge(
            jax.tree.map(lambda x: x[:half], states),
            jax.tree.map(lambda x: x[half:], states),
        )
        pow2 = half
    return jax.tree.map(lambda x: x[0], states)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Vocabulary:
    """Finalized tables: value → appearing-sequence ordinal.

    ``table`` has the layout of the state it was finalized from: column
    ``c``'s value ``v`` maps through ``table[layout.offsets[c] + v]``.
    From :func:`finalize` every *present* value gets a dense ordinal in
    ``[0, sizes[c])`` and absent values map to 0. From the frequency-
    capped finalizers (:func:`finalize_topk` / :func:`finalize_min_count`)
    every *kept* value gets a dense ordinal in ``[0, sizes[c])`` and every
    other value — dropped or never seen — maps to the explicit **OOV
    ordinal** ``sizes[c]``, so a serving embedding needs ``sizes[c] + 1``
    rows per column.
    """

    table: jnp.ndarray   # int32 [layout.entries]
    sizes: jnp.ndarray   # int32 [n_columns] — number of present/kept values
    layout: VocabLayout = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @property
    def ranges(self) -> tuple[int, ...]:
        return self.layout.ranges

    @property
    def oov_ordinals(self) -> jnp.ndarray:
        """Per-column OOV ordinal of the capped finalizers (== sizes)."""
        return self.sizes

    def column(self, c: int) -> jnp.ndarray:
        """Column ``c``'s table."""
        return self.layout.column(self.table, c)


def _by_range(lay: VocabLayout, rank_rows, *planes):
    """Run ``rank_rows`` (``[k, r]`` stacks → table ``[k, r]``, sizes
    ``[k]``) on each group of equal-range columns, and lay the tables
    back out flat. Pad entries map to 0 (no key reaches them)."""
    tables: dict[int, jnp.ndarray] = {}
    sizes: dict[int, jnp.ndarray] = {}
    for r, cols in lay.groups():
        segments = [[x[lay.offsets[c] : lay.offsets[c] + r] for c in cols] for x in planes]
        if len(cols) < FINALIZE_MIN_BATCH:
            # a [k, r] batch of fewer rows than a tile's sublanes is padded
            # to them on a TPU: rank such columns one by one, 1-D
            for j, c in enumerate(cols):
                tables[c], sizes[c] = rank_rows(*(seg[j] for seg in segments))
            continue
        table, size = rank_rows(*(jnp.stack(seg) for seg in segments))
        for j, c in enumerate(cols):
            tables[c], sizes[c] = table[j], size[j]
    pieces = []
    for c, r in enumerate(lay.ranges):
        pieces.append(tables[c])
        pad = _round_up(r, LANE) - r
        if pad:
            pieces.append(jnp.zeros((pad,), jnp.int32))
    pieces.append(jnp.zeros((lay.entries - lay.used,), jnp.int32))
    sizes = [sizes[c] for c in range(lay.n_columns)]
    return (
        jnp.concatenate(pieces),
        jnp.stack(sizes).astype(jnp.int32) if sizes else jnp.zeros((0,), jnp.int32),
    )


def _rank_present(first_pos: jnp.ndarray):
    """Per column (the last axis): each present value's rank by first
    position; absent values map to 0."""
    present = first_pos < NEVER
    # Rank by first-occurrence position. argsort(argsort(x)) gives the rank;
    # absent values (NEVER) rank behind every present one, and within absent
    # ties the rank is arbitrary but they are masked to 0 below.
    order = jnp.argsort(first_pos, axis=-1, stable=True)
    ranks = jnp.argsort(order, axis=-1, stable=True)
    table = jnp.where(present, ranks, 0).astype(jnp.int32)
    sizes = jnp.sum(present.astype(jnp.int32), axis=-1)
    return table, sizes


@functools.partial(jax.jit, static_argnames=("lay",))
def _finalize(first_pos: jnp.ndarray, lay: VocabLayout):
    return _by_range(lay, _rank_present, first_pos)


def finalize(state: VocabState) -> Vocabulary:
    """Rank each column's present values by first occurrence, column by
    column of the layout (columns of one range rank as one batch)."""
    table, sizes = _finalize(state.first_pos, state.layout)
    return Vocabulary(table=table, sizes=sizes, layout=state.layout)


def _capped_rows(first_pos: jnp.ndarray, kept: jnp.ndarray):
    """Ordinals for an explicit keep-mask: kept values rank by first
    occurrence (appearing-sequence order among the keepers); everything
    else maps to the per-column OOV ordinal ``sizes[c]``."""
    key = jnp.where(kept, first_pos, NEVER)
    order = jnp.argsort(key, axis=-1, stable=True)
    ranks = jnp.argsort(order, axis=-1, stable=True)
    sizes = jnp.sum(kept.astype(jnp.int32), axis=-1)
    table = jnp.where(kept, ranks, sizes[..., None]).astype(jnp.int32)
    return table, sizes.astype(jnp.int32)


def _require_counts(state: VocabState) -> jnp.ndarray:
    if state.counts is None:
        raise ValueError(
            "frequency-capped finalize needs a count-tracking VocabState — "
            "build loop ① with VocabState.init(..., track_counts=True) "
            "(PipelineConfig.track_vocab_counts=True)"
        )
    return state.counts


def _topk_rows(first_pos: jnp.ndarray, counts: jnp.ndarray, k: int):
    present = first_pos < NEVER
    # Order values by (count desc, first occurrence asc). Both keys come
    # from commutative-monoid accumulators, and (count, first_pos) is a
    # total order over present values (positions are unique), so the
    # kept set — and therefore the table — is bit-deterministic under
    # any shard/merge order. Absent values sort behind every present one
    # (their count key is +1 > every negated real count).
    neg_count = jnp.where(present, -counts, 1)
    pos_key = jnp.where(present, first_pos, NEVER)
    order = jnp.lexsort((pos_key, neg_count), axis=-1)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return _capped_rows(first_pos, present & (rank < k))


@functools.partial(jax.jit, static_argnames=("lay", "k"))
def _finalize_topk(first_pos, counts, lay: VocabLayout, k: int):
    return _by_range(lay, functools.partial(_topk_rows, k=k), first_pos, counts)


def finalize_topk(state: VocabState, k: int) -> Vocabulary:
    """Frequency-capped finalize: keep each column's ``k`` most frequent
    values, ties broken by earlier first occurrence.

    Kept values get dense ordinals in appearing-sequence order (rank of
    ``first_pos`` among the keepers — so the ordinal assignment matches
    :func:`finalize` restricted to the kept set); every other value maps
    to the explicit OOV ordinal ``sizes[c]``. Requires a count-tracking
    state (``track_counts=True``). Deterministic under any merge order:
    both ``counts`` (sum) and ``first_pos`` (min) are commutative-monoid
    reductions, and the sort key (count, first-occurrence) is a total
    order.
    """
    counts = _require_counts(state)
    if k < 0:
        raise ValueError(f"finalize_topk needs k >= 0, got {k}")
    table, sizes = _finalize_topk(state.first_pos, counts, state.layout, int(k))
    return Vocabulary(table=table, sizes=sizes, layout=state.layout)


def _min_count_rows(first_pos, counts, min_count: int):
    return _capped_rows(
        first_pos, (first_pos < NEVER) & (counts >= jnp.int32(min_count))
    )


@functools.partial(jax.jit, static_argnames=("lay", "min_count"))
def _finalize_min_count(first_pos, counts, lay: VocabLayout, min_count: int):
    return _by_range(
        lay,
        functools.partial(_min_count_rows, min_count=min_count),
        first_pos,
        counts,
    )


def finalize_min_count(state: VocabState, min_count: int) -> Vocabulary:
    """Frequency-capped finalize: keep values seen at least ``min_count``
    times; everything else maps to the OOV ordinal ``sizes[c]``.

    Kept values get dense ordinals in appearing-sequence order, exactly
    like :func:`finalize` restricted to the kept set. Requires a
    count-tracking state. Deterministic under any merge order (counts
    sum; first positions min — both commutative monoids).
    """
    counts = _require_counts(state)
    if min_count < 1:
        raise ValueError(f"finalize_min_count needs min_count >= 1, got {min_count}")
    table, sizes = _finalize_min_count(
        state.first_pos, counts, state.layout, int(min_count)
    )
    return Vocabulary(table=table, sizes=sizes, layout=state.layout)


def lookup(
    vocab: Vocabulary, modded: jnp.ndarray, columns: tuple[int, ...] | None = None
) -> jnp.ndarray:
    """Loop-2 mapping (ApplyVocab-2): gather ordinals for every feature.

    modded: int32 [rows, k] → int32 [rows, k], one gather from the flat
    table at ``offsets[c] + modded[r, j]`` (``c = columns[j]``; default:
    the vocabulary's columns in order).
    (Pure-jnp HBM-tier path; the VMEM-tier Pallas kernel lives in
    kernels/vocab and is selected by ``core.ops.apply_vocab``.)
    """
    return vocab.table[keys(vocab.layout, modded, columns)]
