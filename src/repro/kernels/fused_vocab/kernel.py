"""Pallas TPU kernel: the whole loop-① operator chain in one VMEM pass.

PR 3 gave loop ② the paper's no-materialization dataflow (a row tile
streams through Modulus → ApplyVocab ∥ Neg2Zero → Logarithm on-chip);
loop ① still ran decode → ``positive_modulus`` → scatter-min
``vocab.update`` as separate dispatches, round-tripping the modded
matrix through HBM between them — exactly the producer-side per-op
materialization the paper identifies as the CPU's GenVocab bottleneck
(row-wise synchronization on the shared dictionary). This kernel
collapses the chain:

``fused_genvocab_kernel`` (VMEM tier)
    One grid step per row tile. The raw sparse tile (int32 hash
    bitcasts, straight out of Decode) is reduced modulo each column's
    range as uint32 *inside* the kernel, then scatter-min'd into
    the :class:`~repro.core.vocab.VocabState` ``first_pos`` accumulator
    — which uses a **constant index map** plus an input/output alias,
    so Pallas DMAs the whole state into VMEM once at the first grid
    step and keeps it resident (and carried) across every row tile of
    the call: the FPGA's on-chip-BRAM dictionary build, with the modded
    values never leaving the chip. The scatter itself is the literal
    II=2 read-modify-write loop of the FPGA, kept serial *within* the
    tile because two equal hashes in one tile must min-combine; the
    result is nevertheless order-independent (min is commutative), so
    it is bit-identical to the vectorized XLA scatter-min oracle.

``fused_genvocab_slab_kernel`` (HBM-slab tier)
    The same chain for states that exceed the VMEM residency budget.
    ``first_pos`` (and the optional occurrence-count plane) lives in HBM
    as a flat, offset-indexed array (``vocab.VocabLayout``) cut into
    runs of ``slab`` entries — the **slabs**; the grid is ``(n_slabs, n_row_tiles)`` with the slab index
    outermost, so for each slab the whole chunk streams through while
    that slab's block — a constant index map *over the inner row-tile
    dim* plus an input/output alias, generalizing the VMEM kernel's
    grid-carry machinery — stays resident in VMEM and is written back
    to HBM exactly once when the grid advances to the next slab. The
    Pallas pipeline double-buffers the slab DMAs against compute, and
    loop ① stays ONE fused dispatch at ANY ``vocab_range``.

    A pre-pass in the same program reduces the chunk modulo each
    column's range (vectorized), lays each row tile out column by column
    and, with more than one slab, sorts each column of the tile by value
    — as one int32 key ``value << row_bits | row``, or as the values
    with their rows as a second operand where that key would not fit 31
    bits (ranges past about 2.1M). Columns lie in offset order, so the
    tile is then sorted by flat key, duplicates side by side, earliest
    row first. Each entry becomes one int32 ``local << row_bits | row``
    (``local`` its index inside its slab), and the pre-pass counts
    where each slab's run starts in the tile. Grid step ``(s, t)`` then
    walks just tile ``t``'s run of slab ``s`` — one loop, whatever the
    columns: every entry is visited once a chunk, with no modulus and no
    skip in the kernel. With a single slab (the vmem tier with tracked
    counts) nothing is sorted, and the run is the whole tile.

XLA-fallback tier (degenerate widths where not even one 128-lane slab
per column fits the slab budget) — there is no kernel: the modulus and
scatter-min fall back to the XLA oracle (ops.py), the same
many-outstanding-writes pattern ``vocab.update`` already uses for
HBM-resident state. Identical results — property-tested.

Like every kernel package here, the kernels run ``interpret=True`` on
CPU and compiled Mosaic on a TPU backend (ops.py switches per backend
through ``kernels.interpret``). Three choices make the serial RMW lower
through Mosaic; each answers a refusal of the TPU compiler
(tests/test_tpu_compile.py compiles both kernels for a v5e):

  * the scalars come from SMEM: the sparse tile arrives flattened
    (``[row_block * n_cols]``) and the positions as ``[row_block]``,
    both as SMEM blocks (so do the slab kernel's entries and each
    tile's slab offsets, padded to :data:`SMEM_GRAIN` words). Mosaic
    cannot read a scalar out of a vector (``modded[i, c]`` lowers to
    ``dynamic_slice``), and a ``(1, row_block)`` VMEM block of positions
    breaks the (8, 128) tiling;
  * the VMEM kernel's modulus runs on the scalar in int32 arithmetic
    (Mosaic has no scalar bitcast to uint32);
  * the RMW touches the (8, 128) tile that holds the entry — the state
    block is viewed as ``[entries // 1024, 8, 128]`` and indexed on its
    leading axis — and selects the entry's sublane and lane. Mosaic
    stores no scalars to VMEM and loads no vector at an unaligned
    dynamic index; a leading-axis index is plain address arithmetic.
    The layout keeps the state a whole number of tiles.

The VMEM kernel's column loop is unrolled (``n_cols`` is static and
small), which keeps each column's range and offset static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import vocab as vocab_lib

# The RMW window: one (8, 128) vector register tile of the state.
LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES
TILE_BITS = TILE.bit_length() - 1
# A packed entry key (value << row bits | row) must stay a non-negative
# int32.
KEY_BITS = 31
# 1-D SMEM blocks are whole arrays or multiples of this many words.
SMEM_GRAIN = 1024


def _u32_mod(h: jnp.ndarray, vocab_range: int) -> jnp.ndarray:
    """uint32 modulus of one int32 hash bitcast, in int32 arithmetic
    (sparse hashes are unsigned — paper §3.2). For ``h < 0`` the uint32
    value is ``h + 2**32``, so its residue is ``rem(h) + 2**32 % V``
    brought back into ``[0, V)``."""
    r = jax.lax.rem(h, jnp.int32(vocab_range))
    r = jnp.where(h < 0, r + jnp.int32((1 << 32) % vocab_range), r)
    return jnp.where(r < 0, r + jnp.int32(vocab_range), r)


def _rmw(ref, k, fn) -> None:
    """``flat[k] = fn(flat[k])`` through the (8, 128) tile that holds
    entry ``k`` (non-negative) of a ``[tiles, 8, 128]`` block (dynamic
    leading index)."""
    t = k >> TILE_BITS
    win = ref[t]
    idx = jax.lax.broadcasted_iota(
        jnp.int32, (SUBLANES, LANES), 0
    ) * LANES + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    ref[t] = jnp.where(idx == (k & (TILE - 1)), fn(win), win)


def _tiles(flat: jnp.ndarray) -> jnp.ndarray:
    return flat.reshape(-1, SUBLANES, LANES)


def _compiler_params(n_planes: int, entries: int):
    """Scoped-VMEM limit for ``n_planes`` carried blocks of ``entries``
    int32 each. Each is allocated as an input and an output block, both
    double-buffered: at the 1M point one slab plane takes 4 x 1,040,384
    x 4 B = 16.6 MB, past the compiler's default 16 MiB scope (v5e has
    128 MiB of VMEM)."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=4 * n_planes * entries * 4 + (4 << 20)
    )


def _smem_block(n: int, index_map):
    return pl.BlockSpec((n,), index_map, memory_space=pltpu.SMEM)


def _check_rows(rows: int, row_block: int, pos) -> None:
    if rows % row_block:
        raise ValueError(f"rows ({rows}) must divide by row_block ({row_block})")
    if pos.shape != (rows,):
        raise ValueError(f"pos shape {pos.shape} != {(rows,)}")


def _fused_genvocab_kernel(
    sparse_ref, pos_ref, state_in_ref, state_ref, *, ranges, offsets
):
    # sparse_ref:   int32 [R_BLK * n_cols] SMEM — raw hash bitcasts
    # pos_ref:      int32 [R_BLK] SMEM — global row positions (NEVER = padding)
    # state_in_ref: int32 [tiles, 8, 128] — prior first_pos (aliased)
    # state_ref:    int32 [tiles, 8, 128] — accumulator, constant index
    #               map: resident in VMEM and carried across all grid steps
    @pl.when(pl.program_id(0) == 0)
    def _init():  # first tile: seed the accumulator from the carried state
        state_ref[...] = state_in_ref[...]

    n_cols = len(ranges)

    def row_body(i, _):
        p = pos_ref[i]
        for c in range(n_cols):
            k = offsets[c] + _u32_mod(sparse_ref[i * n_cols + c], ranges[c])
            _rmw(state_ref, k, lambda w: jnp.minimum(w, p))  # II=2 RMW
        return 0

    jax.lax.fori_loop(0, pos_ref.shape[0], row_body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("ranges", "offsets", "row_block", "interpret"),
    donate_argnums=(0,),
)
def fused_genvocab(
    state: jnp.ndarray,
    sparse: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    ranges: tuple[int, ...],
    offsets: tuple[int, ...],
    row_block: int = 1024,
    interpret: bool = True,
) -> jnp.ndarray:
    """Whole loop-① chain per row tile, state resident in VMEM.

    state  int32 [entries] — flat first_pos (a whole number of tiles)
    sparse int32 [rows, n_cols] (raw hash bitcasts, pre-modulus)
    pos    int32 [rows] global positions (``vocab.NEVER`` for
           padding/invalid rows)
    ranges, offsets — each column's modulus range and its first entry
    → updated first_pos int32 [entries]

    ``rows`` must divide by ``row_block`` (ops.py pads; padding rows
    carry NEVER positions, which min() ignores). On a TPU ``row_block``
    must also be ``rows`` or a multiple of 1024 (the SMEM tiling).
    """
    (entries,) = state.shape
    rows, n_cols = sparse.shape
    _check_rows(rows, row_block, pos)
    block = pl.BlockSpec((entries // TILE, SUBLANES, LANES), lambda r: (0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_fused_genvocab_kernel, ranges=ranges, offsets=offsets),
        grid=(rows // row_block,),
        in_specs=[
            _smem_block(row_block * n_cols, lambda r: (r,)),
            _smem_block(row_block, lambda r: (r,)),
            block,
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(
            (entries // TILE, SUBLANES, LANES), jnp.int32
        ),
        input_output_aliases={2: 0},
        compiler_params=_compiler_params(1, entries),
        interpret=interpret,
    )(sparse.reshape(-1), pos, _tiles(state))
    return out.reshape(-1)


def key_row_bits(max_range: int, row_block: int) -> int | None:
    """Bits of the row index in the pre-pass's packed sort key ``value
    << bits | row``, or None where such a key would not stay a
    non-negative int32 for values below ``max_range`` (then the values
    are sorted with their rows as a second operand)."""
    bits = (row_block - 1).bit_length()
    return bits if (max_range - 1) >> (KEY_BITS - bits) == 0 else None


def offsets_width(n_slabs: int) -> int:
    """Words of one row tile's slab offsets (``n_slabs + 1``), padded to
    the SMEM block grain."""
    return -(-(n_slabs + 1) // SMEM_GRAIN) * SMEM_GRAIN


def _entries_by_slab(sparse, *, ranges, offsets, slab, n_slabs, row_block):
    """The pre-pass of the slab kernel: each row tile's entries reduced
    modulo their column's range, laid out column by column and, with
    more than one slab, sorted by value within each column — so sorted
    by flat key over the tile. Returns the entries (``[n_tiles × n_cols
    × row_block]``, one int32 ``local << row_bits | row`` each, ``local``
    the entry's index inside its slab) and per tile the ``n_slabs + 1``
    starts of the slabs' runs, as indices into the tile's block
    (``[n_tiles × offsets_width]``)."""
    rows, n_cols = sparse.shape
    n_tiles = rows // row_block
    bits = (row_block - 1).bit_length()
    u = jax.lax.bitcast_convert_type(sparse, jnp.uint32)
    v = (u % jnp.asarray(ranges, jnp.uint32)[None, :]).astype(jnp.int32)
    v = v.reshape(n_tiles, row_block, n_cols).transpose(0, 2, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
    if n_slabs > 1:
        if key_row_bits(max(ranges), row_block) is not None:
            packed = jax.lax.sort((v << bits) | row, dimension=2)
            v, row = packed >> bits, packed & ((1 << bits) - 1)
        else:
            v, row = jax.lax.sort((v, row), dimension=2, num_keys=1)
    flat = (v + jnp.asarray(offsets, jnp.int32)[None, :, None]).reshape(n_tiles, -1)
    local = flat % slab
    entries = (local << bits) | row.reshape(n_tiles, -1)
    if n_slabs > 1:
        bounds = jnp.arange(n_slabs + 1, dtype=jnp.int32) * slab
        starts = jax.vmap(lambda f: jnp.searchsorted(f, bounds))(flat)
    else:
        starts = jnp.broadcast_to(
            jnp.asarray([0, n_cols * row_block], jnp.int32), (n_tiles, 2)
        )
    starts = jnp.pad(
        starts.astype(jnp.int32),
        ((0, 0), (0, offsets_width(n_slabs) - (n_slabs + 1))),
    )
    return entries.reshape(-1), starts.reshape(-1)


def _fused_genvocab_slab_kernel(*refs, row_bits: int, track_counts: bool):
    # grid = (n_slabs, n_row_tiles), slab index outermost: for a fixed
    # slab the row-tile dim iterates innermost, so the slab's state (and
    # count) block — index map constant over that inner dim — stays
    # resident in VMEM across the whole chunk and is written back to HBM
    # once, when the slab index advances.
    if track_counts:
        (entries_ref, pos_ref, off_ref, state_in_ref, counts_in_ref,
         state_ref, counts_ref) = refs
    else:
        entries_ref, pos_ref, off_ref, state_in_ref, state_ref = refs
        counts_in_ref = counts_ref = None
    s = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():  # first row tile of this slab: seed from the HBM block
        state_ref[...] = state_in_ref[...]
        if track_counts:
            counts_ref[...] = counts_in_ref[...]

    never = jnp.int32(vocab_lib.NEVER)

    # The tile's entries that fall in slab s form one run of its block:
    # walk that run alone.
    def entry(j, _):
        k = entries_ref[j]
        local = k >> row_bits
        p = pos_ref[k & ((1 << row_bits) - 1)]
        # the FPGA's II=2 RMW, streamed slab by slab
        _rmw(state_ref, local, lambda w: jnp.minimum(w, p))
        if track_counts:
            # p == NEVER marks padding/invalid/past-ceiling rows — they
            # drop from the counts exactly as from the state.
            inc = jnp.where(p != never, 1, 0)
            _rmw(counts_ref, local, lambda w: w + inc)
        return 0

    jax.lax.fori_loop(off_ref[s], off_ref[s + 1], entry, 0)


@functools.partial(
    jax.jit,
    static_argnames=("ranges", "offsets", "slab", "row_block", "interpret"),
    donate_argnums=(0, 1),
)
def fused_genvocab_slabs(
    state: jnp.ndarray,
    counts: jnp.ndarray | None,
    sparse: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    ranges: tuple[int, ...],
    offsets: tuple[int, ...],
    slab: int,
    row_block: int = 1024,
    interpret: bool = True,
):
    """Whole loop-① chain at any ranges — ONE dispatch, the HBM-resident
    flat state streamed through VMEM slab by slab.

    state     int32 [entries] — flat first_pos, a whole number of slabs
    counts    int32 [entries] occurrence counts, or None
    sparse    int32 [rows, n_cols] (raw hash bitcasts, pre-modulus)
    pos       int32 [rows] global positions (``vocab.NEVER`` for
              padding/invalid rows)
    ranges, offsets — each column's modulus range and its first entry
    slab      — entries per slab, a multiple of :data:`TILE`
    → (updated first_pos, updated counts | None), same shapes.

    A pre-pass in this same program (:func:`_entries_by_slab`) reduces
    the entries and, with more than one slab, buckets each row tile's
    entries by slab; grid step ``(s, t)`` walks only tile ``t``'s
    entries in slab ``s``.

    ``state`` (and ``counts``) are donated-into: each slab block is
    aliased input→output, the same in-place convention as
    :func:`fused_genvocab`. ``row_block`` as there.
    """
    (entries,) = state.shape
    if slab % TILE or entries % slab:
        raise ValueError(
            f"state of {entries} entries must hold whole slabs of "
            f"{slab} (a multiple of {TILE}); the layout sizes both"
        )
    n_slabs = entries // slab
    rows, n_cols = sparse.shape
    _check_rows(rows, row_block, pos)
    row_bits = (row_block - 1).bit_length()
    if (slab - 1) >> (31 - row_bits):  # local << row_bits | row: an int32
        raise ValueError(f"slab ({slab}) too wide for a packed entry key")
    track_counts = counts is not None
    packed, starts = _entries_by_slab(
        sparse,
        ranges=ranges,
        offsets=offsets,
        slab=slab,
        n_slabs=n_slabs,
        row_block=row_block,
    )

    def tile(s, r):  # the row tile's SMEM blocks, whatever the slab
        return (r,)

    slab_spec = pl.BlockSpec(
        (slab // TILE, SUBLANES, LANES), lambda s, r: (s, 0, 0)
    )
    in_specs = [
        _smem_block(row_block * n_cols, tile),
        _smem_block(row_block, tile),
        _smem_block(offsets_width(n_slabs), tile),
        slab_spec,
    ]
    shape = jax.ShapeDtypeStruct((entries // TILE, SUBLANES, LANES), jnp.int32)
    out_shape = [shape]
    operands = [packed, pos, starts, _tiles(state)]
    aliases = {3: 0}
    if track_counts:
        in_specs.append(slab_spec)
        out_shape.append(shape)
        operands.append(_tiles(counts))
        aliases[4] = 1
    out = pl.pallas_call(
        functools.partial(
            _fused_genvocab_slab_kernel,
            row_bits=row_bits,
            track_counts=track_counts,
        ),
        grid=(n_slabs, rows // row_block),
        in_specs=in_specs,
        out_specs=[slab_spec] * len(out_shape),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_compiler_params(len(out_shape), slab),
        interpret=interpret,
    )(*operands)
    if track_counts:
        return out[0].reshape(-1), out[1].reshape(-1)
    return out[0].reshape(-1), None
