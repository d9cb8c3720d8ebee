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
    bitcasts, straight out of Decode) is reduced modulo ``vocab_range``
    as uint32 *inside* the kernel, then scatter-min'd into
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
    The same chain for state stacks that exceed the VMEM residency
    budget. ``first_pos`` (and the optional occurrence-count plane)
    lives in HBM partitioned into ``[n_cols, slab_range]`` **slabs**;
    the grid is ``(n_slabs, n_row_tiles)`` with the slab index
    outermost, so for each slab the whole chunk streams through while
    that slab's block — a constant index map *over the inner row-tile
    dim* plus an input/output alias, generalizing the VMEM kernel's
    grid-carry machinery — stays resident in VMEM and is written back
    to HBM exactly once when the grid advances to the next slab. The
    Pallas pipeline double-buffers the slab DMAs against compute, and
    loop ① stays ONE fused dispatch at ANY ``vocab_range``.

    A pre-pass in the same program reduces the chunk modulo
    ``vocab_range`` (vectorized), lays each row tile out column by
    column as one int32 key ``value << row_bits | row`` (or the values
    with their positions as a second operand where that key would not
    fit 31 bits) and, with more than one slab, sorts each column of the
    tile by value (which also puts duplicates side by side, earliest
    row first). It also counts where each slab's run starts, per tile
    and column. Grid step ``(s, t)`` then runs, for each static column,
    a loop over just the run of tile ``t`` in slab ``s``: every entry is
    visited once a chunk, with no modulus and no skip in the kernel.
    With a single slab (the vmem tier with tracked counts) nothing is
    sorted, and each column's run is the whole column of the tile.

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
  * the RMW touches the 128-lane window that holds the entry, at a
    static column and a dynamic 128-aligned lane offset, and selects
    the entry's lane. Mosaic stores no scalars to VMEM and loads no
    vector at an unaligned dynamic index. The state width is therefore
    padded to a multiple of 128 lanes, so every window lies inside the
    block in interpret mode too.

The column loop is unrolled (``n_cols`` is static and small), which is
what keeps the column index static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import vocab as vocab_lib

# The RMW window: one vector register row. State widths and slab widths
# are multiples of it.
LANES = 128
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


def _rmw(ref, c: int, v, fn) -> None:
    """``ref[c, v] = fn(ref[c, v])`` through the 128-lane window that
    holds ``v`` (static row ``c``, dynamic 128-aligned lane offset)."""
    base = pl.multiple_of((v // LANES) * LANES, LANES)
    win = ref[pl.ds(c, 1), pl.ds(base, LANES)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    ref[pl.ds(c, 1), pl.ds(base, LANES)] = jnp.where(
        lane == v - base, fn(win), win
    )


def _compiler_params(n_planes: int, n_cols: int, width: int):
    """Scoped-VMEM limit for ``n_planes`` carried ``[n_cols, width]``
    blocks. Each is allocated as an input and an output block, both
    double-buffered, with ``n_cols`` padded to 8 sublanes: at the 1M
    point one slab plane takes 4 x 32 x 40064 x 4 B = 20.5 MB, past the
    compiler's default 16 MiB scope (v5e has 128 MiB of VMEM)."""
    block = -(-n_cols // 8) * 8 * width * 4
    return pltpu.CompilerParams(
        vmem_limit_bytes=4 * n_planes * block + (4 << 20)
    )


def _smem_block(n: int, index_map):
    return pl.BlockSpec((n,), index_map, memory_space=pltpu.SMEM)


def _smem_specs(rows: int, n_cols: int, row_block: int, index_map):
    """SMEM blocks of the flattened sparse tile and of the positions."""
    if rows % row_block:
        raise ValueError(f"rows ({rows}) must divide by row_block ({row_block})")
    return [
        _smem_block(row_block * n_cols, index_map),
        _smem_block(row_block, index_map),
    ]


def _fused_genvocab_kernel(
    sparse_ref, pos_ref, state_in_ref, state_ref, *, n_cols, vocab_range
):
    # sparse_ref:   int32 [R_BLK * n_cols] SMEM — raw hash bitcasts
    # pos_ref:      int32 [R_BLK] SMEM — global row positions (NEVER = padding)
    # state_in_ref: int32 [n_cols, width] — prior first_pos (aliased)
    # state_ref:    int32 [n_cols, width] — accumulator, constant index
    #               map: resident in VMEM and carried across all grid steps
    @pl.when(pl.program_id(0) == 0)
    def _init():  # first tile: seed the accumulator from the carried state
        state_ref[...] = state_in_ref[...]

    def row_body(i, _):
        p = pos_ref[i]
        for c in range(n_cols):
            v = _u32_mod(sparse_ref[i * n_cols + c], vocab_range)
            _rmw(state_ref, c, v, lambda w: jnp.minimum(w, p))  # II=2 RMW
        return 0

    jax.lax.fori_loop(0, pos_ref.shape[0], row_body, 0)


def _pad_lanes(state: jnp.ndarray) -> jnp.ndarray:
    pad = (-state.shape[1]) % LANES
    if not pad:
        return state
    return jnp.pad(state, ((0, 0), (0, pad)), constant_values=vocab_lib.NEVER)


@functools.partial(
    jax.jit, static_argnames=("row_block", "interpret"), donate_argnums=(0,)
)
def fused_genvocab(
    state: jnp.ndarray,
    sparse: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    row_block: int = 1024,
    interpret: bool = True,
) -> jnp.ndarray:
    """Whole loop-① chain per row tile, state resident in VMEM.

    state  int32 [n_cols, vocab_range] — first_pos accumulator
    sparse int32 [rows, n_cols] (raw hash bitcasts, pre-modulus)
    pos    int32 [rows] global positions (``vocab.NEVER`` for
           padding/invalid rows)
    → updated first_pos int32 [n_cols, vocab_range]

    ``rows`` must divide by ``row_block`` (ops.py pads; padding rows
    carry NEVER positions, which min() ignores). On a TPU ``row_block``
    must also be ``rows`` or a multiple of 1024 (the SMEM tiling).
    """
    n_cols, vocab_range = state.shape
    rows = sparse.shape[0]
    if pos.shape != (rows,):
        raise ValueError(f"pos shape {pos.shape} != {(rows,)}")
    wide = _pad_lanes(state)
    width = wide.shape[1]
    out = pl.pallas_call(
        functools.partial(
            _fused_genvocab_kernel, n_cols=n_cols, vocab_range=vocab_range
        ),
        grid=(rows // row_block,),
        in_specs=_smem_specs(rows, n_cols, row_block, lambda r: (r,))
        + [pl.BlockSpec((n_cols, width), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((n_cols, width), lambda r: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cols, width), jnp.int32),
        input_output_aliases={2: 0},
        compiler_params=_compiler_params(1, n_cols, width),
        interpret=interpret,
    )(sparse.reshape(-1), pos, wide)
    return out[:, :vocab_range]


def key_row_bits(padded_range: int, row_block: int) -> int | None:
    """Bits of the row index in a packed entry key ``value << bits |
    row``, or None where such a key would not stay a non-negative int32
    (then the values travel with their positions as a second operand)."""
    bits = (row_block - 1).bit_length()
    return bits if (padded_range - 1) >> (KEY_BITS - bits) == 0 else None


def offsets_width(n_cols: int, n_slabs: int) -> int:
    """Words of one row tile's slab offsets (``n_cols × (n_slabs + 1)``),
    padded to the SMEM block grain."""
    return -(-(n_cols * (n_slabs + 1)) // SMEM_GRAIN) * SMEM_GRAIN


def _entries_by_slab(
    sparse, pos, *, vocab_range, slab_range, n_slabs, row_block, row_bits
):
    """The pre-pass of the slab kernel: each row tile's entries reduced
    modulo ``vocab_range``, laid out column by column and, with more
    than one slab, sorted by value. Returns the flat entries (``[n_tiles
    × n_cols × row_block]``, a tile's column ``c`` at ``[c·row_block,
    (c+1)·row_block)`` of its block), the positions (``[rows]`` read by
    the packed key's row, or laid out beside the values), and per tile
    the ``n_slabs + 1`` starts of each column's slab runs, as indices
    into the tile's block (``[n_tiles × offsets_width]``)."""
    rows, n_cols = sparse.shape
    n_tiles = rows // row_block
    v = _u32_mod(sparse, vocab_range)
    v = v.reshape(n_tiles, row_block, n_cols).transpose(0, 2, 1)
    if row_bits is not None:
        # one int32 key; sorted, duplicates lie earliest row first
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 2)
        entries = (v << row_bits) | row
        if n_slabs > 1:
            entries = jax.lax.sort(entries, dimension=2)
            v = entries >> row_bits
    else:
        p = jnp.broadcast_to(pos.reshape(n_tiles, 1, row_block), v.shape)
        if n_slabs > 1:
            v, p = jax.lax.sort((v, p), dimension=2, num_keys=1)
        entries, pos = v, p
    bounds = jnp.arange(n_slabs + 1, dtype=jnp.int32) * slab_range
    starts = jnp.sum(
        v[:, :, None, :] < bounds[:, None], axis=-1, dtype=jnp.int32
    ) + (jnp.arange(n_cols, dtype=jnp.int32) * row_block)[:, None]
    starts = starts.reshape(n_tiles, n_cols * (n_slabs + 1))
    pad = offsets_width(n_cols, n_slabs) - starts.shape[1]
    starts = jnp.pad(starts, ((0, 0), (0, pad)))
    return entries.reshape(-1), pos.reshape(-1), starts.reshape(-1)


def _fused_genvocab_slab_kernel(
    *refs,
    n_cols: int,
    n_slabs: int,
    slab_range: int,
    track_counts: bool,
    row_bits: int | None,
):
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
    lo = s * slab_range

    @pl.when(pl.program_id(1) == 0)
    def _init():  # first row tile of this slab: seed from the HBM block
        state_ref[...] = state_in_ref[...]
        if track_counts:
            counts_ref[...] = counts_in_ref[...]

    never = jnp.int32(vocab_lib.NEVER)

    # Each column's entries of this tile that fall in slab s form one
    # run of the tile's block: walk that run alone.
    for c in range(n_cols):

        def entry(j, _, c=c):
            k = entries_ref[j]
            if row_bits is None:
                local, p = k - lo, pos_ref[j]
            else:
                local = (k >> row_bits) - lo
                p = pos_ref[k & ((1 << row_bits) - 1)]
            # the FPGA's II=2 RMW, streamed slab by slab
            _rmw(state_ref, c, local, lambda w: jnp.minimum(w, p))
            if track_counts:
                # p == NEVER marks padding/invalid/past-ceiling rows —
                # they drop from the counts exactly as from the state.
                inc = jnp.where(p != never, 1, 0)
                _rmw(counts_ref, c, local, lambda w: w + inc)
            return 0

        base = c * (n_slabs + 1) + s
        jax.lax.fori_loop(off_ref[base], off_ref[base + 1], entry, 0)


@functools.partial(
    jax.jit,
    static_argnames=("slab_range", "vocab_range", "row_block", "interpret"),
    donate_argnums=(0, 1),
)
def fused_genvocab_slabs(
    state: jnp.ndarray,
    counts: jnp.ndarray | None,
    sparse: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    slab_range: int,
    vocab_range: int,
    row_block: int = 1024,
    interpret: bool = True,
):
    """Whole loop-① chain at any ``vocab_range`` — ONE dispatch, the
    HBM-resident state streamed through VMEM slab by slab.

    state     int32 [n_cols, padded_range] — first_pos, padded to a
              ``slab_range`` multiple (pad entries NEVER; ops.py slices)
    counts    int32 [n_cols, padded_range] occurrence counts, or None
    sparse    int32 [rows, n_cols] (raw hash bitcasts, pre-modulus)
    pos       int32 [rows] global positions (``vocab.NEVER`` for
              padding/invalid rows)
    slab_range — a multiple of :data:`LANES`
    vocab_range — the TRUE modulus range (≤ padded_range)
    → (updated first_pos, updated counts | None), same padded shapes.

    A pre-pass in this same program (:func:`_entries_by_slab`) reduces
    the entries and, with more than one slab, buckets each row tile's
    entries by slab; grid step ``(s, t)`` walks only tile ``t``'s
    entries in slab ``s``.

    ``state`` (and ``counts``) are donated-into: each slab block is
    aliased input→output, the same in-place convention as
    :func:`fused_genvocab`. ``row_block`` as there.
    """
    n_cols, padded_range = state.shape
    if slab_range % LANES:
        raise ValueError(f"slab_range ({slab_range}) must divide by {LANES}")
    if padded_range % slab_range:
        raise ValueError(
            f"state width ({padded_range}) must divide by slab_range "
            f"({slab_range}); ops.py pads"
        )
    if not 0 < vocab_range <= padded_range:
        raise ValueError(f"vocab_range {vocab_range} vs padded {padded_range}")
    n_slabs = padded_range // slab_range
    rows = sparse.shape[0]
    if pos.shape != (rows,):
        raise ValueError(f"pos shape {pos.shape} != {(rows,)}")
    track_counts = counts is not None
    row_bits = key_row_bits(padded_range, row_block)
    entries, pos_in, offsets = _entries_by_slab(
        sparse,
        pos,
        vocab_range=vocab_range,
        slab_range=slab_range,
        n_slabs=n_slabs,
        row_block=row_block,
        row_bits=row_bits,
    )

    def tile(s, r):  # the row tile's SMEM blocks, whatever the slab
        return (r,)

    in_specs = _smem_specs(rows, n_cols, row_block, tile)
    if row_bits is None:  # positions laid out beside the values
        in_specs[1] = _smem_block(row_block * n_cols, tile)
    in_specs.append(_smem_block(offsets_width(n_cols, n_slabs), tile))
    slab_spec = pl.BlockSpec((n_cols, slab_range), lambda s, r: (0, s))
    in_specs.append(slab_spec)
    out_shape = [jax.ShapeDtypeStruct((n_cols, padded_range), jnp.int32)]
    operands = [entries, pos_in, offsets, state]
    aliases = {3: 0}
    if track_counts:
        in_specs.append(slab_spec)
        out_shape.append(
            jax.ShapeDtypeStruct((n_cols, padded_range), jnp.int32)
        )
        operands.append(counts)
        aliases[4] = 1
    out = pl.pallas_call(
        functools.partial(
            _fused_genvocab_slab_kernel,
            n_cols=n_cols,
            n_slabs=n_slabs,
            slab_range=slab_range,
            track_counts=track_counts,
            row_bits=row_bits,
        ),
        grid=(n_slabs, rows // row_block),
        in_specs=in_specs,
        out_specs=[slab_spec] * len(out_shape),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_compiler_params(len(out_shape), n_cols, slab_range),
        interpret=interpret,
    )(*operands)
    if track_counts:
        return out[0], out[1]
    return out[0], None
