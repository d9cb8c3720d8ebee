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
    Pallas pipeline double-buffers the slab DMAs against compute.
    Entries whose modded value falls outside the current slab are
    skipped by a scalar branch, so loop ① stays ONE fused dispatch at
    ANY ``vocab_range``.

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
    both as SMEM blocks. Mosaic cannot read a scalar out of a vector
    (``modded[i, c]`` lowers to ``dynamic_slice``), and a ``(1,
    row_block)`` VMEM block of positions breaks the (8, 128) tiling;
  * the modulus runs on the scalar in int32 arithmetic (Mosaic has no
    scalar bitcast to uint32);
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


def _smem_specs(rows: int, n_cols: int, row_block: int, index_map):
    """SMEM blocks of the flattened sparse tile and of the positions."""
    if rows % row_block:
        raise ValueError(f"rows ({rows}) must divide by row_block ({row_block})")
    return [
        pl.BlockSpec(
            (row_block * n_cols,), index_map, memory_space=pltpu.SMEM
        ),
        pl.BlockSpec((row_block,), index_map, memory_space=pltpu.SMEM),
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


def _fused_genvocab_slab_kernel(
    *refs, n_cols: int, vocab_range: int, slab_range: int, track_counts: bool
):
    # grid = (n_slabs, n_row_tiles), slab index outermost: for a fixed
    # slab the row-tile dim iterates innermost, so the slab's state (and
    # count) block — index map constant over that inner dim — stays
    # resident in VMEM across the whole chunk and is written back to HBM
    # once, when the slab index advances.
    if track_counts:
        (sparse_ref, pos_ref, state_in_ref, counts_in_ref,
         state_ref, counts_ref) = refs
    else:
        sparse_ref, pos_ref, state_in_ref, state_ref = refs
        counts_in_ref = counts_ref = None
    lo = pl.program_id(0) * slab_range

    @pl.when(pl.program_id(1) == 0)
    def _init():  # first row tile of this slab: seed from the HBM block
        state_ref[...] = state_in_ref[...]
        if track_counts:
            counts_ref[...] = counts_in_ref[...]

    never = jnp.int32(vocab_lib.NEVER)

    def row_body(i, _):
        p = pos_ref[i]
        for c in range(n_cols):
            # Modulus by the TRUE vocab_range (the state may be padded to
            # a slab multiple; the pad region is never a target).
            local = _u32_mod(sparse_ref[i * n_cols + c], vocab_range) - lo

            @pl.when((local >= 0) & (local < slab_range))
            def _hit(c=c, local=local):
                # the FPGA's II=2 RMW, streamed slab by slab
                _rmw(state_ref, c, local, lambda w: jnp.minimum(w, p))
                if track_counts:
                    # p == NEVER marks padding/invalid/past-ceiling rows —
                    # they drop from the counts exactly as from the state.
                    inc = jnp.where(p != never, 1, 0)
                    _rmw(counts_ref, c, local, lambda w: w + inc)

        return 0

    jax.lax.fori_loop(0, pos_ref.shape[0], row_body, 0)


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
    slab_spec = pl.BlockSpec((n_cols, slab_range), lambda s, r: (0, s))
    in_specs = _smem_specs(rows, n_cols, row_block, lambda s, r: (r,)) + [
        slab_spec
    ]
    out_shape = [jax.ShapeDtypeStruct((n_cols, padded_range), jnp.int32)]
    operands = [sparse.reshape(-1), pos, state]
    aliases = {2: 0}
    if track_counts:
        in_specs.append(slab_spec)
        out_shape.append(
            jax.ShapeDtypeStruct((n_cols, padded_range), jnp.int32)
        )
        operands.append(counts)
        aliases[3] = 1
    out = pl.pallas_call(
        functools.partial(
            _fused_genvocab_slab_kernel,
            n_cols=n_cols,
            vocab_range=vocab_range,
            slab_range=slab_range,
            track_counts=track_counts,
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
