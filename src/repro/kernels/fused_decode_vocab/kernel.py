"""Pallas TPU kernel: bytes-in → vocab-delta — the WHOLE loop ① in one pass.

PR 5 fused loop ①'s compute chain (Modulus → GenVocab scatter-min) into
one dispatch, but the chunk still entered it as a decoded ``[rows,
n_cols]`` matrix: ``decode_utf8`` ran as a standalone dispatch whose
field table round-tripped HBM before the fused kernel consumed it — the
last materialization the paper's dataflow forbids (fig. 10 counts decode
*inside* the accelerated pipeline). This kernel closes that gap:

``fused_decode_genvocab_kernel`` (VMEM tier)
    One grid step per ``BLOCK``-byte tile of the raw UTF-8 chunk. Each
    step runs the *identical* segmented-scan byte decode as the
    standalone kernel — :func:`repro.kernels.decode_utf8.kernel.
    decode_block`, shared code, same SMEM ``(m, a, neg, ndelim)`` carry —
    then, instead of materializing per-byte values for a later scatter,
    reduces each completed sparse field modulo ``vocab_range`` and
    scatter-mins its global row position straight into the
    :class:`~repro.core.vocab.VocabState` ``first_pos`` accumulator. The
    state uses the same **constant index map + input/output alias**
    machinery as ``kernels/fused_vocab``: DMA'd into VMEM once at the
    first grid step, resident and carried across every byte tile of the
    call. A UTF-8 chunk therefore touches HBM exactly once (the byte
    read); no decoded table, no modded matrix, ever exists off-chip.

    The scatter is **branch-free**: every byte lane computes a target
    ``(column, value, position)`` triple, with non-delimiter lanes, dense
    /label fields, and out-of-range rows all mapped to position
    ``NEVER`` — the identity of min — so the serial II=2 read-modify-
    write loop (the FPGA's dictionary port) needs no per-lane
    conditionals and the result is bit-identical to decode → Modulus →
    XLA scatter-min in any lane order.

HBM tier (state stack over the residency budget) — no bytes-in kernel:
the wrapper (ops.py) falls back to the reference decode + the tier-
routed ``fused_vocab`` chain, which itself degrades to the XLA oracle.

Like every kernel package here, ``interpret=True`` on CPU (tier-1 CI
exercises the logic without accelerator hardware) and compiled Mosaic on
a TPU backend (ops.py switches per backend). Mosaic refuses this kernel
as written: its ``(1, block)`` byte-tile block breaks the (8, 128)
tiling (compiled for a described v5e; ROADMAP speed item 2). That is
why ``PipelineConfig.use_fused_decode=None`` resolves to *off* on every
backend and this path is opt-in via ``True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import vocab as vocab_lib
from repro.kernels.decode_utf8 import kernel as decode_kernel

BLOCK = decode_kernel.BLOCK


def _fused_decode_genvocab_kernel(
    bytes_ref,      # uint8 [1, BLOCK] VMEM — raw UTF-8 tile
    limits_ref,     # int32 [2] SMEM — (capped row count, global row offset)
    state_in_ref,   # int32 [n_cols, vocab_range] — prior first_pos (aliased)
    state_ref,      # int32 [n_cols, vocab_range] — accumulator, constant
    #                 index map: resident in VMEM, carried across byte tiles
    carry_ref,      # int32 [4] SMEM scratch: decode carry (m, a, neg, ndelim)
    *,
    n_fields: int,
    hex_start: int,
    vocab_range: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():  # first tile: decode identity + seed the accumulator
        decode_kernel.init_carry(carry_ref)
        state_ref[...] = state_in_ref[...]

    b = bytes_ref[...].astype(jnp.int32)
    value, ordinal, isdelim = decode_kernel.decode_block(
        b, carry_ref, n_fields=n_fields, hex_start=hex_start
    )

    n_rows = limits_ref[0]      # already min(newlines, max_rows) — ops.py
    row_offset = limits_ref[1]  # state.rows_seen at chunk entry
    row = ordinal // n_fields
    col = ordinal - row * n_fields
    n_cols = n_fields - hex_start

    # Branch-free scatter triple per byte lane. Dead lanes (non-delimiter,
    # label/dense fields, truncated or overflow rows) carry pos = NEVER —
    # min's identity — so the RMW below is unconditional. Position
    # arithmetic runs in uint32 saturated at NEVER (vocab.positions'
    # convention): offsets near the int32 ceiling drop rows instead of
    # wrapping negative or aliasing the sentinel.
    is_vocab = (isdelim == 1) & (col >= hex_start) & (row < n_rows)
    pos_sat = jnp.minimum(
        row_offset.astype(jnp.uint32) + row.astype(jnp.uint32),
        jnp.uint32(vocab_lib.NEVER),
    ).astype(jnp.int32)
    pos = jnp.where(is_vocab, pos_sat, vocab_lib.NEVER)
    c = jnp.clip(col - hex_start, 0, n_cols - 1)
    u = jax.lax.bitcast_convert_type(value, jnp.uint32)
    v = (u % jnp.uint32(vocab_range)).astype(jnp.int32)

    def body(i, _):
        ci = c[0, i]
        vi = v[0, i]
        cur = state_ref[ci, vi]
        state_ref[ci, vi] = jnp.minimum(cur, pos[0, i])  # the FPGA's II=2 RMW
        return 0

    jax.lax.fori_loop(0, b.shape[1], body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("n_fields", "hex_start", "vocab_range", "interpret", "block"),
    donate_argnums=(0,),
)
def fused_decode_genvocab(
    first_pos: jnp.ndarray,
    byte_buf: jnp.ndarray,
    limits: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    vocab_range: int | None = None,
    interpret: bool = True,
    block: int = BLOCK,
) -> jnp.ndarray:
    """Bytes-in loop ① — decode → Modulus → scatter-min, state in VMEM.

    first_pos int32 [n_fields - hex_start, width] — the accumulator
    vocab_range — the modulus (``width`` when None; a wider state's
              lanes past it are padding)
    byte_buf  uint8 [B] — whole rows + zero padding; B must divide by
              ``block`` (ops.py pads; zero bytes are inert to the decode)
    limits    int32 [2] — (min(row count, max_rows), global row offset)
    → updated first_pos (``rows_seen`` advances in the wrapper).

    The buffer is donated-into: ``first_pos`` is aliased to the output,
    the same in-place convention as ``fused_vocab.fused_genvocab``.
    """
    n_cols, width = first_pos.shape
    vocab_range = vocab_range or width
    n = byte_buf.shape[0]
    if n % block:
        raise ValueError(f"buffer ({n}) must be a multiple of block ({block})")
    n_blocks = n // block
    buf2d = byte_buf.reshape(n_blocks, block)
    return pl.pallas_call(
        functools.partial(
            _fused_decode_genvocab_kernel,
            n_fields=n_fields,
            hex_start=hex_start,
            vocab_range=vocab_range,
        ),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n_cols, width), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_cols, width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cols, width), jnp.int32),
        scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(buf2d, limits, first_pos)
