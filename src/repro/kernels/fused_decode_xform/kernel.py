"""Pallas TPU kernel: bytes-in → dense features — the WHOLE loop ② in one pass.

The loop-② counterpart of ``kernels/fused_decode_vocab``: PR 3 fused the
compute chain (Modulus → ApplyVocab ∥ Neg2Zero → Logarithm) into one
dispatch, but its input was still a decoded field table that a separate
``decode_utf8`` dispatch had materialized to HBM. This kernel consumes
the raw UTF-8 chunk directly:

``fused_decode_transform_kernel`` (VMEM tier)
    One grid step per ``BLOCK``-byte tile. Each step runs the *shared*
    segmented-scan byte decode (:func:`repro.kernels.decode_utf8.kernel.
    decode_block` — identical code and SMEM carry as the standalone
    kernel), then transforms every completed field **in place of the
    StoreData scatter**: label fields store raw, dense (decimal) fields
    store the f32 bits of ``log1p(max(v, 0))``, and sparse (hex) fields
    store the vocabulary ordinal ``table[c, u32(v) % range]`` — a VMEM
    gather against the vocabulary stack, which uses a constant index map
    (DMA'd on-chip once, resident for the whole call, the FPGA's SRAM
    dictionaries). The accumulated output table ``[max_rows + 1,
    n_fields]`` is itself a constant-index-map output carried in VMEM
    across byte tiles; row ``max_rows`` is the **trash row** — the
    kernel's branch-free replica of the reference scatter's
    ``mode="drop"``: non-delimiter lanes and overflow rows write there
    unconditionally, so the serial store loop needs no conditionals.

    At the first grid step the table is seeded with the *transform of a
    zero field* per column (0 raw, ``log1p(0)`` bits, ``table[c, 0]``) —
    exactly what decode-then-transform produces for never-written
    padding cells — which is what makes the kernel bit-identical to the
    unfused composition on **all** ``max_rows`` rows, valid or not.

HBM tier (vocab stack + output table over the 8 MiB residency budget) —
no bytes-in kernel: the wrapper (ops.py) falls back to the reference
decode + the tier-routed ``fused_xform`` chain.

``interpret=True`` on CPU (the repo-wide CI convention), compiled Mosaic
on TPU (ops.py switches). Mosaic refuses this kernel as written: its
``(1, block)`` byte-tile block breaks the (8, 128) tiling (compiled for
a described v5e; ROADMAP speed item 2). That is why
``PipelineConfig.use_fused_decode=None`` resolves to *off* on every
backend and this path is opt-in via ``True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import schema as schema_lib
from repro.kernels.decode_utf8 import kernel as decode_kernel

BLOCK = decode_kernel.BLOCK


def _fused_decode_transform_kernel(
    bytes_ref,   # uint8 [1, BLOCK] VMEM — raw UTF-8 tile
    table_ref,   # int32 [n_sparse, vocab_range] VMEM-resident vocabulary
    out_ref,     # int32 [max_rows + 1, n_fields] — accumulated output
    #              (constant index map; row max_rows is the trash row)
    carry_ref,   # int32 [4] SMEM scratch: decode carry (m, a, neg, ndelim)
    *,
    n_fields: int,
    hex_start: int,
    vocab_range: int,
    max_rows: int,
):
    n_sparse = n_fields - hex_start

    @pl.when(pl.program_id(0) == 0)
    def _init():
        decode_kernel.init_carry(carry_ref)
        # Seed every cell with the transform of a zero field — what the
        # reference chain leaves in never-written cells: label/dense 0
        # (log1p(0) bits == 0), sparse table[c, 0] (u32(0) % V == 0).
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, n_fields), 1)
        c0 = jnp.clip(col_ids - hex_start, 0, n_sparse - 1)
        sparse_default = table_ref[...][:, 0][c0[0]][None, :]
        default_row = jnp.where(col_ids >= hex_start, sparse_default, 0)
        out_ref[...] = jnp.broadcast_to(default_row, (max_rows + 1, n_fields))

    b = bytes_ref[...].astype(jnp.int32)
    value, ordinal, isdelim = decode_kernel.decode_block(
        b, carry_ref, n_fields=n_fields, hex_start=hex_start
    )

    row = ordinal // n_fields
    col = ordinal - row * n_fields
    # Trash row = the reference scatter's mode="drop": non-delimiter lanes
    # and rows past the capacity land on row max_rows, sliced off by ops.py.
    row_t = jnp.where(isdelim == 1, jnp.minimum(row, max_rows), max_rows)
    c = jnp.clip(col - hex_start, 0, n_sparse - 1)
    u = jax.lax.bitcast_convert_type(value, jnp.uint32)
    v = (u % jnp.uint32(vocab_range)).astype(jnp.int32)
    # Neg2Zero + Logarithm on every lane (vector pass); stored as f32 bits
    # in the int32 table, bitcast back by the wrapper.
    dense_bits = jax.lax.bitcast_convert_type(
        jnp.log1p(jnp.maximum(value.astype(jnp.float32), 0.0)), jnp.int32
    )

    def body(i, _):
        cc = col[0, i]
        gathered = table_ref[c[0, i], v[0, i]]  # the FPGA's II=2 SRAM read
        val = jnp.where(
            cc == 0,
            value[0, i],
            jnp.where(cc < hex_start, dense_bits[0, i], gathered),
        )
        out_ref[row_t[0, i], cc] = val
        return 0

    jax.lax.fori_loop(0, b.shape[1], body, 0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_fields", "hex_start", "max_rows", "vocab_range", "interpret", "block"
    ),
)
def fused_decode_transform(
    table: jnp.ndarray,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    max_rows: int,
    vocab_range: int | None = None,
    interpret: bool = True,
    block: int = BLOCK,
):
    """Bytes-in loop ② — decode → Modulus → ApplyVocab ∥ Neg2Zero+Log1p.

    table    int32 [n_fields - hex_start, width] — finalized vocab
    vocab_range — the modulus (``width`` when None)
    byte_buf uint8 [B] — whole rows + zero padding; B must divide by
             ``block`` (ops.py pads; zero bytes are inert)
    → (label int32 [max_rows], dense f32 [max_rows, hex_start - 1],
       ids int32 [max_rows, n_sparse], valid bool [max_rows]) — exactly
    ``ref.decode_bytes`` + the loop-② transform, padding rows included.
    """
    n_sparse, width = table.shape
    vocab_range = vocab_range or width
    n = byte_buf.shape[0]
    if n % block:
        raise ValueError(f"buffer ({n}) must be a multiple of block ({block})")
    n_blocks = n // block
    buf2d = byte_buf.reshape(n_blocks, block)
    out = pl.pallas_call(
        functools.partial(
            _fused_decode_transform_kernel,
            n_fields=n_fields,
            hex_start=hex_start,
            vocab_range=vocab_range,
            max_rows=max_rows,
        ),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((n_sparse, width), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((max_rows + 1, n_fields), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((max_rows + 1, n_fields), jnp.int32),
        scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        interpret=interpret,
    )(buf2d, table)
    label = out[:max_rows, 0]
    dense = jax.lax.bitcast_convert_type(
        out[:max_rows, 1:hex_start], jnp.float32
    )
    ids = out[:max_rows, hex_start:]
    n_rows = jnp.sum((byte_buf == schema_lib.NEWLINE).astype(jnp.int32))
    valid = jnp.arange(max_rows) < n_rows
    return label, dense, ids, valid
