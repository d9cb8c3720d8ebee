"""Pallas TPU kernel: parallel UTF-8 tabular decode (PIPER §3.3, Script 1).

Hardware mapping
----------------
The FPGA unit consumes a 4-byte window per cycle with a carried 32-bit
value register. The TPU kernel widens the window to a whole VMEM tile
(``BLOCK`` bytes) per grid step:

  * per-byte classification (delimiter / minus / digit+base) — VPU lanes
  * delimiter counting and the value recurrence ``v ← v·base + d`` — a
    log₂(BLOCK)-step Hillis–Steele *segmented affine scan* in registers
    (the affine maps ``x ↦ m·x + a`` compose associatively; delimiters
    reset segments)
  * the FPGA's carried register becomes an SMEM carry ``(m, a, neg,
    ndelim)`` propagated across the sequential TPU grid — identical
    algebra, so output is bit-identical to the byte-serial machine.

Restriction vs. the jnp reference: the kernel assumes the *contiguous*
column layout (decimal fields first, hex fields from ``hex_start``) so
the per-byte base is a lane comparison instead of a VMEM gather — true
for the paper's Criteo schema and anything `TableSchema` expresses.

The kernel emits per-byte ``(completed value, delimiter ordinal,
is-delimiter)``; the jit'd wrapper (ops.py) performs the final scatter
into the ``[rows, fields]`` table (the paper's StoreData stage, an XLA
scatter that is negligible next to the byte stream).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import schema as schema_lib

# Bytes per grid step: 16 int32 VREG rows of 128 lanes.
BLOCK = 2048


def _shift_right(x: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """Shift a [1, B] row right by d lanes, filling with ``fill``."""
    return jnp.concatenate(
        [jnp.full((x.shape[0], d), fill, x.dtype), x[:, :-d]], axis=1
    )


def _segmented_scan(m, a, neg, rst):
    """Inclusive Hillis–Steele segmented scan of affine elements.

    combine(L, R) = R (value part)                      if R.reset
                  = (L.m·R.m, L.a·R.m + R.a, L.neg|R.neg) otherwise
    reset part is always L.reset|R.reset.
    """
    width = m.shape[1]
    d = 1
    while d < width:
        lm = _shift_right(m, d, 1)
        la = _shift_right(a, d, 0)
        lneg = _shift_right(neg, d, 0)
        lrst = _shift_right(rst, d, 0)
        blocked = rst == 1
        new_m = jnp.where(blocked, m, lm * m)
        new_a = jnp.where(blocked, a, la * m + a)
        new_neg = jnp.where(blocked, neg, lneg | neg)
        new_rst = rst | lrst
        m, a, neg, rst = new_m, new_a, new_neg, new_rst
        d *= 2
    return m, a, neg, rst


def _cumsum_incl(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive Hillis–Steele cumsum along lanes of a [1, B] row."""
    width = x.shape[1]
    d = 1
    while d < width:
        x = x + _shift_right(x, d, 0)
        d *= 2
    return x


def init_carry(carry_ref) -> None:
    """Seed the SMEM carry ``(m, a, neg, ndelim)`` with the scan identity.

    Shared by every kernel that embeds :func:`decode_block` — call it
    under ``@pl.when(pl.program_id(0) == 0)`` before the first block.
    """
    carry_ref[0] = 1  # m: identity affine map
    carry_ref[1] = 0  # a
    carry_ref[2] = 0  # neg
    carry_ref[3] = 0  # ndelim


def decode_block(b, carry_ref, *, n_fields: int, hex_start: int):
    """One block of the segmented-scan byte decode, carry threaded in SMEM.

    The reusable core of ``_decode_kernel`` — the per-byte classifier
    (delimiter / minus / digit+base), the Hillis–Steele segmented affine
    scan, and the cross-block carry fold. The bytes-in fused kernels
    (kernels/fused_decode_vocab, kernels/fused_decode_xform) embed this
    same block step so their decode half is the *identical* computation,
    not a reimplementation.

    Args:
      b: int32 [1, block] — the block's bytes, widened.
      carry_ref: int32 [4] SMEM — ``(m, a, neg, ndelim)``; read at entry,
        **updated in place** to the carry for the next block.

    Returns:
      (value, ordinal, isdelim) — int32 [1, block] each: the completed
      field value at delimiter lanes (0 elsewhere), the global delimiter
      ordinal, and the delimiter mask.
    """
    is_delim = jnp.logical_or(b == schema_lib.TAB, b == schema_lib.NEWLINE)
    is_minus = b == schema_lib.MINUS
    is_dec = jnp.logical_and(b >= schema_lib.BYTE_0, b <= schema_lib.BYTE_9)
    is_hexa = jnp.logical_and(
        b >= schema_lib.BYTE_A_LOWER, b <= schema_lib.BYTE_F_LOWER
    )
    is_digit = jnp.logical_or(is_dec, is_hexa)
    digit = jnp.where(is_dec, b - schema_lib.BYTE_0, 0) + jnp.where(
        is_hexa, b - schema_lib.BYTE_A_LOWER + 10, 0
    )

    delim_i32 = is_delim.astype(jnp.int32)
    incl = _cumsum_incl(delim_i32)
    excl_local = incl - delim_i32
    carry_nd = carry_ref[3]
    excl_global = excl_local + carry_nd

    # Contiguous layout: fields [hex_start, n_fields) are hexadecimal.
    field_idx = jax.lax.rem(excl_global, n_fields)
    base = jnp.where(field_idx >= hex_start, 16, 10)

    one = jnp.ones_like(b)
    zero = jnp.zeros_like(b)
    m0 = jnp.where(is_digit, base, one)
    a0 = jnp.where(is_digit, digit, zero)
    neg0 = is_minus.astype(jnp.int32)
    rst0 = delim_i32

    m, a, neg, rst = _segmented_scan(m0, a0, neg0, rst0)

    # Fold in the cross-block carry: combine(carry, scanned_i).
    c_m, c_a, c_neg = carry_ref[0], carry_ref[1], carry_ref[2]
    blocked = rst == 1
    g_m = jnp.where(blocked, m, c_m * m)
    g_a = jnp.where(blocked, a, c_a * m + a)
    g_neg = jnp.where(blocked, neg, c_neg | neg)

    # Completed value at a delimiter = signed accumulated value of the byte
    # just before it; the first byte's "previous" is the incoming carry.
    prev_a = _shift_right(g_a, 1, 0).at[0, 0].set(c_a)
    prev_neg = _shift_right(g_neg, 1, 0).at[0, 0].set(c_neg)
    value = jnp.where(prev_neg == 1, -prev_a, prev_a)

    # New carry = combine(carry, block_total) = last global element.
    carry_ref[0] = g_m[0, -1]
    carry_ref[1] = g_a[0, -1]
    carry_ref[2] = g_neg[0, -1]
    carry_ref[3] = carry_nd + incl[0, -1]

    return jnp.where(is_delim, value, 0), excl_global, delim_i32


def _decode_kernel(
    bytes_ref,      # uint8 [1, BLOCK] VMEM
    value_ref,      # int32 [1, BLOCK] VMEM out: completed field values
    ordinal_ref,    # int32 [1, BLOCK] VMEM out: global delimiter ordinal
    isdelim_ref,    # int32 [1, BLOCK] VMEM out
    carry_ref,      # int32 [4] SMEM scratch: (m, a, neg, ndelim)
    *,
    n_fields: int,
    hex_start: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        init_carry(carry_ref)

    b = bytes_ref[...].astype(jnp.int32)
    value, ordinal, isdelim = decode_block(
        b, carry_ref, n_fields=n_fields, hex_start=hex_start
    )
    value_ref[...] = value
    ordinal_ref[...] = ordinal
    isdelim_ref[...] = isdelim


@functools.partial(
    jax.jit, static_argnames=("n_fields", "hex_start", "interpret", "block")
)
def decode_scan(
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    hex_start: int,
    interpret: bool = True,
    block: int = BLOCK,
):
    """Run the decode kernel over a padded byte buffer.

    Returns per-byte (value, ordinal, is_delim) — int32 [B] each.
    ``interpret=True`` runs the Pallas interpreter (the CPU); on a TPU
    pass False for the Mosaic path (``repro.kernels.interpret()``).
    """
    n = byte_buf.shape[0]
    if n % block:
        raise ValueError(f"buffer ({n}) must be a multiple of block ({block})")
    rows = n // block
    buf2d = byte_buf.reshape(rows, block)

    out_shape = [
        jax.ShapeDtypeStruct((rows, block), jnp.int32),  # value
        jax.ShapeDtypeStruct((rows, block), jnp.int32),  # ordinal
        jax.ShapeDtypeStruct((rows, block), jnp.int32),  # is_delim
    ]
    kernel = functools.partial(
        _decode_kernel, n_fields=n_fields, hex_start=hex_start
    )
    value, ordinal, isdelim = pl.pallas_call(
        kernel,
        grid=(rows,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        interpret=interpret,
    )(buf2d)
    return value.reshape(n), ordinal.reshape(n), isdelim.reshape(n)
