"""Stateless PIPER operators (paper Table 1) + memory-tier dispatch.

Each operator is a pure jnp function; ``apply_vocab``/``dense_transform``
optionally dispatch to the Pallas kernels (kernels/vocab,
kernels/dense_xform) following the paper's SRAM-vs-HBM placement policy,
``fused_transform`` collapses the whole loop-② chain into one dispatch
(kernels/fused_xform — Piper's on-chip dataflow), and
``fused_vocab_update`` does the same for loop ①'s Modulus → GenVocab
scatter-min (kernels/fused_vocab).
``Decode`` and ``FillMissing`` live in kernels/decode_utf8 (FillMissing is
folded into Decode, as on the FPGA). ``Hex2Int`` needs no explicit op —
the decoder already produces integers, mirroring the paper's observation
that "the FPGA handles bits directly".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import vocab as vocab_lib


def positive_modulus(sparse: jnp.ndarray, vocab_range) -> jnp.ndarray:
    """Modulus: map unsigned 32-bit hashes into [0, vocab_range).

    The decoder stores hashes as int32 bitcasts; the modulus is defined on
    the uint32 value (sparse features "are always positive", paper §3.2).
    ``vocab_range`` is one int, or one range per column of ``sparse``
    (``[rows, n_cols]``).
    """
    u = jax.lax.bitcast_convert_type(sparse, jnp.uint32)
    if isinstance(vocab_range, (tuple, list)):
        ranges = tuple(int(r) for r in vocab_range)
        if len(set(ranges)) > 1:
            return (u % jnp.asarray(ranges, jnp.uint32)[None, :]).astype(jnp.int32)
        vocab_range = ranges[0] if ranges else 1
    return (u % jnp.uint32(vocab_range)).astype(jnp.int32)


def neg2zero(dense: jnp.ndarray) -> jnp.ndarray:
    """Neg2Zero: clamp negative dense features to zero (ternary op)."""
    return jnp.maximum(dense, 0)


def logarithm(dense: jnp.ndarray) -> jnp.ndarray:
    """Logarithm: log(x+1) on dense features."""
    return jnp.log1p(dense.astype(jnp.float32))


def clip(dense: jnp.ndarray, lo: float, hi: float) -> jnp.ndarray:
    """Clip: clamp dense features to ``[lo, hi]`` (f32)."""
    return jnp.clip(dense.astype(jnp.float32), lo, hi)


def minmax_scale(dense: jnp.ndarray, lo: float, hi: float) -> jnp.ndarray:
    """MinMaxScale: clip to ``[lo, hi]``, rescale to ``[0, 1]``."""
    return (clip(dense, lo, hi) - lo) / (hi - lo)


def bucketize(dense: jnp.ndarray, boundaries: tuple[float, ...]) -> jnp.ndarray:
    """Bucketize: value → f32 bucket index against strictly-increasing
    static ``boundaries``; ``x == boundary`` lands in the upper bucket
    (``side="right"``), so indices span ``[0, len(boundaries)]``."""
    edges = jnp.asarray(boundaries, jnp.float32)
    idx = jnp.searchsorted(edges, dense.astype(jnp.float32), side="right")
    return idx.astype(jnp.float32)


def hash_cross(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """HashCross: mix two raw sparse hash columns into one synthetic column.

    Murmur3-style finalizer on the uint32 views (the decoder stores hashes
    as int32 bitcasts, like ``positive_modulus``): multiply-rotate-xor so
    the cross distributes over the modulus range even when the inputs
    share low bits. Returns the int32 bitcast of the mixed uint32, i.e. a
    raw hash column shaped exactly like a decoded sparse column — feed it
    ``Modulus → GenVocab → ApplyVocab`` like any other.
    """
    ua = jax.lax.bitcast_convert_type(a, jnp.uint32)
    ub = jax.lax.bitcast_convert_type(b, jnp.uint32)
    h = ua * jnp.uint32(0x85EBCA6B)
    h = h ^ ((ub << jnp.uint32(13)) | (ub >> jnp.uint32(19)))  # rotl(b, 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return jax.lax.bitcast_convert_type(h, jnp.int32)


def dense_transform(dense: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
    """Fused Neg2Zero + Logarithm (one VMEM pass on TPU)."""
    if use_kernel:
        from repro.kernels.dense_xform import ops as dx_ops

        return dx_ops.dense_transform(dense)
    return logarithm(neg2zero(dense.astype(jnp.float32)))


def fused_transform(
    vocab: vocab_lib.Vocabulary,
    sparse: jnp.ndarray,
    dense: jnp.ndarray,
    use_kernel: bool = True,
    columns: tuple[int, ...] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Whole loop-② chain — Modulus → ApplyVocab ∥ Neg2Zero → Logarithm —
    as ONE dispatch (paper §3.2/§4.4: the row streams through the entire
    operator graph on-chip, no per-op materialization).

    With ``use_kernel`` the chain runs through the fused Pallas kernel
    (kernels/fused_xform), tier-routed: tables within the VMEM budget
    stay resident on-chip for the whole call; larger tables fall back to
    a fused modulus+dense pass plus an XLA gather. Without it, the
    unfused ops above compose — same results (ids bit-identical, dense
    identical formula), used as the differential oracle.

    sparse int32 [rows, k] (raw hash bitcasts); dense [rows, n_dense]
    → (ids int32 [rows, k], dense float32 [rows, n_dense]). Column ``j``
    of ``sparse`` maps through the vocabulary's column ``columns[j]``
    (default: column ``j``).
    """
    if use_kernel:
        from repro.kernels.fused_xform import ops as fx_ops

        return fx_ops.fused_transform(vocab, sparse, dense, columns)
    modded = positive_modulus(sparse, column_ranges(vocab.ranges, columns))
    return apply_vocab(vocab, modded, columns=columns), dense_transform(dense)


def column_ranges(ranges: tuple[int, ...], columns: tuple[int, ...] | None):
    """The ranges of ``columns`` (all of them when None)."""
    return ranges if columns is None else tuple(ranges[c] for c in columns)


def fused_vocab_update(
    state: vocab_lib.VocabState,
    sparse: jnp.ndarray,
    valid: jnp.ndarray,
    use_kernel: bool = True,
    *,
    slab_range: int | None = None,
) -> vocab_lib.VocabState:
    """Whole loop-① chain — Modulus → GenVocab scatter-min — as ONE
    dispatch (paper §3.2/§4.4: the row streams through the operator
    graph on-chip; the modded matrix never round-trips HBM between the
    modulus and the state update).

    With ``use_kernel`` the chain runs through the fused Pallas kernel
    (kernels/fused_vocab), tier-routed: state stacks within the VMEM
    budget stay resident on-chip across row tiles; larger stacks stream
    HBM-resident slabs through VMEM (one dispatch either way); only
    degenerate widths fall back to the XLA modulus + scatter-min oracle.
    Without it, the unfused ops compose — **bit-identical** state either
    way (scatter-min is order-independent), used as the differential
    oracle. ``slab_range`` forces the slab tier with that per-column
    slab width (``PipelineConfig.vocab_slab_range``; None = tier policy
    decides).

    sparse int32 [rows, n_cols] (raw hash bitcasts); valid bool [rows]
    → the updated :class:`~repro.core.vocab.VocabState`. With
    ``use_kernel`` the input ``state`` is **consumed** (its buffer is
    donated for in-place accumulation on backends that honor donation);
    thread the returned state through instead of reusing the old one.
    """
    if use_kernel:
        from repro.kernels.fused_vocab import ops as fv_ops

        return fv_ops.fused_update(state, sparse, valid, slab_range=slab_range)
    modded = positive_modulus(sparse, state.layout.ranges)
    return vocab_lib.update(state, modded, valid)


def fused_decode_transform(
    vocab: vocab_lib.Vocabulary,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    n_dense: int,
    n_sparse: int,
    max_rows: int,
    use_kernel: bool = True,
):
    """The ENTIRE loop ② — Decode → Modulus → ApplyVocab ∥ Neg2Zero →
    Logarithm — as ONE dispatch from raw UTF-8 bytes (paper §3.3 + §3.2:
    decode is part of the accelerated dataflow; nothing materializes
    between it and the transforms).

    With ``use_kernel`` the chain runs through the bytes-in Pallas kernel
    (kernels/fused_decode_xform), tier-routed: vocabulary stack + output
    table within the VMEM budget stay resident on-chip for the whole
    call; otherwise the chunk decodes via the reference scan and takes
    the existing ``fused_transform`` chain. Without it, the unfused
    composition — reference decode + per-op chain — is the differential
    oracle. Sparse ids/labels bit-identical, dense identical-formula,
    padding rows included, on every path.

    byte_buf uint8 [B] — whole rows + zero padding, any length.
    → (label int32 [max_rows], dense f32 [max_rows, n_dense],
       ids int32 [max_rows, n_sparse], valid bool [max_rows]).
    """
    hex_start = 1 + n_dense
    if use_kernel:
        from repro.kernels.fused_decode_xform import ops as fdx_ops

        return fdx_ops.fused_decode_transform(
            vocab,
            byte_buf,
            n_fields=n_fields,
            hex_start=hex_start,
            max_rows=max_rows,
        )
    from repro.kernels.decode_utf8 import ref as decode_ref

    label, dense, sparse, valid = decode_ref.decode_bytes(
        byte_buf,
        jnp.arange(n_fields) >= hex_start,
        n_fields=n_fields,
        max_rows=max_rows,
        n_dense=n_dense,
        n_sparse=n_sparse,
    )
    modded = positive_modulus(sparse, vocab.ranges)
    return label, dense_transform(dense), apply_vocab(vocab, modded), valid


def fused_decode_vocab_update(
    state: vocab_lib.VocabState,
    byte_buf: jnp.ndarray,
    *,
    n_fields: int,
    n_dense: int,
    n_sparse: int,
    max_rows: int,
    use_kernel: bool = True,
) -> vocab_lib.VocabState:
    """The ENTIRE loop ① — Decode → Modulus → GenVocab scatter-min — as
    ONE dispatch from raw UTF-8 bytes (kernels/fused_decode_vocab),
    tier-routed like :func:`fused_vocab_update` with the same VMEM
    residency budget. Without ``use_kernel``, the unfused composition
    (reference decode + modulus + XLA scatter-min) is the oracle —
    **bit-identical** state either way.

    With ``use_kernel`` the input ``state`` is **consumed** (donated);
    thread the returned state through, as every engine's loop ① does.
    """
    hex_start = 1 + n_dense
    if use_kernel:
        from repro.kernels.fused_decode_vocab import ops as fdv_ops

        return fdv_ops.fused_decode_update(
            state,
            byte_buf,
            n_fields=n_fields,
            hex_start=hex_start,
            max_rows=max_rows,
        )
    from repro.kernels.decode_utf8 import ref as decode_ref

    _, _, sparse, valid = decode_ref.decode_bytes(
        byte_buf,
        jnp.arange(n_fields) >= hex_start,
        n_fields=n_fields,
        max_rows=max_rows,
        n_dense=n_dense,
        n_sparse=n_sparse,
    )
    modded = positive_modulus(sparse, state.layout.ranges)
    return vocab_lib.update(state, modded, valid)


def apply_vocab(
    vocab: vocab_lib.Vocabulary,
    modded: jnp.ndarray,
    use_kernel: bool = False,
    columns: tuple[int, ...] | None = None,
) -> jnp.ndarray:
    """ApplyVocab-2 with memory-tier dispatch (paper §3.2 / §4.4.6).

    VMEM tier (small tables of one range): Pallas kernel holding
    per-column tables in VMEM — the FPGA's on-chip-SRAM mode. HBM tier (large tables): XLA
    gather against the HBM-resident table — the FPGA's HBM mode, where the
    paper recovers II≈1 by interleaving columns across HBM channels; XLA's
    batched gather provides the same many-outstanding-reads behaviour.
    """
    lay = vocab.layout
    if use_kernel and lay.uniform and max(lay.ranges) <= vocab_lib.VMEM_TIER_MAX:
        from repro.kernels.vocab import ops as vocab_ops

        rect = lay.rect(vocab.table)
        if columns is not None:
            rect = rect[jnp.asarray(columns, jnp.int32)]
        return vocab_ops.apply_vocab_vmem(rect, modded)
    return vocab_lib.lookup(vocab, modded, columns)


def concatenate(parts: list[jnp.ndarray], axis: int = 0) -> jnp.ndarray:
    """Concatenate: merge results (trivially row-ordered on device)."""
    return jnp.concatenate(parts, axis=axis)
