"""Pure-jnp oracle for the fused loop-① (GenVocab) state update.

Exactly the unfused op chain the fused kernel replaces:
``positive_modulus`` → ``vocab.update``'s vectorized scatter-min, taking
the *raw* decoded sparse columns (int32 hash bitcasts). The differential
tests (tests/test_fused_vocab.py) hold the kernel to this oracle
bit-for-bit — scatter-min is order-independent, so serial-RMW kernel and
vectorized XLA scatter must agree exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("ranges", "offsets"))
def fused_genvocab(
    first_pos: jnp.ndarray,
    sparse: jnp.ndarray,
    pos: jnp.ndarray,
    *,
    ranges: tuple[int, ...],
    offsets: tuple[int, ...],
) -> jnp.ndarray:
    """first_pos int32 [entries] (flat, ``vocab.VocabLayout``); sparse
    int32 [rows, n_cols] (raw hashes, pre-modulus); pos int32 [rows]
    (NEVER for invalid rows); each column's range and offset
    → updated first_pos."""
    u = jax.lax.bitcast_convert_type(sparse, jnp.uint32)
    modded = (u % jnp.asarray(ranges, jnp.uint32)[None, :]).astype(jnp.int32)
    keys = modded + jnp.asarray(offsets, jnp.int32)[None, :]
    return first_pos.at[keys.reshape(-1)].min(
        jnp.broadcast_to(pos[:, None], keys.shape).reshape(-1)
    )
