"""Vocabulary engine: scatter-min formulation vs the dict oracle;
kernel vs ref; shard-merge invariance."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep — property tests skip, rest run
    from tests._hypothesis_fallback import given, settings, strategies as st

from repro.core import vocab as vocab_lib
from repro.kernels.vocab import kernel as vk
from repro.kernels.vocab import ops as vops
from repro.kernels.vocab import ref as vref


def _dict_oracle(cols: np.ndarray) -> np.ndarray:
    """Appearing-sequence ids per column, serial dict semantics."""
    rows, n_cols = cols.shape
    out = np.zeros_like(cols)
    for c in range(n_cols):
        table: dict[int, int] = {}
        for r in range(rows):
            v = int(cols[r, c])
            if v not in table:
                table[v] = len(table)
            out[r, c] = table[v]
    return out


@pytest.mark.parametrize("vocab_range,rows,n_cols", [(17, 50, 3), (256, 300, 8), (1024, 128, 1)])
def test_appearing_sequence_matches_dict(vocab_range, rows, n_cols):
    rng = np.random.default_rng(0)
    vals = rng.integers(0, vocab_range, size=(rows, n_cols)).astype(np.int32)
    state = vocab_lib.VocabState.init(n_cols, vocab_range)
    state = vocab_lib.update(state, jnp.asarray(vals), jnp.ones(rows, bool))
    vocab = vocab_lib.finalize(state)
    ids = vocab_lib.lookup(vocab, jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(ids), _dict_oracle(vals))


def test_chunked_equals_oneshot():
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 64, size=(120, 4)).astype(np.int32)
    one = vocab_lib.update(
        vocab_lib.VocabState.init(4, 64), jnp.asarray(vals), jnp.ones(120, bool)
    )
    chunked = vocab_lib.VocabState.init(4, 64)
    for i in range(0, 120, 17):
        blk = vals[i : i + 17]
        chunked = vocab_lib.update(
            chunked, jnp.asarray(blk), jnp.ones(blk.shape[0], bool)
        )
    np.testing.assert_array_equal(np.asarray(one.first_pos), np.asarray(chunked.first_pos))


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(2, 100),
    split=st.integers(1, 99),
    seed=st.integers(0, 1 << 30),
)
def test_shard_merge_invariance(rows, split, seed):
    """Property: splitting rows across shards + min-merge == serial.

    This is THE property that makes PIPER's distribution sound: the
    appearing-sequence vocabulary is invariant to how rows are sharded,
    because first-occurrence positions are global.
    """
    split = min(split, rows - 1) or 1
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 32, size=(rows, 2)).astype(np.int32)

    serial = vocab_lib.update(
        vocab_lib.VocabState.init(2, 32), jnp.asarray(vals), jnp.ones(rows, bool)
    )

    s1 = vocab_lib.VocabState.init(2, 32)
    s1 = vocab_lib.update(s1, jnp.asarray(vals[:split]), jnp.ones(split, bool))
    s2 = vocab_lib.VocabState.init(2, 32)
    # shard 2 must use global positions — emulate via rows_seen offset
    s2 = dataclasses.replace(s2, rows_seen=jnp.int32(split))
    s2 = vocab_lib.update(
        s2, jnp.asarray(vals[split:]), jnp.ones(rows - split, bool)
    )
    merged = vocab_lib.merge(s1, s2)
    np.testing.assert_array_equal(
        np.asarray(vocab_lib.finalize(serial).table),
        np.asarray(vocab_lib.finalize(merged).table),
    )


@pytest.mark.parametrize("vocab_range,rows", [(64, 128), (512, 256)])
def test_genvocab_kernel_matches_ref(vocab_range, rows):
    rng = np.random.default_rng(2)
    n_cols = 5
    vals_t = rng.integers(0, vocab_range, size=(n_cols, rows)).astype(np.int32)
    pos = np.arange(rows, dtype=np.int32)
    state0 = np.full((n_cols, vocab_range), vocab_lib.NEVER, np.int32)
    out_k = vk.genvocab(jnp.asarray(state0), jnp.asarray(vals_t), jnp.asarray(pos))
    out_r = vref.genvocab(jnp.asarray(state0), jnp.asarray(vals_t), jnp.asarray(pos))
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("rows", [64, 100, 1024])
def test_apply_vocab_kernel_matches_ref(rows):
    rng = np.random.default_rng(3)
    n_cols, vocab_range = 4, 300
    table = rng.integers(0, 10_000, size=(n_cols, vocab_range)).astype(np.int32)
    vals = rng.integers(0, vocab_range, size=(rows, n_cols)).astype(np.int32)
    out = vops.apply_vocab_vmem(jnp.asarray(table), jnp.asarray(vals))
    exp = vref.apply_vocab(jnp.asarray(table), jnp.asarray(vals.T)).T
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_duplicate_values_in_chunk_min_combine():
    """Two equal hashes in one chunk must keep the earlier position —
    the serial RMW loop and the vectorized scatter must agree."""
    vals_t = jnp.asarray([[5, 5, 5, 2, 2]], dtype=jnp.int32)
    pos = jnp.asarray([10, 3, 7, 9, 1], dtype=jnp.int32)
    # note: the kernel DONATES its state argument (in-place chunk
    # accumulation) — each call needs a fresh buffer
    make_state = lambda: jnp.full((1, 8), vocab_lib.NEVER, jnp.int32)
    out_k = vk.genvocab(make_state(), vals_t, pos)
    out_r = vref.genvocab(make_state(), vals_t, pos)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    assert int(out_k[0, 5]) == 3 and int(out_k[0, 2]) == 1
