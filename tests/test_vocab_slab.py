"""HBM-slab loop-① tier, frequency-capped finalize, and the int32
position-overflow regression suite.

Three concerns pinned here:

* **Slab streaming** (kernels/fused_vocab hbm_slab tier): one Pallas
  dispatch per chunk streams the HBM-resident ``[n_cols, slab_range]``
  state slabs through VMEM. Every slab configuration — boundary
  straddles, partial last slabs, single-slab residency, tracked counts —
  must be bit-identical to the unfused ``positive_modulus`` →
  ``vocab.update`` oracle, and the forced-slab path must equal the VMEM
  path on ranges that fit both.

* **Capped finalizers** (``vocab.finalize_topk`` / ``finalize_min_count``):
  keep-set selection orders by (count desc, first occurrence asc) — both
  commutative-monoid accumulators — so the serving table must be
  bit-deterministic under any shard/merge order, with the explicit OOV
  ordinal ``sizes[c]`` for everything dropped.

* **Overflow regression**: positions are int32 with ``NEVER`` reserved;
  before the fix, ``rows_seen + arange(rows)`` wrapped negative past the
  ceiling and corrupted the scatter-min. Every loop-① path (plain
  update, per-column kernel, fused vmem, fused slab, bytes-in decode)
  must saturate at ``NEVER`` under jit and raise ``OverflowError``
  eagerly / at host-driven entry points.

Everything runs the kernels in Pallas ``interpret=True`` mode (the
repo-wide CPU convention).
"""

import dataclasses
import functools
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep — property tests skip, rest run
    from tests._hypothesis_fallback import given, settings, strategies as st

from repro.core import ops, pipeline as P, schema as schema_lib, vocab as vocab_lib
from repro.data import synth
from repro.kernels.fused_decode_vocab import ops as fdv_ops
from repro.kernels.fused_vocab import ops as fv_ops
from repro.kernels.vocab import ops as vops
from tests.multidevice import run_with_devices

GOLDEN = os.path.join(os.path.dirname(__file__), "goldens", "fused_small.npz")


def _hashes(rng, rows: int, n_cols: int) -> jnp.ndarray:
    """Raw hash bitcasts spanning the full int32 range."""
    return jnp.asarray(
        rng.integers(-(2**31), 2**31 - 1, size=(rows, n_cols), dtype=np.int64).astype(
            np.int32
        )
    )


def _np_counts(sparse, valid, vocab_range: int) -> np.ndarray:
    """Serial numpy occurrence-count oracle (uint32 modulus semantics)."""
    vals = np.ascontiguousarray(np.asarray(sparse), np.int32)
    modded = vals.view(np.uint32) % np.uint32(vocab_range)
    valid = np.asarray(valid)
    out = np.zeros((vals.shape[1], vocab_range), np.int32)
    for r in range(vals.shape[0]):
        if valid[r]:
            for c in range(vals.shape[1]):
                out[c, modded[r, c]] += 1
    return out


def _fresh(n_cols, vocab_range, offset=0, track_counts=False):
    st0 = vocab_lib.VocabState.init(n_cols, vocab_range, track_counts=track_counts)
    return vocab_lib.VocabState(
        first_pos=st0.first_pos,
        rows_seen=jnp.int32(offset),
        counts=st0.counts,
    )


def _assert_states_equal(got, want):
    np.testing.assert_array_equal(
        np.asarray(got.first_pos), np.asarray(want.first_pos)
    )
    assert int(got.rows_seen) == int(want.rows_seen)
    assert (got.counts is None) == (want.counts is None)
    if got.counts is not None:
        np.testing.assert_array_equal(
            np.asarray(got.counts), np.asarray(want.counts)
        )


# --------------------------------------------------------------------- #
# slab tier: differential vs the unfused oracle
# --------------------------------------------------------------------- #


@settings(max_examples=15, deadline=None)
@given(
    rows=st.integers(1, 70),
    n_cols=st.integers(1, 5),
    seed=st.integers(0, 1 << 30),
    offset=st.integers(0, 1 << 20),
    vocab_range=st.sampled_from([100, 129, 997, 1000]),
    slab_range=st.sampled_from([128, 256, 512]),
    track_counts=st.booleans(),
)
def test_slab_matches_oracle_property(
    rows, n_cols, seed, offset, vocab_range, slab_range, track_counts
):
    """∀ shapes, offsets, slab widths (incl. partial last slabs and
    single-slab residency), with and without the count plane: the forced
    hbm_slab dispatch ≡ the unfused XLA oracle, bit for bit."""
    rng = np.random.default_rng(seed)
    sparse = _hashes(rng, rows, n_cols)
    valid = jnp.asarray(rng.random(rows) < 0.7)
    want = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range, offset, track_counts),
        sparse,
        valid,
        use_kernel=False,
    )
    got = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range, offset, track_counts),
        sparse,
        valid,
        use_kernel=True,
        slab_range=slab_range,
    )
    _assert_states_equal(got, want)


def test_slab_boundary_straddle_values():
    """Values landing exactly on slab edges (0, sr−1, sr, last slab's
    partial tail, V−1) must scatter into the right slab — the in-kernel
    local index and the out-of-slab identity lanes meet here."""
    vocab_range, sr = 1000, 128  # 8 slabs, last one 104 entries wide
    edges = [0, 127, 128, 255, 895, 896, 999, 128, 0, 999]
    sparse = jnp.asarray(np.array(edges, np.int32)[:, None])  # in-range ⇒ own modulus
    valid = jnp.ones(len(edges), bool)
    want = ops.fused_vocab_update(
        _fresh(1, vocab_range, track_counts=True), sparse, valid, use_kernel=False
    )
    got = ops.fused_vocab_update(
        _fresh(1, vocab_range, track_counts=True),
        sparse,
        valid,
        use_kernel=True,
        slab_range=sr,
    )
    _assert_states_equal(got, want)
    fp = np.asarray(got.first_pos)[0]
    assert fp[0] == 0 and fp[127] == 1 and fp[128] == 2 and fp[999] == 6
    cnt = np.asarray(got.counts)[0]
    assert cnt[0] == 2 and cnt[128] == 2 and cnt[999] == 2 and cnt.sum() == 10


def test_slab_equals_vmem_bit_identity():
    """On a range that fits both tiers, forced slabs ≡ the resident VMEM
    kernel ≡ the oracle — the tier choice is invisible in the results."""
    rng = np.random.default_rng(11)
    sparse = _hashes(rng, 300, 4)
    valid = jnp.asarray(rng.random(300) < 0.9)
    assert fv_ops.fused_vocab_tier(4, 5000) == "vmem"
    assert fv_ops.fused_vocab_tier(4, 5000, slab_range=1280) == "hbm_slab"
    vmem = ops.fused_vocab_update(
        _fresh(4, 5000), sparse, valid, use_kernel=True
    )
    slab = ops.fused_vocab_update(
        _fresh(4, 5000), sparse, valid, use_kernel=True, slab_range=1280
    )
    _assert_states_equal(slab, vmem)


def test_slab_all_invalid_chunk():
    """All-invalid chunks (decode padding) on the slab tier leave every
    slab untouched and advance nothing."""
    upd = ops.fused_vocab_update(
        _fresh(2, 1000, track_counts=True),
        jnp.zeros((40, 2), jnp.int32),
        jnp.zeros(40, bool),
        use_kernel=True,
        slab_range=256,
    )
    assert (np.asarray(upd.first_pos) == vocab_lib.NEVER).all()
    assert int(np.asarray(upd.counts).sum()) == 0
    assert int(upd.rows_seen) == 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 30), n_chunks=st.integers(2, 4))
def test_slab_chunk_carry_property(seed, n_chunks):
    """Chained chunks through the slab dispatch: the HBM-resident state
    (and counts) carried across calls equals one oracle pass."""
    rng = np.random.default_rng(seed)
    f_state = _fresh(3, 700, track_counts=True)
    u_state = _fresh(3, 700, track_counts=True)
    for _ in range(n_chunks):
        rows = int(rng.integers(1, 40))
        sparse = _hashes(rng, rows, 3)
        valid = jnp.asarray(rng.random(rows) < 0.8)
        u_state = ops.fused_vocab_update(u_state, sparse, valid, use_kernel=False)
        f_state = ops.fused_vocab_update(
            f_state, sparse, valid, use_kernel=True, slab_range=256
        )
    _assert_states_equal(f_state, u_state)


def test_counts_match_numpy_reference():
    """Tracked counts vs the serial numpy oracle, on both the single-
    resident-slab (vmem+counts) and multi-slab dispatches."""
    rng = np.random.default_rng(21)
    sparse = _hashes(rng, 200, 3)
    valid = jnp.asarray(rng.random(200) < 0.85)
    expect = _np_counts(sparse, valid, 500)
    for slab_range in (None, 128):  # None ⇒ vmem tier, counts ride one slab
        upd = ops.fused_vocab_update(
            _fresh(3, 500, track_counts=True),
            sparse,
            valid,
            use_kernel=True,
            slab_range=slab_range,
        )
        np.testing.assert_array_equal(np.asarray(upd.counts), expect)


def test_auto_tier_above_vmem_cutoff_uses_slabs():
    """Just above VMEM_TIER_MAX the policy (no forcing) must pick slabs,
    partition the range evenly, and still match the oracle."""
    vocab_range = vocab_lib.VMEM_TIER_MAX + 128
    n_cols = 26  # the Criteo stack: one column's slab budget is ~1M
    # entries, so a single column would fit one slab — the full stack
    # is what forces a real multi-slab partition
    assert fv_ops.fused_vocab_tier(n_cols, vocab_range) == "hbm_slab"
    n_slabs = fv_ops.vocab_slab_count(n_cols, vocab_range)
    assert n_slabs > 1
    sr = fv_ops.default_slab_range(n_cols, vocab_range)
    assert sr % fv_ops.SLAB_LANE == 0 and (n_slabs - 1) * sr < vocab_range
    rng = np.random.default_rng(31)
    sparse = _hashes(rng, 64, n_cols)
    valid = jnp.ones(64, bool)
    want = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range), sparse, valid, use_kernel=False
    )
    got = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range), sparse, valid, use_kernel=True
    )
    _assert_states_equal(got, want)


# --------------------------------------------------------------------- #
# slab tier: entries bucketed by slab before the grid
# --------------------------------------------------------------------- #


def _in_range(rng, rows, n_cols, lo, hi):
    """Hashes already in ``[lo, hi)`` (each its own modulus)."""
    return jnp.asarray(rng.integers(lo, hi, size=(rows, n_cols)).astype(np.int32))


def _case_one_slab_first(rng):
    return _in_range(rng, 1500, 3, 0, 128), jnp.ones(1500, bool), 1000, 128


def _case_one_slab_last(rng):
    return _in_range(rng, 1500, 3, 896, 1000), jnp.ones(1500, bool), 1000, 128


def _case_empty_slabs(rng):
    # values only in slabs 1 and 5 of 8: the other six runs are empty
    vals = np.where(
        rng.random((1200, 3)) < 0.5,
        rng.integers(128, 256, size=(1200, 3)),
        rng.integers(640, 768, size=(1200, 3)),
    ).astype(np.int32)
    return jnp.asarray(vals), jnp.ones(1200, bool), 1000, 128


def _case_padded_last_slab(rng):
    # 1000 is no multiple of 384: three slabs, the last 232 wide + 152 pad
    vals = _in_range(rng, 1100, 2, 600, 1000)
    return vals, jnp.asarray(rng.random(1100) < 0.9), 1000, 384


def _case_negative_hashes(rng):
    # every hash has the top bit set: the uint32 modulus, not int32's
    vals = rng.integers(-(2**31), 0, size=(1300, 3), dtype=np.int64)
    return jnp.asarray(vals.astype(np.int32)), jnp.ones(1300, bool), 997, 256


def _case_never_rows_partial_tile(rng):
    # three row tiles: the middle one all invalid (NEVER), the last one
    # 552 rows and 472 padding rows
    valid = np.ones(2600, bool)
    valid[1024:2048] = False
    valid[2048:] = rng.random(552) < 0.5
    return _hashes(rng, 2600, 2), jnp.asarray(valid), 1000, 256


def _case_hot_key(rng):
    # one hot value in slab 3 on most rows of every tile, the rest spread
    # over every slab
    vals = np.asarray(_in_range(rng, 2100, 3, 0, 1000))
    hot = rng.random((2100, 3)) < 0.6
    vals = np.where(hot, 400, vals).astype(np.int32)
    return jnp.asarray(vals), jnp.asarray(rng.random(2100) < 0.8), 1000, 128


def _case_single_slab(rng):
    # slab_range ≥ vocab_range: one slab, walked as it comes
    return _hashes(rng, 1500, 3), jnp.ones(1500, bool), 1000, 1024


_BUCKET_CASES = {
    "one_slab_first": _case_one_slab_first,
    "one_slab_last": _case_one_slab_last,
    "empty_slabs": _case_empty_slabs,
    "padded_last_slab": _case_padded_last_slab,
    "negative_hashes": _case_negative_hashes,
    "never_rows_partial_tile": _case_never_rows_partial_tile,
    "hot_key": _case_hot_key,
    "single_slab": _case_single_slab,
}


def _check_bucketed(case, track_counts, seed=7):
    """The forced slab dispatch ≡ ref.py, the unfused chain, the VMEM
    tier and (tracked) the numpy counts, bit for bit; returns its slab
    count."""
    from repro.kernels.fused_vocab import ref as fv_ref

    sparse, valid, vocab_range, slab_range = _BUCKET_CASES[case](
        np.random.default_rng(seed)
    )
    n_cols = sparse.shape[1]
    offset = 12345
    got = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range, offset, track_counts),
        sparse,
        valid,
        use_kernel=True,
        slab_range=slab_range,
    )
    want = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range, offset, track_counts),
        sparse,
        valid,
        use_kernel=False,
    )
    _assert_states_equal(got, want)
    vmem = ops.fused_vocab_update(
        _fresh(n_cols, vocab_range, offset, track_counts),
        sparse,
        valid,
        use_kernel=True,
    )
    _assert_states_equal(got, vmem)
    pos = vocab_lib.positions(jnp.int32(offset), sparse.shape[0], valid)
    np.testing.assert_array_equal(
        np.asarray(got.first_pos),
        np.asarray(
            fv_ref.fused_genvocab(
                _fresh(n_cols, vocab_range).first_pos, sparse, pos
            )
        ),
    )
    if track_counts:
        np.testing.assert_array_equal(
            np.asarray(got.counts), _np_counts(sparse, valid, vocab_range)
        )
    return fv_ops.vocab_slab_count(
        n_cols, vocab_range, slab_range=slab_range, track_counts=track_counts
    )


@pytest.mark.parametrize("track_counts", [False, True], ids=["first_pos", "counts"])
@pytest.mark.parametrize("case", sorted(_BUCKET_CASES))
def test_bucketed_slab_cases(case, track_counts):
    """Entries bucketed by slab before the slab grid: every chunk shape
    that moves where the runs start — all entries in the first or the
    last slab, empty slabs, a padded last slab, hashes with the top bit
    set, all-NEVER tiles and a partial last tile, a hot key across tiles
    and slabs, and a single slab (each column's run the whole column,
    nothing sorted) — with and without the count plane."""
    n_slabs = _check_bucketed(case, track_counts)
    assert (n_slabs > 1) == (case != "single_slab")


@pytest.fixture
def fresh_jit_caches():
    """Clear JAX's caches around a test that steers a trace-time choice,
    so neither it nor a later test runs a program traced the other way."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", ["hot_key", "never_rows_partial_tile", "negative_hashes"])
def test_bucketed_slab_two_operand_sort(case, monkeypatch, fresh_jit_caches):
    """Where a packed int32 key (value << row bits | row) would not fit,
    the values sort with their positions as a second operand; narrowing
    the key to 12 bits takes that path on small shapes."""
    from repro.kernels.fused_vocab import kernel as fv_kernel

    monkeypatch.setattr(fv_kernel, "KEY_BITS", 12)
    assert fv_kernel.key_row_bits(1024, 1024) is None
    assert fv_kernel.key_row_bits(4, 4) == 2
    _check_bucketed(case, track_counts=True)


def test_slab_offsets_in_declared_footprint():
    """The bucketed tiles' SMEM blocks are in ``vmem_accounting``: the
    per-tile slab offsets (padded to the SMEM grain), and the positions
    as a whole entry tile where a packed key does not fit."""
    from repro.kernels.fused_vocab import kernel as fv_kernel

    sr = fv_ops.default_slab_range(26, 1_000_000)
    acct = fv_ops.vmem_accounting(26, 1_000_000, slab_range=sr)
    assert acct["offsets_tile"] == fv_kernel.offsets_width(26, 25) * 4 == 1024 * 4
    assert acct["pos_tile"] == 1024 * 4  # packed key: 20 value + 10 row bits
    sr10 = fv_ops.default_slab_range(26, 10_000_000)
    wide = fv_ops.vmem_accounting(26, 10_000_000, slab_range=sr10)
    assert wide["pos_tile"] == wide["sparse_tile"]
    assert "offsets_tile" not in fv_ops.vmem_accounting(26, 5000)
    # the vmem tier with counts runs the slab kernel with one slab
    counted = fv_ops.vmem_accounting(26, 5000, track_counts=True)
    assert counted["offsets_tile"] == fv_kernel.offsets_width(26, 1) * 4


# --------------------------------------------------------------------- #
# capped finalizers
# --------------------------------------------------------------------- #


def _count_state(first_pos_rows, counts_rows):
    """Build a VocabState from explicit per-column first_pos/count rows."""
    return vocab_lib.VocabState(
        first_pos=jnp.asarray(np.array(first_pos_rows, np.int32)),
        rows_seen=jnp.int32(100),
        counts=jnp.asarray(np.array(counts_rows, np.int32)),
    )


def test_finalize_topk_keeps_most_frequent_ties_by_first_pos():
    N = vocab_lib.NEVER
    # value:       v0  v1  v2  v3  v4(absent)
    state = _count_state(
        [[7, 0, 3, 5, N]],  # first positions
        [[3, 5, 3, 1, 0]],  # counts: v0 and v2 tie at 3
    )
    vocab = vocab_lib.finalize_topk(state, 2)
    # keep v1 (count 5) and the count-3 tie winner v2 (first_pos 3 < 7);
    # ordinals follow appearing-sequence order among the keepers.
    table = np.asarray(vocab.table)[0]
    assert int(vocab.sizes[0]) == 2
    assert table[1] == 0 and table[2] == 1  # v1 first (pos 0), then v2
    assert table[0] == 2 and table[3] == 2 and table[4] == 2  # OOV ordinal
    assert int(vocab.oov_ordinals[0]) == 2


def test_finalize_topk_edge_cases():
    N = vocab_lib.NEVER
    state = _count_state([[4, 1, N]], [[2, 9, 0]])
    # k = 0: everything OOV, ordinal 0
    v0 = vocab_lib.finalize_topk(state, 0)
    assert int(v0.sizes[0]) == 0 and (np.asarray(v0.table) == 0).all()
    # k ≥ present: kept ordinals match plain finalize; absent → OOV
    vk = vocab_lib.finalize_topk(state, 10)
    plain = vocab_lib.finalize(state)
    assert int(vk.sizes[0]) == 2
    np.testing.assert_array_equal(
        np.asarray(vk.table)[0][:2], np.asarray(plain.table)[0][:2]
    )
    assert int(np.asarray(vk.table)[0][2]) == 2  # absent → sizes, not 0
    with pytest.raises(ValueError, match="k >= 0"):
        vocab_lib.finalize_topk(state, -1)
    with pytest.raises(ValueError, match="min_count >= 1"):
        vocab_lib.finalize_min_count(state, 0)
    untracked = vocab_lib.VocabState.init(1, 3)
    with pytest.raises(ValueError, match="track_counts"):
        vocab_lib.finalize_topk(untracked, 1)
    with pytest.raises(ValueError, match="track_counts"):
        vocab_lib.finalize_min_count(untracked, 2)


def test_finalize_min_count_matches_numpy():
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.integers(0, 40, size=(300, 2)).astype(np.int32))
    valid = jnp.ones(300, bool)
    state = ops.fused_vocab_update(
        _fresh(2, 40, track_counts=True), vals, valid, use_kernel=False
    )
    fp = np.asarray(state.first_pos)
    cnt = np.asarray(state.counts)
    for min_count in (1, 5, 12):
        vocab = vocab_lib.finalize_min_count(state, min_count)
        kept = (fp < vocab_lib.NEVER) & (cnt >= min_count)
        for c in range(2):
            kept_vals = np.nonzero(kept[c])[0]
            order = kept_vals[np.argsort(fp[c][kept_vals], kind="stable")]
            assert int(vocab.sizes[c]) == len(order)
            table = np.asarray(vocab.table)[c]
            for rank, v in enumerate(order):
                assert table[v] == rank
            dropped = np.setdiff1d(np.arange(40), order)
            assert (table[dropped] == len(order)).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 30), k=st.integers(1, 12))
def test_capped_finalize_merge_order_invariance(seed, k):
    """THE determinism property: counts (sum) and first_pos (min) are
    commutative monoids and (count, first_pos) totally orders present
    values, so finalize_topk must emit the identical table for every
    shard merge order — and match the unsharded serial state."""
    rng = np.random.default_rng(seed)
    rows = 90
    vals = _hashes(rng, rows, 2)
    serial = ops.fused_vocab_update(
        _fresh(2, 50, track_counts=True),
        vals,
        jnp.ones(rows, bool),
        use_kernel=False,
    )
    bounds = [0, 30, 60, rows]
    shards = []
    for lo, hi in zip(bounds, bounds[1:]):
        shards.append(
            ops.fused_vocab_update(
                _fresh(2, 50, offset=lo, track_counts=True),
                vals[lo:hi],
                jnp.ones(hi - lo, bool),
                use_kernel=False,
            )
        )
    ref = vocab_lib.finalize_topk(serial, k)
    for perm in itertools.permutations(range(3)):
        merged = functools.reduce(vocab_lib.merge, [shards[i] for i in perm])
        got = vocab_lib.finalize_topk(merged, k)
        np.testing.assert_array_equal(np.asarray(got.table), np.asarray(ref.table))
        np.testing.assert_array_equal(np.asarray(got.sizes), np.asarray(ref.sizes))
    # and the log-depth tree agrees with the linear reduction
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *shards)
    tree = vocab_lib.finalize_topk(vocab_lib.merge_tree(stacked), k)
    np.testing.assert_array_equal(np.asarray(tree.table), np.asarray(ref.table))


# --------------------------------------------------------------------- #
# merge compatibility
# --------------------------------------------------------------------- #


def test_merge_shape_mismatch_raises():
    with pytest.raises(ValueError, match="vocab layouts"):
        vocab_lib.merge(
            vocab_lib.VocabState.init(2, 64), vocab_lib.VocabState.init(2, 65)
        )
    with pytest.raises(ValueError, match="vocab layouts"):
        vocab_lib.merge(
            vocab_lib.VocabState.init(2, 64), vocab_lib.VocabState.init(3, 64)
        )


def test_merge_counts_mismatch_raises():
    with pytest.raises(ValueError, match="track_counts"):
        vocab_lib.merge(
            vocab_lib.VocabState.init(2, 64),
            vocab_lib.VocabState.init(2, 64, track_counts=True),
        )


def test_merge_dtype_mismatch_raises():
    a = vocab_lib.VocabState.init(1, 8)
    b = vocab_lib.VocabState(
        first_pos=a.first_pos.astype(jnp.int16), rows_seen=a.rows_seen
    )
    with pytest.raises(ValueError, match="dtypes"):
        vocab_lib.merge(a, b)


def test_merge_tree_counts_identity_padding():
    """merge_tree on a non-power-of-two stack of tracked states pads with
    the monoid identity (zero counts) and equals the linear reduction."""
    rng = np.random.default_rng(3)
    shards, offset = [], 0
    for rows in (20, 35, 15):
        shards.append(
            vocab_lib.update(
                _fresh(2, 30, offset=offset, track_counts=True),
                jnp.asarray(rng.integers(0, 30, (rows, 2)).astype(np.int32)),
                jnp.ones(rows, bool),
            )
        )
        offset += rows
    linear = functools.reduce(vocab_lib.merge, shards)
    tree = vocab_lib.merge_tree(jax.tree.map(lambda *x: jnp.stack(x), *shards))
    _assert_states_equal(tree, linear)


# --------------------------------------------------------------------- #
# int32 position-overflow regression (the bugfix this PR pins)
# --------------------------------------------------------------------- #

_CEILING_PATHS = {
    "plain-update": lambda s, v, m: vocab_lib.update(s, v, m),
    "vocab-kernel": lambda s, v, m: vops.genvocab_update(s, v, m),
    "fused-vmem": lambda s, v, m: ops.fused_vocab_update(
        s, v, m, use_kernel=True
    ),
    "fused-slab": lambda s, v, m: ops.fused_vocab_update(
        s, v, m, use_kernel=True, slab_range=128
    ),
}


@pytest.mark.parametrize("path", sorted(_CEILING_PATHS), ids=sorted(_CEILING_PATHS))
@pytest.mark.parametrize("track_counts", [False, True], ids=["plain", "counts"])
def test_positions_saturate_at_ceiling_jit(path, track_counts):
    """rows_seen three below the ceiling + 8 valid rows, under jit (the
    engines' calling convention): exactly the 3 representable positions
    are written, nothing wraps negative, rows_seen saturates at NEVER,
    and saturated rows are dropped from the counts. Before the uint32
    saturating arithmetic this wrapped ``NEVER + i`` negative and
    corrupted the scatter-min — this test fails on that code."""
    if path == "vocab-kernel" and track_counts is False:
        pytest.skip("covered by plain variant (same code path)")
    N = vocab_lib.NEVER
    rows, n_cols, vocab_range = 8, 2, 64
    # distinct in-range values: their uint32 modulus is themselves, so
    # every loop-① formulation sees the same scatter targets
    vals = jnp.asarray(
        (np.arange(rows * n_cols, dtype=np.int32).reshape(rows, n_cols))
    )
    valid = jnp.ones(rows, bool)

    def run(rows_seen):
        st0 = vocab_lib.VocabState.init(
            n_cols, vocab_range, track_counts=track_counts
        )
        state = vocab_lib.VocabState(
            first_pos=st0.first_pos, rows_seen=rows_seen, counts=st0.counts
        )
        return _CEILING_PATHS[path](state, vals, valid)

    out = jax.jit(run)(jnp.int32(N - 3))
    fp = np.asarray(out.first_pos)
    assert (fp >= 0).all(), "positions wrapped negative past the ceiling"
    written = fp[fp < N]
    assert set(written.tolist()) == {N - 3, N - 2, N - 1}
    assert int(out.rows_seen) == N  # saturated, not wrapped
    if track_counts:
        # rows past the ceiling are dropped from the counts too
        assert int(np.asarray(out.counts).sum()) == 3 * n_cols


def test_ceiling_raises_eagerly():
    """Host-driven (eager) entry points fail loudly instead of silently
    saturating: check_row_ceiling fires on concrete rows_seen."""
    state = vocab_lib.VocabState(
        first_pos=jnp.full((1, 64), vocab_lib.NEVER, jnp.int32),
        rows_seen=jnp.int32(vocab_lib.NEVER - 3),
    )
    vals = jnp.zeros((8, 1), jnp.int32)
    with pytest.raises(OverflowError, match="ceiling"):
        vocab_lib.update(state, vals, jnp.ones(8, bool))
    with pytest.raises(OverflowError, match="ceiling"):
        ops.fused_vocab_update(state, vals, jnp.ones(8, bool), use_kernel=True)


def test_bytes_in_kernel_saturates_at_ceiling():
    """The bytes-in loop-① dispatch (fused decode kernel + its fallback
    fill) saturates identically to the decode → update oracle near the
    ceiling — no negative positions from either the kernel's in-tile
    ``offset + row`` or the wrapper's short-row fill."""
    schema = schema_lib.TableSchema(n_dense=2, n_sparse=3, vocab_range=97)
    cfg = synth.SynthConfig(schema=schema, rows=24, seed=5)
    raw = synth.encode_utf8(synth.generate_binary(cfg), cfg)
    buf = jnp.asarray(synth.pad_bytes(raw, multiple=2048))
    N = vocab_lib.NEVER

    def run(rows_seen, use_kernel):
        state = vocab_lib.VocabState(
            first_pos=jnp.full((3, 97), N, jnp.int32), rows_seen=rows_seen
        )
        if use_kernel:
            return fdv_ops.fused_decode_update(
                state, buf, n_fields=6, hex_start=3, max_rows=32
            )
        return ops.fused_decode_vocab_update(
            state, buf, n_fields=6, n_dense=2, n_sparse=3, max_rows=32,
            use_kernel=False,
        )

    got = jax.jit(functools.partial(run, use_kernel=True))(jnp.int32(N - 3))
    want = jax.jit(functools.partial(run, use_kernel=False))(jnp.int32(N - 3))
    fp = np.asarray(got.first_pos)
    assert (fp >= 0).all()
    np.testing.assert_array_equal(fp, np.asarray(want.first_pos))
    assert int(got.rows_seen) == int(want.rows_seen) == N


def test_build_state_stream_guards_ceiling(criteo_small, monkeypatch):
    """The host-side stream guard syncs + raises before the saturating
    kernels would silently drop rows (ceiling shrunk for the test)."""
    buf, _, cfg = criteo_small
    monkeypatch.setattr(vocab_lib, "MAX_ROWS", 300)
    pipe = P.PiperPipeline(
        P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=256)
    )
    with pytest.raises(OverflowError, match="ceiling"):
        pipe.build_state_stream(synth.chunk_stream(buf, 4096))


def test_absorb_past_ceiling_raises(criteo_small):
    from repro.stream import StreamingPreprocessService

    buf, _, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    svc = StreamingPreprocessService(
        pc, P.PiperPipeline(pc).init_state(), bucket_rows=(32,), queue_depth=4
    )
    spans = synth.row_spans(buf)
    payload = buf[spans[0, 0] : spans[11, 1]]  # 12 rows
    with pytest.raises(OverflowError, match="ceiling"):
        svc.absorb(payload, row_offset=vocab_lib.MAX_ROWS - 5)


# --------------------------------------------------------------------- #
# pipeline / plan wiring: tier routing, counts knob, service finalizer
# --------------------------------------------------------------------- #


def test_vocab_route_reports_tier():
    """compile_plan surfaces which loop-① tier will run — the observable
    the obs spans and the stale-comment reconciliation hang off."""
    slab = P.PiperPipeline(
        P.PipelineConfig(use_fused_vocab=True, vocab_slab_range=1280)
    )
    assert slab.compiled.vocab_route == "fused/hbm_slab"
    assert slab.compiled.vocab_slabs == 4  # 5000 / 1280
    assert "fused/hbm_slab" in slab.compiled.describe()
    big_schema = dataclasses.replace(
        P.PipelineConfig().schema, vocab_range=vocab_lib.VMEM_TIER_MAX + 128
    )
    auto = P.PiperPipeline(
        P.PipelineConfig(schema=big_schema, use_fused_vocab=True)
    )
    assert auto.compiled.vocab_route == "fused/hbm_slab"
    assert auto.compiled.vocab_slabs > 1
    # degenerate widths: thousands of columns where not even one
    # 128-lane slab fits the budget → the XLA oracle, reported as such
    assert fv_ops.fused_vocab_tier(9000, 300) == "xla_fallback"
    assert fv_ops.vocab_slab_count(9000, 300) == 1


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_track_counts_pipeline_wiring(criteo_small, fused):
    """PipelineConfig.track_vocab_counts threads the count plane through
    init_state and the whole loop-① stream; fused and unfused agree and
    the totals reconcile with rows_seen."""
    buf, _, cfg = criteo_small
    pc = P.PipelineConfig(
        schema=cfg.schema,
        max_rows_per_chunk=256,
        track_vocab_counts=True,
        use_fused_vocab=fused,
    )
    pipe = P.PiperPipeline(pc)
    assert pipe.init_state().counts is not None
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    assert state.counts is not None
    assert int(np.asarray(state.counts).sum()) == (
        int(state.rows_seen) * cfg.schema.n_sparse
    )
    if fused:
        untracked = P.PiperPipeline(
            P.PipelineConfig(
                schema=cfg.schema, max_rows_per_chunk=256, use_fused_vocab=True
            )
        ).build_state_stream(synth.chunk_stream(buf, 16384))
        np.testing.assert_array_equal(
            np.asarray(state.first_pos), np.asarray(untracked.first_pos)
        )


def test_service_counts_mismatch_raises(criteo_small):
    """A tracked state against an untracked config (or vice versa) fails
    at construction, not inside the service loop."""
    from repro.stream import StreamingPreprocessService

    buf, _, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    tracked = vocab_lib.VocabState.init(
        cfg.schema.n_sparse, cfg.schema.vocab_range, track_counts=True
    )
    with pytest.raises(ValueError):
        StreamingPreprocessService(pc, tracked, bucket_rows=(32,), queue_depth=4)


def test_refresh_vocab_incompatible_delta_raises(criteo_small):
    """Incompatible deltas fail at ingestion (refresh_vocab), naming the
    mismatch — not later inside the service loop."""
    from repro.stream import StreamingPreprocessService

    buf, _, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    svc = StreamingPreprocessService(
        pc, P.PiperPipeline(pc).init_state(), bucket_rows=(32,), queue_depth=4
    )
    with pytest.raises(ValueError, match="track_counts"):
        svc.refresh_vocab(
            vocab_lib.VocabState.init(
                cfg.schema.n_sparse, cfg.schema.vocab_range, track_counts=True
            )
        )
    with pytest.raises(ValueError, match="vocab layouts"):
        svc.refresh_vocab(
            vocab_lib.VocabState.init(cfg.schema.n_sparse, 77)
        )


def test_service_capped_serving(criteo_small):
    """End to end: a count-tracking pipeline + ``finalize_topk`` as the
    service finalizer bounds every served ordinal by k, with k itself the
    live OOV ordinal — the HBM-scale serving-table story."""
    from repro.stream import StreamingPreprocessService

    buf, _, cfg = criteo_small
    k = 7
    pc = P.PipelineConfig(schema=cfg.schema, track_vocab_counts=True)
    state = P.PiperPipeline(pc).build_state_stream(synth.chunk_stream(buf, 16384))
    svc = StreamingPreprocessService(
        pc,
        state,
        bucket_rows=(32, 128),
        queue_depth=8,
        finalizer=functools.partial(vocab_lib.finalize_topk, k=k),
    ).start()
    try:
        handles = [
            svc.submit(p)
            for p in synth.request_payloads(buf, None, [40], "utf8")
        ]
        svc.drain(timeout=120)
        out = handles[0].result(timeout=5)
    finally:
        svc.stop()
    ids = np.asarray(out["sparse"])
    assert ids.min() >= 0 and ids.max() <= k
    assert (ids == k).any()  # the OOV ordinal is live (range ≫ k values)


# --------------------------------------------------------------------- #
# golden: 8-shard engine with the slab dispatch inside every shard body
# --------------------------------------------------------------------- #

_SHARDED_GOLDEN_SLAB_VOCAB = """
import hashlib, numpy as np, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, sharded_pipeline as SP
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

g = np.load({golden_path!r})
cb = int(g["chunk_bytes"])
pc = P.PipelineConfig(chunk_bytes=cb, max_rows_per_chunk=int(g["max_rows_per_chunk"]),
                      use_fused_kernel=True, use_fused_vocab=True,
                      vocab_slab_range=1280)
mesh = make_data_mesh(8)
feed = loader.TabularChunkFeed(g["buf"], cb, 8)
stacks, offsets = feed.shard_stacks()
eng = SP.ShardedPiperPipeline(pc, mesh)
assert eng.compiled.vocab_route == "fused/hbm_slab", eng.compiled.vocab_route
cs, os_ = put_shard_feed(jnp.asarray(stacks), jnp.asarray(offsets), mesh)
out = SP.flatten_sharded(eng.run_scan(cs, os_))
v = np.asarray(out.valid)
label = np.asarray(out.label)[v]; sparse = np.asarray(out.sparse)[v]
np.testing.assert_array_equal(label, g["label"])
np.testing.assert_array_equal(sparse, g["sparse"])
np.testing.assert_allclose(np.asarray(out.dense)[v], g["dense"], rtol=1e-6)
h = hashlib.sha256()
h.update(np.ascontiguousarray(label, np.int32).tobytes())
h.update(np.ascontiguousarray(sparse, np.int32).tobytes())
assert h.hexdigest() == str(g["digest"]), "digest drift"
print("OK")
"""


@pytest.mark.slow
def test_golden_sharded_8_devices_slab_vocab():
    """The 8-shard engine with the slab-streaming loop-① dispatch forced
    inside every shard_map body (unchanged merge_tree) reproduces the
    golden digest bit-for-bit — resharding invisibility at the slab tier."""
    code = _SHARDED_GOLDEN_SLAB_VOCAB.format(golden_path=GOLDEN)
    assert "OK" in run_with_devices(code, n_devices=8)
