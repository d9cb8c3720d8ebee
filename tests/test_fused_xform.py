"""Fused single-pass loop-② kernel: differential tests vs the unfused chain.

The fused kernel (kernels/fused_xform) must be **bit-identical** on
sparse ids and allclose (rtol 1e-6, NaN-preserving) on dense floats vs
the unfused op chain, across both memory tiers, any shape, and the edge
cases decode can hand it (padding rows, negative/overflow/NaN dense
values). Hypothesis property tests sweep random shapes; the
deterministic tests below them carry the same coverage on environments
without hypothesis (tests/_hypothesis_fallback.py).

Everything here runs the kernels in Pallas ``interpret=True`` mode (the
repo-wide CPU convention), so tier-1 CI exercises the kernel logic
without accelerator hardware.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep — property tests skip, rest run
    from tests._hypothesis_fallback import given, settings, strategies as st

from repro.core import ops, pipeline as P, schema as schema_lib, vocab as vocab_lib
from repro.data import synth
from repro.kernels.fused_xform import kernel as fx_kernel
from repro.kernels.fused_xform import ops as fx_ops
from repro.kernels.fused_xform import ref as fx_ref


def _random_vocab(rng, n_cols: int, vocab_range: int) -> vocab_lib.Vocabulary:
    """A plausible finalized vocabulary: random subset of values present."""
    fp = rng.integers(0, 100_000, size=(n_cols, vocab_range)).astype(np.int32)
    seen = rng.random((n_cols, vocab_range)) < 0.6
    fp = np.where(seen, fp, vocab_lib.NEVER)
    lay = vocab_lib.layout((vocab_range,) * n_cols)
    return vocab_lib.finalize(
        vocab_lib.VocabState(
            first_pos=lay.pack(fp, vocab_lib.NEVER),
            rows_seen=jnp.int32(0),
            layout=lay,
        )
    )


def _random_inputs(rng, rows: int, n_cols: int, n_dense: int):
    sparse = rng.integers(
        -(2**31), 2**31 - 1, size=(rows, n_cols), dtype=np.int64
    ).astype(np.int32)
    dense = rng.integers(
        -(2**31), 2**31 - 1, size=(rows, n_dense), dtype=np.int64
    ).astype(np.int32)
    return jnp.asarray(sparse), jnp.asarray(dense)


def _assert_fused_matches_unfused(vocab, sparse, dense):
    ids_f, den_f = ops.fused_transform(vocab, sparse, dense, use_kernel=True)
    ids_u, den_u = ops.fused_transform(vocab, sparse, dense, use_kernel=False)
    assert ids_f.dtype == jnp.int32 and den_f.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_u))
    np.testing.assert_allclose(
        np.asarray(den_f), np.asarray(den_u), rtol=1e-6, equal_nan=True
    )


# --------------------------------------------------------------------- #
# hypothesis: random shapes, tier straddle, adversarial dense values
# --------------------------------------------------------------------- #


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 70),
    n_cols=st.integers(1, 6),
    n_dense=st.integers(1, 5),
    seed=st.integers(0, 1 << 30),
    vocab_range=st.sampled_from(
        [3, 97, 5000, vocab_lib.VMEM_TIER_MAX, vocab_lib.VMEM_TIER_MAX + 3]
    ),
)
def test_fused_equals_reference_property(rows, n_cols, n_dense, seed, vocab_range):
    """∀ shapes and vocab ranges straddling VMEM_TIER_MAX: fused == unfused."""
    rng = np.random.default_rng(seed)
    vocab = _random_vocab(rng, n_cols, vocab_range)
    sparse, dense = _random_inputs(rng, rows, n_cols, n_dense)
    _assert_fused_matches_unfused(vocab, sparse, dense)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 1 << 30),
    special=st.sampled_from(["nan", "inf", "-inf", "int_min", "int_max"]),
)
def test_fused_dense_special_values_property(seed, special):
    """NaN/±inf/overflow dense inputs transform identically on both paths."""
    rng = np.random.default_rng(seed)
    vocab = _random_vocab(rng, 2, 50)
    sparse, _ = _random_inputs(rng, 16, 2, 3)
    dense = rng.normal(0, 1e4, size=(16, 3)).astype(np.float32)
    val = {
        "nan": np.nan,
        "inf": np.inf,
        "-inf": -np.inf,
        "int_min": float(-(2**31)),
        "int_max": float(2**31 - 1),
    }[special]
    dense[rng.integers(0, 16), rng.integers(0, 3)] = val
    _assert_fused_matches_unfused(vocab, sparse, jnp.asarray(dense))


# --------------------------------------------------------------------- #
# deterministic: same coverage without hypothesis
# --------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "vocab_range,tier",
    [
        (5000, "vmem"),
        (vocab_lib.VMEM_TIER_MAX, "vmem"),
        (vocab_lib.VMEM_TIER_MAX + 1, "hbm"),
    ],
    ids=["paper-5k", "tier-max", "tier-max+1"],
)
def test_fused_matches_unfused_both_tiers(vocab_range, tier):
    """Differential equivalence on either side of the VMEM cutoff.

    Row counts deliberately straddle the wrapper's padding logic:
    300 > 256 forces blk=256 with 212 pad rows sliced back off; 5 < 8
    forces blk=8 with 3 pad rows (the _row_block floor)."""
    assert fx_ops.fused_tier(vocab_lib.layout((vocab_range,) * 2)) == tier
    rng = np.random.default_rng(0)
    vocab = _random_vocab(rng, 2, vocab_range)
    for rows in (300, 5):
        sparse, dense = _random_inputs(rng, rows, 2, 4)
        _assert_fused_matches_unfused(vocab, sparse, dense)


def test_fused_table_budget_routes_to_hbm():
    """A wide table under the per-column cutoff but over the whole-stack
    VMEM budget must route to the HBM tier (the fused kernel keeps ALL
    column tables resident, unlike the one-column-at-a-time vocab kernel)."""
    vocab_range = vocab_lib.VMEM_TIER_MAX  # per-column: fits
    n_cols_over = fx_ops.FUSED_TABLE_VMEM_BYTES // (vocab_range * 4) + 1
    assert fx_ops.fused_tier(vocab_lib.layout((vocab_range,) * n_cols_over)) == "hbm"
    assert fx_ops.fused_tier(vocab_lib.layout((vocab_range,))) == "vmem"


def test_fused_dense_special_values():
    """NaN, ±inf and int32 extremes: fused dense == unfused dense."""
    rng = np.random.default_rng(1)
    vocab = _random_vocab(rng, 3, 97)
    sparse, _ = _random_inputs(rng, 24, 3, 4)
    dense = np.zeros((24, 4), np.float32)
    dense[0, 0] = np.nan
    dense[1, 1] = np.inf
    dense[2, 2] = -np.inf
    dense[3, 3] = float(-(2**31))
    dense[4, 0] = float(2**31 - 1)
    dense[5, 1] = -0.0
    _assert_fused_matches_unfused(vocab, sparse, jnp.asarray(dense))
    # int32 extremes through the int path too (decode hands us int32)
    dense_i = np.full((8, 2), -(2**31), np.int32)
    dense_i[0] = 2**31 - 1
    sparse_i, _ = _random_inputs(rng, 8, 3, 2)
    _assert_fused_matches_unfused(vocab, sparse_i, jnp.asarray(dense_i))


def test_fused_empty_rows():
    """Zero-row chunks produce empty, correctly-shaped, correctly-typed
    outputs on both tiers (no Pallas grid is launched)."""
    rng = np.random.default_rng(2)
    for vocab_range in (50, vocab_lib.VMEM_TIER_MAX + 1):
        vocab = _random_vocab(rng, 2, vocab_range)
        sparse = jnp.zeros((0, 2), jnp.int32)
        dense = jnp.zeros((0, 3), jnp.int32)
        ids, den = ops.fused_transform(vocab, sparse, dense, use_kernel=True)
        assert ids.shape == (0, 2) and ids.dtype == jnp.int32
        assert den.shape == (0, 3) and den.dtype == jnp.float32


def test_fused_all_padding_rows_chunk():
    """A chunk whose rows are all decode padding (valid all-False) still
    transforms bit-identically — padding rows flow through the chain
    unmasked in both the fused and unfused paths."""
    schema = schema_lib.TableSchema(n_dense=3, n_sparse=2, vocab_range=64)
    cfgs = [
        P.PipelineConfig(
            schema=schema, input_format="binary", use_fused_kernel=f
        )
        for f in (True, False)
    ]
    chunk = {
        "label": jnp.zeros(16, jnp.int32),
        "dense": jnp.zeros((16, 3), jnp.int32),
        "sparse": jnp.zeros((16, 2), jnp.int32),
        "valid": jnp.zeros(16, bool),
    }
    rng = np.random.default_rng(3)
    vocab = _random_vocab(rng, 2, 64)
    outs = [P.PiperPipeline(c).transform_chunk(vocab, chunk) for c in cfgs]
    np.testing.assert_array_equal(np.asarray(outs[0].sparse), np.asarray(outs[1].sparse))
    np.testing.assert_allclose(np.asarray(outs[0].dense), np.asarray(outs[1].dense), rtol=1e-6)
    assert not np.asarray(outs[0].valid).any()


@pytest.mark.parametrize("row_block", [8, 64, 256])
def test_fused_kernel_interpret_mode_row_blocks(row_block):
    """The raw kernels under interpret=True across tile sizes — the grid,
    block specs, and padding interplay the CPU CI must pin down."""
    rng = np.random.default_rng(4)
    rows = row_block * 3
    lay = vocab_lib.VocabLayout((97, 5, 300))
    vocab = vocab_lib.Vocabulary(
        table=jnp.asarray(rng.integers(0, 97, size=lay.entries).astype(np.int32)),
        sizes=jnp.full((3,), 97, jnp.int32),
        layout=lay,
    )
    cols = dict(ranges=lay.ranges, offsets=lay.offsets, row_block=row_block)
    sparse, dense = _random_inputs(rng, rows, 3, 2)
    ids, den = fx_kernel.fused_transform(
        vocab.table, sparse, dense, interpret=True, **cols
    )
    ids_r, den_r = fx_ref.fused_transform(vocab, sparse, dense)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))
    np.testing.assert_allclose(np.asarray(den), np.asarray(den_r), rtol=1e-6)

    keys, den2 = fx_kernel.fused_mod_dense(sparse, dense, interpret=True, **cols)
    exp_mod = np.asarray(sparse).view(np.uint32) % np.asarray(lay.ranges, np.uint32)
    exp_keys = exp_mod.astype(np.int32) + np.asarray(lay.offsets, np.int32)
    np.testing.assert_array_equal(np.asarray(keys), exp_keys)
    np.testing.assert_allclose(np.asarray(den2), np.asarray(den_r), rtol=1e-6)


def test_fused_modulus_uint32_semantics():
    """The kernel's modulus treats int32 bitcasts as unsigned, including
    INT32_MIN / -1 / INT32_MAX (the hashes-are-always-positive contract)."""
    rng = np.random.default_rng(5)
    vocab = _random_vocab(rng, 1, 5000)
    edge = np.array(
        [[-(2**31)], [-1], [0], [1], [2**31 - 1], [-(2**31) + 1]], np.int32
    )
    dense = jnp.zeros((6, 1), jnp.int32)
    ids_f, _ = ops.fused_transform(vocab, jnp.asarray(edge), dense, use_kernel=True)
    exp = np.asarray(vocab.column(0))[edge.view(np.uint32) % np.uint32(5000)]
    np.testing.assert_array_equal(np.asarray(ids_f), exp.reshape(6, 1))


# --------------------------------------------------------------------- #
# end-to-end: the pipeline knob, all execution styles
# --------------------------------------------------------------------- #


def test_pipeline_fused_knob_matches_unfused(criteo_small, oracle_small):
    """run_stream with use_fused_kernel=True ≡ =False ≡ the CPU oracle."""
    buf, _, cfg = criteo_small
    outs = {}
    for fused in (False, True):
        pipe = P.PiperPipeline(
            P.PipelineConfig(
                schema=cfg.schema, max_rows_per_chunk=256, use_fused_kernel=fused
            )
        )
        res = list(pipe.run_stream(lambda: synth.chunk_stream(buf, 16384)))
        v = [np.asarray(o.valid) for o in res]
        outs[fused] = {
            "sparse": np.concatenate([np.asarray(o.sparse)[m] for o, m in zip(res, v)]),
            "dense": np.concatenate([np.asarray(o.dense)[m] for o, m in zip(res, v)]),
            "label": np.concatenate([np.asarray(o.label)[m] for o, m in zip(res, v)]),
        }
    np.testing.assert_array_equal(outs[True]["sparse"], outs[False]["sparse"])
    np.testing.assert_array_equal(outs[True]["label"], outs[False]["label"])
    np.testing.assert_allclose(outs[True]["dense"], outs[False]["dense"], rtol=1e-6)
    np.testing.assert_array_equal(outs[True]["sparse"], oracle_small["sparse"])
    np.testing.assert_allclose(outs[True]["dense"], oracle_small["dense"], rtol=1e-6)


def test_pipeline_fused_scan_matches_stream(criteo_small):
    """The fully-jitted scan path traces the fused kernel inside lax.scan
    and matches the host-driven stream path row-for-row."""
    buf, _, cfg = criteo_small
    pipe = P.PiperPipeline(
        P.PipelineConfig(
            schema=cfg.schema, max_rows_per_chunk=256, use_fused_kernel=True
        )
    )
    chunks = [jnp.asarray(c) for c in synth.chunk_stream(buf, 16384)]
    outs_stream = list(pipe.run_stream(lambda: iter(chunks)))
    out_scan = P.flatten_processed(pipe.run_scan(jnp.stack(chunks)))
    spa_s = np.concatenate(
        [np.asarray(o.sparse)[np.asarray(o.valid)] for o in outs_stream]
    )
    v = np.asarray(out_scan.valid)
    np.testing.assert_array_equal(np.asarray(out_scan.sparse)[v], spa_s)


def test_fused_knob_auto_resolution():
    """use_fused_kernel=None resolves to on only where Pallas *compiles*
    (TPU backend + importable toolchain — interpret mode on CPU is
    slower than the XLA-fused unfused chain, so auto stays off there);
    explicit values pass through; the knob survives dataclasses.replace
    (the scheduler's per-bucket config derivation)."""
    import jax

    from repro import kernels as kernels_lib

    cfg = P.PipelineConfig()
    assert cfg.use_fused_kernel is None
    expect = kernels_lib.pallas_available() and jax.default_backend() == "tpu"
    assert cfg.fused_enabled == expect
    assert P.PipelineConfig(use_fused_kernel=True).fused_enabled is True
    assert P.PipelineConfig(use_fused_kernel=False).fused_enabled is False
    derived = dataclasses.replace(cfg, use_fused_kernel=True, max_rows_per_chunk=64)
    assert derived.fused_enabled is True
