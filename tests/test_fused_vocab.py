"""Fused single-pass loop-① kernel: differential tests vs ``vocab.update``.

The fused kernel (kernels/fused_vocab) collapses Modulus → GenVocab
scatter-min into one dispatch and must be **bit-identical** to the
unfused ``positive_modulus`` → ``vocab.update`` chain — scatter-min is
order-independent, so the serial in-kernel RMW and the vectorized XLA
scatter must agree exactly — across both memory tiers, any shape,
random valid masks, duplicate keys, and hash values that overflow the
vocab range. Hypothesis property tests sweep random shapes; the
deterministic tests below carry the same coverage on environments
without hypothesis (tests/_hypothesis_fallback.py). The golden tests
pin the sha256 digest of the final preprocessing table on the 8-shard
and streaming-service paths with the fused loop-① enabled.

Everything here runs the kernels in Pallas ``interpret=True`` mode (the
repo-wide CPU convention), so tier-1 CI exercises the kernel logic
without accelerator hardware.
"""

import dataclasses
import hashlib
import os

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep — property tests skip, rest run
    from tests._hypothesis_fallback import given, settings, strategies as st

from repro.core import ops, pipeline as P, vocab as vocab_lib
from repro.data import synth
from repro.kernels.fused_vocab import kernel as fv_kernel
from repro.kernels.fused_vocab import ops as fv_ops
from repro.kernels.fused_vocab import ref as fv_ref
from tests.multidevice import run_with_devices

GOLDEN = os.path.join(os.path.dirname(__file__), "goldens", "fused_small.npz")


def _random_inputs(rng, rows: int, n_cols: int):
    """Raw hash bitcasts spanning the full int32 range (so the uint32
    modulus and vocab-range overflow both get exercised)."""
    return jnp.asarray(
        rng.integers(-(2**31), 2**31 - 1, size=(rows, n_cols), dtype=np.int64).astype(
            np.int32
        )
    )


def _assert_fused_matches_unfused(state, sparse, valid):
    # oracle first: the fused kernel donates the state's first_pos buffer
    upd_u = ops.fused_vocab_update(state, sparse, valid, use_kernel=False)
    upd_f = ops.fused_vocab_update(state, sparse, valid, use_kernel=True)
    assert upd_f.first_pos.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(upd_f.first_pos), np.asarray(upd_u.first_pos)
    )
    assert int(upd_f.rows_seen) == int(upd_u.rows_seen)
    return upd_u


# --------------------------------------------------------------------- #
# hypothesis: random shapes, valid masks, duplicates, range overflow
# --------------------------------------------------------------------- #


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 70),
    n_cols=st.integers(1, 6),
    seed=st.integers(0, 1 << 30),
    offset=st.integers(0, 1 << 20),
    vocab_range=st.sampled_from(
        [3, 97, 5000, vocab_lib.VMEM_TIER_MAX, vocab_lib.VMEM_TIER_MAX + 3]
    ),
)
def test_fused_equals_update_property(rows, n_cols, seed, offset, vocab_range):
    """∀ shapes, valid masks, and vocab ranges straddling VMEM_TIER_MAX:
    fused ≡ ``vocab.update`` oracle. vocab_range=3 forces duplicate keys
    in every chunk; full-range int32 hashes overflow every range."""
    rng = np.random.default_rng(seed)
    sparse = _random_inputs(rng, rows, n_cols)
    valid = jnp.asarray(rng.random(rows) < 0.7)
    st0 = vocab_lib.VocabState.init(n_cols, vocab_range)
    st0 = dataclasses.replace(st0, rows_seen=jnp.int32(offset))
    _assert_fused_matches_unfused(st0, sparse, valid)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1 << 30), n_chunks=st.integers(2, 5))
def test_fused_chunk_carry_property(seed, n_chunks):
    """Chained chunks: the VMEM-resident accumulator carried across
    calls (and across grid steps within a call) equals one oracle pass."""
    rng = np.random.default_rng(seed)
    f_state = vocab_lib.VocabState.init(3, 53)
    u_state = vocab_lib.VocabState.init(3, 53)
    for _ in range(n_chunks):
        rows = int(rng.integers(1, 40))
        sparse = _random_inputs(rng, rows, 3)
        valid = jnp.asarray(rng.random(rows) < 0.8)
        u_state = ops.fused_vocab_update(u_state, sparse, valid, use_kernel=False)
        f_state = ops.fused_vocab_update(f_state, sparse, valid, use_kernel=True)
    np.testing.assert_array_equal(
        np.asarray(f_state.first_pos), np.asarray(u_state.first_pos)
    )
    assert int(f_state.rows_seen) == int(u_state.rows_seen)


# --------------------------------------------------------------------- #
# deterministic: same coverage without hypothesis
# --------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "vocab_range,tier",
    [
        (5000, "vmem"),
        (vocab_lib.VMEM_TIER_MAX, "vmem"),
        (vocab_lib.VMEM_TIER_MAX + 1, "hbm_slab"),
    ],
    ids=["paper-5k", "tier-max", "tier-max+1"],
)
def test_fused_matches_update_both_tiers(vocab_range, tier):
    """Differential equivalence on either side of the VMEM cutoff.

    Row counts straddle the wrapper's padding logic: 1500 > 1024 forces
    blk=1024 with 548 pad rows, 5 runs as one whole-array block —
    padding must scatter nothing."""
    assert fv_ops.fused_vocab_tier((vocab_range,)) == tier
    rng = np.random.default_rng(0)
    for rows in (1500, 5):
        sparse = _random_inputs(rng, rows, 1)
        valid = jnp.asarray(rng.random(rows) < 0.9)
        _assert_fused_matches_unfused(
            vocab_lib.VocabState.init(1, vocab_range), sparse, valid
        )


def test_fused_state_budget_routes_to_hbm():
    """A state stack under the per-column cutoff but over the whole-stack
    VMEM budget must route to the hbm_slab tier (the fused kernel keeps
    ALL column states resident, unlike the one-column-at-a-time genvocab
    kernel)."""
    vocab_range = vocab_lib.VMEM_TIER_MAX  # per-column: fits
    n_over = fv_ops.FUSED_STATE_VMEM_BYTES // (vocab_range * 4) + 1
    assert fv_ops.fused_vocab_tier((vocab_range,) * n_over) == "hbm_slab"
    assert fv_ops.fused_vocab_tier((vocab_range,)) == "vmem"


def test_fused_duplicate_keys_min_combine():
    """Equal hashes within one chunk (and across tiles) must keep the
    smallest position — the serial RMW and the vectorized scatter-min
    agree bit-for-bit."""
    rng = np.random.default_rng(1)
    # every value collides many times: 600 rows into range 7
    sparse = jnp.asarray(rng.integers(0, 7, size=(600, 4), dtype=np.int64).astype(np.int32))
    valid = jnp.ones(600, bool)
    upd = _assert_fused_matches_unfused(
        vocab_lib.VocabState.init(4, 7), sparse, valid
    )
    # non-vacuous: all 7 buckets of every column were hit
    assert all((np.asarray(upd.column(c)) < vocab_lib.NEVER).all() for c in range(4))


def test_fused_all_invalid_chunk_sweep():
    """All-invalid chunks (decode padding) leave first_pos untouched and
    advance rows_seen by zero, on both tiers and across row blocks."""
    for vocab_range in (50, vocab_lib.VMEM_TIER_MAX + 1):
        for rows in (1, 8, 300):
            st0 = vocab_lib.VocabState.init(2, vocab_range)
            upd = ops.fused_vocab_update(
                st0,
                jnp.zeros((rows, 2), jnp.int32),
                jnp.zeros(rows, bool),
                use_kernel=True,
            )
            assert (np.asarray(upd.first_pos) == vocab_lib.NEVER).all()
            assert int(upd.rows_seen) == 0


def test_fused_empty_shapes():
    """Zero-row and zero-column chunks: no Pallas grid is launched; the
    state passes through with only rows_seen bookkeeping."""
    st0 = vocab_lib.VocabState.init(2, 40)
    upd = ops.fused_vocab_update(
        st0, jnp.zeros((0, 2), jnp.int32), jnp.zeros(0, bool)
    )
    assert upd.first_pos.shape == st0.first_pos.shape and int(upd.rows_seen) == 0
    assert upd.layout == st0.layout and upd.layout.ranges == (40, 40)
    st1 = vocab_lib.VocabState.init(0, 40)
    upd1 = ops.fused_vocab_update(
        st1, jnp.zeros((16, 0), jnp.int32), jnp.ones(16, bool)
    )
    assert upd1.layout.ranges == () and int(upd1.rows_seen) == 16
    assert (np.asarray(upd1.first_pos) == vocab_lib.NEVER).all()


@pytest.mark.parametrize("row_block", [8, 64, 256])
def test_fused_kernel_interpret_mode_row_blocks(row_block):
    """The raw kernel under interpret=True across tile sizes — the grid,
    the constant-index-map state residency, the first-step aliased-state
    copy, and the cross-tile carry the CPU CI must pin down."""
    rng = np.random.default_rng(4)
    rows = row_block * 3
    sparse = _random_inputs(rng, rows, 3)
    pos = jnp.arange(rows, dtype=jnp.int32)
    lay = vocab_lib.VocabLayout((97, 5, 300))
    state = lay.pack(
        [
            np.where(rng.random(r) < 0.3, rng.integers(0, 50, size=r), vocab_lib.NEVER)
            for r in lay.ranges
        ],
        vocab_lib.NEVER,
    )
    cols = dict(ranges=lay.ranges, offsets=lay.offsets)
    expect = fv_ref.fused_genvocab(state, sparse, pos, **cols)
    got = fv_kernel.fused_genvocab(
        state,  # donated — ref computed first
        sparse,
        pos,
        row_block=row_block,
        interpret=True,
        **cols,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_fused_modulus_uint32_semantics():
    """The kernel's modulus treats int32 bitcasts as unsigned, including
    INT32_MIN / -1 / INT32_MAX (the hashes-are-always-positive contract)."""
    edge = np.array(
        [[-(2**31)], [-1], [0], [1], [2**31 - 1], [-(2**31) + 1]], np.int32
    )
    st0 = vocab_lib.VocabState.init(1, 5000)
    upd = ops.fused_vocab_update(
        st0, jnp.asarray(edge), jnp.ones(6, bool), use_kernel=True
    )
    exp_vals = edge.view(np.uint32)[:, 0] % np.uint32(5000)
    fp = np.asarray(upd.column(0))
    for i, v in enumerate(exp_vals):
        assert fp[int(v)] <= i  # first occurrence at (or before) row i
    assert (fp < vocab_lib.NEVER).sum() == len(set(exp_vals.tolist()))


# --------------------------------------------------------------------- #
# end-to-end: the pipeline knob, all execution styles
# --------------------------------------------------------------------- #


def test_pipeline_fused_vocab_knob_matches_unfused(criteo_small):
    """build_state_stream with use_fused_vocab=True ≡ =False, bit-for-bit
    (state AND finalized table), and the scan path matches the stream
    path with the fused kernel traced inside lax.scan."""
    buf, _, cfg = criteo_small
    states = {}
    for fv in (False, True):
        pipe = P.PiperPipeline(
            P.PipelineConfig(
                schema=cfg.schema, max_rows_per_chunk=256, use_fused_vocab=fv
            )
        )
        states[fv] = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    np.testing.assert_array_equal(
        np.asarray(states[True].first_pos), np.asarray(states[False].first_pos)
    )
    assert int(states[True].rows_seen) == int(states[False].rows_seen)

    pipe = P.PiperPipeline(
        P.PipelineConfig(
            schema=cfg.schema, max_rows_per_chunk=256, use_fused_vocab=True
        )
    )
    chunks = [jnp.asarray(c) for c in synth.chunk_stream(buf, 16384)]
    vocab_scan = pipe.build_vocab_scan(jnp.stack(chunks))
    vocab_stream = vocab_lib.finalize(states[False])
    np.testing.assert_array_equal(
        np.asarray(vocab_scan.table), np.asarray(vocab_stream.table)
    )
    np.testing.assert_array_equal(
        np.asarray(vocab_scan.sizes), np.asarray(vocab_stream.sizes)
    )


def test_fused_vocab_knob_auto_resolution():
    """use_fused_vocab=None resolves exactly like use_fused_kernel=None
    (kernels.resolve_fused: on iff Pallas compiles — TPU backend);
    explicit values pass through; the knob survives dataclasses.replace
    (the scheduler's per-bucket config derivation)."""
    import jax

    from repro import kernels as kernels_lib

    cfg = P.PipelineConfig()
    assert cfg.use_fused_vocab is None
    expect = kernels_lib.pallas_available() and jax.default_backend() == "tpu"
    assert cfg.fused_vocab_enabled == expect
    assert P.PipelineConfig(use_fused_vocab=True).fused_vocab_enabled is True
    assert P.PipelineConfig(use_fused_vocab=False).fused_vocab_enabled is False
    derived = dataclasses.replace(cfg, use_fused_vocab=True, max_rows_per_chunk=64)
    assert derived.fused_vocab_enabled is True
    # and the compiler surfaces the route
    pipe = P.PiperPipeline(P.PipelineConfig(use_fused_vocab=True))
    assert pipe.compiled.vocab_route == "fused/vmem"
    assert "vocab ×26 → fused/vmem" in pipe.compiled.describe()
    pipe_off = P.PiperPipeline(P.PipelineConfig(use_fused_vocab=False))
    assert pipe_off.compiled.vocab_route == "unfused"


def test_fused_vocab_with_crossed_plan():
    """HashCross vocab rows route through the same fused loop-① dispatch:
    a crossed plan builds bit-identical state fused vs unfused."""
    from repro.core import plan as plan_lib

    schema = dataclasses.replace(P.PipelineConfig().schema, n_dense=3, n_sparse=4)
    plan = plan_lib.crossed_criteo(schema)
    rng = np.random.default_rng(9)
    chunk = {
        "label": jnp.asarray(rng.integers(0, 2, 64).astype(np.int32)),
        "dense": jnp.asarray(rng.integers(-50, 500, (64, 3)).astype(np.int32)),
        "sparse": jnp.asarray(
            rng.integers(-(2**31), 2**31 - 1, (64, 4), dtype=np.int64).astype(np.int32)
        ),
        "valid": jnp.asarray(rng.random(64) < 0.9),
    }
    states = {}
    for fv in (False, True):
        pipe = P.PiperPipeline(
            P.PipelineConfig(
                schema=schema, input_format="binary", plan=plan, use_fused_vocab=fv
            )
        )
        states[fv] = pipe.build_state_stream([chunk])
    # n_sparse plain columns + 1 cross, each with its own vocab row
    assert states[True].layout.n_columns == schema.n_sparse + 1
    np.testing.assert_array_equal(
        np.asarray(states[True].first_pos), np.asarray(states[False].first_pos)
    )


# --------------------------------------------------------------------- #
# goldens: sha256 digest on the stream and 8-shard paths
# --------------------------------------------------------------------- #


def _digest(label: np.ndarray, sparse: np.ndarray) -> str:
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(label, np.int32).tobytes())
    h.update(np.ascontiguousarray(sparse, np.int32).tobytes())
    return h.hexdigest()


def test_golden_stream_service_fused_vocab():
    """The streaming service with loop ① run ONLINE through the fused
    dispatch (service.absorb per chunk) reproduces the golden digest —
    the online-ingested vocabulary is bit-identical to the offline one."""
    from repro.stream import StreamingPreprocessService

    g = np.load(GOLDEN)
    cfg = P.PipelineConfig(
        chunk_bytes=int(g["chunk_bytes"]),
        max_rows_per_chunk=int(g["max_rows_per_chunk"]),
        use_fused_vocab=True,
    )
    # empty starting state: every row of the vocabulary is absorbed online
    empty = P.PiperPipeline(cfg).init_state()
    rows = int(g["rows"])
    svc = StreamingPreprocessService(cfg, empty, bucket_rows=(32, 128), queue_depth=8)
    spans = synth.row_spans(g["buf"])
    with svc:
        row0 = 0
        while row0 < rows:  # 12-row slices stay inside chunk_bytes=4096
            n = min(12, rows - row0)
            payload = g["buf"][spans[row0, 0] : spans[row0 + n - 1, 1]]
            svc.absorb(payload, row_offset=row0)
            row0 += n
        # wait for the between-steps atomic swap of the last delta
        import time

        deadline = time.time() + 30
        while int(svc.vocab_state.rows_seen) < rows:
            assert time.time() < deadline, "absorbed deltas never applied"
            time.sleep(0.002)
        sizes = [7, 1, 30, 13, rows - 51]
        handles = [
            svc.submit(p)
            for p in synth.request_payloads(g["buf"], None, sizes, "utf8")
        ]
        svc.drain(timeout=120)
        results = [h.result(timeout=5) for h in handles]
    label = np.concatenate([r["label"] for r in results])
    sparse = np.concatenate([r["sparse"] for r in results])
    dense = np.concatenate([r["dense"] for r in results])
    np.testing.assert_array_equal(label, g["label"])
    np.testing.assert_array_equal(sparse, g["sparse"])
    np.testing.assert_allclose(dense, g["dense"], rtol=1e-6)
    assert _digest(label, sparse) == str(g["digest"])


_SHARDED_GOLDEN_FUSED_VOCAB = """
import hashlib, numpy as np, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, sharded_pipeline as SP
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

g = np.load({golden_path!r})
cb = int(g["chunk_bytes"])
pc = P.PipelineConfig(chunk_bytes=cb, max_rows_per_chunk=int(g["max_rows_per_chunk"]),
                      use_fused_kernel=True, use_fused_vocab=True)
mesh = make_data_mesh(8)
feed = loader.TabularChunkFeed(g["buf"], cb, 8)
stacks, offsets = feed.shard_stacks()
eng = SP.ShardedPiperPipeline(pc, mesh)
assert eng.compiled.vocab_route == "fused/vmem", eng.compiled.vocab_route
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
def test_golden_sharded_8_devices_fused_vocab():
    """The 8-shard engine with the fused loop-① dispatch inside every
    shard_map body (unchanged merge_tree) reproduces the golden digest
    bit-for-bit."""
    code = _SHARDED_GOLDEN_FUSED_VOCAB.format(golden_path=GOLDEN)
    assert "OK" in run_with_devices(code, n_devices=8)
