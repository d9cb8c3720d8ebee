"""Plan IR + compiler tests.

Three layers of assurance:

  * **Golden bit-identity** — an *explicit* ``plan.criteo_default()``
    compiled through the new compiler reproduces the pre-refactor golden
    fixture (tests/goldens/fused_small.npz, sha256-digested) on the
    single-device, 8-shard, and streaming-service paths.
  * **Semantics** — deterministic numpy references for the new ops
    (``Bucketize`` / ``Clip`` / ``MinMaxScale`` / ``HashCross``), a
    first-occurrence-ordinal reference for crossed vocab columns, and a
    hypothesis property holding random per-column dense recipes to their
    per-op references through grouping + assembly.
  * **Validation** — malformed plans (unknown column, vocab op on a dense
    column, broken chains, bad params) fail compile with
    :class:`~repro.core.plan_compiler.PlanError` before any tracing.
"""

import hashlib
import os

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from tests._hypothesis_fallback import given, settings, strategies as st

from repro.core import ops
from repro.core import pipeline as P
from repro.core import plan as plan_lib
from repro.core import plan_compiler
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib
from repro.core.plan import ColumnSpec, PreprocPlan, op
from repro.data import synth
from tests.multidevice import run_with_devices

GOLDEN = os.path.join(os.path.dirname(__file__), "goldens", "fused_small.npz")

SMALL = schema_lib.TableSchema(n_dense=4, n_sparse=5, vocab_range=101)


@pytest.fixture(scope="module")
def golden():
    g = np.load(GOLDEN)
    return {k: g[k] for k in g.files}


# --------------------------------------------------------------------- #
# numpy references
# --------------------------------------------------------------------- #
def hash_cross_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    ua, ub = a.view(np.uint32), b.view(np.uint32)
    h = np.multiply(ua, np.uint32(0x85EBCA6B), dtype=np.uint32)
    h = h ^ ((ub << np.uint32(13)) | (ub >> np.uint32(19)))
    h = np.multiply(h, np.uint32(0xC2B2AE35), dtype=np.uint32)
    h = h ^ (h >> np.uint32(16))
    return h.view(np.int32)


def ordinals_np(modded: np.ndarray) -> np.ndarray:
    """Appearing-sequence ordinals of one modded column (the GenVocab/
    ApplyVocab contract): rank of each value's first occurrence."""
    vals, first = np.unique(modded, return_index=True)
    rank = {v: r for r, v in enumerate(vals[np.argsort(first, kind="stable")])}
    return np.array([rank[v] for v in modded], np.int32)


def _binary_batch(schema, rows, seed):
    table = synth.generate_binary(
        synth.SynthConfig(schema=schema, rows=rows, seed=seed, sparse_pool=64)
    )
    return table, schema_lib.TabularBatch(
        label=jnp.asarray(table["label"]),
        dense=jnp.asarray(table["dense"]),
        sparse=jnp.asarray(table["sparse"]),
        valid=jnp.ones(rows, bool),
    )


# --------------------------------------------------------------------- #
# golden bit-identity: explicit plan through the compiler
# --------------------------------------------------------------------- #
def _digest(label, sparse):
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(label, np.int32).tobytes())
    h.update(np.ascontiguousarray(sparse, np.int32).tobytes())
    return h.hexdigest()


def _golden_config(golden, **overrides):
    overrides.setdefault("plan", plan_lib.criteo_default(schema_lib.CRITEO))
    return P.PipelineConfig(
        chunk_bytes=int(golden["chunk_bytes"]),
        max_rows_per_chunk=int(golden["max_rows_per_chunk"]),
        **overrides,
    )


def _assert_golden(golden, label, dense, sparse):
    np.testing.assert_array_equal(label, golden["label"])
    np.testing.assert_array_equal(sparse, golden["sparse"])
    np.testing.assert_allclose(dense, golden["dense"], rtol=1e-6)
    assert _digest(label, sparse) == str(golden["digest"])


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_plan_golden_single_device(golden, fused):
    """criteo_default through compile_plan ≡ the pre-refactor golden."""
    pipe = P.PiperPipeline(_golden_config(golden, use_fused_kernel=fused))
    assert pipe.compiled.n_vocab_columns == schema_lib.CRITEO.n_sparse
    outs = list(
        pipe.run_stream(
            lambda: synth.chunk_stream(golden["buf"], int(golden["chunk_bytes"]))
        )
    )
    v = [np.asarray(o.valid) for o in outs]
    _assert_golden(
        golden,
        np.concatenate([np.asarray(o.label)[m] for o, m in zip(outs, v)]),
        np.concatenate([np.asarray(o.dense)[m] for o, m in zip(outs, v)]),
        np.concatenate([np.asarray(o.sparse)[m] for o, m in zip(outs, v)]),
    )


def test_plan_golden_stream_service(golden):
    from repro.stream import StreamingPreprocessService

    cfg = _golden_config(golden, use_fused_kernel=True)
    pipe = P.PiperPipeline(cfg)
    state = pipe.build_state_stream(
        synth.chunk_stream(golden["buf"], int(golden["chunk_bytes"]))
    )
    rows = int(golden["rows"])
    sizes = [5, 17, 2, 40] + [rows - 64]
    with StreamingPreprocessService(cfg, state, bucket_rows=(64, 128)) as svc:
        handles = [
            svc.submit(p)
            for p in synth.request_payloads(golden["buf"], None, sizes, "utf8")
        ]
        svc.drain(timeout=120)
        results = [h.result(timeout=5) for h in handles]
    _assert_golden(
        golden,
        np.concatenate([r["label"] for r in results]),
        np.concatenate([r["dense"] for r in results]),
        np.concatenate([r["sparse"] for r in results]),
    )


_SHARDED_SNIPPET = """
import hashlib, numpy as np, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, plan as plan_lib, sharded_pipeline as SP
from repro.core import schema as schema_lib
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

g = np.load({golden_path!r})
cb = int(g["chunk_bytes"])
pc = P.PipelineConfig(chunk_bytes=cb, max_rows_per_chunk=int(g["max_rows_per_chunk"]),
                      use_fused_kernel=True,
                      plan=plan_lib.criteo_default(schema_lib.CRITEO))
mesh = make_data_mesh(8)
feed = loader.TabularChunkFeed(g["buf"], cb, 8)
stacks, offsets = feed.shard_stacks()
eng = SP.ShardedPiperPipeline(pc, mesh)
assert eng.compiled.n_vocab_columns == 26
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
def test_plan_golden_sharded_8_devices():
    """Explicit criteo_default plan, 8-shard engine ≡ the golden digest."""
    code = _SHARDED_SNIPPET.format(golden_path=GOLDEN)
    assert "OK" in run_with_devices(code, n_devices=8)


@given(seed=st.integers(0, 2**16 - 1))
@settings(max_examples=10, deadline=None)
def test_plan_criteo_property_matches_legacy_chain(seed):
    """Property: the compiled default plan ≡ the pre-IR inline chain
    (modulus → lookup ∥ neg2zero → log1p) on random binary batches."""
    rows = 64
    _, batch = _binary_batch(schema_lib.CRITEO, rows, seed)
    pipe = P.PiperPipeline(
        P.PipelineConfig(input_format="binary", use_fused_kernel=False)
    )
    state = pipe.vocab_step(pipe.init_state(), dataclass_chunk(batch))
    vocabulary = vocab_lib.finalize(state)
    out = pipe.transform_chunk(vocabulary, dataclass_chunk(batch))
    modded = ops.positive_modulus(batch.sparse, schema_lib.CRITEO.vocab_range)
    np.testing.assert_array_equal(
        np.asarray(out.sparse), np.asarray(vocab_lib.lookup(vocabulary, modded))
    )
    np.testing.assert_allclose(
        np.asarray(out.dense),
        np.log1p(np.maximum(np.asarray(batch.dense, np.float32), 0.0)),
        rtol=1e-6,
    )


def dataclass_chunk(batch):
    return {
        "label": batch.label,
        "dense": batch.dense,
        "sparse": batch.sparse,
        "valid": batch.valid,
    }


# --------------------------------------------------------------------- #
# new-op semantics
# --------------------------------------------------------------------- #
def test_bucketize_semantics():
    x = jnp.asarray([[-5.0], [0.0], [0.5], [1.0], [9.0], [10.0], [1e9]])
    got = np.asarray(ops.bucketize(x, (0.0, 1.0, 10.0)))
    # x == boundary lands in the upper bucket (side="right")
    np.testing.assert_array_equal(got[:, 0], [0, 1, 1, 2, 2, 3, 3])
    assert got.dtype == np.float32


def test_clip_and_minmax_semantics():
    x = jnp.asarray([[-3.0, 0.0, 2.5, 99.0]])
    np.testing.assert_allclose(
        np.asarray(ops.clip(x, 0.0, 10.0))[0], [0.0, 0.0, 2.5, 10.0]
    )
    np.testing.assert_allclose(
        np.asarray(ops.minmax_scale(x, 0.0, 10.0))[0], [0.0, 0.0, 0.25, 1.0]
    )


def test_hash_cross_matches_numpy_reference():
    rng = np.random.default_rng(5)
    a = rng.integers(0, 1 << 32, size=257, dtype=np.uint64).astype(np.uint32).view(np.int32)
    b = rng.integers(0, 1 << 32, size=257, dtype=np.uint64).astype(np.uint32).view(np.int32)
    got = np.asarray(ops.hash_cross(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, hash_cross_np(a, b))
    # the cross must differ from both inputs (it is a new feature)
    assert (got != a).any() and (got != b).any()


def test_crossed_vocab_ordinals_match_reference():
    """A HashCross → Modulus → GenVocab → ApplyVocab column carries its own
    vocab row whose ordinals follow the appearing-sequence contract."""
    rows = 300
    table, batch = _binary_batch(SMALL, rows, seed=9)
    plan = plan_lib.crossed_criteo(
        SMALL, crosses=((1, 3),), bucket_cols=(), boundaries=(0.0,)
    )
    pipe = P.PiperPipeline(
        P.PipelineConfig(schema=SMALL, input_format="binary", plan=plan)
    )
    vocabulary = vocab_lib.finalize(
        pipe.vocab_step(pipe.init_state(), dataclass_chunk(batch))
    )
    out = pipe.transform_chunk(vocabulary, dataclass_chunk(batch))
    crossed = hash_cross_np(table["sparse"][:, 1], table["sparse"][:, 3])
    modded = crossed.view(np.uint32) % np.uint32(SMALL.vocab_range)
    np.testing.assert_array_equal(
        np.asarray(out.sparse)[:, SMALL.n_sparse], ordinals_np(modded)
    )
    # source columns keep their plain ordinals
    m1 = table["sparse"][:, 1].view(np.uint32) % np.uint32(SMALL.vocab_range)
    np.testing.assert_array_equal(np.asarray(out.sparse)[:, 1], ordinals_np(m1))


_DENSE_RECIPES = {
    "canonical": (
        plan_lib.DENSE_CANONICAL,
        lambda x: np.log1p(np.maximum(x.astype(np.float32), 0.0)),
    ),
    "clip": (
        (op("Clip", lo=-5.0, hi=50.0),),
        lambda x: np.clip(x.astype(np.float32), np.float32(-5.0), np.float32(50.0)),
    ),
    "minmax": (
        (op("MinMaxScale", lo=0.0, hi=100.0),),
        lambda x: np.clip(x.astype(np.float32), np.float32(0), np.float32(100))
        / np.float32(100.0),
    ),
    "bucketize": (
        (op("Bucketize", boundaries=(0.0, 10.0, 100.0)),),
        lambda x: np.searchsorted(
            np.asarray([0.0, 10.0, 100.0], np.float32),
            x.astype(np.float32),
            side="right",
        ).astype(np.float32),
    ),
    "clip_log": (
        (op("Clip", lo=0.0, hi=1000.0), op("Logarithm")),
        lambda x: np.log1p(np.clip(x.astype(np.float32), np.float32(0), np.float32(1000))),
    ),
}


@given(seed=st.integers(0, 2**16 - 1))
@settings(max_examples=15, deadline=None)
def test_random_dense_recipes_property(seed):
    """Property: any per-column mix of dense recipes — which exercises
    grouping, multi-route assembly, and column scatter — matches the
    per-op numpy references column by column."""
    rng = np.random.default_rng(seed)
    names = list(_DENSE_RECIPES)
    picks = [names[i] for i in rng.integers(0, len(names), size=SMALL.n_dense)]
    cols = [
        ColumnSpec(kind="dense", source=i, ops=_DENSE_RECIPES[p][0], name=f"d{i}_{p}")
        for i, p in enumerate(picks)
    ] + [
        ColumnSpec(kind="sparse", source=j, ops=plan_lib.SPARSE_CANONICAL, name=f"s{j}")
        for j in range(SMALL.n_sparse)
    ]
    plan = PreprocPlan(columns=tuple(cols))
    table, batch = _binary_batch(SMALL, 128, seed)
    pipe = P.PiperPipeline(
        P.PipelineConfig(schema=SMALL, input_format="binary", plan=plan)
    )
    vocabulary = vocab_lib.finalize(
        pipe.vocab_step(pipe.init_state(), dataclass_chunk(batch))
    )
    out = np.asarray(pipe.transform_chunk(vocabulary, dataclass_chunk(batch)).dense)
    for i, p in enumerate(picks):
        np.testing.assert_allclose(
            out[:, i],
            _DENSE_RECIPES[p][1](table["dense"][:, i]),
            rtol=1e-6,
            err_msg=f"column {i} recipe {p}",
        )


# --------------------------------------------------------------------- #
# compiler structure
# --------------------------------------------------------------------- #
def test_grouping_by_signature():
    plan = plan_lib.crossed_criteo(SMALL, crosses=((0, 1), (2, 3)), bucket_cols=(0, 2))
    compiled = plan_compiler.compile_plan(plan, SMALL, fused=False)
    kinds = {(g.kind, tuple(o.name for o in g.signature)): g for g in compiled.groups}
    # 26→5 canonical sparse in ONE group, both crosses in ONE group
    assert len(kinds[("sparse", ("Modulus", "GenVocab", "ApplyVocab"))].out_slots) == 5
    cross = kinds[("sparse", ("HashCross", "Modulus", "GenVocab", "ApplyVocab"))]
    assert cross.out_slots == (5, 6) and cross.sources == ((0, 1), (2, 3))
    # both bucketized dense columns share one group; the rest are canonical
    assert len(kinds[("dense", ("Bucketize",))].out_slots) == 2
    assert len(kinds[("dense", ("Neg2Zero", "Logarithm"))].out_slots) == 2
    assert compiled.n_vocab_columns == 7
    assert "HashCross" in compiled.describe()


def test_modulus_only_column_keeps_schema_default_range():
    """A param-less Modulus on a non-vocab column defaults to the SCHEMA's
    range even when the plan's vocab columns override theirs (regression:
    the compiler once leaked the vocab range into it)."""
    cols = (
        ColumnSpec(kind="sparse", source=0,
                   ops=(op("Modulus", range=1000), op("GenVocab"), op("ApplyVocab"))),
        ColumnSpec(kind="sparse", source=1, ops=(op("Modulus"),)),
        ColumnSpec(kind="dense", source=0, ops=plan_lib.DENSE_CANONICAL),
    )
    table, batch = _binary_batch(SMALL, 64, seed=3)
    pipe = P.PiperPipeline(
        P.PipelineConfig(schema=SMALL, input_format="binary",
                         plan=PreprocPlan(cols), use_fused_kernel=False)
    )
    assert pipe.compiled.vocab_ranges == (1000,)
    vocabulary = vocab_lib.finalize(
        pipe.vocab_step(pipe.init_state(), dataclass_chunk(batch))
    )
    out = pipe.transform_chunk(vocabulary, dataclass_chunk(batch))
    expect = table["sparse"][:, 1].view(np.uint32) % np.uint32(SMALL.vocab_range)
    np.testing.assert_array_equal(np.asarray(out.sparse)[:, 1], expect.astype(np.int32))


def test_tier_uses_apply_columns_not_vocab_rows():
    """GenVocab-without-ApplyVocab columns add vocab rows that the fused
    gather never reads, but they live in the one flat table the gather
    holds — the reported tier must be the tier of that table, the one
    the dispatch picks at run time."""
    big = schema_lib.TableSchema(n_dense=1, n_sparse=8, vocab_range=500_000)
    # 7 GenVocab-only columns inflate the vocab table stack past the fused
    # residency budget; the single apply column still fits VMEM.
    cols = tuple(
        ColumnSpec(kind="sparse", source=j, ops=(op("Modulus"), op("GenVocab")))
        for j in range(7)
    ) + (
        ColumnSpec(kind="sparse", source=7,
                   ops=(op("Modulus"), op("GenVocab"), op("ApplyVocab"))),
        ColumnSpec(kind="dense", source=0, ops=plan_lib.DENSE_CANONICAL),
    )
    compiled = plan_compiler.compile_plan(PreprocPlan(cols), big, fused=True)
    assert compiled.n_vocab_columns == 8
    from repro.kernels.fused_xform import ops as fx_ops

    assert compiled._apply_columns() == (7,)
    assert compiled.tier == fx_ops.fused_tier(compiled.vocab_layout) == "hbm"
    one = vocab_lib.VocabLayout((big.vocab_range,))
    assert fx_ops.fused_tier(one) == "vmem"  # one column's table alone fits


def test_fused_hint_without_canonical_dense_routes_unfused():
    """With every dense column bucketized there is no dense half for the
    fused kernel to carry; the compiler must route the vocab-apply group
    unfused (and say so) instead of silently falling back to the jnp
    oracle behind a 'fused' label."""
    plan = plan_lib.crossed_criteo(
        SMALL, crosses=(), bucket_cols=tuple(range(SMALL.n_dense))
    )
    compiled = plan_compiler.compile_plan(plan, SMALL, fused=True)
    assert not compiled._fused_dispatch
    routes = {g.route for g in compiled.groups if g.kind == "sparse"}
    assert routes == {"unfused"}
    # outputs still match the unfused-compiled program exactly
    _, batch = _binary_batch(SMALL, 64, seed=13)
    ref = plan_compiler.compile_plan(plan, SMALL, fused=False)
    vocabulary = vocab_lib.finalize(compiled.vocab_step(compiled.init_state(), batch))
    np.testing.assert_array_equal(
        np.asarray(compiled.transform(vocabulary, batch).sparse),
        np.asarray(ref.transform(vocabulary, batch).sparse),
    )


def test_vocab_range_override_routes_tier():
    cols = tuple(
        ColumnSpec(
            kind="sparse",
            source=j,
            ops=(op("Modulus", range=2_000_000), op("GenVocab"), op("ApplyVocab")),
        )
        for j in range(SMALL.n_sparse)
    ) + (ColumnSpec(kind="dense", source=0, ops=plan_lib.DENSE_CANONICAL),)
    compiled = plan_compiler.compile_plan(PreprocPlan(cols), SMALL, fused=True)
    assert compiled.vocab_ranges == (2_000_000,) * SMALL.n_sparse
    assert compiled.tier == "hbm"
    small = plan_compiler.compile_plan(plan_lib.criteo_default(SMALL), SMALL, fused=True)
    assert small.tier == "vmem"


# --------------------------------------------------------------------- #
# validation
# --------------------------------------------------------------------- #
def _compile(cols):
    return plan_compiler.compile_plan(PreprocPlan(tuple(cols)), SMALL, fused=False)


def test_validation_errors():
    PlanError = plan_compiler.PlanError
    dense_ok = ColumnSpec(kind="dense", source=0, ops=plan_lib.DENSE_CANONICAL)
    with pytest.raises(PlanError, match="unknown column"):
        _compile([ColumnSpec(kind="sparse", source=99, ops=plan_lib.SPARSE_CANONICAL)])
    with pytest.raises(PlanError, match="applies to sparse columns"):
        _compile([ColumnSpec(kind="dense", source=0,
                             ops=(op("Modulus"), op("GenVocab"), op("ApplyVocab")))])
    with pytest.raises(PlanError, match="unknown op"):
        _compile([ColumnSpec(kind="dense", source=0, ops=(op("Sqrt"),))])
    with pytest.raises(PlanError, match="ApplyVocab requires"):
        _compile([ColumnSpec(kind="sparse", source=0,
                             ops=(op("Modulus"), op("ApplyVocab")))])
    with pytest.raises(PlanError, match="GenVocab requires"):
        _compile([ColumnSpec(kind="sparse", source=0, ops=(op("GenVocab"),))])
    with pytest.raises(PlanError, match="pair source"):
        _compile([ColumnSpec(kind="sparse", source=0, ops=(op("HashCross"),))])
    with pytest.raises(PlanError, match="HashCross"):
        _compile([ColumnSpec(kind="sparse", source=(0, 1), ops=(op("Modulus"),))])
    # each vocab column keeps its own range (one flat, offset-indexed
    # state), two UNNAMED specs over the same source included
    assert _compile([
        ColumnSpec(kind="sparse", source=0,
                   ops=(op("Modulus", range=7), op("GenVocab"), op("ApplyVocab"))),
        ColumnSpec(kind="sparse", source=0,
                   ops=(op("Modulus", range=8), op("GenVocab"), op("ApplyVocab"))),
    ]).vocab_ranges == (7, 8)
    for bad in (0, -3):
        with pytest.raises(PlanError, match="positive int"):
            _compile([
                ColumnSpec(kind="sparse", source=0,
                           ops=(op("Modulus", range=bad), op("GenVocab"),
                                op("ApplyVocab"))),
            ])
    with pytest.raises(PlanError, match="boundaries"):
        _compile([ColumnSpec(kind="dense", source=0,
                             ops=(op("Bucketize", boundaries=(3.0, 1.0)),))])
    with pytest.raises(PlanError, match="lo < hi"):
        _compile([ColumnSpec(kind="dense", source=0, ops=(op("Clip", lo=5.0, hi=1.0),))])
    with pytest.raises(PlanError, match="no param"):
        _compile([ColumnSpec(kind="dense", source=0, ops=(op("Neg2Zero", gain=2),))])
    with pytest.raises(PlanError, match="no columns"):
        _compile([])
    import dataclasses

    named = dataclasses.replace(dense_ok, name="x")
    with pytest.raises(PlanError, match="duplicate column names"):
        _compile([named, dataclasses.replace(named, source=1)])


def test_service_rejects_mismatched_vocab_state():
    from repro.stream import StreamingPreprocessService

    crossed = plan_lib.crossed_criteo(SMALL, crosses=((0, 1),), bucket_cols=())
    cfg = P.PipelineConfig(schema=SMALL, input_format="binary", plan=crossed)
    # a state built with the *default* plan has one vocab row too few
    default_pipe = P.PiperPipeline(
        P.PipelineConfig(schema=SMALL, input_format="binary")
    )
    with pytest.raises(ValueError, match="does not match the plan"):
        StreamingPreprocessService(cfg, default_pipe.init_state())


# --------------------------------------------------------------------- #
# crossed plan end-to-end: single-device ≡ sharded ≡ streaming
# --------------------------------------------------------------------- #
def _crossed_plan():
    return plan_lib.crossed_criteo(
        schema_lib.CRITEO,
        crosses=((0, 1), (4, 9)),
        bucket_cols=(0, 5),
        boundaries=(0.0, 2.0, 20.0, 200.0),
    )


def test_crossed_plan_end_to_end(criteo_small):
    """The acceptance scenario: a crossed + bucketized plan runs through
    the single-device engine (stream + scan), the sharded engine, and
    the streaming service, all bit-identical to each other."""
    buf, table, cfg = criteo_small
    plan = _crossed_plan()
    chunk_bytes = 1 << 15
    pc = P.PipelineConfig(
        schema=cfg.schema,
        chunk_bytes=chunk_bytes,
        max_rows_per_chunk=512,
        plan=plan,
        use_fused_kernel=False,
    )
    pipe = P.PiperPipeline(pc)
    assert pipe.compiled.n_sparse_out == cfg.schema.n_sparse + 2
    outs = list(pipe.run_stream(lambda: synth.chunk_stream(buf, chunk_bytes)))
    v = [np.asarray(o.valid) for o in outs]
    ref_sparse = np.concatenate([np.asarray(o.sparse)[m] for o, m in zip(outs, v)])
    ref_dense = np.concatenate([np.asarray(o.dense)[m] for o, m in zip(outs, v)])
    ref_label = np.concatenate([np.asarray(o.label)[m] for o, m in zip(outs, v)])
    assert ref_sparse.shape[1] == cfg.schema.n_sparse + 2

    # bucketized dense columns hold integral bucket ids, not log1p values
    assert np.all(ref_dense[:, 0] == np.floor(ref_dense[:, 0]))
    assert ref_dense[:, 0].max() <= 4

    # sharded path (1 'data' shard on the single test device — the full
    # 8-shard sweep runs in the slow subprocess test below)
    from repro.core import sharded_pipeline as SP
    from repro.data import loader
    from repro.distributed.sharding import put_shard_feed
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(1)
    feed = loader.TabularChunkFeed(buf, chunk_bytes, 1)
    stacks, offsets = feed.shard_stacks()
    eng = SP.ShardedPiperPipeline(pc, mesh)
    cs, os_ = put_shard_feed(jnp.asarray(stacks), jnp.asarray(offsets), mesh)
    sh = SP.flatten_sharded(eng.run_scan(cs, os_))
    m = np.asarray(sh.valid)
    np.testing.assert_array_equal(np.asarray(sh.sparse)[m], ref_sparse)
    np.testing.assert_allclose(np.asarray(sh.dense)[m], ref_dense, rtol=1e-6)

    # streaming path
    from repro.stream import StreamingPreprocessService

    state = pipe.build_state_stream(synth.chunk_stream(buf, chunk_bytes))
    rows = ref_label.shape[0]
    sizes = [13, 100, 1, 86] + [rows - 200]
    with StreamingPreprocessService(pc, state, bucket_rows=(256, 512)) as svc:
        handles = [
            svc.submit(p) for p in synth.request_payloads(buf, None, sizes, "utf8")
        ]
        svc.drain(timeout=120)
        results = [h.result(timeout=5) for h in handles]
    np.testing.assert_array_equal(
        np.concatenate([r["sparse"] for r in results]), ref_sparse
    )
    np.testing.assert_allclose(
        np.concatenate([r["dense"] for r in results]), ref_dense, rtol=1e-6
    )
    np.testing.assert_array_equal(
        np.concatenate([r["label"] for r in results]), ref_label
    )


_CROSSED_SHARDED_SNIPPET = """
import numpy as np, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, plan as plan_lib, sharded_pipeline as SP
from repro.core import schema as schema_lib
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

cfg = synth.SynthConfig(rows=400, seed=42)
buf, _ = synth.make_dataset(cfg)
plan = plan_lib.crossed_criteo(schema_lib.CRITEO, crosses=((0, 1), (4, 9)),
                               bucket_cols=(0, 5),
                               boundaries=(0.0, 2.0, 20.0, 200.0))
cb = 1 << 15
pc = P.PipelineConfig(schema=cfg.schema, chunk_bytes=cb, max_rows_per_chunk=512,
                      plan=plan, use_fused_kernel=False)
pipe = P.PiperPipeline(pc)
outs = list(pipe.run_stream(lambda: synth.chunk_stream(buf, cb)))
v = [np.asarray(o.valid) for o in outs]
ref_sparse = np.concatenate([np.asarray(o.sparse)[m] for o, m in zip(outs, v)])
ref_dense = np.concatenate([np.asarray(o.dense)[m] for o, m in zip(outs, v)])

mesh = make_data_mesh(8)
feed = loader.TabularChunkFeed(buf, cb, 8)
stacks, offsets = feed.shard_stacks()
eng = SP.ShardedPiperPipeline(pc, mesh)
cs, os_ = put_shard_feed(jnp.asarray(stacks), jnp.asarray(offsets), mesh)
out = SP.flatten_sharded(eng.run_scan(cs, os_))
m = np.asarray(out.valid)
np.testing.assert_array_equal(np.asarray(out.sparse)[m], ref_sparse)
np.testing.assert_allclose(np.asarray(out.dense)[m], ref_dense, rtol=1e-6)
print("OK")
"""


@pytest.mark.slow
def test_crossed_plan_sharded_8_devices():
    """Crossed + bucketized plan: 8-shard engine ≡ single-device engine."""
    assert "OK" in run_with_devices(_CROSSED_SHARDED_SNIPPET, n_devices=8)
