"""Schemas whose sparse columns each keep their own modulus range.

One flat, offset-indexed ``VocabState`` serves every schema
(``vocab.VocabLayout``): column ``c``'s value ``v`` lives at
``offsets[c] + uint32(v) mod ranges[c]``. These tests hold every engine
to the row-wise baseline (``core/baseline.py``) and to a numpy
first-occurrence reference on small ragged schemas: both input formats,
both loop-① tiers, both of the slab pre-pass's sort forms, ``run_scan``,
the capped finalizers, four forced host devices, and the stream service.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import baseline, pipeline as P, schema as schema_lib, vocab as vocab_lib
from repro.data import synth
from repro.kernels.fused_vocab import kernel as fv_kernel
from tests.multidevice import run_with_devices

# ranges from a handful of buckets to a column that spans several 2,048-
# entry slabs; the 4th column's range is a lane multiple, the others not
RAGGED = schema_lib.TableSchema(n_dense=4, n_sparse=6, vocab_range=(3, 130, 1000, 5120, 9000, 7))
SLAB = 2048


@pytest.fixture(scope="module")
def data():
    cfg = synth.SynthConfig(schema=RAGGED, rows=700, seed=29, sparse_pool=3000)
    buf, table = synth.make_dataset(cfg)
    return buf, table, baseline.run_pipeline(buf, RAGGED, n_threads=3)


def first_occurrence(sparse: np.ndarray, ranges) -> tuple[np.ndarray, np.ndarray]:
    """numpy reference: per column, each row's ``uint32 % range`` as its
    rank in order of first appearance; and each column's size."""
    u = sparse.view(np.uint32).astype(np.int64) % np.asarray(ranges, np.int64)
    ids = np.empty(u.shape, np.int32)
    sizes = []
    for c in range(u.shape[1]):
        uniq, first, inv = np.unique(u[:, c], return_index=True, return_inverse=True)
        rank = np.empty(len(uniq), np.int64)
        rank[np.argsort(first)] = np.arange(len(uniq))
        ids[:, c] = rank[inv.reshape(-1)]
        sizes.append(len(uniq))
    return ids, np.asarray(sizes)


def _chunks(buf, table, fmt):
    if fmt == "utf8":
        return list(synth.chunk_stream(buf, 4096))
    rows = table["label"].shape[0]
    return [
        {
            "label": table["label"][lo : lo + 128],
            "dense": table["dense"][lo : lo + 128],
            "sparse": table["sparse"][lo : lo + 128],
        }
        for lo in range(0, rows, 128)
    ]


def _config(fmt, tier, **kw):
    return P.PipelineConfig(
        schema=RAGGED,
        input_format=fmt,
        chunk_bytes=4096,
        max_rows_per_chunk=128,
        use_fused_vocab=tier != "unfused",
        vocab_slab_range=SLAB if tier == "hbm_slab" else None,
        **kw,
    )


def _valid_rows(outs):
    outs = [jax.tree.map(np.asarray, o) for o in outs]
    return {
        k: np.concatenate([getattr(o, k)[o.valid] for o in outs])
        for k in ("label", "dense", "sparse")
    }


@pytest.fixture
def fresh_jit_caches():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_layout_offsets_and_counters():
    lay = vocab_lib.layout(RAGGED.ranges)
    assert lay.offsets == (0, 128, 384, 1408, 6528, 15616)
    assert lay.range_entries == sum(RAGGED.ranges) == 15260
    assert lay.entries % 1024 == 0 and lay.entries >= lay.used == 15744
    reg = obs.metrics()
    before = {n: reg.counter(n).value for n in ("vocab.state_entries", "vocab.range_entries")}
    st = vocab_lib.VocabState.init(lay)
    assert reg.counter("vocab.state_entries").value - before["vocab.state_entries"] == st.first_pos.size
    assert reg.counter("vocab.range_entries").value - before["vocab.range_entries"] == 15260
    assert vocab_lib.layout((5000,) * 26).offsets[1] == 5120  # uniform: c × 5120


@pytest.mark.parametrize("fmt", ["utf8", "binary"])
@pytest.mark.parametrize("tier", ["unfused", "vmem", "hbm_slab"])
def test_pipeline_matches_baseline_and_reference(data, fmt, tier):
    buf, table, oracle = data
    pipe = P.PiperPipeline(_config(fmt, tier))
    assert pipe.compiled.vocab_route == ("unfused" if tier == "unfused" else f"fused/{tier}")
    chunks = _chunks(buf, table, fmt)
    state = pipe.build_state_stream(chunks)
    assert state.layout.ranges == RAGGED.ranges
    if tier == "hbm_slab":
        assert state.layout.n_slabs > 1  # the 9,000 column spans slabs
    vocabulary = vocab_lib.finalize(state)
    got = _valid_rows(pipe.transform_stream(vocabulary, chunks))
    ids, sizes = first_occurrence(table["sparse"], RAGGED.ranges)
    np.testing.assert_array_equal(got["sparse"], ids)
    np.testing.assert_array_equal(got["sparse"], oracle["sparse"])
    np.testing.assert_array_equal(got["label"], oracle["label"])
    np.testing.assert_allclose(got["dense"], oracle["dense"], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(vocabulary.sizes), sizes)
    assert (ids.max(axis=0) < np.asarray(RAGGED.ranges)).all()


def test_two_operand_sort_form(data, monkeypatch, fresh_jit_caches):
    """Ranges past the packed sort key (value << row bits | row) sort
    their values with the rows as a second operand; narrowing the key to
    12 bits takes that form here."""
    buf, table, _ = data
    monkeypatch.setattr(fv_kernel, "KEY_BITS", 12)
    assert fv_kernel.key_row_bits(max(RAGGED.ranges), 128) is None
    pipe = P.PiperPipeline(_config("binary", "hbm_slab"))
    chunks = _chunks(buf, table, "binary")
    got = pipe.build_state_stream(chunks)
    want = P.PiperPipeline(_config("binary", "unfused")).build_state_stream(chunks)
    np.testing.assert_array_equal(np.asarray(got.first_pos), np.asarray(want.first_pos))


@pytest.mark.parametrize("tier", ["unfused", "hbm_slab"])
def test_run_scan_matches_reference(data, tier):
    buf, table, _ = data
    pipe = P.PiperPipeline(_config("utf8", tier))
    stacked = jnp.stack([jnp.asarray(c) for c in _chunks(buf, table, "utf8")])
    got = _valid_rows([P.flatten_processed(pipe.run_scan(stacked))])
    ids, _ = first_occurrence(table["sparse"], RAGGED.ranges)
    np.testing.assert_array_equal(got["sparse"], ids)


@pytest.mark.parametrize("tier", ["vmem", "hbm_slab"])
def test_capped_finalizers_per_column(data, tier):
    buf, table, _ = data
    pipe = P.PiperPipeline(_config("binary", tier, track_vocab_counts=True))
    state = pipe.build_state_stream(_chunks(buf, table, "binary"))
    u = table["sparse"].view(np.uint32).astype(np.int64) % np.asarray(RAGGED.ranges)
    for c, r in enumerate(RAGGED.ranges):
        counts = np.bincount(u[:, c], minlength=r)
        np.testing.assert_array_equal(np.asarray(state.layout.column(state.counts, c)), counts)
    for vocab, keep in (
        (vocab_lib.finalize_topk(state, 4), lambda cnt, first: np.lexsort((first, -cnt))[:4]),
        (vocab_lib.finalize_min_count(state, 3), lambda cnt, first: np.nonzero(cnt >= 3)[0]),
    ):
        for c, r in enumerate(RAGGED.ranges):
            col = u[:, c]
            cnt = np.bincount(col, minlength=r)
            first = np.full(r, np.iinfo(np.int64).max)
            np.minimum.at(first, col, np.arange(col.size))
            kept = [v for v in keep(cnt, first) if cnt[v] > 0]
            kept = sorted(kept, key=lambda v: first[v])
            table_c = np.asarray(vocab.column(c))
            want = np.full(r, len(kept), np.int32)  # OOV ordinal = size
            want[kept] = np.arange(len(kept))
            np.testing.assert_array_equal(table_c, want)
            assert int(vocab.sizes[c]) == len(kept)


_SHARDED_RAGGED = """
import numpy as np, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, schema as S, sharded_pipeline as SP
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

schema = S.TableSchema(n_dense=4, n_sparse=6, vocab_range=(3, 130, 1000, 5120, 9000, 7))
cfg = synth.SynthConfig(schema=schema, rows=600, seed=31, sparse_pool=3000)
buf, _ = synth.make_dataset(cfg)
for slab in (None, 2048):
    pc = P.PipelineConfig(schema=schema, chunk_bytes=4096, max_rows_per_chunk=128,
                          use_fused_vocab=True, vocab_slab_range=slab)
    for n_shards in (1, 4):
        mesh = make_data_mesh(n_shards)
        feed = loader.TabularChunkFeed(buf, 4096, n_shards)
        stacks, offsets = feed.shard_stacks()
        eng = SP.ShardedPiperPipeline(pc, mesh)
        cs, os_ = put_shard_feed(jnp.asarray(stacks), jnp.asarray(offsets), mesh)
        out_sh = SP.flatten_sharded(eng.run_scan(cs, os_))
        pipe = P.PiperPipeline(pc)
        out_ref = P.flatten_processed(pipe.run_scan(jnp.asarray(feed.stacked.reshape(-1, 4096))))
        for name in ("label", "valid", "sparse", "dense"):
            np.testing.assert_array_equal(np.asarray(getattr(out_sh, name)),
                                          np.asarray(getattr(out_ref, name)),
                                          err_msg=f"slab={slab} shards={n_shards} {name}")
        voc_sh = eng.build_vocab_scan(cs, os_)
        voc_ref = pipe.build_vocab_scan(jnp.asarray(feed.stacked.reshape(-1, 4096)))
        np.testing.assert_array_equal(np.asarray(voc_sh.table), np.asarray(voc_ref.table))
        assert voc_sh.layout == voc_ref.layout and voc_sh.layout.ranges == schema.ranges
print("OK")
"""


@pytest.mark.slow
def test_sharded_ragged_bit_identical_to_single_device():
    assert "OK" in run_with_devices(_SHARDED_RAGGED, n_devices=4)


def test_stream_service_takes_a_ragged_schema(data):
    from repro.stream import StreamingPreprocessService

    buf, table, oracle = data
    cfg = _config("utf8", "vmem")
    state = P.PiperPipeline(cfg).build_state_stream(_chunks(buf, table, "utf8"))
    sizes = [5, 40, 100, 200]
    with StreamingPreprocessService(cfg, state, bucket_rows=(32, 128), queue_depth=8) as svc:
        handles = [svc.submit(p) for p in synth.request_payloads(buf, None, sizes, "utf8")]
        svc.drain(timeout=120)
        results = [h.result(timeout=5) for h in handles]
    got = np.concatenate([r["sparse"] for r in results])
    np.testing.assert_array_equal(got, oracle["sparse"][: sum(sizes)])


def test_engines_without_ragged_support_refuse_it():
    from repro.core import sharded
    from repro.launch.mesh import make_data_mesh
    from repro.models import dlrm

    with pytest.raises(ValueError, match="per-column ranges"):
        dlrm.DLRMConfig(vocab_range=RAGGED.vocab_range)
    with pytest.raises(ValueError, match="per-column ranges"):
        sharded.ShardedPiper(_config("utf8", "vmem"), make_data_mesh(1))


def test_uniform_layout_is_the_rectangle():
    """A uniform schema is the special case offsets[c] = c × padded
    range: its flat state reshapes to the old [n_cols, range] rows."""
    lay = vocab_lib.layout((100,) * 3)
    rng = np.random.default_rng(0)
    rect = rng.integers(0, 50, size=(3, 100)).astype(np.int32)
    flat = lay.pack(rect, vocab_lib.NEVER)
    np.testing.assert_array_equal(np.asarray(lay.rect(flat))[:, :100], rect)
    for c in range(3):
        np.testing.assert_array_equal(np.asarray(lay.column(flat, c)), rect[c])
