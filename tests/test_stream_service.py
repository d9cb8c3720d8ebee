"""Online streaming service: bit-identity with the offline engine,
shape discipline (no steady-state recompiles), vocab refresh, lifecycle."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as P, schema as schema_lib, vocab as vocab_lib
from repro.data import synth
from repro.stream import StreamingPreprocessService, make_request
from repro.stream import scheduler as scheduler_lib

BUCKETS = (32, 128, 512)


def _offline_reference(pipe, buf):
    """Valid rows of the offline two-loop engine (the ground truth the
    service's reassembled per-request outputs must match bit-for-bit)."""
    lab, den, spa = [], [], []
    for o in pipe.run_stream(lambda: synth.chunk_stream(buf, 16384)):
        v = np.asarray(o.valid)
        lab.append(np.asarray(o.label)[v])
        den.append(np.asarray(o.dense)[v])
        spa.append(np.asarray(o.sparse)[v])
    return np.concatenate(lab), np.concatenate(den), np.concatenate(spa)


def _random_splits(rng, total, max_size):
    sizes, left = [], total
    while left > 0:
        n = int(min(rng.integers(1, max_size + 1), left))
        sizes.append(n)
        left -= n
    return sizes


def _submit_rows(svc, fmt, buf, table, spans, row0, n):
    if fmt == "utf8":
        return svc.submit(buf[spans[row0, 0] : spans[row0 + n - 1, 1]])
    return svc.submit({k: table[k][row0 : row0 + n] for k in ("label", "dense", "sparse")})


def _reassemble(handles):
    outs = [h.result(timeout=60) for h in handles]
    return (
        np.concatenate([o["label"] for o in outs]),
        np.concatenate([o["dense"] for o in outs]),
        np.concatenate([o["sparse"] for o in outs]),
    )


# --------------------------------------------------------------------- #
# bit-identity: any request interleaving reassembles to loop ②'s table
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("fmt", ["utf8", "binary"])
def test_stream_reassembles_offline_table(criteo_small, fmt):
    buf, table, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=256, input_format=fmt)
    pipe = P.PiperPipeline(pc)

    if fmt == "utf8":
        state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
        ref_pipe, ref_buf = pipe, buf
    else:
        chunk = {k: jnp.asarray(table[k]) for k in ("label", "dense", "sparse")}
        state = pipe.build_state_stream([chunk])
        # reference through the utf8 engine: binary serving must reproduce
        # the Config I/II table exactly (binary ≡ utf8, online included)
        ref_pipe = P.PiperPipeline(P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=256))
        ref_buf = buf
    ref_lab, ref_den, ref_spa = _offline_reference(ref_pipe, ref_buf)

    spans = synth.row_spans(buf)
    rng = np.random.default_rng(5)
    rows = cfg.rows
    svc = StreamingPreprocessService(pc, state, bucket_rows=BUCKETS, queue_depth=8)
    with svc:
        handles, row0 = [], 0
        for n in _random_splits(rng, rows, 300):
            handles.append(_submit_rows(svc, fmt, buf, table, spans, row0, n))
            row0 += n
        svc.drain(timeout=120)
        lab, den, spa = _reassemble(handles)

    np.testing.assert_array_equal(lab, ref_lab)
    np.testing.assert_array_equal(spa, ref_spa)
    np.testing.assert_array_equal(den, ref_den)  # bit-identical floats


# --------------------------------------------------------------------- #
# mid-stream incremental vocab refresh
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("fmt", ["utf8", "binary"])
def test_mid_stream_vocab_refresh(criteo_small, fmt):
    """Serve the first half on a half-built vocab, fold in the second
    half's loop-① delta mid-stream, serve the rest: the reassembled table
    equals the offline full-dataset run bit-for-bit (ordinals of values
    already present never change — later first-occurrences only append)."""
    buf, table, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=256, input_format=fmt)
    pipe = P.PiperPipeline(pc)
    ref_pipe = P.PiperPipeline(P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=256))
    ref_lab, ref_den, ref_spa = _offline_reference(ref_pipe, buf)

    rows = cfg.rows
    half = rows // 2
    spans = synth.row_spans(buf)

    if fmt == "utf8":
        first_chunks = list(synth.chunk_stream(buf[: spans[half - 1, 1]], 8192))
        delta_chunks = list(synth.chunk_stream(buf[spans[half, 0] :], 8192))
    else:
        cols = ("label", "dense", "sparse")
        first_chunks = [{k: jnp.asarray(table[k][:half]) for k in cols}]
        delta_chunks = [{k: jnp.asarray(table[k][half:]) for k in cols}]

    state_half = pipe.build_state_stream(first_chunks)
    # loop-① delta over the second half with *global* row positions: seed
    # rows_seen with the split offset, exactly how a follow-up offline job
    # over new data would report its state
    delta = dataclasses.replace(pipe.init_state(), rows_seen=jnp.int32(half))
    for chunk in delta_chunks:
        delta = pipe.vocab_step(delta, jax.tree.map(jnp.asarray, chunk))

    # the refresh genuinely grows the vocabulary (test is non-vacuous)
    sizes_half = np.asarray(vocab_lib.finalize(state_half).sizes)
    sizes_full = np.asarray(
        vocab_lib.finalize(vocab_lib.merge(state_half, delta)).sizes
    )
    assert (sizes_full > sizes_half).any()

    rng = np.random.default_rng(6)
    svc = StreamingPreprocessService(pc, state_half, bucket_rows=BUCKETS, queue_depth=8)
    with svc:
        handles, row0 = [], 0
        for n in _random_splits(rng, half, 200):
            handles.append(_submit_rows(svc, fmt, buf, table, spans, row0, n))
            row0 += n
        svc.refresh_vocab(delta)
        # wait for the between-steps atomic swap before feeding rows that
        # contain second-half-only values
        deadline = time.time() + 30
        while svc.vocab_state is state_half:
            assert time.time() < deadline, "vocab swap never applied"
            time.sleep(0.002)
        for n in _random_splits(rng, rows - half, 200):
            handles.append(_submit_rows(svc, fmt, buf, table, spans, row0, n))
            row0 += n
        svc.drain(timeout=120)
        lab, den, spa = _reassemble(handles)

    np.testing.assert_array_equal(lab, ref_lab)
    np.testing.assert_array_equal(spa, ref_spa)
    np.testing.assert_array_equal(den, ref_den)


# --------------------------------------------------------------------- #
# scheduler shape discipline: no recompilation after warmup
# --------------------------------------------------------------------- #


def test_no_recompile_after_warmup(criteo_small):
    """The no-recompile guarantee, asserted on the scheduler's own
    ``stream.recompiles_total`` counter (which measures compile-cache
    growth around *every* dispatch) rather than external jit cache-miss
    counting: after one warmup pass per bucket, the full bucket ladder
    AND an atomic vocab refresh cause zero further compilations."""
    buf, table, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    pipe = P.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    spans = synth.row_spans(buf)
    rows = cfg.rows

    svc = StreamingPreprocessService(pc, state, bucket_rows=BUCKETS, queue_depth=8)
    recompiles = svc.registry.counter("stream.recompiles_total")
    with svc:
        # warmup: hit every bucket once — each first dispatch compiles
        for cap in BUCKETS:
            n = min(cap, rows)
            _submit_rows(svc, "utf8", buf, table, spans, 0, n).result(timeout=60)
        assert recompiles.value == len(BUCKETS)  # one compile per bucket
        assert svc.compile_cache_size() == len(BUCKETS)

        # steady state across the FULL ladder: sizes landing in every
        # bucket, zero recompiles
        rng = np.random.default_rng(7)
        handles = []
        for cap in BUCKETS:
            for _ in range(8):
                n = int(rng.integers(max(1, cap // 2), min(cap, rows) + 1))
                handles.append(_submit_rows(svc, "utf8", buf, table, spans, 0, n))
        svc.drain(timeout=120)
        for h in handles:
            assert h.result()["label"].shape[0] > 0
        assert recompiles.value == len(BUCKETS)

        # an atomic vocab refresh swaps the table as a jit *argument* —
        # same shapes, so it must not invalidate any bucket executable
        delta = dataclasses.replace(pipe.init_state(), rows_seen=jnp.int32(rows))
        for chunk in synth.chunk_stream(buf, 16384):
            delta = pipe.vocab_step(delta, jax.tree.map(jnp.asarray, chunk))
        prev = svc.vocab_state
        svc.refresh_vocab(delta)
        # the loop publishes the merged state before it finalizes and
        # swaps; the apply counter moves only once the swap is done
        applied = svc.registry.counter("stream.vocab_apply_total")
        deadline = time.time() + 30
        while applied.value < 1:
            assert time.time() < deadline, "vocab swap never applied"
            time.sleep(0.002)
        assert svc.vocab_state is not prev

        # post-swap: the whole ladder again, still zero recompiles
        handles = [
            _submit_rows(
                svc, "utf8", buf, table, spans, 0, min(cap, rows)
            )
            for cap in BUCKETS
        ]
        svc.drain(timeout=120)
        for h in handles:
            assert h.result()["label"].shape[0] > 0
        assert recompiles.value == len(BUCKETS)  # zero steady-state recompiles
        assert svc.compile_cache_size() == len(BUCKETS)


# --------------------------------------------------------------------- #
# lifecycle: backpressure, drain, stop, admission errors
# --------------------------------------------------------------------- #


def test_backpressure_bounded_ingress(criteo_small):
    buf, table, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    pipe = P.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    spans = synth.row_spans(buf)

    svc = StreamingPreprocessService(pc, state, bucket_rows=(32, 128), queue_depth=2)
    with svc:
        handles = [
            _submit_rows(svc, "utf8", buf, table, spans, i * 4, 4) for i in range(50)
        ]
        svc.drain(timeout=120)
        assert all(h.done for h in handles)
        snap = svc.metrics.snapshot()
    assert snap["requests"] == 50
    assert snap["rows"] == 200
    assert snap["rows_per_s"] > 0
    assert snap["p99_ms"] >= snap["p50_ms"] >= 0


def test_oversized_request_split_utf8(criteo_small):
    """A utf8 request larger than the biggest bucket is split into
    bucket-sized whole-row sub-chunks whose row spans reassemble — the
    composite result is bit-identical to the offline reference."""
    buf, _, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    pipe = P.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    ref_lab, ref_den, ref_spa = _offline_reference(pipe, buf)
    spans = synth.row_spans(buf)
    svc = StreamingPreprocessService(pc, state, bucket_rows=(32, 64), queue_depth=8)
    with svc:
        h = svc.submit(buf[: spans[-1, 1]])  # 400 rows > 64-row max bucket
        assert isinstance(h, scheduler_lib.CompositeRequest)
        assert h.n_rows == cfg.rows and len(h.parts) == -(-cfg.rows // 64)
        out = h.result(timeout=120)
        assert h.done and h.latency_s is not None
    np.testing.assert_array_equal(out["label"], ref_lab)
    np.testing.assert_array_equal(out["sparse"], ref_spa)
    np.testing.assert_array_equal(out["dense"], ref_den)
    svc.stop()  # idempotent second stop


def test_oversized_request_split_over_16ki_rows():
    """A binary request bigger than the largest DEFAULT bucket (16Ki
    rows) splits into 16Ki-row sub-chunks and reassembles exactly."""
    schema = schema_lib.TableSchema(n_dense=2, n_sparse=3, vocab_range=64)
    pc = P.PipelineConfig(schema=schema, input_format="binary")
    rows = (1 << 14) + 2048  # 18432 > the 16Ki default max bucket
    rng = np.random.default_rng(11)
    table = {
        "label": rng.integers(0, 2, rows).astype(np.int32),
        "dense": rng.integers(-40, 400, (rows, 2)).astype(np.int32),
        "sparse": rng.integers(-(2**31), 2**31 - 1, (rows, 3), dtype=np.int64).astype(
            np.int32
        ),
    }
    pipe = P.PiperPipeline(pc)
    chunk = {k: jnp.asarray(v) for k, v in table.items()}
    state = pipe.build_state_stream([dict(chunk, valid=jnp.ones(rows, bool))])
    vocab = vocab_lib.finalize(state)
    ref = pipe.transform_chunk(vocab, dict(chunk, valid=jnp.ones(rows, bool)))

    svc = StreamingPreprocessService(pc, state, queue_depth=8)  # default buckets
    assert svc.scheduler.max_rows == 16384
    with svc:
        h = svc.submit(table)
        assert isinstance(h, scheduler_lib.CompositeRequest)
        assert [p.n_rows for p in h.parts] == [16384, rows - 16384]
        out = h.result(timeout=300)
    np.testing.assert_array_equal(out["label"], np.asarray(ref.label))
    np.testing.assert_array_equal(out["sparse"], np.asarray(ref.sparse))
    np.testing.assert_array_equal(out["dense"], np.asarray(ref.dense))


def test_split_single_oversized_row_rejected():
    """No row-aligned split exists when one row alone exceeds the byte
    capacity — that (and only that) still raises a clear error."""
    schema = schema_lib.TableSchema(n_dense=2, n_sparse=2, vocab_range=64)
    pc = P.PipelineConfig(schema=schema)
    state = vocab_lib.VocabState.init(2, 64)
    svc = StreamingPreprocessService(
        pc, state, bucket_rows=(4,), bytes_per_row=8, queue_depth=2
    )
    giant_row = ("1\t" + "9" * 40 + "\t2\tabc\tdef\n").encode()
    with svc:
        with pytest.raises(ValueError, match="no row-aligned split"):
            svc.submit(np.frombuffer(giant_row * 8, np.uint8))


def test_make_request_validation():
    pc = P.PipelineConfig(schema=schema_lib.CRITEO)
    with pytest.raises(ValueError, match="whole rows"):
        make_request(np.frombuffer(b"1\t2\t3", np.uint8), pc)
    pc_bin = P.PipelineConfig(schema=schema_lib.CRITEO, input_format="binary")
    with pytest.raises(ValueError, match="schema"):
        make_request(
            {
                "label": np.zeros(4, np.int32),
                "dense": np.zeros((4, 3), np.int32),
                "sparse": np.zeros((4, 26), np.int32),
            },
            pc_bin,
        )


def test_scheduler_bucket_selection():
    pc = P.PipelineConfig(schema=schema_lib.CRITEO)
    vocab = vocab_lib.finalize(vocab_lib.VocabState.init(26, 5000))
    sched = scheduler_lib.MicroBatchScheduler(pc, vocab, bucket_rows=(32, 128, 512))
    assert sched.select_bucket(1, 0).rows == 32
    assert sched.select_bucket(32, 0).rows == 32
    assert sched.select_bucket(33, 0).rows == 128
    assert sched.select_bucket(512, 0).rows == 512
    with pytest.raises(ValueError):
        sched.select_bucket(513, 0)
