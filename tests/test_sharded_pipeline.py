"""Data-parallel sharded engine: bit-identity with the single-device
engine (8 host devices, subprocess) + merge monoid laws (in-process)."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vocab as vocab_lib
from tests.multidevice import run_with_devices

# --------------------------------------------------------------------- #
# (a) sharded run_scan ≡ single-device run_scan, shard counts 1/2/4/8
# --------------------------------------------------------------------- #

_DATA_PARALLEL = """
import numpy as np, jax, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, sharded_pipeline as SP
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

cfg = synth.SynthConfig(rows=600, seed=11)
buf, _ = synth.make_dataset(cfg)
pc = P.PipelineConfig(schema=cfg.schema, chunk_bytes=8192, max_rows_per_chunk=128)

for n_shards in (1, 2, 4, 8):
    mesh = make_data_mesh(n_shards)
    feed = loader.TabularChunkFeed(buf, 8192, n_shards)
    stacks, offsets = feed.shard_stacks()
    eng = SP.ShardedPiperPipeline(pc, mesh)
    cs, os_ = put_shard_feed(jnp.asarray(stacks), jnp.asarray(offsets), mesh)
    out_sh = SP.flatten_sharded(eng.run_scan(cs, os_))

    pipe = P.PiperPipeline(pc)
    out_ref = P.flatten_processed(pipe.run_scan(jnp.asarray(feed.stacked.reshape(-1, 8192))))

    # bit-identical: same vocabulary ordinals, same dense float transforms
    for name in ("label", "valid", "sparse", "dense"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_sh, name)),
            np.asarray(getattr(out_ref, name)),
            err_msg=f"shards={n_shards} field={name}",
        )
    # vocabulary itself is identical too (not just the mapped ids)
    voc_sh = eng.build_vocab_scan(cs, os_)
    voc_ref = pipe.build_vocab_scan(jnp.asarray(feed.stacked.reshape(-1, 8192)))
    np.testing.assert_array_equal(np.asarray(voc_sh.table), np.asarray(voc_ref.table))
    np.testing.assert_array_equal(np.asarray(voc_sh.sizes), np.asarray(voc_ref.sizes))
print("OK")
"""


@pytest.mark.slow
def test_sharded_pipeline_bit_identical_to_single_device():
    assert "OK" in run_with_devices(_DATA_PARALLEL, n_devices=8)


# --------------------------------------------------------------------- #
# (a') binary (paper Config III) input path through the sharded engine:
#      sharded binary ≡ single-device binary ≡ single-device utf8
# --------------------------------------------------------------------- #

_BINARY_CONFIG_III = """
import numpy as np, jax, jax.numpy as jnp
from repro.data import synth, loader
from repro.core import pipeline as P, sharded_pipeline as SP
from repro.launch.mesh import make_data_mesh
from repro.distributed.sharding import put_shard_feed

cfg = synth.SynthConfig(rows=600, seed=13)
buf, table = synth.make_dataset(cfg)
pc_bin = P.PipelineConfig(schema=cfg.schema, input_format="binary", max_rows_per_chunk=128)
pc_utf = P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=128)

def valid_rows(out):
    v = np.asarray(out.valid)
    return {k: np.asarray(getattr(out, k))[v] for k in ("label", "dense", "sparse")}

# utf8 single-device reference (Config I/II)
pipe_utf = P.PiperPipeline(pc_utf)
ref_utf = valid_rows(P.flatten_processed(
    pipe_utf.run_scan(jnp.stack([jnp.asarray(c) for c in synth.chunk_stream(buf, 8192)]))))

for n_shards in (1, 2, 4, 8):
    feed = loader.BinaryChunkFeed(table, rows_per_chunk=128, n_row_shards=n_shards)

    # single-device binary scan over the identical chunk sequence
    pipe_bin = P.PiperPipeline(pc_bin)
    flat = {k: jnp.asarray(v) for k, v in feed.flat_chunks().items()}
    out_ref = P.flatten_processed(pipe_bin.run_scan(flat))

    chunks, offsets = feed.shard_stacks()
    mesh = make_data_mesh(n_shards)
    eng = SP.ShardedPiperPipeline(pc_bin, mesh)
    cs, os_ = put_shard_feed(
        {k: jnp.asarray(v) for k, v in chunks.items()}, jnp.asarray(offsets), mesh)
    out_sh = SP.flatten_sharded(eng.run_scan(cs, os_))

    # sharded binary ≡ single-device binary, padding rows included
    for name in ("label", "valid", "sparse", "dense"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out_sh, name)), np.asarray(getattr(out_ref, name)),
            err_msg=f"shards={n_shards} field={name}")
    # binary ≡ utf8 on valid rows (Config III produces Config I's table)
    got = valid_rows(out_sh)
    for name in ("label", "sparse", "dense"):
        np.testing.assert_array_equal(got[name], ref_utf[name],
            err_msg=f"shards={n_shards} binary-vs-utf8 field={name}")
print("OK")
"""


@pytest.mark.slow
def test_sharded_binary_config_iii_bit_identical():
    assert "OK" in run_with_devices(_BINARY_CONFIG_III, n_devices=8)


# --------------------------------------------------------------------- #
# (b) merge is a commutative monoid under random states (no hypothesis
#     dependency — plain numpy randomness, runs on the bare environment)
# --------------------------------------------------------------------- #


def _rand_state(rng, n_cols=3, vocab_range=41) -> vocab_lib.VocabState:
    """A random plausible loop-① state: ~half the values seen."""
    fp = rng.integers(0, 10_000, size=(n_cols, vocab_range)).astype(np.int32)
    seen = rng.random((n_cols, vocab_range)) < 0.5
    fp = np.where(seen, fp, vocab_lib.NEVER)
    lay = vocab_lib.layout((vocab_range,) * n_cols)
    return vocab_lib.VocabState(
        first_pos=lay.pack(fp, vocab_lib.NEVER),
        rows_seen=jnp.int32(int(rng.integers(0, 1000))),
        layout=lay,
    )


def _assert_state_equal(a: vocab_lib.VocabState, b: vocab_lib.VocabState):
    np.testing.assert_array_equal(np.asarray(a.first_pos), np.asarray(b.first_pos))
    np.testing.assert_array_equal(np.asarray(a.rows_seen), np.asarray(b.rows_seen))


def test_merge_associative():
    rng = np.random.default_rng(0)
    for _ in range(10):
        a, b, c = (_rand_state(rng) for _ in range(3))
        _assert_state_equal(
            vocab_lib.merge(vocab_lib.merge(a, b), c),
            vocab_lib.merge(a, vocab_lib.merge(b, c)),
        )


def test_merge_commutative():
    rng = np.random.default_rng(1)
    for _ in range(10):
        a, b = (_rand_state(rng) for _ in range(2))
        _assert_state_equal(vocab_lib.merge(a, b), vocab_lib.merge(b, a))


def test_merge_identity():
    """VocabState.init is the monoid identity element."""
    rng = np.random.default_rng(2)
    a = _rand_state(rng)
    ident = vocab_lib.VocabState.init(a.layout)
    _assert_state_equal(vocab_lib.merge(a, ident), a)
    _assert_state_equal(vocab_lib.merge(ident, a), a)


@pytest.mark.parametrize("n_shards", [1, 2, 3, 5, 8])
def test_merge_tree_matches_sequential_reduce(n_shards):
    """Tree-reduce == left fold, for power-of-two and ragged shard counts."""
    rng = np.random.default_rng(3 + n_shards)
    shards = [_rand_state(rng) for _ in range(n_shards)]
    stacked = vocab_lib.VocabState(
        first_pos=jnp.stack([s.first_pos for s in shards]),
        rows_seen=jnp.stack([s.rows_seen for s in shards]),
        layout=shards[0].layout,
    )
    _assert_state_equal(
        vocab_lib.merge_tree(stacked), functools.reduce(vocab_lib.merge, shards)
    )


def test_merge_order_invariant_vocabulary():
    """Finalized vocabulary is invariant to shard merge order — the
    property that makes the multi-instance deployment deterministic."""
    rng = np.random.default_rng(4)
    shards = [_rand_state(rng, n_cols=2, vocab_range=17) for _ in range(4)]
    perm = [2, 0, 3, 1]
    fwd = functools.reduce(vocab_lib.merge, shards)
    shuffled = functools.reduce(vocab_lib.merge, [shards[i] for i in perm])
    np.testing.assert_array_equal(
        np.asarray(vocab_lib.finalize(fwd).table),
        np.asarray(vocab_lib.finalize(shuffled).table),
    )
