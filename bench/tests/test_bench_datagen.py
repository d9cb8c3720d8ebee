"""The benchmark's vectorized generator against the program's per-row
encoder and its row-wise reference decoder."""

import json
import pathlib

import numpy as np
import pytest

import datagen
from repro.core import baseline, schema
from repro.data import synth

BENCH = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cfg():
    return json.loads((BENCH / "configs" / "criteo-kaggle-5k.json").read_text())


def test_encoding_equals_per_row_encoder(cfg):
    table = datagen.make_table(cfg, 500, seed=2**31 + 11)
    got = datagen.encode_utf8(table)
    want = np.frombuffer(synth.encode_utf8(table, synth.SynthConfig()), np.uint8)
    assert np.array_equal(got, want)


def test_extreme_values_encode_like_per_row_encoder(cfg):
    table = datagen.make_table(cfg, 8, seed=5)
    table["dense"][0] = np.iinfo(np.int32).max
    table["dense"][1] = -np.iinfo(np.int32).max
    table["dense"][2] = 0
    table["dense_empty"][:3] = False
    table["sparse"][3] = np.array(0xFFFFFFFF, np.uint32).view(np.int32)
    table["sparse"][4] = 0
    table["sparse_empty"][3:5] = False
    got = datagen.encode_utf8(table)
    want = np.frombuffer(synth.encode_utf8(table, synth.SynthConfig()), np.uint8)
    assert np.array_equal(got, want)


def test_bytes_decode_to_ground_truth(cfg):
    table = datagen.make_table(cfg, 300, seed=7)
    decoded = baseline.decode_rows_serial(datagen.encode_utf8(table), schema.CRITEO)
    for k in ("label", "dense", "sparse"):
        assert np.array_equal(decoded[k], table[k]), k


def test_job_data_is_whole_chunks_of_the_table(cfg):
    small = dict(cfg, pipeline={"chunk_bytes": 8192, "max_rows_per_chunk": 64})
    table, chunks, rows, nbytes = datagen.make_job_data(small, 5, seed=3)
    assert chunks.shape == (5, 8192)
    assert rows.sum() == table["label"].shape[0]
    for c, r, b in zip(chunks, rows, nbytes):
        assert (c[:b] == datagen.NEWLINE).sum() == r and c[b - 1] == datagen.NEWLINE
        assert not c[b:].any()
    flat = np.concatenate([c[:b] for c, b in zip(chunks, nbytes)])
    assert np.array_equal(flat, datagen.encode_utf8(table))


def test_seed_fixes_the_data(cfg):
    a = datagen.make_table(cfg, 100, seed=2**31 + 3)
    b = datagen.make_table(cfg, 100, seed=2**31 + 3)
    c = datagen.make_table(cfg, 100, seed=2**31 + 4)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["sparse"], c["sparse"])


def test_columns_keep_their_cardinality(cfg):
    table = datagen.make_table(cfg, 20000, seed=1)
    u = table["sparse"].view(np.uint32)
    for c, card in enumerate(cfg["cardinalities"]):
        distinct = np.unique(u[~table["sparse_empty"][:, c], c]).size
        assert distinct <= card
        if card <= 30:
            assert distinct == card


def test_open_loop_schedule_is_poisson_and_the_same_work_for_every_seed():
    """Every seed offers the same gaps and sizes, rotated to start at
    another point; the arrivals per second vary as a Poisson process's
    do (no smoothing)."""
    import types

    import open_loop

    rate, seconds = 144.0, 51.0

    def schedule(seed):
        job = types.SimpleNamespace(params={"max_rows": 8192}, rows=120_000, seed=seed)
        return open_loop.Job.schedule(job, rate, seconds)

    (a1, s1, o1), (a2, s2, o2) = schedule(2**31 + 21), schedule(2**31 + 22)
    assert len(a1) == len(a2) and a1[-1] < seconds
    assert abs(len(a1) - rate * seconds) < 4 * np.sqrt(rate * seconds)
    assert np.array_equal(np.sort(s1), np.sort(s2)) and not np.array_equal(s1, s2)
    g1, g2 = np.diff(a1, prepend=0), np.diff(a2, prepend=0)
    rolled = [k for k in range(len(s1)) if np.array_equal(np.roll(s1, -k), s2)]
    assert len(rolled) == 1 and np.allclose(np.roll(g1, -rolled[0]), g2)
    assert s1.min() >= 1 and s1.max() <= 8192 and np.all(o1 + s1 <= 120_000)
    per_s = np.bincount(a1.astype(int))
    assert 0.6 * np.sqrt(rate) < per_s.std() < 1.5 * np.sqrt(rate)
