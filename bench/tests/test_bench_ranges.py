"""The Criteo 1TB configuration at MLPerf's per-column sizes and the
Config III binary cell: found by name, their reference and roofline
bytes per column, their runs correct on the CPU at a tiny size and not
correct with a fault planted, and the state's pad share read off the
program's counters."""

import json
import pathlib
import shutil
import time

import jax
import numpy as np
import pytest

import harness
import needed_bytes
import reference
import vocab_state
from repro import obs
from repro.core import pipeline as pipeline_lib
from repro.core import vocab as vocab_lib
from repro.obs import counters as counters_lib

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 977
RAGGED = "criteo-1tb-mlperf-40m.offline-utf8"
BINARY = "criteo-kaggle-1m.offline-binary"
NEW_METRICS = ("vocab_finalize_roofline", "vocab_state_pad_share")


def _run(root, workload, trace=False):
    cat = harness.Catalog(root)
    return harness.run_cell(
        workload, SEED, 0.0, trace, time.perf_counter(), catalog=cat, devices=jax.devices()[:1]
    )


@pytest.fixture
def ragged_root(tmp_path, tiny_root):
    """The tiny catalog with the 40M configuration's ranges cut to at
    most 3,000 (three columns of 3, 4 and 10 stay as they are)."""
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    p = root / "bench/configs/criteo-1tb-mlperf-40m.json"
    cfg = json.loads(p.read_text())
    cfg["vocab_ranges"] = [min(r, 3000) for r in cfg["vocab_ranges"]]
    p.write_text(json.dumps(cfg))
    return root


def test_catalog_finds_the_new_parts():
    cat = harness.Catalog(ROOT)
    cfg = cat.config("criteo-1tb-mlperf-40m")
    assert cfg["vocab_ranges"] == [min(c, cfg["vocab_range"]) for c in cfg["cardinalities"]]
    assert vocab_state.range_entries(cfg) == 204_184_588
    assert cat.cell(RAGGED)["kind"] == "offline_ranges"
    assert cat.cell(RAGGED)["params"] == {"chunks": 128}
    assert cat.cell(BINARY)["kind"] == "offline_binary"
    for w in (RAGGED, BINARY):
        assert cat.workload(w)["chips"] == 1
        assert {m["name"] for m in cat.end_to_end(w)} == {"offline_rows_per_s", "setup_s"}
        layer = {m["name"] for m in cat.per_layer(w)}
        assert set(NEW_METRICS) <= layer and "loop1_roofline" in layer
    assert set(NEW_METRICS) <= {m["name"] for m in cat.per_layer("criteo-kaggle-1m.offline-utf8")}
    for m in NEW_METRICS:
        assert callable(cat.reader(m).read)
    lay = vocab_lib.layout(cfg["vocab_ranges"])
    assert lay.entries <= 1.01 * 204_184_588


def test_reference_and_needed_bytes_take_a_list_of_ranges():
    rng = np.random.default_rng(5)
    ranges = [3, 130, 1000, 5000, 40_000_000]
    sparse = rng.integers(-(2**31), 2**31 - 1, size=(400, 5), dtype=np.int64).astype(np.int32)
    ids = reference.first_occurrence_ids(sparse, ranges)
    distinct = 0
    for c, r in enumerate(ranges):
        seen: dict[int, int] = {}
        want = [seen.setdefault(int(v) % r, len(seen)) for v in sparse[:, c].view(np.uint32)]
        np.testing.assert_array_equal(ids[:, c], want)
        distinct += len(seen)
    assert needed_bytes.distinct_keys(sparse, ranges) == distinct


def test_binary_job_sees_the_offline_jobs_rows(tiny_root):
    import offline_binary
    import offline_job

    cell = harness.Catalog(tiny_root).cell(BINARY)
    ctx = {"cell": cell, "seed": SEED, "devices": jax.devices()[:1]}
    utf8, binary = offline_job.Job(ctx), offline_binary.Job(ctx)
    for k in ("label", "dense", "sparse"):
        np.testing.assert_array_equal(binary.table[k], utf8.table[k])
    cap = cell["config"]["pipeline"]["max_rows_per_chunk"]
    assert binary.rows == utf8.rows == int(binary.rows_per_chunk.sum())
    assert all(c["sparse"].shape == (cap, 26) for c in binary.chunks)
    np.testing.assert_array_equal(binary.bytes_per_chunk, binary.rows_per_chunk * 160)


@pytest.mark.parametrize("workload", [RAGGED, BINARY])
def test_new_cells_are_correct(ragged_root, workload):
    out = _run(ragged_root, workload)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"offline_rows_per_s", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("workload", [RAGGED, BINARY])
def test_state_left_unchanged_is_not_correct(ragged_root, monkeypatch, workload):
    monkeypatch.setattr(pipeline_lib.PiperPipeline, "vocab_step", lambda self, state, chunk: state)
    out = _run(ragged_root, workload)
    assert out["correct"] is False
    assert out["checks"]["vocab_size_mismatches"]["value"] > 0


def test_pad_share_reads_the_counters():
    reg = counters_lib.Registry()
    assert vocab_state.pad_share(reg) is None  # a program without them
    reg.counter("vocab.state_entries").add(2048)
    reg.counter("vocab.range_entries").add(1536)
    assert vocab_state.pad_share(reg) == pytest.approx(25.0)


def test_traced_ragged_run_reports_the_pad_share(ragged_root):
    for name in ("vocab.state_entries", "vocab.range_entries"):
        obs.metrics().counter(name).reset()
    out = _run(ragged_root, RAGGED, trace=True)
    assert out["correct"] is True
    ranges = json.loads((ragged_root / "bench/configs/criteo-1tb-mlperf-40m.json").read_text())["vocab_ranges"]
    lay = vocab_lib.layout(ranges)
    want = 100.0 * (1.0 - sum(ranges) / lay.entries)
    assert out["metrics"]["vocab_state_pad_share"]["value"] == pytest.approx(want)
    # the CPU has no device plane: the finalize's roofline stays silent
    assert "vocab_finalize_roofline" not in out["metrics"]
