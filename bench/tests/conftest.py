"""Fixtures of the benchmark's own tests: the harness's directories on
``sys.path``, and a tiny copy of the catalog for runs on the CPU."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH / "traffic"), str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Sizes small enough for the CPU; every width of the row stays as it is.
TINY_PIPELINE = {"chunk_bytes": 16384, "max_rows_per_chunk": 128}
TINY_PARAMS = {"chunks": 4, "pool_chunks": 4, "max_rows": 64, "drain_s": 30, "check_share": 0.5}
TINY_RANGE_1M = 20000


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """A catalog root holding ``BENCHMARK.json`` and the benchmark's data
    files with CPU-sized chunks, job sizes and (for the 1M configuration)
    modulus range; metric readers are copied as they are."""
    (dest / "bench").mkdir(parents=True)
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    for sub in ("configs", "traffic", "cells"):
        (dest / "bench" / sub).mkdir()
        for f in (BENCH / sub).glob("*.json"):
            d = json.loads(f.read_text())
            if sub == "configs":
                d["pipeline"] = dict(TINY_PIPELINE)
                if d["vocab_range"] > 5000:
                    d["vocab_range"] = TINY_RANGE_1M
            if sub == "traffic":
                d["params"] = {k: TINY_PARAMS.get(k, v) for k, v in d["params"].items()}
                if d["kind"] == "sharded_job":
                    d["params"]["chunks"] = 8
            (dest / "bench" / sub / f.name).write_text(json.dumps(d))
    shutil.copytree(BENCH / "metrics", dest / "bench" / "metrics")
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    return make_tiny_root(tmp_path_factory.mktemp("bench-tiny"))
