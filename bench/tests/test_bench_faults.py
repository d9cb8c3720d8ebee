"""A run drives the timed path on the CPU at a tiny size (no look for a
chip) and decides ``correct``: true for the program as it is, false for
each fault planted under the timed path, and false for the control."""

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
from repro.core import pipeline as pipeline_lib

BENCH = pathlib.Path(__file__).resolve().parents[1]
SEED = 2**31 + 101


def _run(root, workload, trace=False, seconds=0.0):
    cat = harness.Catalog(root)
    chips = cat.cell(workload)["chips"]
    return harness.run_cell(
        workload, SEED, seconds, trace, time.perf_counter(), catalog=cat, devices=jax.devices()[:chips]
    )


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(pipeline_lib.PiperPipeline, "vocab_step", lambda self, state, chunk: state)


def _half_rows(monkeypatch):
    """Loop 2 leaves out half of each chunk's rows; the service routes
    only the first half of each request's rows back."""
    from repro.stream import scheduler

    orig_route = scheduler.MicroBatchScheduler.route

    def half_route(self, batch, out):
        return [{k: v[: (len(v) + 1) // 2] for k, v in r.items()} for r in orig_route(self, batch, out)]

    monkeypatch.setattr(scheduler.MicroBatchScheduler, "route", half_route)
    orig = pipeline_lib.PiperPipeline.transform_chunk

    def half(self, vocabulary, chunk):
        out = orig(self, vocabulary, chunk)
        rank = jnp.cumsum(out.valid)
        return dataclasses.replace(out, valid=out.valid & (2 * rank <= rank[-1]))

    monkeypatch.setattr(pipeline_lib.PiperPipeline, "transform_chunk", half)


def _answer_altered(monkeypatch):
    orig = pipeline_lib.PiperPipeline.transform_chunk

    def altered(self, vocabulary, chunk):
        out = orig(self, vocabulary, chunk)
        return dataclasses.replace(out, sparse=out.sparse.at[0, 0].add(1))

    monkeypatch.setattr(pipeline_lib.PiperPipeline, "transform_chunk", altered)


FAULTS = {"state_unchanged": _state_unchanged, "half_rows": _half_rows, "answer_altered": _answer_altered}


def test_offline_run_is_correct(tiny_root):
    out = _run(tiny_root, "criteo-kaggle-5k.offline-utf8")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"offline_rows_per_s", "setup_s"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_traced_run_is_correct_and_reports_its_window(tiny_root):
    out = _run(tiny_root, "criteo-kaggle-1m.offline-utf8", trace=True)
    assert out["correct"] is True
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: every device reader stays silent
    assert "loop1_roofline" not in out["metrics"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["criteo-kaggle-5k.offline-utf8", "criteo-kaggle-5k.serve-poisson"])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, fault, workload):
    FAULTS[fault](monkeypatch)
    out = _run(tiny_root, workload, seconds=1.0)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_serve_run_is_correct(tiny_root):
    out = _run(tiny_root, "criteo-kaggle-5k.serve-poisson", seconds=1.0)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_p50_ms", "serve_p99_ms", "setup_s"}
    assert out["failed"] == 0 and out["attempted"] >= 1


SHARDED = r"""
import json, pathlib, sys, time
sys.path[:0] = sys.argv[2:]
import jax
import harness
from repro.core import vocab as vocab_lib
root = pathlib.Path(sys.argv[1])
cat = harness.Catalog(root)
def run():
    return harness.run_cell("criteo-kaggle-1m.offline-utf8.x4", 2**31 + 5, 0.0, False,
                            time.perf_counter(), catalog=cat, devices=jax.devices())["correct"]
sound = run()
vocab_lib.merge_tree = lambda states: jax.tree.map(lambda x: x[0], states)
print(json.dumps({"sound": sound, "no_exchange": run()}))
"""


def test_sharded_run_without_the_exchange_is_not_correct(tiny_root):
    """Two host devices stand in for the four chips: the merge across
    them is the same ``merge_tree``, and fewer spinning collective
    threads keep the CPU test suite's other multi-device tests on time."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=2")
    paths = [str(BENCH / "traffic"), str(BENCH), str(BENCH.parent / "src")]
    proc = subprocess.run(
        [sys.executable, "-c", SHARDED, str(tiny_root), *paths],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"sound": True, "no_exchange": False}


def test_control_is_not_correct():
    """The reference in bfloat16 in the program's place fails the dense
    limit; float32 passes it."""
    cfg = json.loads((BENCH / "configs" / "criteo-kaggle-5k.json").read_text())
    import datagen

    table = datagen.make_table(cfg, 4000, seed=SEED)
    want = reference.dense_reference(table["dense"])
    f32 = np.log1p(np.maximum(table["dense"].astype(np.float32), 0))
    assert reference.dense_rel_err(f32, want) <= reference.DENSE_REL_LIMIT
    assert reference.dense_rel_err(reference.dense_control(table["dense"]), want) > reference.DENSE_REL_LIMIT
