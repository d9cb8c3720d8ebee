"""The trace reduction, on hand-made traces and on a trace recorded on a
TPU v5e (``bench/record_trace.py``: one warm offline job of two 1 MiB
chunks at the 5K point)."""

import gzip
import json
import pathlib

import pytest

import devtrace
import harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]


def _trace(ops, modules=(), host=()):
    return {"devices": {"0": {"ops": [list(o) for o in ops], "modules": [list(m) for m in modules]}},
            "host": [list(h) for h in host]}


def test_busy_is_the_union_of_ops_inside_the_window():
    # ops [0,10) and [5,15) overlap; [20,30) is half outside the window [0,25)
    t = _trace([("a", 0, 10), ("b", 5, 10), ("c", 20, 10)])
    assert devtrace.busy_seconds(t, (0, 25)) == pytest.approx(20e-9)
    assert devtrace.idle_share(t, (0, 25)) == pytest.approx(1 - 20 / 25)


def test_busy_is_averaged_over_devices():
    t = _trace([("a", 0, 10)])
    t["devices"]["1"] = {"ops": [["a", 0, 30]], "modules": []}
    assert devtrace.busy_seconds(t, (0, 40)) == pytest.approx(20e-9)


def test_program_time_groups_module_executions_by_name():
    mods = [("jit_vocab_step(11)", 0, 7), ("jit_vocab_step(11)", 10, 5),
            ("jit__finalize(3)", 20, 2), ("jit_transform_chunk(9)", 30, 4), ("jit_vocab_step(11)", 100, 9)]
    t = _trace([], mods)
    secs, runs = devtrace.program_seconds(t, (0, 50), devtrace.is_loop1)
    assert secs == pytest.approx(12e-9) and runs == 2
    assert devtrace.program_table(t, (0, 50)) == pytest.approx(
        {"jit_vocab_step": 12e-9, "jit__finalize": 2e-9, "jit_transform_chunk": 4e-9})


def test_idle_gaps_take_the_innermost_open_host_span():
    ops = [("x", 10, 10), ("y", 30, 10)]
    host = [("bench/job", 0, 50), ("loop1/chunk", 22, 6), ("bench/window", 0, 50)]
    gaps = dict(devtrace.idle_gaps(_trace(ops, host=host), (0, 50)))
    # [0,10) and [40,50) fall in bench/job only; [20,30) has loop1/chunk open at 25
    assert gaps == pytest.approx({"bench/job": 20e-9, "loop1/chunk": 10e-9})


def test_op_labels_and_program_attribution():
    hlo = "%fused_genvocab.1 = s32[26,5120]{1,0:T(8,128)S(1)} custom-call(s32[425984]{0} %r), x=y"
    assert devtrace.op_label(hlo) == "%fused_genvocab.1 custom-call"
    tup = "%sort.2 = (s32[26,5000]{1,0:T(8,128)}, s32[26,5000]{1,0}) sort(s32[26,5000]{1,0} %a), dimensions={1}"
    assert devtrace.op_label(tup) == "%sort.2 sort"
    t = _trace([(hlo, 1, 4), (tup, 11, 2)], [("jit_vocab_step(1)", 0, 6), ("jit__finalize(2)", 10, 5)])
    assert devtrace.top_ops(t, (0, 20)) == [
        ["jit_vocab_step %fused_genvocab.1 custom-call", pytest.approx(4e-9)],
        ["jit__finalize %sort.2 sort", pytest.approx(2e-9)],
    ]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "trace_offline_5k_2chunks.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces_to_its_programs(recorded):
    t = recorded["trace"]
    window = devtrace.window_of(t, "bench/window")
    table = devtrace.program_table(t, window)
    assert {"jit_vocab_step", "jit_transform_chunk", "jit__finalize"} <= set(table)
    assert table == pytest.approx(recorded["programs"])
    s = devtrace.summary(t, window)
    assert 0 < s["busy_s"] <= s["window_s"]
    # modules never overlap on one device: their sum is at most the window
    assert sum(table.values()) <= s["window_s"]
    assert len(s["breakdown"]["device_ops"]) == 10
    assert s["breakdown"]["device_ops"][0][0] == "jit_vocab_step %fused_genvocab.1 custom-call"
    assert sum(v for _, v in s["breakdown"]["idle_gaps"]) == pytest.approx(s["window_s"] - s["busy_s"])


def test_recorded_trace_feeds_every_offline_reader(recorded):
    t = recorded["trace"]
    window = devtrace.window_of(t, "bench/window")
    cat = harness.Catalog(ROOT)
    ctx = dict(devtrace.summary(t, window), trace=t, window=window, kind="offline",
               chunks=recorded["chunks"], jobs=1, needed_bytes=recorded["needed_bytes"],
               device_kind=recorded["device_kind"])
    values = {m["name"]: cat.reader(m["name"]).read(ctx) for m in cat.per_layer("criteo-kaggle-5k.offline-utf8")}
    assert all(v is not None for v in values.values()), values
    for name, v in values.items():
        assert 0 < v, name
        if name.endswith("roofline") or name.startswith("device_idle_share"):
            assert v <= 100, name
    loop1 = recorded["programs"]["jit_vocab_step"]
    assert values["loop1_ms_per_chunk"] == pytest.approx(1e3 * loop1 / 2)
    assert values["loop1_roofline"] == pytest.approx(100 * recorded["needed_bytes"]["loop1"] / 819e9 / loop1)
    # serve readers find nothing to read in an offline trace
    serve = {m["name"] for m in cat.per_layer("criteo-kaggle-5k.serve-poisson")}
    assert all(cat.reader(n).read(ctx) is None for n in serve)
