"""Observability layer: tracer/registry/stall units, trace schema, the
service's request and batch records, and the non-semantic guarantee —
instrumentation never changes a single output bit on any engine."""

import json
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import pipeline as P
from repro.data import synth
from repro.obs import counters as counters_lib
from repro.obs import stall as stall_lib
from repro.obs import trace as trace_lib
from repro.stream import StreamingPreprocessService
from repro.stream import metrics as metrics_lib


@pytest.fixture
def instrumented():
    """Enable tracing for one test, restoring the global toggle (and
    draining the global tracer ring) afterwards."""
    was_enabled = obs.enabled()
    obs.enable()
    obs.tracer().reset()
    yield obs.tracer()
    obs.tracer().reset()
    if not was_enabled:
        obs.disable()


# --------------------------------------------------------------------- #
# counters / gauges / histograms
# --------------------------------------------------------------------- #


def test_counter_monotonic():
    c = counters_lib.Counter("c")
    c.add()
    c.add(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="cannot decrease"):
        c.add(-1)
    c.reset()
    assert c.value == 0


def test_gauge_last_write_wins():
    g = counters_lib.Gauge("g")
    g.set(7)
    g.set(3)
    assert g.value == 3.0
    assert g.snapshot() == {"kind": "gauge", "value": 3}


def test_histogram_exact_until_reservoir():
    h = counters_lib.Histogram("h", reservoir=100)
    for v in range(100):
        h.observe(v)
    pct = h.percentiles((50.0, 99.0))
    assert pct[50.0] == pytest.approx(49.5)
    assert h.count == 100 and h.sum == sum(range(100))
    snap = h.snapshot()
    assert snap["min"] == 0.0 and snap["max"] == 99.0
    assert snap["mean"] == pytest.approx(49.5)


def test_histogram_memory_bounded_counts_exact():
    """The fix for the old unbounded ``ServiceMetrics._latencies``: any
    number of observations, O(reservoir) memory, exact count/sum."""
    h = counters_lib.Histogram("h", reservoir=64)
    n = 50_000
    for v in range(n):
        h.observe(v)
    assert len(h._samples) == 64  # bounded, no matter the volume
    assert h.count == n  # exact
    assert h.sum == sum(range(n))  # exact
    # reservoir stays representative: median of U[0, n) within ~20%
    assert abs(h.percentiles((50.0,))[50.0] - n / 2) < n * 0.2


def test_histogram_deterministic_reservoir():
    def fill(name):
        h = counters_lib.Histogram(name, reservoir=32)
        for v in range(1000):
            h.observe(v)
        return list(h._samples)

    assert fill("same") == fill("same")  # seeded per name


def test_registry_get_or_create_and_kind_clash():
    r = counters_lib.Registry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")
    assert r.names() == ["x"]
    assert r.get("missing") is None


def test_registry_threadsafe_concurrent_adds():
    r = counters_lib.Registry()

    def work():
        for _ in range(1000):
            r.counter("hits").add(1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.counter("hits").value == 8000


def test_registry_snapshot_and_jsonl(tmp_path):
    r = counters_lib.Registry()
    r.counter("a").add(2)
    r.gauge("b").set(1.5)
    r.histogram("c").observe(0.25)
    snap = r.snapshot()
    assert snap["a"] == {"kind": "counter", "value": 2}
    assert snap["c"]["count"] == 1
    path = tmp_path / "metrics.jsonl"
    r.export_jsonl(str(path), extra={"run": "t1"})
    r.export_jsonl(str(path))
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 2  # appends: the trajectory format
    assert lines[0]["run"] == "t1"
    assert lines[1]["metrics"]["a"]["value"] == 2


# --------------------------------------------------------------------- #
# tracer
# --------------------------------------------------------------------- #


def test_tracer_nested_spans_chrome_export(tmp_path):
    tr = trace_lib.Tracer()
    with tr.span("outer", cat="test", tier="vmem"):
        with tr.span("inner"):
            pass
    tr.instant("marker", note=7)
    doc = tr.to_chrome()
    assert trace_lib.validate_trace(doc) == []
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] in ("X", "i")}
    assert evs["outer"]["args"] == {"tier": "vmem"}
    # inner recorded first (exits first) and is contained in outer
    outer, inner = evs["outer"], evs["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert evs["marker"]["args"] == {"note": 7}
    # thread-name metadata present for the recording thread
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in doc["traceEvents"])
    path = tmp_path / "t.json"
    tr.export(str(path))
    assert trace_lib.validate_trace(json.loads(path.read_text())) == []


def test_tracer_disabled_is_noop():
    tr = trace_lib.Tracer()
    tr.enabled = False
    with tr.span("invisible"):
        pass
    tr.instant("also-invisible")
    assert tr.events() == []
    assert tr.span("x") is tr.span("y")  # shared null span, zero alloc


def test_tracer_ring_bounded_and_counts_drops():
    tr = trace_lib.Tracer(max_events=8)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 8
    assert tr.dropped == 12
    assert tr.to_chrome()["otherData"]["dropped_events"] == 12


def test_validate_trace_flags_malformed():
    assert trace_lib.validate_trace([]) != []
    assert trace_lib.validate_trace({"traceEvents": "nope"}) != []
    bad = {
        "traceEvents": [
            {"name": "x", "ph": "Z", "pid": 1, "tid": 1},
            {"name": "", "ph": "i", "ts": 0, "pid": 1, "tid": 1},
            {"name": "y", "ph": "X", "ts": 0, "dur": -1, "pid": 1, "tid": 1},
        ]
    }
    errors = trace_lib.validate_trace(bad)
    assert len(errors) == 3


def test_tracer_threadsafe():
    tr = trace_lib.Tracer()

    def work(k):
        for i in range(200):
            with tr.span(f"t{k}"):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == 800  # no lost events under contention
    # distinct tracks per live thread (idents may be reused once a
    # thread exits, so the exact count is OS-dependent)
    assert len({e["tid"] for e in evs}) >= 1


# --------------------------------------------------------------------- #
# stall attribution
# --------------------------------------------------------------------- #


def test_stall_clock_exhaustive_attribution():
    r = counters_lib.Registry()
    clock = stall_lib.StallClock(r)
    clock.start()
    clock.lap("queue_wait")
    clock.lap("host_assembly")
    clock.lap("device_dispatch")
    clock.lap("vocab_merge")
    clock.stop()
    rep = stall_lib.report(r)
    # every segment lands in exactly one bucket: Σ buckets == wall
    assert rep["attributed_s"] == rep["wall_s"] > 0
    assert set(rep["buckets_s"]) == set(stall_lib.BUCKETS)
    assert sum(rep["fractions"].values()) == pytest.approx(1.0, abs=0.01)
    # lap before start is a no-op segment, stop is idempotent
    clock.stop()
    assert stall_lib.report(r)["wall_s"] == rep["wall_s"]


def test_stall_report_empty_registry():
    rep = stall_lib.report(counters_lib.Registry())
    assert rep["wall_s"] == 0.0
    assert all(v == 0.0 for v in rep["fractions"].values())


# --------------------------------------------------------------------- #
# the non-semantic guarantee: bit-identity with instrumentation on
# --------------------------------------------------------------------- #


def _run_offline(buf, schema):
    pc = P.PipelineConfig(schema=schema, max_rows_per_chunk=256)
    pipe = P.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    outs = list(
        pipe.run_stream(lambda: synth.chunk_stream(buf, 16384))
    )
    lab = np.concatenate([np.asarray(o.label)[np.asarray(o.valid)] for o in outs])
    den = np.concatenate([np.asarray(o.dense)[np.asarray(o.valid)] for o in outs])
    spa = np.concatenate([np.asarray(o.sparse)[np.asarray(o.valid)] for o in outs])
    return np.asarray(state.first_pos), lab, den, spa


def test_tracing_and_stage_spans_non_semantic(criteo_small, instrumented):
    """The acceptance pin: tracing enabled produces byte-for-byte the
    outputs of the uninstrumented run — loop-① state included — and
    runs the same program: one dispatch per chunk and loop, no separate
    decode stage."""
    buf, _, cfg = criteo_small
    obs.disable()
    ref = _run_offline(buf, cfg.schema)
    assert obs.tracer().events() == []
    obs.enable()
    got = _run_offline(buf, cfg.schema)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)
    # and the instrumented run actually recorded the span hierarchy
    names = {e["name"] for e in obs.tracer().events()}
    assert {"loop1/chunk", "loop2/chunk"} <= names
    assert not names & {"decode", "vocab_update", "transform"}


def test_stage_span_labels_carry_tier_and_route(criteo_small, instrumented):
    """Each chunk of each loop leaves one labelled span, and nothing is
    nested under it: the route and tier labels name the code path the
    chunk's single dispatch took."""
    buf, _, cfg = criteo_small
    _run_offline(buf, cfg.schema)
    n_chunks = len(list(synth.chunk_stream(buf, 16384)))
    events = obs.tracer().events()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    for name in ("loop1/chunk", "loop2/chunk"):
        # one span per chunk of each pass (run_stream repeats loop 1)
        assert len(by_name[name]) % n_chunks == 0 and by_name[name]
        args = by_name[name][0]["args"]
        assert args["engine"] == "piper"
        assert "tier" in args and "route" in args
        for span in by_name[name]:
            inside = [
                e
                for e in events
                if e is not span
                and e["tid"] == span["tid"]
                and span["ts"] <= e["ts"]
                and e["ts"] + e.get("dur", 0) <= span["ts"] + span["dur"]
            ]
            assert inside == []
    doc = obs.tracer().to_chrome()
    assert trace_lib.validate_trace(doc) == []


def test_engine_counters_accumulate(criteo_small, instrumented):
    from repro.core import vocab as vocab_lib

    buf, _, cfg = criteo_small
    reg = obs.metrics()
    c1 = reg.counter("pipeline.loop1_rows_total").value
    c2 = reg.counter("pipeline.loop2_rows_total").value
    b1 = reg.counter("pipeline.loop1_bytes_total").value
    pc = P.PipelineConfig(schema=cfg.schema, max_rows_per_chunk=256)
    pipe = P.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    list(
        pipe.transform_stream(
            vocab_lib.finalize(state), synth.chunk_stream(buf, 16384)
        )
    )
    assert reg.counter("pipeline.loop1_rows_total").value - c1 == cfg.rows
    assert reg.counter("pipeline.loop2_rows_total").value - c2 == cfg.rows
    assert reg.counter("pipeline.loop1_bytes_total").value - b1 >= len(buf)


# --------------------------------------------------------------------- #
# service: stall report + bounded metrics
# --------------------------------------------------------------------- #


def test_service_stall_report_sums_to_wall(criteo_small):
    buf, table, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    pipe = P.PiperPipeline(pc)
    state = pipe.build_state_stream(synth.chunk_stream(buf, 16384))
    spans = synth.row_spans(buf)

    svc = StreamingPreprocessService(pc, state, bucket_rows=(32, 128), queue_depth=8)
    with svc:
        handles = [
            svc.submit(buf[spans[i * 8, 0] : spans[i * 8 + 7, 1]]) for i in range(20)
        ]
        svc.drain(timeout=120)
        for h in handles:
            assert h.result()["label"].shape[0] == 8
    rep = svc.stall_report()
    # the acceptance bound: bucket times sum to within 5% of wall
    assert rep["wall_s"] > 0
    assert rep["attributed_s"] == pytest.approx(rep["wall_s"], rel=0.05)
    assert sum(rep["buckets_s"].values()) == pytest.approx(rep["wall_s"], rel=0.05)
    # the device-bound share must be visible (work actually dispatched)
    assert rep["buckets_s"]["device_dispatch"] > 0
    # and the service's registry carries the queue/packing instruments
    snap = svc.registry.snapshot()
    assert snap["stream.batches_total"]["value"] > 0
    assert snap["stream.valid_rows_total"]["value"] == 20 * 8
    assert 0 < snap["stream.valid_rows_total"]["value"] <= snap["stream.bucket_rows_total"]["value"]
    assert 0 < snap["stream.request_bytes_total"]["value"] <= snap["stream.bucket_bytes_total"]["value"]
    assert "stream.bucket_occupancy" not in snap and "stream.padding_rows" not in snap


def test_service_metrics_is_registry_view_and_bounded():
    r = counters_lib.Registry()
    m = metrics_lib.ServiceMetrics(r)
    n = metrics_lib.LATENCY_RESERVOIR + 500
    m.note_submit(0.0)
    for i in range(n):
        m.record(0.001 * (i % 10 + 1), 4, now=float(i))
    snap = m.snapshot()
    assert snap["requests"] == n and snap["rows"] == 4 * n  # exact counts
    hist = r.get("stream.request_latency_s")
    assert len(hist._samples) == metrics_lib.LATENCY_RESERVOIR  # bounded
    assert snap["p50_ms"] > 0 and snap["p99_ms"] >= snap["p50_ms"]
    # same numbers visible through the registry (a view, not a silo)
    assert r.get("stream.requests_total").value == n
    m.reset()
    assert m.snapshot()["requests"] == 0
    assert r.get("stream.requests_total").value == 0


# --------------------------------------------------------------------- #
# explicit-stamp records and clock anchors
# --------------------------------------------------------------------- #


def test_tracer_complete_records_explicit_stamps():
    tr = trace_lib.Tracer()
    t0 = tr.epoch_ns + 5_000
    tr.complete("stream/request", t0, t0 + 2_500, cat="stream", id=7, taken=t0 + 1_000)
    (ev,) = tr.events()
    assert ev["ph"] == "X" and ev["cat"] == "stream"
    assert ev["ts"] == pytest.approx(5.0) and ev["dur"] == pytest.approx(2.5)
    assert tr.epoch_ns + ev["ts"] * 1e3 == pytest.approx(t0)
    assert ev["args"] == {"id": 7, "taken": t0 + 1_000}
    assert trace_lib.validate_trace(tr.to_chrome()) == []
    tr.enabled = False
    tr.complete("stream/request", t0, t0 + 1)
    assert len(tr.events()) == 1


class _FakeAnnotation:
    names: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.names.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


def test_tracer_anchors_only_while_a_profiler_session_runs(monkeypatch):
    """The first span of a session leaves an ``obs/clock/<ns>`` anchor
    carrying the tracer's clock, then at most one per
    ``ANCHOR_EVERY_NS``; with no session, none."""
    _FakeAnnotation.names = []
    session = {"on": False}
    tr = trace_lib.Tracer()
    tr._annotation = _FakeAnnotation
    tr._profiler_active = lambda: session["on"]
    with tr.span("a/b"):
        pass
    tr.complete("stream/batch", tr.epoch_ns, tr.epoch_ns + 1)
    anchors = [n for n in _FakeAnnotation.names if n.startswith(trace_lib.ANCHOR_PREFIX)]
    assert anchors == []
    session["on"] = True
    before = time.perf_counter_ns()
    for _ in range(3):
        with tr.span("a/b"):
            pass
    anchors = [n for n in _FakeAnnotation.names if n.startswith(trace_lib.ANCHOR_PREFIX)]
    assert len(anchors) == 1  # throttled
    assert int(anchors[0][len(trace_lib.ANCHOR_PREFIX) :]) >= before
    monkeypatch.setattr(trace_lib, "ANCHOR_EVERY_NS", 0)
    tr.complete("stream/batch", tr.epoch_ns, tr.epoch_ns + 1)
    anchors = [n for n in _FakeAnnotation.names if n.startswith(trace_lib.ANCHOR_PREFIX)]
    assert len(anchors) == 2
    # a new session anchors at once, whatever the spacing
    monkeypatch.setattr(trace_lib, "ANCHOR_EVERY_NS", 10**18)
    session["on"] = False
    tr.complete("stream/batch", tr.epoch_ns, tr.epoch_ns + 1)
    session["on"] = True
    tr.complete("stream/batch", tr.epoch_ns, tr.epoch_ns + 1)
    anchors = [n for n in _FakeAnnotation.names if n.startswith(trace_lib.ANCHOR_PREFIX)]
    assert len(anchors) == 3


def test_tracer_without_profiler_bridge_never_anchors():
    tr = trace_lib.Tracer(annotate=False)
    assert tr._profiler_active is None
    with tr.span("a/b"):
        pass
    assert [e["name"] for e in tr.events()] == ["a/b"]


# --------------------------------------------------------------------- #
# service: per-request and per-batch records
# --------------------------------------------------------------------- #


def _service(criteo_small, **kw):
    buf, _, cfg = criteo_small
    pc = P.PipelineConfig(schema=cfg.schema)
    state = P.PiperPipeline(pc).build_state_stream(synth.chunk_stream(buf, 16384))
    return StreamingPreprocessService(pc, state, bucket_rows=(32, 128), **kw)


def _records(events, name):
    return [e for e in events if e["name"] == name]


def _serve_burst(criteo_small, sizes):
    """Submit requests of ``sizes`` rows back to back; returns the
    handles (every one answered) and the service."""
    buf, _, _ = criteo_small
    spans = synth.row_spans(buf)
    svc = _service(criteo_small, queue_depth=64)
    handles, row = [], 0
    with svc:
        for n in sizes:
            handles.append(svc.submit(buf[spans[row, 0] : spans[row + n - 1, 1]]))
            row += n
        svc.drain(timeout=120)
    for h, n in zip(handles, sizes):
        assert h.result()["label"].shape[0] == n
    return handles, svc


SIZES = [8, 40, 1, 32, 100, 5, 17, 64, 3, 90]


def test_service_records_ids_unique_and_linked(criteo_small, instrumented):
    handles, _ = _serve_burst(criteo_small, SIZES)
    events = obs.tracer().events()
    reqs = _records(events, "stream/request")
    batches = _records(events, "stream/batch")
    assert len(reqs) == len(SIZES)
    assert [r["args"]["id"] for r in reqs] == [h.id for h in handles]  # FIFO
    assert len({h.id for h in handles}) == len(handles)
    assert len({b["args"]["id"] for b in batches}) == len(batches) >= 2
    by_batch = {b["args"]["id"]: b for b in batches}
    for r, h in zip(reqs, handles):
        assert r["args"]["batch"] == h.batch_id and h.batch_id in by_batch
        assert r["args"]["rows"] == h.n_rows and r["args"]["bytes"] == h.n_bytes
    for bid, b in by_batch.items():
        members = [r for r in reqs if r["args"]["batch"] == bid]
        assert b["args"]["requests"] == len(members)
        assert b["args"]["rows"] == sum(r["args"]["rows"] for r in members)
        assert b["args"]["bytes"] == sum(r["args"]["bytes"] for r in members)
        assert b["args"]["bucket_rows"] in (32, 128) and b["args"]["rows"] <= b["args"]["bucket_rows"]
    # ids grow with time: batches are routed in the order they were built
    assert [b["args"]["id"] for b in batches] == sorted(by_batch)


def test_service_record_phases_are_ordered_and_sum(criteo_small, instrumented):
    """submit ≤ taken ≤ batch taken ≤ assembled ≤ dispatched ≤ ready ≤
    routed for every request, and the phases add up to its record's
    length (submit → routed) exactly."""
    handles, _ = _serve_burst(criteo_small, SIZES)
    tr = obs.tracer()
    events = tr.events()
    to_ns = lambda e, k: round(tr.epoch_ns + e[k] * 1e3)
    batches = {b["args"]["id"]: b for b in _records(events, "stream/batch")}
    for r, h in zip(_records(events, "stream/request"), handles):
        b = batches[r["args"]["batch"]]
        a = b["args"]
        submit, routed = to_ns(r, "ts"), round(tr.epoch_ns + (r["ts"] + r["dur"]) * 1e3)
        assert abs(submit - h.submit_ns) <= 1 and abs(routed - to_ns(b, "ts") - b["dur"] * 1e3) <= 2
        stamps = [h.submit_ns, r["args"]["taken"], to_ns(b, "ts"), a["assembled"], a["dispatched"], a["ready"], routed]
        assert all(x <= y + 1 for x, y in zip(stamps, stamps[1:])), stamps
        phases = [y - x for x, y in zip(stamps, stamps[1:])]
        assert sum(phases) == pytest.approx(r["dur"] * 1e3, abs=2)


def test_service_fill_counters_by_hand(criteo_small, instrumented):
    """On a (32, 128) ladder, one request a batch: each batch takes the
    smallest bucket holding its rows, and the four counters are sums of
    rows, capacities and bytes computed here."""
    buf, _, cfg = criteo_small
    spans = synth.row_spans(buf)
    svc = _service(criteo_small)
    sizes, row, nbytes = [8, 40, 1, 32, 100, 33], 0, []
    with svc:
        for n in sizes:
            payload = buf[spans[row, 0] : spans[row + n - 1, 1]]
            nbytes.append(int(payload.size))
            svc.submit(payload).result(timeout=120)
            row += n
    snap = svc.registry.snapshot()
    cap = {32: 32, 128: 128}
    bucket = [32 if n <= 32 else 128 for n in sizes]
    per_row = cfg.schema.max_row_bytes
    assert snap["stream.batches_total"]["value"] == len(sizes)
    assert snap["stream.valid_rows_total"]["value"] == sum(sizes)
    assert snap["stream.bucket_rows_total"]["value"] == sum(cap[b] for b in bucket)
    assert snap["stream.request_bytes_total"]["value"] == sum(nbytes)
    assert snap["stream.bucket_bytes_total"]["value"] == sum(b * per_row for b in bucket)
    # the batch records carry the same numbers
    batches = _records(obs.tracer().events(), "stream/batch")
    assert sum(b["args"]["bucket_bytes"] for b in batches) == snap["stream.bucket_bytes_total"]["value"]
    assert sum(b["args"]["bytes"] for b in batches) == sum(nbytes)


def test_service_records_nothing_when_disabled(criteo_small, instrumented):
    obs.disable()
    handles, svc = _serve_burst(criteo_small, SIZES[:4])
    assert obs.tracer().events() == []
    # the stamps and counters are the program's own and stay
    assert all(h.taken_ns >= h.submit_ns for h in handles)
    assert svc.registry.snapshot()["stream.valid_rows_total"]["value"] == sum(SIZES[:4])
