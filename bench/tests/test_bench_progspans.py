"""The service's records on the device trace's clock: the tracer's clock
anchors in a profile recorded on the CPU, each reader of the records on
a hand-made ring and trace, and a traced tiny serving run."""

import time

import jax
import pytest

import devtrace
import harness
import progspans
from repro import obs
from repro.obs import trace as trace_lib

SEED = 2**31 + 303
SERVE = "criteo-kaggle-5k.serve-poisson"
RECORD_METRICS = ("serve_queue_ms", "serve_dispatch_ms_per_batch", "serve_route_ms_per_batch", "serve_bucket_byte_fill")


@pytest.fixture
def tracing():
    was = obs.enabled()
    obs.enable()
    yield
    if not was:
        obs.disable()


def test_anchors_map_the_ring_onto_a_profile_within_50us(monkeypatch):
    """Spans bridged into the profile start where the ring, mapped by the
    anchors, says they do: within 50 us for the median span and for all
    but a few (a span preempted between its annotation and its clock
    read is off by the preemption)."""
    monkeypatch.setattr(trace_lib, "ANCHOR_EVERY_NS", 20_000_000)
    tr = trace_lib.Tracer()
    cap = devtrace.Capture()
    cap.start()
    try:
        for i in range(40):
            with tr.span(f"probe/s{i}"):
                time.sleep(0.002)
            time.sleep(0.003)
    finally:
        cap.stop()
    trace = cap.load()
    anchors = [h for h in trace["host"] if progspans.ANCHOR.match(h[0])]
    assert len(anchors) >= 5
    assert all(dur > 0 for _, _, dur in anchors)
    offset = progspans.clock_offset_ns(trace)
    in_profile = {name: start for name, start, _ in trace["host"] if name.startswith("probe/")}
    ring = {e["name"]: tr.epoch_ns + e["ts"] * 1e3 + offset for e in tr.events()}
    assert set(ring) == set(in_profile) and len(ring) == 40
    errors = sorted(abs(ring[n] - in_profile[n]) for n in ring)
    assert errors[len(errors) // 2] <= 50_000 and errors[-5] <= 50_000, errors


# --------------------------------------------------------------------- #
# the readers on a hand-made ring and trace
# --------------------------------------------------------------------- #
MS = 1_000_000


def _synthetic(tracer):
    """Three window requests in two batches, one warm-up request before
    the window; stamps on the tracer's clock, from ``base``."""
    base = tracer.epoch_ns + 10 * MS
    t = lambda ms: base + int(ms * MS)

    def batch(bid, taken, assembled, dispatched, ready, routed, reqs, bucket_bytes):
        for rid, submit, r_taken, nbytes in reqs:
            tracer.complete("stream/request", t(submit), t(routed), cat="stream", id=rid, batch=bid,
                            rows=nbytes // 100, bytes=nbytes, taken=t(r_taken))
        tracer.complete("stream/batch", t(taken), t(routed), cat="stream", id=bid, bucket_rows=32,
                        bucket_bytes=bucket_bytes, rows=sum(r[3] for r in reqs) // 100,
                        bytes=sum(r[3] for r in reqs), requests=len(reqs),
                        assembled=t(assembled), dispatched=t(dispatched), ready=t(ready))

    batch(9, -6, -5.5, -5, -2, -1, [(90, -8, -6, 500)], 4000)  # warm-up, before the window
    batch(10, 4, 5, 6, 16, 17, [(100, 1, 3, 100), (101, 2, 3.5, 200)], 1000)
    batch(11, 22, 23, 26, 40, 44, [(102, 20, 21, 300)], 2000)
    offset = 7_000 - base  # trace time = perf_counter_ns + offset
    tr_ns = lambda ms: t(ms) + offset
    trace = {
        "host": [[f"obs/clock/{t(0.5)}", tr_ns(0.5), 1000], ["bench/window", tr_ns(0), 100 * MS]],
        "devices": {"0": {"ops": [], "modules": [
            ["jit_transform_chunk(12)", tr_ns(-5), 2 * MS],  # the warm-up's
            ["jit_other(3)", tr_ns(5.2), 0.1 * MS],
            ["jit_transform_chunk(12)", tr_ns(5.5), 9.5 * MS],  # ends 15: lag 1 ms
            ["jit_transform_chunk(12)", tr_ns(26.5), 11.5 * MS],  # ends 38: lag 2 ms
        ]}},
    }
    return {"kind": "serve", "trace": trace, "window": (tr_ns(0), tr_ns(100))}


@pytest.mark.parametrize("metric, want", [
    ("serve_queue_ms", (2 + 1.5 + 1) / 3),
    ("serve_dispatch_ms_per_batch", (1 + 3) / 2),
    ("serve_route_ms_per_batch", (1 + 4) / 2),
    ("serve_bucket_byte_fill", 100 * 600 / 3000),
    ("serve_complete_lag_ms", (1 + 2) / 2),
])
def test_reader_on_a_synthetic_ring(monkeypatch, metric, want):
    tr = trace_lib.Tracer(annotate=False)
    ctx = _synthetic(tr)
    monkeypatch.setattr(progspans, "_tracer", lambda: tr)
    read = harness.Catalog().reader(metric).read
    assert read(ctx) == pytest.approx(want, rel=1e-9)
    # nothing to read: another kind of cell, or a capture without anchors
    # (a program that writes no records)
    assert read(dict(ctx, kind="offline")) is None
    bare = dict(ctx["trace"], host=[h for h in ctx["trace"]["host"] if not progspans.ANCHOR.match(h[0])])
    assert read(dict(ctx, trace=bare)) is None


def test_window_records_select_and_link():
    tr = trace_lib.Tracer(annotate=False)
    ctx = _synthetic(tr)
    rec = progspans.window_records(ctx, tr)
    assert [r["id"] for r in rec["requests"]] == [100, 101, 102]
    assert [b["id"] for b in rec["batches"]] == [10, 11]
    phases = progspans.request_phases_ms(rec)
    assert sum(v for k, v in phases.items() if k != "total") == pytest.approx(phases["total"])
    assert phases["queue"] == pytest.approx(1.5) and phases["route"] == pytest.approx((1 + 1 + 4) / 3)


def test_readers_refuse_a_ring_that_dropped_window_events(monkeypatch):
    tr = trace_lib.Tracer(max_events=4, annotate=False)
    ctx = _synthetic(tr)
    assert tr.dropped > 0
    monkeypatch.setattr(progspans, "_tracer", lambda: tr)
    assert progspans.window_records(ctx, tr) is None
    for m in RECORD_METRICS + ("serve_complete_lag_ms",):
        assert harness.Catalog().reader(m).read(ctx) is None
    # drops older than a kept event that ended before the window are harmless
    tr2 = trace_lib.Tracer(max_events=6, annotate=False)
    ctx2 = _synthetic(tr2)
    assert tr2.dropped == 1 and len(progspans.window_records(ctx2, tr2)["requests"]) == 3


def test_complete_lag_needs_a_device_plane(monkeypatch):
    tr = trace_lib.Tracer(annotate=False)
    ctx = _synthetic(tr)
    monkeypatch.setattr(progspans, "_tracer", lambda: tr)
    ctx["trace"]["devices"] = {}
    assert harness.Catalog().reader("serve_complete_lag_ms").read(ctx) is None


# --------------------------------------------------------------------- #
# a traced tiny serving run
# --------------------------------------------------------------------- #
def test_traced_serve_run_reports_the_record_metrics(tiny_root, tracing):
    cat = harness.Catalog(tiny_root)
    out = harness.run_cell(SERVE, SEED, 1.0, True, time.perf_counter(), catalog=cat, devices=jax.devices()[:1])
    assert out["correct"] is True
    assert set(RECORD_METRICS) <= set(out["metrics"])
    # the CPU has no device plane
    assert "serve_complete_lag_ms" not in out["metrics"]
    assert 0 < out["metrics"]["serve_bucket_byte_fill"]["value"] <= 100
    assert out["metrics"]["serve_queue_ms"]["value"] >= 0


def test_traced_serve_records_match_the_service(tiny_root, tracing):
    """Every request the window submitted has one record, and the byte
    fill read from the records equals the service's own byte counters
    over the window."""
    cat = harness.Catalog(tiny_root)
    cell = cat.cell(SERVE)
    kind = cat.kind(cell["kind"])
    job = kind.setup({"cell": cell, "seed": SEED + 1, "devices": jax.devices()[:1]})
    reg = job.svc.registry
    names = ("stream.request_bytes_total", "stream.bucket_bytes_total")
    before = [reg.get(n).value for n in names]
    res = kind.run(job, 1.0, True)
    got_bytes, cap_bytes = (reg.get(n).value - b for n, b in zip(names, before))
    ctx = dict(res["trace_ctx"], cell=cell)
    rec = progspans.window_records(ctx)
    assert len(rec["requests"]) == res["attempted"] > 0
    assert len({r["id"] for r in rec["requests"]}) == res["attempted"]
    fill = cat.reader("serve_bucket_byte_fill").read(ctx)
    assert fill == pytest.approx(100.0 * got_bytes / cap_bytes, rel=1e-3)
    phases = progspans.request_phases_ms(rec)
    assert sum(v for k, v in phases.items() if k != "total") == pytest.approx(phases["total"], rel=1e-6)
