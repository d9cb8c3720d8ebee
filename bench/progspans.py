"""The program's own records, on the device trace's clock.

The stream service writes one ``stream/request`` record per routed
request and one ``stream/batch`` record per routed batch into the
process-wide tracer (``repro.obs.tracer()``), every stamp a
``time.perf_counter_ns`` reading. While the profiler runs, the tracer
leaves host spans named ``obs/clock/<perf_counter_ns>`` in the capture;
their start in the trace minus the number in their name is the offset
from that clock to the trace's (the median over the anchors is taken).

:func:`window_records` maps the records onto the trace's clock and keeps
those of the window: the requests submitted inside ``ctx["window"]``
and the batches that carried them. A request submitted within
``EDGE_NS`` of the window's edges counts as inside: the anchors' own
error (tens of microseconds) must not drop the window's last request,
submitted just before the window's span closes. It returns ``None``
(the readers then report nothing) where the capture holds no anchor, as
with a program that writes no records, or where the ring dropped events
of the window.
"""

from __future__ import annotations

import re
import statistics

ANCHOR = re.compile(r"^obs/clock/(\d+)$")
EDGE_NS = 1_000_000

# a request's life, stamp by stamp: name of each phase, its two ends
PHASES = (
    ("queue", "submit", "taken"),
    ("gather", "taken", "batch_taken"),
    ("assemble", "batch_taken", "assembled"),
    ("dispatch", "assembled", "dispatched"),
    ("in_flight", "dispatched", "ready"),
    ("route", "ready", "routed"),
)


def clock_offset_ns(trace: dict) -> float | None:
    """Trace time minus ``perf_counter_ns`` (median over the anchors);
    ``None`` without an anchor."""
    offsets = [float(start) - int(m.group(1)) for name, start, _ in trace["host"] if (m := ANCHOR.match(name))]
    return statistics.median(offsets) if offsets else None


def _tracer():
    from repro import obs

    return obs.tracer()


def window_records(ctx: dict, tracer=None) -> dict | None:
    """``{"requests": [...], "batches": [...]}`` of the window, each a
    dict of the record's args with every stamp (``submit``, ``taken``,
    ``routed``; ``taken``, ``assembled``, ``dispatched``, ``ready``,
    ``routed``) in nanoseconds on the trace's clock."""
    offset = clock_offset_ns(ctx["trace"])
    if offset is None:
        return None
    tracer = tracer if tracer is not None else _tracer()
    events = tracer.events()
    epoch = tracer.epoch_ns
    lo, hi = ctx["window"]
    span = lambda e: (epoch + e["ts"] * 1e3 + offset, epoch + (e["ts"] + e["dur"]) * 1e3 + offset)
    # the ring drops its oldest events first: none of the window's is
    # lost if the oldest kept one ended before the window began
    if tracer.dropped and (not events or span(events[0])[1] >= lo):
        return None
    requests, batches = [], {}
    for e in events:
        if e.get("ph") != "X" or e["name"] not in ("stream/request", "stream/batch"):
            continue
        start, end = span(e)
        rec = dict(e["args"], routed=end)
        if e["name"] == "stream/request":
            if not lo - EDGE_NS <= start <= hi + EDGE_NS:
                continue
            rec["submit"] = start
            rec["taken"] += offset
            requests.append(rec)
        else:
            rec["taken"] = start
            for k in ("assembled", "dispatched", "ready"):
                rec[k] += offset
            batches[rec["id"]] = rec
    ids = {r["batch"] for r in requests}
    return {
        "requests": requests,
        "batches": sorted((b for i, b in batches.items() if i in ids), key=lambda b: b["id"]),
    }


def request_phases_ms(records: dict) -> dict[str, float]:
    """Mean milliseconds of each phase of a request's life over the
    window's requests (``PHASES``), and of the whole (``total``:
    submit → routed), which the phases add up to."""
    batches = {b["id"]: b for b in records["batches"]}
    sums = dict.fromkeys([p for p, _, _ in PHASES] + ["total"], 0.0)
    for r in records["requests"]:
        b = batches[r["batch"]]
        t = dict(b, batch_taken=b["taken"], submit=r["submit"], taken=r["taken"], routed=r["routed"])
        for name, a, z in PHASES:
            sums[name] += t[z] - t[a]
        sums["total"] += r["routed"] - r["submit"]
    n = max(len(records["requests"]), 1)
    return {k: v / n / 1e6 for k, v in sums.items()}
