"""Streaming preprocessing service sweep: rows/s + request latency.

Runs the online service end-to-end per input format (paper Config I/II
utf8 vs Config III binary): offline loop ① builds the vocab state, then
a seeded stream of randomized-size requests is submitted through the
bounded ingress and drained. Reports throughput plus p50/p95/p99
request latency — the latency-bound metrics the offline benchmarks
don't measure.

Output: the usual ``name,us_per_call,derived`` CSV rows plus two
machine-readable JSON lines per format:

    stream_json/{fmt}  {"requests": ..., "rows_per_s": ..., "p50_ms": ...}
    stream_stall/{fmt} {"buckets_s": {...}, "wall_s": ..., "fractions": ...}

With ``--trace out.json`` the run also exports a Perfetto/
chrome://tracing trace of the whole sweep (stage spans enabled, so utf8
chunks show nested decode → vocab/transform spans) plus a metrics
snapshot at ``out.metrics.json`` (per-format registry dump + stall
report + provenance).

    PYTHONPATH=src python benchmarks/stream_service.py [--rows N]
                                                       [--trace out.json]
"""

from __future__ import annotations

import json
import os
import sys

if __package__ in (None, ""):  # direct script invocation
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    sys.path.insert(0, _ROOT)

import numpy as np

from benchmarks import common
from benchmarks.common import emit
from repro import obs
from repro.core import pipeline as pipeline_lib
from repro.data import loader, synth
from repro.stream import StreamingPreprocessService

ROWS = 6_000
BUCKET_ROWS = (256, 1024, 4096)
# Mixed small/large request sizes: plenty of requests for the latency
# percentiles, and micro-batch coalescing actually has work to do.
MAX_REQUEST_ROWS = 400
QUEUE_DEPTH = 32


def _request_sizes(rng: np.random.Generator, total_rows: int) -> list[int]:
    sizes, left = [], total_rows
    while left > 0:
        n = int(min(rng.integers(1, MAX_REQUEST_ROWS + 1), left))
        sizes.append(n)
        left -= n
    return sizes


def run_format(fmt: str, rows: int) -> dict:
    cfg = synth.SynthConfig(rows=rows, seed=0)
    buf, table = synth.make_dataset(cfg)
    pc = pipeline_lib.PipelineConfig(schema=cfg.schema, input_format=fmt)
    pipe = pipeline_lib.PiperPipeline(pc)

    # offline loop ① — the vocabulary the service freezes
    if fmt == "utf8":
        state = pipe.build_state_stream(synth.chunk_stream(buf, 1 << 14))
    else:
        feed = loader.BinaryChunkFeed(table, rows_per_chunk=512)
        flat = feed.flat_chunks()
        state = pipe.build_state_stream(
            {k: v[i] for k, v in flat.items()} for i in range(flat["label"].shape[0])
        )

    rng = np.random.default_rng(7)
    sizes = _request_sizes(rng, rows)

    svc = StreamingPreprocessService(
        pc,
        state,
        bucket_rows=BUCKET_ROWS,
        queue_depth=QUEUE_DEPTH,
    ).start()
    try:
        # warm every bucket once so steady-state latency isn't compile time
        svc.warmup(
            next(synth.request_payloads(buf, table, [min(c, rows)], fmt))
            for c in BUCKET_ROWS
        )
        handles = [
            svc.submit(p) for p in synth.request_payloads(buf, table, sizes, fmt)
        ]
        svc.drain()
        snap = svc.metrics.snapshot()
        compiled = svc.compile_cache_size()
    finally:
        # stop() joins the loop, whose exit charges the tail segment —
        # read the stall report only after, so Σ buckets == full wall
        svc.stop()
    stall = svc.stall_report()

    # one "call" = one request: the us_per_call column carries the mean
    # request latency, keeping the cross-section CSV contract comparable
    emit(
        f"stream/{fmt}",
        snap["mean_ms"] / 1e3,
        f"rows_per_s={snap['rows_per_s']};p50_ms={snap['p50_ms']};"
        f"p95_ms={snap['p95_ms']};p99_ms={snap['p99_ms']};"
        f"requests={snap['requests']};wall_s={snap['wall_s']};compiled={compiled}",
    )
    print(f"stream_json/{fmt} {svc.metrics.to_json()}")
    print(f"stream_stall/{fmt} {json.dumps(stall, sort_keys=True)}")
    return {"metrics": svc.registry.snapshot(), "stall": stall}


def main(rows: int = ROWS, trace: str | None = None) -> None:
    if trace:
        obs.enable()
    per_fmt = {}
    for fmt in ("utf8", "binary"):
        per_fmt[fmt] = run_format(fmt, rows)
    if trace:
        obs.tracer().export(trace)
        mpath = trace.replace(".json", "") + ".metrics.json"
        with open(mpath, "w") as f:
            json.dump(
                {"provenance": common.provenance(), "formats": per_fmt},
                f,
                indent=2,
                sort_keys=True,
            )
        print(f"# wrote {trace} + {mpath}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="export a Perfetto trace + metrics snapshot of the sweep",
    )
    args = ap.parse_args()
    main(rows=args.rows, trace=args.trace)
