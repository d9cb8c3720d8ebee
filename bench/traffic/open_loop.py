"""Traffic kind ``open_loop``: requests to the stream service on a schedule.

Set-up builds the vocabulary with loop 1 over a seeded pool of rows
(``pool_chunks`` chunks), starts ``StreamingPreprocessService`` with its
default bucket ladder and warms every bucket. The window then submits
requests at Poisson arrival times at ``rate_per_s``, whether or not the
service keeps up, and times each from the moment it was due to the
moment its rows are ready on the host, by the benchmark's own clock.

Every seed gets the same work in another order: one fixed draw
(``SCHEDULE_KEY``) of independent exponential gaps between arrivals and
of request sizes, log-uniform over ``[1, max_rows]``, which the seed
rotates, so every window holds the same bursts and lulls, starting at
another point of the draw; the rows a request carries start at a seeded
offset in the pool.
Requests still open ``drain_s`` after the last one was due count as
failed. ``serve_p50_ms`` and ``serve_p99_ms`` are taken over every
request of the window. The client lets each answer go once it has
come and keeps a copy of a seeded ``check_share`` of them for the
comparison with the reference. A traced run traces the whole window.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

import datagen
import devtrace
import reference
from offline_job import pipeline_config

NEWLINE = 0x0A
# The one draw of gaps and sizes that every seed's window rotates.
SCHEDULE_KEY = 0x5EED_A771


class Job:
    def __init__(self, ctx):
        from repro.core import pipeline as pipeline_lib
        from repro.stream import StreamingPreprocessService

        cell = ctx["cell"]
        self.cfg = cell["config"]
        self.params = cell["params"]
        self.seed = ctx["seed"]
        self.table, chunks, rows_per_chunk, _ = datagen.make_job_data(
            self.cfg, int(self.params["pool_chunks"]), self.seed
        )
        self.rows = int(rows_per_chunk.sum())
        self.buf = datagen.encode_utf8(self.table)
        ends = np.flatnonzero(self.buf == NEWLINE) + 1
        self.starts = np.concatenate([[0], ends[:-1]])
        self.ends = ends
        config = pipeline_config(self.cfg)
        state = pipeline_lib.PiperPipeline(config).build_state_stream(list(chunks))
        self.svc = StreamingPreprocessService(config, state)
        self.svc.start()
        self.answers: list = []  # (row offset, rows, answer) of the sample
        self.unanswered = 0

    def payload(self, lo: int, n: int) -> np.ndarray:
        return self.buf[self.starts[lo] : self.ends[lo + n - 1]]

    def warm_up(self):
        self.svc.warmup(self.payload(0, min(b.rows, self.rows)) for b in self.svc.scheduler.buckets)

    def schedule(self, rate: float, seconds: float):
        """Arrival offsets (s), sizes and row offsets of one window: the
        arrivals of the fixed draw's exponential gaps that fall within
        ``seconds``, with gaps and sizes rotated together by the seed."""
        top = min(int(self.params["max_rows"]), self.rows)
        fixed = np.random.default_rng(SCHEDULE_KEY)
        gaps = fixed.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
        n = max(int(np.searchsorted(np.cumsum(gaps), seconds)), 1)
        sizes = np.exp(fixed.uniform(0.0, np.log(top), n)).astype(np.int64).clip(1, top)
        rng = np.random.default_rng(self.seed)
        start = int(rng.integers(n))
        gaps, sizes = np.roll(gaps[:n], -start), np.roll(sizes, -start)
        offsets = rng.integers(0, self.rows - sizes + 1)
        return np.cumsum(gaps), sizes, offsets

    def stall(self) -> dict:
        reg = self.svc.registry
        return {b: float(reg.get(f"stall.{b}_s").value) for b in ("host_assembly", "wall")}


def setup(ctx) -> Job:
    job = Job(ctx)
    try:
        job.warm_up()
    except BaseException:
        job.svc.stop()
        raise
    return job


class _Answers:
    """The client side of the window. A waiter thread takes the requests
    in the order they were submitted, which is the order the service
    answers them in, blocks on each one's result until ``deadline`` and
    stamps the moment it came. It then lets the rows go, keeping a copy
    only of the requests in the seeded sample that the check compares (a
    client that held every answer would hold every batch's host
    buffers)."""

    def __init__(self, n: int, sample: np.ndarray, deadline: float):
        self.done = np.full(n, np.nan)
        self.sample = set(sample.tolist())
        self.kept: dict[int, dict] = {}
        self.deadline = deadline
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._waiter = threading.Thread(target=self._wait_all, name="bench-answers", daemon=True)
        self._waiter.start()

    def add(self, i: int, handle) -> None:
        self._queue.put((i, handle))

    def _wait_all(self) -> None:
        while (item := self._queue.get()) is not None:
            i, handle = item
            try:
                res = handle.result(timeout=max(0.0, self.deadline - time.perf_counter()))
            except Exception:  # unanswered or failed: no time, counted by the check
                continue
            self.done[i] = time.perf_counter()
            if i in self.sample:
                self.kept[i] = {k: np.array(v) for k, v in res.items()}

    def close(self) -> None:
        """Wait for every answer (or the deadline) and end the waiter."""
        self._queue.put(None)
        self._waiter.join()


def serve(job: Job, rate: float, seconds: float, trace: bool = False) -> dict:
    """Submit one window's schedule and wait for every answer (up to
    ``drain_s`` past the last due time). Returns the per-request due and
    done times (NaN where none came), the generator's lateness, the
    service's stall seconds and, when traced, the capture: the profiler
    runs from before the first request to after the last answer, and the
    ``bench/window`` span covers the submissions."""
    from jax.profiler import TraceAnnotation

    arrivals, sizes, offsets = job.schedule(rate, seconds)
    n = len(arrivals)
    rng = np.random.default_rng(job.seed + 1)
    sample = rng.choice(n, size=max(1, int(n * job.params["check_share"])), replace=False)
    cap = devtrace.Capture() if trace else None
    if cap is not None:
        cap.start()
    stall0 = job.stall()
    late = np.zeros(n)
    try:
        t0 = time.perf_counter() + 0.05
        due = t0 + arrivals
        answers = _Answers(n, sample, due[-1] + float(job.params["drain_s"]))
        with TraceAnnotation("bench/window"):
            for i in range(n):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late[i] = time.perf_counter() - due[i]
                answers.add(i, job.svc.submit(job.payload(int(offsets[i]), int(sizes[i]))))
        answers.close()
    except BaseException:
        if cap is not None:
            cap.stop()
            cap.discard()
        raise
    if cap is not None:
        cap.stop()
    stall1 = job.stall()
    job.answers = [(int(offsets[i]), int(sizes[i]), res) for i, res in sorted(answers.kept.items())]
    job.unanswered = int(np.isnan(answers.done).sum())
    return {
        "due": due,
        "done": answers.done,
        "late": late,
        "sizes": sizes,
        "stall": {k: stall1[k] - stall0[k] for k in stall1},
        "capture": cap,
    }


def run(job: Job, seconds: float, trace: bool) -> dict:
    try:
        s = serve(job, float(job.params["rate_per_s"]), seconds, trace)
    finally:
        job.svc.stop()
    lat_ms = (s["done"] - s["due"]) * 1e3
    ok = ~np.isnan(lat_ms)
    n = len(lat_ms)
    late = s["late"]
    from harness import log

    log(
        requests=n,
        completed=int(ok.sum()),
        rows=int(s["sizes"].sum()),
        generator_late_p99_ms=float(np.percentile(late, 99) * 1e3),
        generator_late_max_ms=float(late.max() * 1e3),
    )
    out = {"attempted": n, "failed": int(n - ok.sum()), "metrics": {}}
    if ok.any():
        out["metrics"] = {
            "serve_p50_ms": float(np.percentile(lat_ms[ok], 50)),
            "serve_p99_ms": float(np.percentile(lat_ms[ok], 99)),
        }
    if trace:
        trace_doc = s["capture"].load()
        window = devtrace.window_of(trace_doc, "bench/window")
        out["trace_ctx"] = dict(
            devtrace.summary(trace_doc, window),
            trace=trace_doc,
            window=window,
            kind="serve",
            stall=s["stall"],
        )
    return out


def check(job: Job, checks: reference.Checks) -> None:
    """The seeded sample of the window's answers against the reference:
    each request's rows, labels, ordinals and dense values; and every
    request of the window that was never answered."""
    ids = reference.first_occurrence_ids(job.table["sparse"], job.cfg["vocab_range"])
    rows_missing = 0
    for lo, n, got in job.answers:
        rows_missing += abs(n - got["label"].shape[0])
        reference.compare_rows(checks, got, job.table, ids, lo)
    checks.add("requests_unanswered", job.unanswered, 0)
    checks.add("rows_missing", rows_missing, 0)
    job.answers = []
