"""Traffic kind ``offline_job``: whole two-loop jobs, back to back.

A job is what an offline user runs over a data set: loop 1 over every
chunk (``PiperPipeline.build_state_stream``), ``vocab.finalize``, then
loop 2 over every chunk again (``transform_stream``), with the outputs
left on the device. Each chunk goes from host memory to the device
inside the job, as a job that streams from storage pays.

Parameters: ``chunks`` (1 MiB chunks per job, from the configuration's
chunk size). The window runs whole jobs and ends at the first job
boundary at or after ``--seconds``; ``offline_rows_per_s`` is all rows
of those jobs over all of that time. Jobs shorter than ``AHEAD_S`` are
dispatched ahead of the one waited for. A traced run runs one job, then
traces a second.
"""

from __future__ import annotations

import collections
import time

import numpy as np

import datagen
import devtrace
import needed_bytes
import reference


# Seconds of whole jobs dispatched ahead of the one the window waits for,
# and the most jobs that may be in flight.
AHEAD_S = 6.0
MAX_AHEAD = 16


def pipeline_config(cfg: dict):
    from repro.core import pipeline as pipeline_lib
    from repro.core import schema as schema_lib

    sch = cfg["schema"]
    schema = schema_lib.TableSchema(
        n_dense=sch["n_dense"], n_sparse=sch["n_sparse"], vocab_range=cfg["vocab_range"]
    )
    return pipeline_lib.PipelineConfig(
        schema=schema,
        chunk_bytes=cfg["pipeline"]["chunk_bytes"],
        max_rows_per_chunk=cfg["pipeline"]["max_rows_per_chunk"],
    )


class Job:
    def __init__(self, ctx):
        from repro.core import pipeline as pipeline_lib

        cell = ctx["cell"]
        self.cfg = cell["config"]
        self.params = cell["params"]
        self.table, chunks, self.rows_per_chunk, self.bytes_per_chunk = datagen.make_job_data(
            self.cfg, int(self.params["chunks"]), ctx["seed"]
        )
        self.chunks = list(chunks)
        self.n_chunks = len(self.chunks)
        self.rows = int(self.rows_per_chunk.sum())
        self.pipe = pipeline_lib.PiperPipeline(pipeline_config(self.cfg))
        self.kept: list = []  # (vocabulary, rows_seen, outputs) of the first and last job

    def dispatch(self):
        """One whole job, dispatched; returns its device results unwaited."""
        from jax.profiler import TraceAnnotation

        from repro.core import vocab as vocab_lib

        with TraceAnnotation("bench/job"):
            with TraceAnnotation("bench/loop1"):
                state = self.pipe.build_state_stream(self.chunks)
            with TraceAnnotation("bench/finalize"):
                vocabulary = vocab_lib.finalize(state)
            with TraceAnnotation("bench/loop2"):
                outs = list(self.pipe.transform_stream(vocabulary, self.chunks))
        return vocabulary, state.rows_seen, outs

    def run_once(self):
        """One whole job; returns its device results once they are ready."""
        return wait(self.dispatch())

    def warm_up(self):
        import jax

        from repro.core import vocab as vocab_lib

        state = self.pipe.build_state_stream(self.chunks[:1])
        vocabulary = vocab_lib.finalize(state)
        jax.block_until_ready(list(self.pipe.transform_stream(vocabulary, self.chunks[:1])))

    def host_rows(self, kept):
        """The valid rows of one job's outputs, on the host."""
        vocabulary, rows_seen, outs = kept
        valid = [np.asarray(o.valid) for o in outs]
        got = {
            k: np.concatenate([np.asarray(getattr(o, k))[v] for o, v in zip(outs, valid)])
            for k in ("label", "dense", "sparse")
        }
        return got, np.asarray(vocabulary.sizes), int(rows_seen)


def wait(results):
    import jax
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench/sync"):
        return jax.block_until_ready(results)


def setup(ctx) -> Job:
    job = Job(ctx)
    job.warm_up()
    return job


def run(job, seconds: float, trace: bool) -> dict:
    """The window (shared by every kind whose job has ``run_once``)."""
    if trace:
        job.kept = [job.run_once()]
        cap = devtrace.Capture()
        with cap:
            from jax.profiler import TraceAnnotation

            with TraceAnnotation("bench/window"):
                job.kept.append(job.run_once())
        window = devtrace.window_of(cap.trace, "bench/window")
        need = needed_bytes.job_bytes(
            job.table, job.rows_per_chunk, job.bytes_per_chunk, job.cfg["vocab_range"]
        )
        tctx = dict(
            devtrace.summary(cap.trace, window),
            trace=cap.trace,
            window=window,
            kind="offline",
            chunks=job.n_chunks,
            jobs=1,
            needed_bytes=need,
        )
        return {"attempted": 2, "failed": 0, "metrics": {}, "trace_ctx": tctx}
    # The first job is waited for alone; its time sets how many jobs are
    # then kept dispatched ahead of the one waited for (AHEAD_S of work),
    # so that a stall of the host leaves the chip fed. Once the time is up
    # nothing more is sent, every job sent is waited for, and the clock is
    # read after that wait: all of that work over all of that time.
    t0 = time.perf_counter()
    first = last = job.run_once()
    ahead = min(MAX_AHEAD, int(AHEAD_S / (time.perf_counter() - t0)))
    jobs = 1
    inflight: collections.deque = collections.deque()
    while time.perf_counter() - t0 < seconds:
        inflight.append(job.dispatch())
        jobs += 1
        if len(inflight) > ahead:
            last = wait(inflight.popleft())
    while inflight:
        last = wait(inflight.popleft())
    elapsed = time.perf_counter() - t0
    job.kept = [first] if last is first else [first, last]
    return {
        "attempted": jobs,
        "failed": 0,
        "metrics": {"offline_rows_per_s": jobs * job.rows / elapsed},
    }


def check(job: Job, checks: reference.Checks) -> None:
    """Every row of the first and the last job of the window, and their
    vocabulary sizes, against the reference."""
    results = [job.host_rows(k) for k in job.kept]
    job.kept = []
    ids = reference.first_occurrence_ids(job.table["sparse"], job.cfg["vocab_range"])
    for got, sizes, rows_seen in results:
        checks.add("rows_seen_mismatch", abs(rows_seen - job.rows), 0)
        reference.compare_job(checks, got, sizes, job.table, ids)
