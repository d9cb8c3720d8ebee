"""Traffic kind ``sharded_job``: the offline job, data-parallel over chips.

Each job places the chunk stacks on the mesh (``put_shard_feed``) and
runs ``ShardedPiperPipeline.run_scan``: every chip runs loop 1 over its
own stack, ``vocab.merge_tree`` merges the chips' states, the vocabulary
is finalized and replicated, and every chip runs loop 2 over its stack.
Outputs stay on the chips.

Parameters: ``chunks`` per job, dealt round-robin to the chips (chunk
``i`` to chip ``i % n`` at step ``i // n``, the ``TabularChunkFeed``
layout); a multiple of the chip count. Window and traced run as in
``offline_job``.
"""

from __future__ import annotations

import numpy as np

import datagen
import reference
from offline_job import pipeline_config, run, wait  # noqa: F401  (the same window)


class Job:
    def __init__(self, ctx):
        from repro.core import sharded_pipeline
        from repro.launch.mesh import make_mesh

        cell = ctx["cell"]
        self.cfg = cell["config"]
        n_chunks = int(cell["params"]["chunks"])
        self.n = len(ctx["devices"])
        if n_chunks % self.n:
            raise ValueError(f"{n_chunks} chunks do not deal evenly to {self.n} chips")
        self.table, chunks, self.rows_per_chunk, self.bytes_per_chunk = datagen.make_job_data(
            self.cfg, n_chunks, ctx["seed"]
        )
        self.n_chunks = n_chunks
        self.rows = int(self.rows_per_chunk.sum())
        steps = n_chunks // self.n
        offsets = np.concatenate([[0], np.cumsum(self.rows_per_chunk)[:-1]]).astype(np.int32)
        # [steps, n, ...] -> [n, steps, ...]: chip k holds chunks k, k+n, ...
        self.host_stacks = np.ascontiguousarray(
            chunks.reshape(steps, self.n, -1).transpose(1, 0, 2)
        )
        self.host_offsets = np.ascontiguousarray(offsets.reshape(steps, self.n).T)
        self.mesh = make_mesh((self.n,), ("data",))
        self.eng = sharded_pipeline.ShardedPiperPipeline(pipeline_config(self.cfg), self.mesh)
        self.kept: list = []

    def dispatch(self):
        from jax.profiler import TraceAnnotation

        from repro.distributed.sharding import put_shard_feed

        with TraceAnnotation("bench/job"):
            with TraceAnnotation("bench/place"):
                stacks, offsets = put_shard_feed(self.host_stacks, self.host_offsets, self.mesh)
            with TraceAnnotation("bench/run_scan"):
                return self.eng.run_scan(stacks, offsets)

    def run_once(self):
        return wait(self.dispatch())

    def host_rows(self, out):
        """Valid rows in the single-device chunk order, on the host."""

        def flat(x):
            x = np.asarray(x).swapaxes(0, 1)  # [steps, n, rows, ...]
            return x.reshape((-1,) + x.shape[3:])

        valid = flat(out.valid)
        return {k: flat(getattr(out, k))[valid] for k in ("label", "dense", "sparse")}


def setup(ctx) -> Job:
    job = Job(ctx)
    job.run_once()  # compiles every program the job runs, at its shapes
    return job


def check(job: Job, checks: reference.Checks) -> None:
    """Every row of the first and last job against the reference. The
    output's vocabulary sizes are not returned by ``run_scan``; the
    ordinals of every row cover them."""
    results = [job.host_rows(o) for o in job.kept]
    job.kept = []
    ids = reference.first_occurrence_ids(job.table["sparse"], job.cfg["vocab_range"])
    for got in results:
        rows = got["label"].shape[0]
        checks.add("rows_missing", abs(rows - job.rows), 0)
        if rows == job.rows:
            reference.compare_rows(checks, got, job.table, ids, 0)
        else:
            checks.add("sparse_mismatches", job.rows, 0)
