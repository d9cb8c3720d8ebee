"""Traffic kind ``offline_ranges``: ``offline_job``'s whole two-loop jobs,
on a configuration whose sparse columns each keep a modulus range of
their own.

The configuration lists the ranges as ``vocab_ranges`` (one per sparse
column) beside ``vocab_range``, the cap they are drawn under. The job
hands the program, the reference and the roofline bytes the list, and
is otherwise ``offline_job``'s: the same chunks, window and check.

Parameters: as ``offline_job``.
"""

from __future__ import annotations

import offline_job


class Job(offline_job.Job):
    def __init__(self, ctx):
        cell = ctx["cell"]
        cfg = dict(cell["config"], vocab_range=list(cell["config"]["vocab_ranges"]))
        super().__init__(dict(ctx, cell=dict(cell, config=cfg)))


def setup(ctx) -> Job:
    job = Job(ctx)
    job.warm_up()
    return job


run = offline_job.run
check = offline_job.check
