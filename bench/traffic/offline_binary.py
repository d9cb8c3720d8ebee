"""Traffic kind ``offline_binary``: whole two-loop jobs on pre-decoded
rows (Piper's Config III), back to back.

A job's rows are those of ``offline_job``'s UTF-8 job of the same
``chunks`` (the same table from the same seed), stored decoded: int32
``{label, dense, sparse, valid}`` chunks of the configuration's
``max_rows_per_chunk`` rows, the last one padded with invalid rows.
Decode does no work, so loop 1, finalize and loop 2 set the pace. The
window, the trace and the check are ``offline_job``'s; the roofline
bytes of a chunk are its rows' binary bytes (40 int32 a row).

Parameters: ``chunks`` (1 MiB UTF-8 chunks whose rows make one job).
"""

from __future__ import annotations

import numpy as np

import offline_job

BINARY_ROW_BYTES = 40 * 4  # label + 13 dense + 26 sparse, int32


def binary_chunks(table: dict, rows_per_chunk: int):
    """``table`` cut into int32 chunks of ``rows_per_chunk`` rows; returns
    the chunks and the valid rows of each."""
    rows = int(table["label"].shape[0])
    out, valid_rows = [], []
    for lo in range(0, rows, rows_per_chunk):
        n = min(rows_per_chunk, rows - lo)
        chunk = {}
        for k in ("label", "dense", "sparse"):
            arr = np.asarray(table[k][lo : lo + n], np.int32)
            pad = [(0, rows_per_chunk - n)] + [(0, 0)] * (arr.ndim - 1)
            chunk[k] = np.pad(arr, pad)
        chunk["valid"] = np.arange(rows_per_chunk) < n
        out.append(chunk)
        valid_rows.append(n)
    return out, np.asarray(valid_rows, np.int64)


class Job(offline_job.Job):
    def __init__(self, ctx):
        import dataclasses

        super().__init__(ctx)
        cfg = self.cfg
        self.chunks, self.rows_per_chunk = binary_chunks(
            self.table, cfg["pipeline"]["max_rows_per_chunk"]
        )
        self.bytes_per_chunk = self.rows_per_chunk * BINARY_ROW_BYTES
        self.n_chunks = len(self.chunks)
        from repro.core import pipeline as pipeline_lib

        self.pipe = pipeline_lib.PiperPipeline(
            dataclasses.replace(offline_job.pipeline_config(cfg), input_format="binary")
        )


def setup(ctx) -> Job:
    job = Job(ctx)
    job.warm_up()
    return job


run = offline_job.run
check = offline_job.check
