"""The finalize's share of the HBM roofline over one traced offline job:
the bytes the algorithm needs a job — 4 B to initialize, 4 B to read
and 4 B to write the table, for each of Σ ranges entries, from the
configuration alone (``vocab_state.range_entries``) — over the chip's
peak bandwidth, against the device time per job and chip that
``vocab_finalize_ms`` reads (every program that is neither loop). In
percent."""

import devtrace
import peaks
import vocab_state

ENTRY_BYTES = 3 * 4


def read(ctx):
    if ctx["kind"] != "offline":
        return None
    other = lambda p: not (devtrace.is_loop1(p) or devtrace.is_loop2(p))
    secs, _ = devtrace.program_seconds(ctx["trace"], ctx["window"], other)
    if secs <= 0:
        return None
    secs = secs / max(len(ctx["trace"]["devices"]), 1) / ctx["jobs"]
    bw = peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    need = ENTRY_BYTES * vocab_state.range_entries(ctx["cell"]["config"])
    return 100.0 * need / bw / secs
