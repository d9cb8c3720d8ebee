"""Loop 1's share of the HBM roofline over one traced offline job: the
bytes the algorithm needs (``needed_bytes.loop1_bytes`` per chunk) over
the chip's peak bandwidth, against the loop-1 programs' device time
summed over chips. In percent."""

import devtrace
import peaks


def read(ctx):
    if ctx["kind"] != "offline":
        return None
    secs, _ = devtrace.program_seconds(ctx["trace"], ctx["window"], devtrace.is_loop1)
    if secs <= 0:
        return None
    bw = peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * ctx["needed_bytes"]["loop1"] / bw / secs
