"""Device milliseconds of the loop-1 programs per chunk per chip, over
one traced offline job."""

import devtrace


def read(ctx):
    if ctx["kind"] != "offline":
        return None
    secs, _ = devtrace.program_seconds(ctx["trace"], ctx["window"], devtrace.is_loop1)
    if secs <= 0:
        return None
    return 1e3 * secs / ctx["chunks"]
