"""Device milliseconds of the loop-2 program per micro-batch the service
dispatched, over a traced stretch of the serving window."""

import devtrace


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    secs, runs = devtrace.program_seconds(ctx["trace"], ctx["window"], devtrace.is_loop2)
    if runs <= 0:
        return None
    return 1e3 * secs / len(ctx["trace"]["devices"]) / runs
